"""Device operations launched per prefilled request: every "XLA Ops" event
that starts in the traced window over the engine's `prefills` counter
moved in it.  In a cell whose requests end at their prefill, this is the
executor's dispatch count per request."""


def read(ctx):
    s = ctx.trace
    if s is None or not ctx.prefills:
        return None
    return s.ops / ctx.prefills
