"""Device operations launched per decode-only engine step: "XLA Ops" events
that start inside the `bench.step.decode` spans, over those spans."""

SPAN = "bench.step.decode"


def read(ctx):
    s = ctx.trace
    if s is None or not s.span_count.get(SPAN):
        return None
    return s.span_ops[SPAN] / s.span_count[SPAN]
