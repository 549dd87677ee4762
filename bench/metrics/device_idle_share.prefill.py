"""Share of the traced window in which no operation ran on the device, in
a cell whose window runs prefills (time to first token).  Source: the
device plane's "XLA Ops" events, as a union, over the window from the
first to the last engine step span."""


def read(ctx):
    s = ctx.trace
    if s is None or s.window_ns <= 0 or not ctx.prefills:
        return None
    return 100.0 * (1.0 - s.busy_ns / s.window_ns)
