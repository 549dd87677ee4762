"""Share of the chip's peak that the window's decoded tokens required: for
every token after a request's first that came in the window, the
operations of one layer pass over its row, attention over its keys and
the head (`flops.decode_ops`), over the traced window times the peak of
the cell's arithmetic."""


def read(ctx):
    s, tl = ctx.trace, ctx.timeline
    if s is None or s.window_ns <= 0:
        return None
    ops = 0
    for tr in tl.requests:
        prompt = len(tr.spec.prompt)
        for k, t in enumerate(tr.token_t):
            if k >= 1 and tl.t_open < t <= tl.t_close:
                ops += ctx.flops.decode_ops(ctx.dims, prompt + k)
    if not ops:
        return None
    return 100.0 * ops / (s.window_ns * 1e-9 * ctx.peak_ops)
