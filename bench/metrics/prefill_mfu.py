"""Share of the chip's peak that the window's prefills required: the
operations of every prompt whose first token came in the window
(`flops.prefill_ops`: all layers over the prompt, the head for its last
row) over the traced window times the peak of the cell's arithmetic."""


def read(ctx):
    s, tl = ctx.trace, ctx.timeline
    if s is None or s.window_ns <= 0:
        return None
    ops = sum(ctx.flops.prefill_ops(ctx.dims, len(tr.spec.prompt))
              for tr in tl.requests
              if tr.token_t and tl.t_open < tr.token_t[0] <= tl.t_close)
    if not ops:
        return None
    return 100.0 * ops / (s.window_ns * 1e-9 * ctx.peak_ops)
