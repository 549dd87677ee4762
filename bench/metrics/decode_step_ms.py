"""Traced host time of a decode-only engine step: the mean length of the
`bench.step.decode` spans, each of which ends in the step's host sync."""

SPAN = "bench.step.decode"


def read(ctx):
    s = ctx.trace
    if s is None or not s.span_count.get(SPAN):
        return None
    return s.span_ns[SPAN] / s.span_count[SPAN] * 1e-6
