"""Wall-clock spans of the served path, on the JAX profiler's clock.

Every layer boundary of the numeric serving path opens a
`jax.profiler.TraceAnnotation` under one of the names below: the
engine's admission and decode, each host read of a device result, the
`DecodeSession` slot lifecycle, `exec.execute`, every graph node's
dispatch (named by its op class) and the MMU weight quantization.  They
land in the profiler's own trace beside the device's ops, so an idle
stretch of the device names the program phase the host was in.  With no
profiler running a span costs about a microsecond, and nothing turns
them off.  Spans nest on the host thread: a span's parent is the span
that encloses it.

Unlike `Tracer` (tracer.py), which stamps modelled overlay cycles and is
byte-identical by design, these spans measure host wall time and are
never read by the program itself.  docs/observability.md says how to
read them.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation as span

# opened in repro.core.quant.dense_maybe_quant, which imports nothing of npec
from repro.core.quant import QUANTIZE_WEIGHT_SPAN as EXEC_QUANTIZE_WEIGHT

ENGINE_ADMIT = "npec.engine.admit"          # rid, rows
ENGINE_DECODE = "npec.engine.decode"        # active, bucket
ENGINE_SYNC = "npec.engine.sync"            # the host waits on the device
SESSION_LOAD_SLOT = "npec.session.load_slot"    # slot, rows
SESSION_RESET_SLOT = "npec.session.reset_slot"  # slot
SESSION_MIGRATE = "npec.session.migrate"        # capacity
EXEC_EXECUTE = "npec.exec.execute"          # nodes
EXEC_PREFIX = "npec.exec."

# IR op -> the class its dispatch span is named by (`npec.exec.<class>`).
# A matmul is `mmu` when its second operand is a parameter (a resident
# weight) and `attention` when both operands are activations.
OP_CLASS = {
    "input": "feed",
    "param": "param",
    "matmul": "mmu",
    "softmax": "nvu", "layernorm": "nvu", "rmsnorm": "nvu", "act": "nvu",
    "rope": "nvu",
    "cache": "cache", "cache_append": "cache", "slot_select": "cache",
    "topk": "route", "scatter_slot": "route", "gather": "route",
    "add": "tensor", "mul": "tensor", "concat": "tensor",
    "reshape": "tensor", "embed": "tensor",
}
_NODE_SPAN = {op: EXEC_PREFIX + cls for op, cls in OP_CLASS.items()}
_ATTENTION_SPAN = EXEC_PREFIX + "attention"


def node_span(graph, node) -> str:
    """The span name of `node`'s dispatch in `graph`."""
    if node.op == "matmul" and graph.node(node.inputs[1]).op != "param":
        return _ATTENTION_SPAN
    return _NODE_SPAN[node.op]
