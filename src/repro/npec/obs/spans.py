"""Wall-clock spans of the served path, on the JAX profiler's clock.

Every layer boundary of the numeric serving path opens a
`jax.profiler.TraceAnnotation` under one of the names below: the
engine's admission and decode, each host read of a device result, the
`DecodeSession` slot lifecycle and each `exec.execute`.  They land in
the profiler's own trace beside the device's ops, so an idle stretch of
the device names the program phase the host was in.  With no profiler
running a span costs about a microsecond, and nothing turns them off.
Spans nest on the host thread: a span's parent is the span that
encloses it.

The executor runs each graph as one jitted program, so a node has no
host work of its own to time.  Each execution instead marks its nodes:
while a profiler records, `exec.execute` opens one empty
`npec.exec.<class>` span per graph node, in graph order, with an
`npec.exec.quantize_weight` inside each matmul that runs on a
quantized weight (with the 8-bit MMU an int8 weight, prepared once),
right after it dispatches the program.  Their
counts are the nodes each execution ran; their length is the host's
per-node cost, which is nothing.  The preparation itself opens
`npec.exec.prepare_weights`: once when an engine is built, or while JAX
traces a program handed raw weights, and never in a warm serving loop.
`npec.exec.trace` opens around each trace of a
program by JAX, a graph's or the session's slot writer's, so its count
in a window is the number of executor retraces.  In the compiled program each node's device ops carry the
node's class as a `jax.named_scope`.

Unlike `Tracer` (tracer.py), which stamps modelled overlay cycles and is
byte-identical by design, these spans measure host wall time and are
never read by the program itself.  docs/observability.md says how to
read them.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation as span

ENGINE_ADMIT = "npec.engine.admit"          # rid, rows
ENGINE_DECODE = "npec.engine.decode"        # active, bucket
ENGINE_SYNC = "npec.engine.sync"            # the host waits on the device
SESSION_LOAD_SLOT = "npec.session.load_slot"    # slot, rows, banks
SESSION_RESET_SLOT = "npec.session.reset_slot"  # slot, banks
SESSION_MIGRATE = "npec.session.migrate"        # capacity
EXEC_EXECUTE = "npec.exec.execute"          # nodes
EXEC_TRACE = "npec.exec.trace"              # nodes, or a slot write's rows and
                                            # banks; opens only as JAX traces
EXEC_PREFIX = "npec.exec."
EXEC_QUANTIZE_WEIGHT = "npec.exec.quantize_weight"  # inside an `mmu` mark
EXEC_PREPARE_WEIGHTS = "npec.exec.prepare_weights"  # leaves, bytes

# IR op -> the class its node mark is named by (`npec.exec.<class>`), and
# the `jax.named_scope` its device ops carry in the compiled program.  A
# matmul is `mmu` when its second operand is a parameter (a resident
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


def node_class(graph, node) -> str:
    """The class of `node` in `graph`: its mark is `EXEC_PREFIX` + this."""
    if node.op == "matmul" and graph.node(node.inputs[1]).op != "param":
        return "attention"
    return OP_CLASS[node.op]
