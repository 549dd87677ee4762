"""Chip smoke: serve bert_base at its published width on one TPU.

Drives the numeric serving path through `repro.launch.serve.run_npec`, the
function `python -m repro.launch.serve --backend npec` calls: `NPEEngine`
with weights from the seeded random init (`jax.random.PRNGKey(0)`), at
L=12, d=768, 12 heads, d_ff 3072 and vocab 30720, with 2 slots of 64
cache rows serving 4 requests of 8 new tokens.  All in one process, in
two phases:

  1. float: NPE numerics off; the chip and the reference both run under
     ``jax.default_matmul_precision("highest")``.  The reference is the
     jnp model (`registry.decode_step`) on the host CPU, rolled out
     greedily on the same prompts.  The served tokens must equal the
     reference's, and the first-token logits must agree to
     FLOAT_LOGITS_TOL.
  2. NPE: int8 MMU with int32 accumulation and the PWL NVU (the paper's
     configuration), at the program's own precision.  Each request's
     prefill logits from the chip must agree to NPE_LOGITS_TOL with the
     same compiled stream executed on the host CPU.

Without a TPU it exits nonzero before serving anything.  Wall times on
the earlier lines come from one run and are smoke timings, not metrics.
The last line of standard output is the JSON result.

Usage, from the repository root: python3 chip_smoke.py
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import npec  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import init_compilation_cache  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.npec.runtime import engine as engine_mod  # noqa: E402

SERVE_ARGV = ("--backend", "npec", "--arch", "bert_base", "--batch", "2",
              "--capacity", "64", "--requests", "4", "--gen", "8",
              "--dtype-float32")

# Both sides compute in float32 at HIGHEST precision, so they differ only
# in accumulation order and in the ulp-level error of exp, erf and rsqrt.
# That is ~1e-6 relative per op, compounded over 12 layers, against
# logits of magnitude ~1; 1e-3 leaves margin for that and still fails a
# matmul that ran as one bf16 pass (~4e-3 relative error per product).
FLOAT_LOGITS_TOL = 1e-3

# At the program's own precision the TPU computes the attention einsums
# (QK^T and AV, f32 operands) as one bf16 pass, where the CPU computes
# them in f32: ~4e-3 relative error in the scores and the context.  The
# next MMU input is then requantized to int8, and an element that moved
# across a rounding boundary changes by one step (amax/127), so the
# difference grows over 12 layers; a wrong kernel, weight or mask gives
# errors of the logits' own size (~1).
NPE_LOGITS_TOL = 0.25


@dataclass
class Prefill:
    """One prefill the engine executed: its stream, feeds, numerics
    config and the logits the device produced."""
    program: Any
    feeds: Dict[str, Any]
    cfg: Any
    logits: np.ndarray


@dataclass
class Run:
    """One `run_npec` call as the smoke observed it.  Every time is host
    wall time taken after `jax.block_until_ready` on what the timed call
    produced."""
    engine: Any = None
    wall_s: float = 0.0
    setup_s: float = 0.0                  # NPEEngine(): trace/lower/schedule
    prefill_s: List[float] = field(default_factory=list)
    decode_s: List[float] = field(default_factory=list)
    prefills: List[Prefill] = field(default_factory=list)


@contextlib.contextmanager
def _observe(run: Run):
    """Time engine setup, every prefill and every decode step of the
    engine built inside, and keep each prefill's stream, feeds and
    logits."""
    orig_init = engine_mod.NPEEngine.__init__
    orig_execute = engine_mod.execute
    orig_step = npec.DecodeSession.step

    def init(eng, *a, **kw):
        t0 = time.perf_counter()
        orig_init(eng, *a, **kw)
        if eng.session is not None:
            jax.block_until_ready(eng.session.caches)
        run.setup_s += time.perf_counter() - t0

    def execute(program, params, feeds, **kw):
        t0 = time.perf_counter()
        res = orig_execute(program, params, feeds, **kw)
        jax.block_until_ready((res.outputs, res.kv_exports))
        run.prefill_s.append(time.perf_counter() - t0)
        run.prefills.append(Prefill(program, dict(feeds), kw.get("cfg"),
                                    np.asarray(res[0])))
        return res

    def step(sess, *a, **kw):
        t0 = time.perf_counter()
        out = orig_step(sess, *a, **kw)
        jax.block_until_ready((out, sess.caches))
        run.decode_s.append(time.perf_counter() - t0)
        return out

    engine_mod.NPEEngine.__init__ = init
    engine_mod.execute = execute
    npec.DecodeSession.step = step
    try:
        yield run
    finally:
        engine_mod.NPEEngine.__init__ = orig_init
        engine_mod.execute = orig_execute
        npec.DecodeSession.step = orig_step


def serve_once(argv) -> Run:
    """Parse `argv` as the serve CLI does and run `run_npec` once."""
    args = serve.parse_args(list(argv))
    run = Run()
    with _observe(run):
        t0 = time.perf_counter()
        _, run.engine = serve.run_npec(args)
        run.wall_s = time.perf_counter() - t0
    if len(run.prefills) != len(run.engine.stats.requests):
        raise RuntimeError(
            f"observed {len(run.prefills)} prefills for "
            f"{len(run.engine.stats.requests)} requests")
    return run


def _describe(tag: str, run: Run) -> None:
    eng = run.engine
    cfg = eng.cfg
    served = sum(len(r.generated) for r in eng.stats.requests)
    print(f"[{tag}] {cfg.name}: L={cfg.num_layers} d={cfg.d_model} "
          f"heads={cfg.num_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size}; "
          f"slots={eng.slots} capacity={eng.capacity} "
          f"requests={len(eng.stats.requests)} served_tokens={served} "
          f"prompt_lengths={[len(r.prompt) for r in eng.stats.requests]}")
    decode = np.asarray(run.decode_s)
    print(f"[{tag}] smoke timings, one run, not metrics: "
          f"run_npec {run.wall_s:.3f} s; setup {run.setup_s:.3f} s; "
          f"first prefill {run.prefill_s[0]:.3f} s; "
          f"mean decode step {decode.mean():.4f} s over {decode.size} "
          f"steps (first {decode[0]:.3f} s)")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[{tag}] peak device memory in use: "
          f"{'not reported' if peak is None else f'{peak} bytes'}")


_decode_step = jax.jit(registry.decode_step, static_argnums=0)


def _reference_rollout(cfg, params, prompt, n_tokens: int, capacity: int):
    """Greedy rollout of the jnp model on the current default device:
    the prompt in one `decode_step` at position 0, then one token per
    step.  Returns (first-token logits, the n_tokens generated)."""
    shape = (cfg.num_layers, 1, capacity, cfg.num_kv_heads, cfg.head_dim)
    cache = {"full": {"k": jnp.zeros(shape, jnp.float32),
                      "v": jnp.zeros(shape, jnp.float32)}}
    logits, cache = _decode_step(cfg, params, cache,
                                 jnp.asarray(prompt)[None], jnp.int32(0))
    first = np.asarray(logits[0, -1])
    tokens = [int(np.argmax(first))]
    pos = len(prompt)
    while len(tokens) < n_tokens:
        logits, cache = _decode_step(cfg, params, cache,
                                     jnp.asarray([[tokens[-1]]], jnp.int32),
                                     jnp.int32(pos))
        tokens.append(int(np.argmax(np.asarray(logits[0, -1]))))
        pos += 1
    return first, tokens


def float_phase(serve_argv=SERVE_ARGV) -> List[str]:
    """Serve with NPE numerics off and check against the jnp model on the
    host CPU.  Returns the failures."""
    failures = []
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        run = serve_once(serve_argv)
        _describe("float", run)
        eng = run.engine
        params = jax.device_put(eng.params, cpu)
        worst, matched = 0.0, 0
        with jax.default_device(cpu):
            for req, pre in zip(eng.stats.requests, run.prefills):
                first, tokens = _reference_rollout(
                    eng.cfg, params, req.prompt, len(req.generated),
                    eng.capacity)
                err = float(np.max(np.abs(pre.logits[..., -1, :] - first)))
                worst = max(worst, err)
                if tokens == req.generated:
                    matched += 1
                else:
                    failures.append(
                        f"float: request {req.rid} served {req.generated}, "
                        f"the CPU reference generates {tokens}")
    n = len(eng.stats.requests)
    print(f"[float] served tokens equal to the CPU jnp reference: "
          f"{matched}/{n} requests")
    print(f"[float] first-token logits max|chip - CPU reference| = "
          f"{worst:.6e} (tolerance {FLOAT_LOGITS_TOL:g})")
    if not worst <= FLOAT_LOGITS_TOL:
        failures.append(f"float: first-token logits differ by {worst:.6e} "
                        f"> {FLOAT_LOGITS_TOL:g}")
    return failures


def npe_phase(serve_argv=SERVE_ARGV) -> List[str]:
    """Serve with the int8 MMU and PWL NVU and check each prefill against
    the same compiled stream on the host CPU.  Returns the failures."""
    run = serve_once(tuple(serve_argv) + ("--npe", "--bits", "8"))
    _describe("npe", run)
    cpu = jax.devices("cpu")[0]
    params = jax.device_put(run.engine.params, cpu)
    worst, same_first = 0.0, 0
    with jax.default_device(cpu):
        for req, pre in zip(run.engine.stats.requests, run.prefills):
            res = npec.execute(pre.program, params, pre.feeds, cfg=pre.cfg)
            host = np.asarray(res[0])
            worst = max(worst, float(np.max(np.abs(pre.logits - host))))
            same_first += int(np.argmax(host[..., -1, :])) == req.generated[0]
    n = len(run.prefills)
    print(f"[npe] prefill logits max|chip - CPU, same stream| = "
          f"{worst:.6e} (tolerance {NPE_LOGITS_TOL:g}) over {n} prefills; "
          f"first token equal to the CPU stream's for {same_first}/{n}")
    if not worst <= NPE_LOGITS_TOL:
        return [f"npe: prefill logits differ by {worst:.6e} "
                f"> {NPE_LOGITS_TOL:g}"]
    return []


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's default backend is "
              f"{dev.platform!r}); this smoke runs only on the chip",
              file=sys.stderr)
        return 2
    print(f"compilation cache: {init_compilation_cache()}")
    failures = float_phase()
    failures += npe_phase()
    if failures:
        for f in failures:
            print(f"chip_smoke FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
