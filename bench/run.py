"""The on-chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order, in one process: name the device and refuse without a TPU (or
with fewer chips than the cell asks for); point JAX's persistent
compilation cache at its directory; build the cell's configuration and
its weights on the device from the seed; construct `NPEEngine` with
those weights, so the path is numeric; warm up every prefill shape of
the mix and open the window as the traffic says; run closed-loop clients
for whole engine steps until `--seconds` have passed; check what was
served against the plain reference; print the result.

With `--trace 1` the window runs under the JAX profiler and the result
holds the per-layer metrics read from the trace; with `--trace 0` it
holds the end-to-end metrics.  The last line of standard output is one
JSON object; the last lines of standard error are the numbers the
correctness check compared, each with its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Any, Callable, Dict, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import check  # noqa: E402
import flops  # noqa: E402
import loadgen  # noqa: E402
import spec  # noqa: E402
import trace_reduce  # noqa: E402
import window  # noqa: E402
from weights import make_params  # noqa: E402

PEAKS = BENCH / "peaks.json"
# The executor compiles one small XLA program per (op, shape), each well
# under JAX's default 1 s threshold for the persistent cache; at 0 every
# one is cached, so that only a checkout's first run of a cell compiles.
CACHE_MIN_COMPILE_S = 0.0


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def devices_for(cell: spec.Cell):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default backend is {devs[0].platform!r};"
                     " this benchmark measures only on the chip")
    if len(devs) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs


def peak_ops(kind: str, numerics: Dict[str, Any]) -> float:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS.name}; "
                       f"have {sorted(table)}")
    return float(table[kind][numerics["peak"]])


def program_config(c: Dict[str, Any]):
    """The program's ModelConfig for configuration file `c`: the named
    architecture with the file's overrides, checked against every width
    the file states."""
    from repro.configs import get_config

    prog = c["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["overrides"])
    for key, field_name in prog["fields"].items():
        got = getattr(cfg, field_name)
        if got != c[key]:
            raise ValueError(f"{prog['arch']}.{field_name} is {got!r}; the "
                             f"configuration file states {key} = {c[key]!r}")
    return cfg


@dataclasses.dataclass
class Built:
    params: Any
    engine: Any
    loop: window.ClosedLoop
    ref: Any
    warmed: list                 # set-up's requests, one per prompt length


def build(cell: spec.Cell, seed: int, engine_hook: Optional[Callable] = None,
          annotate: Callable = window._no_annotation,
          clock: Callable[[], float] = time.perf_counter) -> Built:
    """Configuration, weights, engine and clients of one run; the engine
    is warmed and the window's opening state is set up.  `clock` times
    the window (a test gives one that counts calls)."""
    import jax
    from repro.npec.runtime import NPEEngine

    c, t = cell.config, cell.traffic
    loadgen.validate(t)
    cfg = program_config(c)
    ref = spec.reference_module(cell.config_name)
    t0 = time.perf_counter()
    params = make_params(ref.layout(c), seed, c["numerics"]["weights"])
    jax.block_until_ready(params)
    log(f"set-up: weights {time.perf_counter() - t0:.3f} s")
    vocab = ref.dims(c).vocab
    engine = NPEEngine(cfg, slots=t["slots"], capacity=t["capacity"],
                       bits=c["numerics"]["bits"], npe=c["numerics"]["npe"],
                       params=params)
    log(f"set-up: engine {time.perf_counter() - t0:.3f} s")
    if engine_hook is not None:
        engine_hook(engine)
    streams = [loadgen.client_stream(t, seed, i, vocab)
               for i in range(t["clients"])]
    warmed = window.warm(engine, loadgen.warm_requests(t, seed, vocab))
    log(f"set-up: warm prefills {time.perf_counter() - t0:.3f} s")
    loop = window.ClosedLoop(engine, streams, clock=clock, annotate=annotate)
    if t.get("open_busy"):
        loop.settle()
        log(f"set-up: slots filled {time.perf_counter() - t0:.3f} s")
    return Built(params, engine, loop, ref, warmed)


def served_items(b: Built, tl: window.Timeline):
    """Every request the run's engine served a token for: the window's
    and set-up's, all through the same compiled streams at the cell's
    sizes."""
    reqs = b.warmed + [tr.req for tr in window.served(tl)]
    return [check.Served(r.prompt, list(r.generated)) for r in reqs]


def _trace_context(cell, tl, summary, dims, peak, prefills, decode_steps):
    return SimpleNamespace(cell=cell.name, timeline=tl, trace=summary,
                           dims=dims, peak_ops=peak, flops=flops,
                           prefills=prefills, decode_steps=decode_steps)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START, engine_hook: Optional[Callable] = None,
             require_tpu: bool = True,
             clock: Callable[[], float] = time.perf_counter
             ) -> Dict[str, Any]:
    """One run; returns the result object (the last line's content)."""
    import jax

    devs = devices_for(cell) if require_tpu else jax.devices()
    dev = devs[0]
    from repro.launch.compile_cache import init_compilation_cache
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}; compilation cache: {init_compilation_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      CACHE_MIN_COMPILE_S)

    annotate = (jax.profiler.TraceAnnotation if trace
                else window._no_annotation)
    b = build(cell, seed, engine_hook, annotate, clock)
    eng = b.engine
    prefills0, decodes0 = eng.stats.prefills, eng.stats.decode_steps
    logdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    setup_s = time.perf_counter() - t_start
    log(f"{cell.name}: set-up {setup_s:.3f} s; window of {seconds} s opens")
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        tl = b.loop.run(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    e2e = window.summarize(tl)
    prefills = eng.stats.prefills - prefills0
    decode_steps = eng.stats.decode_steps - decodes0
    log(f"{cell.name}: window {e2e['window_s']:.3f} s, {e2e['steps']} steps,"
        f" {prefills} prefills, {decode_steps} decode steps, "
        f"{e2e['output_tokens']} tokens; samples: ttft {e2e['ttft_samples']}"
        f" ({e2e['censored']} censored), itl {e2e['itl_samples']}")
    mislabel = window.step_kind_errors(tl)
    if mislabel:
        log(f"{cell.name}: warning: {mislabel}")
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:cell.chips])

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak_mem)}
    result: Dict[str, Any] = {}
    if trace:
        summary = trace_reduce.summarize(
            trace_reduce.read_xplane(trace_reduce.find_xplane(logdir)))
        shutil.rmtree(logdir, ignore_errors=True)
        if summary is None:
            raise RuntimeError("the trace holds no device op or no step span")
        dims = b.ref.dims(cell.config)
        ctx = _trace_context(cell, tl, summary, dims,
                             peak_ops(dev.device_kind, cell.config["numerics"]),
                             prefills, decode_steps)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m.name)(ctx)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
        device["busy_s"] = summary.busy_ns * 1e-9
        device["window_s"] = summary.window_ns * 1e-9
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[n, s] for n, s in summary.top_gaps]}
    else:
        e2e["setup_s"] = setup_s
        metrics = {}
        for m in cell.end_to_end:
            if m.name not in e2e:
                raise RuntimeError(f"{cell.name}: the window gave no sample "
                                   f"for {m.name}")
            metrics[m.name] = {"value": float(e2e[m.name]), "unit": m.unit}

    # correctness: after the window, with the program's state freed
    items = check.sample(served_items(b, tl),
                         check.SAMPLE_REQUESTS, seed)
    attempted = len(tl.requests)
    params, ref = b.params, b.ref
    del b, eng
    gc.collect()
    t0 = time.perf_counter()
    gaps, _ = check.gaps(ref, cell.config, params, items,
                         pad=cell.traffic["capacity"])
    verdict = check.judge(gaps, cell.config["check"]["max_logit_gap"],
                          len(items))
    log(f"{cell.name}: reference over {verdict.requests} requests, "
        f"{verdict.tokens} served tokens, {time.perf_counter() - t0:.3f} s")
    if not verdict.correct:
        log(f"{cell.name}: not correct: {verdict.reason}")
    result.update({"correct": verdict.correct, "attempted": attempted,
                   "failed": 0, "metrics": metrics, "device": device,
                   "checks": verdict.checks()})   # the line's last key
    return result


def emit(result: Dict[str, Any]) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        log(f"{name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
