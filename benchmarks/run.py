"""Benchmark driver: paper tables + kernel microbenches + roofline summary.

Prints one CSV block per paper table (name,us_per_call,derived columns) and
a wall-clock microbench of every Pallas kernel (compiled on a TPU, in the
Pallas interpreter elsewhere; each row names the device and the mode).
Also writes machine-readable records so PRs have a compiler-perf
trajectory to track: npec-compiled vs hand-built BERT cycle counts per
(seq, bits) to results/npec_cycles.json, autoregressive prefill+decode
throughput from compiled KV-cache streams to
results/npec_decode_cycles.json (guarded by tests/test_npec_decode.py),
compiled MoE routing super-blocks to results/npec_moe_cycles.json
(guarded by tests/test_npec_conformance.py), batched-decode serving
streams + engine runs to results/npec_serve_cycles.json (guarded by
tests/test_npec_runtime.py), the tile-streaming vs whole-op DAG
schedule deltas to results/npec_stream_cycles.json (guarded by
tests/test_npec_stream.py), and the multi-overlay fleet serving sweep
(replicate/expert/pipeline sharding) to results/npec_fleet_cycles.json
(guarded by tests/test_npec_fleet.py), the tensor-parallel fleet
latency-vs-overlays table to results/npec_tensor_cycles.json (guarded
by tests/test_npec_fleet.py), the chunked-prefill /
prefill-decode-disaggregation latency table to
results/npec_disagg_cycles.json (guarded by
tests/test_npec_serving_props.py), and the length-bucketed/windowed
decode table to results/npec_buckets_cycles.json (guarded by
tests/test_npec_buckets.py).

Usage: PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path


def _print_table(name: str, rows):
    print(f"\n### {name}")
    if not rows:
        print("(empty)")
        return
    cols = list(dict.fromkeys(k for r in rows for k in r))
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r.get(c, "")) for c in cols))


def bench_kernels(quick: bool = False):
    """Microbenchmark each Pallas kernel vs its jnp oracle on the default
    device; off a TPU the kernels run in the Pallas interpreter."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.core import pwl

    dev = jax.devices()[0]
    where = (f"{'interpret' if ops.interpret_mode() else 'compiled'} on "
             f"{dev.platform} ({dev.device_kind})")
    key = jax.random.PRNGKey(0)
    m, n, k = (256, 256, 256) if quick else (512, 512, 512)
    x = jax.random.normal(key, (m, k))
    w = jax.random.normal(key, (k, n)) / (k ** 0.5)
    q = jax.random.normal(key, (1, 4, m, 64))
    kv = jax.random.normal(key, (1, 2, m, 64))

    cases = {
        "pwl_eval_kernel": lambda: ops.pwl_activation(x, "gelu"),
        "pwl_eval_ref": lambda: ref.pwl_eval(x, pwl.get_table("gelu", 16)),
        "quant_matmul_kernel": lambda: ops.quant_matmul(
            x, w, block_m=min(256, m), block_n=128, block_k=128),
        "softmax_kernel": lambda: ops.softmax(x),
        "softmax_ref": lambda: ref.nvu_softmax(x),
        "layernorm_kernel": lambda: ops.layernorm(x, jnp.ones((k,)),
                                                  jnp.zeros((k,))),
        "flash_attention_kernel": lambda: ops.flash_attention(
            q, kv, kv, use_pwl=True, block_q=128, block_kv=128),
        "attention_ref": lambda: ref.attention(q, kv, kv, use_pwl=False),
    }
    rows = []
    for name, fn in cases.items():
        fn()   # warmup/compile
        reps = 3 if quick else 5
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn())
        us = 1e6 * (time.perf_counter() - t0) / reps
        rows.append(dict(name=name, us_per_call=round(us, 1),
                         derived=where))
    return rows


def write_npec_record(path: Path, rows=None,
                      schema: str = "npec_cycles/v1") -> None:
    """Persist a compiler cycle record (npec-vs-hand or decode) as JSON."""
    if rows is None:
        from benchmarks import paper_tables
        rows = (paper_tables.npec_decode() if "decode" in schema
                else paper_tables.npec_moe() if "moe" in schema
                else paper_tables.npec_serve() if "serve" in schema
                else paper_tables.npec_stream() if "stream" in schema
                else paper_tables.npec_fleet() if "fleet" in schema
                else paper_tables.npec_tensor() if "tensor" in schema
                else paper_tables.npec_disagg() if "disagg" in schema
                else paper_tables.npec_buckets() if "buckets" in schema
                else paper_tables.npec_vs_hand())
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(
        {"schema": schema, "rows": rows}, indent=2) + "\n")
    print(f"\nwrote {path} ({len(rows)} rows)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-kernels", action="store_true")
    ap.add_argument("--json-out", default="results/npec_cycles.json",
                    help="npec-vs-hand cycle record ('' disables)")
    ap.add_argument("--json-out-decode",
                    default="results/npec_decode_cycles.json",
                    help="autoregressive decode cycle record ('' disables)")
    ap.add_argument("--json-out-moe",
                    default="results/npec_moe_cycles.json",
                    help="MoE routing-stream cycle record ('' disables)")
    ap.add_argument("--json-out-serve",
                    default="results/npec_serve_cycles.json",
                    help="batched-serve cycle record ('' disables)")
    ap.add_argument("--json-out-stream",
                    default="results/npec_stream_cycles.json",
                    help="dag-vs-streaming schedule record ('' disables)")
    ap.add_argument("--json-out-fleet",
                    default="results/npec_fleet_cycles.json",
                    help="multi-overlay fleet cycle record ('' disables)")
    ap.add_argument("--json-out-tensor",
                    default="results/npec_tensor_cycles.json",
                    help="tensor-parallel fleet cycle record ('' disables)")
    ap.add_argument("--json-out-disagg",
                    default="results/npec_disagg_cycles.json",
                    help="chunked-prefill/disaggregation cycle record "
                         "('' disables)")
    ap.add_argument("--json-out-buckets",
                    default="results/npec_buckets_cycles.json",
                    help="length-bucketed/windowed decode cycle record "
                         "('' disables)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import init_compilation_cache
    init_compilation_cache()

    from benchmarks import paper_tables
    npec_rows = decode_rows = moe_rows = serve_rows = stream_rows = None
    fleet_rows = tensor_rows = disagg_rows = buckets_rows = None
    for name, fn in paper_tables.ALL.items():
        t0 = time.perf_counter()
        rows = fn()
        dt = time.perf_counter() - t0
        _print_table(f"{name}  ({dt:.2f}s)", rows)
        if name == "npec_vs_hand":
            npec_rows = rows
        elif name == "npec_decode":
            decode_rows = rows
        elif name == "npec_moe":
            moe_rows = rows
        elif name == "npec_serve":
            serve_rows = rows
        elif name == "npec_stream":
            stream_rows = rows
        elif name == "npec_fleet":
            fleet_rows = rows
        elif name == "npec_tensor":
            tensor_rows = rows
        elif name == "npec_disagg":
            disagg_rows = rows
        elif name == "npec_buckets":
            buckets_rows = rows

    if args.json_out:
        write_npec_record(Path(args.json_out), npec_rows)
    if args.json_out_decode:
        write_npec_record(Path(args.json_out_decode), decode_rows,
                          schema="npec_decode_cycles/v1")
    if args.json_out_moe:
        write_npec_record(Path(args.json_out_moe), moe_rows,
                          schema="npec_moe_cycles/v1")
    if args.json_out_serve:
        write_npec_record(Path(args.json_out_serve), serve_rows,
                          schema="npec_serve_cycles/v1")
    if args.json_out_stream:
        write_npec_record(Path(args.json_out_stream), stream_rows,
                          schema="npec_stream_cycles/v1")
    if args.json_out_fleet:
        write_npec_record(Path(args.json_out_fleet), fleet_rows,
                          schema="npec_fleet_cycles/v1")
    if args.json_out_tensor:
        write_npec_record(Path(args.json_out_tensor), tensor_rows,
                          schema="npec_tensor_cycles/v1")
    if args.json_out_disagg:
        write_npec_record(Path(args.json_out_disagg), disagg_rows,
                          schema="npec_disagg_cycles/v1")
    if args.json_out_buckets:
        write_npec_record(Path(args.json_out_buckets), buckets_rows,
                          schema="npec_buckets_cycles/v1")

    if not args.skip_kernels:
        _print_table("kernel_microbench", bench_kernels(args.quick))

    # roofline summary (if the dry-run sweep has produced results)
    if Path("results/roofline.md").exists():
        print("\n### roofline (regenerate with `python -m benchmarks.roofline`)")
        print(Path("results/roofline.md").read_text())


if __name__ == "__main__":
    main()
