"""Readings the correctness limit of a cell is set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: the cell's run up to the close of its
window, exactly as `run.py` drives it, then the widest logit gap of the
served tokens against the float32 reference (the program's reading) and,
at the same positions, that of the int4 reference's first choice (the
control's reading).  One JSON line per seed on standard output.  The
benchmark's own runs never run the control; PERF.md gives the readings
and the limit set between them.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

import check  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402


def readings(cell: spec.Cell, seed: int, seconds: float,
             clock=time.perf_counter) -> Dict[str, float]:
    b = run.build(cell, seed, clock=clock)
    tl = b.loop.run(seconds)
    items = check.sample(run.served_items(b, tl),
                         check.SAMPLE_REQUESTS, seed)
    params, ref = b.params, b.ref
    del b
    gc.collect()
    prog, ctrl = check.gaps(ref, cell.config, params, items,
                            pad=cell.traffic["capacity"],
                            control_bits=check.CONTROL_BITS)
    served = {t for it in items for t in it.tokens}
    return {"seed": seed, "requests": len(items), "tokens": int(prog.size),
            "distinct_tokens": len(served),
            "control_flips": int((ctrl > 0).sum()),
            "program_max_gap": float(prog.max()),
            "program_p50_gap": float(np.median(prog)),
            "control_max_gap": float(ctrl.max()),
            "control_p50_gap": float(np.median(ctrl)),
            "control_exact_share": float(np.mean(ctrl == 0))}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        run.devices_for(cell)
    except run.NoChip as e:
        run.log(f"calibrate: {e}")
        return 2
    import jax
    from repro.launch.compile_cache import init_compilation_cache
    init_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      run.CACHE_MIN_COMPILE_S)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = readings(cell, seed, args.seconds)
        r["seconds"] = time.perf_counter() - t0
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
