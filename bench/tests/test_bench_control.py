"""The control at a size a test run can hold: the reference computed in
int4, the precision below the int8 the configurations state, has to
fail the comparison that the program passes, on three seeds.  At the
cells' own size the same readings come from `bench/calibrate.py` on the
chip (PERF.md)."""
import pytest

import bench_tiny
import calibrate
from test_bench_faults import LIMITS

SEEDS = [3, 17, 2147483659]


@pytest.mark.parametrize("cell_name", sorted(LIMITS))
def test_int4_control_fails_where_the_program_passes(cell_name):
    cell = bench_tiny.tiny_cell(cell_name)
    limit = LIMITS[cell_name]
    for seed in SEEDS:
        r = calibrate.readings(cell, seed, 3.0, clock=bench_tiny.Ticks())
        assert r["tokens"] >= 3
        assert r["program_max_gap"] <= limit < r["control_max_gap"], r
