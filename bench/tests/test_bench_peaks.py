"""The table of peaks: a known device gives its published peak, and an
unknown one is an error, never a default."""
import json

import pytest

import bench_tiny  # noqa: F401
import run


def test_v5e_int8_peak_and_its_source():
    table = json.loads(run.PEAKS.read_text())
    assert "TPU v5e" in table["source"] and "393 TOP/s" in table["source"]
    assert run.peak_ops("TPU v5 lite", {"peak": "int8_ops_per_s"}) == 393e12
    assert run.peak_ops("TPU v5 lite", {"peak": "bf16_flops_per_s"}) == 197e12


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="not in peaks.json"):
        run.peak_ops("TPU v9 imaginary", {"peak": "int8_ops_per_s"})
