"""A cell, a configuration, a mix and a metric added as files only are
found by their names in BENCHMARK.json."""
import json
import shutil
from types import SimpleNamespace

import pytest

import bench_tiny  # noqa: F401
import spec


def test_every_committed_name_resolves():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config_name == w["config"]
        assert cell.traffic["clients"] >= 1
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        mod = spec.reference_module(cell.config_name)
        assert callable(mod.forward) and callable(mod.layout)
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m.name))
            assert m.moves in {e.name for e in cell.end_to_end}


def test_a_cell_added_as_files_only_is_found(tmp_path):
    bench_dir = tmp_path / "bench"
    shutil.copytree(spec.BENCH_DIR / "configs", bench_dir / "configs")
    (bench_dir / "traffic").mkdir()
    (bench_dir / "metrics").mkdir()
    traffic = json.loads(
        (spec.BENCH_DIR / "traffic" / "encode.json").read_text())
    traffic["clients"] = 3
    (bench_dir / "traffic" / "burst.json").write_text(json.dumps(traffic))
    (bench_dir / "metrics" / "queue_depth.mean.py").write_text(
        "def read(ctx):\n    return ctx.depth * 2\n")
    bench = spec.load_benchmark()
    bench["workloads"].append({"name": "bert_base.burst",
                               "config": "bert_base", "traffic": "burst",
                               "chips": 1, "why": "a cell added as files"})
    bench["per_layer"].append({"name": "queue_depth.mean", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "engine", "moves": "ttft_p50_ms",
                               "workloads": ["bert_base.burst"]})
    cell = spec.load_cell("bert_base.burst", bench, bench_dir)
    assert cell.traffic["clients"] == 3 and cell.config_name == "bert_base"
    assert [m.name for m in cell.per_layer] == ["queue_depth.mean"]
    # metrics without a workloads key apply to every cell
    assert "setup_s" in [m.name for m in cell.end_to_end]
    read = spec.metric_reader("queue_depth.mean", bench_dir)
    assert read(SimpleNamespace(depth=2.5)) == 5.0
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell("bert_base.missing", bench, bench_dir)


def test_benchmark_json_holds_only_contract_keys():
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in bench["configs"]:
        assert c["file"].startswith("bench/")
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert c["source"] in cfg["source"]
