"""BENCHMARK.json's shape, and discovery of cells' parts by name."""
import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from bench import check, spec  # noqa: E402

import bench_tiny  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_entries():
    cfg_names = {c["name"] for c in BENCH["configs"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfg_names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(cells) // 2)
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        layers.setdefault(m["layer"], m["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [x["name"] for x in BENCH["configs"] + BENCH["workloads"]
             + BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len({w["name"] for w in BENCH["workloads"]}) == len(BENCH["workloads"])


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(workload):
    cell = spec.load_cell(ROOT, workload)
    assert hasattr(cell.runner(), "Runner")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    assert set(cell.config["check"]["limits"]) == set(check.NUMBERS)
    assert set(cell.config["reduced"]) <= set(cell.config)


def test_a_new_cell_mix_and_metric_are_files_and_entries(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "bench" / "traffic" / "one-epoch.json").write_text(json.dumps(
        {"runner": "fit", "epochs_hidden": 1, "epochs_readout": 0,
         "shuffle": False}))
    (root / "bench" / "metrics" / "batches_seen.py").write_text(
        "def read(span):\n    return float(span.batches)\n")
    bench["workloads"].append({"name": "tiny-one-epoch", "config": "tiny-hidden",
                               "traffic": "one-epoch", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "batches_seen", "unit": "batches",
                               "better": "higher", "source": "program_counter",
                               "layer": "device", "moves": "train_samples_per_s",
                               "workloads": ["tiny-one-epoch"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(root, "tiny-one-epoch")
    assert cell.traffic["epochs_hidden"] == 1
    assert cell.config["n_hcu"] == bench_tiny.TINY["n_hcu"]
    assert [m["name"] for m in cell.per_layer] == ["batches_seen"]

    class FakeSpan:
        batches = 7

    assert cell.reader("batches_seen").read(FakeSpan()) == 7.0
    with pytest.raises(KeyError):
        spec.load_cell(root, "no-such-cell")
