"""A copy of the benchmark with tiny cells added, for CPU tests.

The tiny configurations keep every key, the check's limits among them, of
the configuration they shrink, and change only the sizes.
"""
import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]

TINY = dict(n_features=64, n_train=512, n_hcu=4, n_mcu=8, fan_in=16)
CELLS = {  # tiny cell -> (configuration it shrinks, global batch)
    "tiny-hidden": ("stl10-20x150", 32),
    "tiny-dp4-hidden": ("stl10-20x150-dp4", 64),
}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout at ``tmp`` holding the benchmark plus the tiny cells."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell, (base, batch) in CELLS.items():
        cfg = json.loads((ROOT / "bench" / "configs" / f"{base}.json").read_text())
        cfg.update(TINY, name=cell, batch=batch)
        (tmp / "bench" / "configs" / f"{cell}.json").write_text(json.dumps(cfg))
        chips = 4 if "mesh" in cfg else 1
        bench["configs"].append({"name": cell, "source": "test",
                                 "file": f"bench/configs/{cell}.json",
                                 "reduced": [], "why": "CPU test"})
        bench["workloads"].append({"name": cell, "config": cell,
                                   "traffic": "hidden-epochs", "chips": chips,
                                   "why": "CPU test"})
        for m in bench["per_layer"]:
            m.setdefault("workloads", []).append(cell)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
