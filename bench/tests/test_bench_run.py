"""Whole runs of tiny cells on the CPU, with the chip check bypassed.

A sound run is correct; the control (the reference in bfloat16 in the
program's place) and every fault a training cell can have, planted in the
program underneath the timed path, come out not correct under the real
cells' limits.  Off a TPU the real entry point refuses to run.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]

import bench_tiny  # noqa: E402
from bench import check, run, spec  # noqa: E402
from repro.core.layers import StructuralPlasticityLayer  # noqa: E402

ARGS = ["--seed", "2147483999", "--seconds", "0.2"]


def _run(root, workload, capsys, trace=0):
    rc = run.main(["--workload", workload, *ARGS, "--trace", str(trace)],
                  root=root, chips=lambda n: jax.devices()[:n],
                  cache=lambda r: None)
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("trace", [0, 1])
def test_sound_run_and_last_line(root, capsys, trace):
    rc, res, err = _run(root, "tiny-hidden", capsys, trace)
    assert rc == 0 and res["correct"] is True
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device",
                         *(["breakdown"] if trace else []), "checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        # On the CPU only the host-side share has anything to read.
        assert set(res["metrics"]) == {"fit_host_share"}
    else:
        assert set(res["metrics"]) == {"train_samples_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["checks"]) == set(check.NUMBERS)
    assert err.strip().splitlines()[-1].startswith("bench: check ")


def _frozen(self, state, x):
    return state, None


def _half(self, state, x, _orig=StructuralPlasticityLayer.train_batch):
    return _orig(self, state, x[: x.shape[0] // 2])


def _token(self, state, x, _orig=StructuralPlasticityLayer.train_batch):
    return _orig(self, state, x.at[0].set(1 - x[0]))


@pytest.mark.parametrize("broken", [_frozen, _half, _token],
                         ids=["state-unchanged", "half-batch", "row-altered"])
def test_broken_program_is_not_correct(root, capsys, monkeypatch, broken):
    monkeypatch.setattr(StructuralPlasticityLayer, "train_batch", broken)
    rc, res, _ = _run(root, "tiny-hidden", capsys)
    assert rc == 0 and res["correct"] is False


def test_control_is_not_correct(root):
    """The reference in bfloat16, put in the program's place, fails."""
    cell = spec.load_cell(root, "tiny-hidden")
    drv = cell.runner().Runner(cell, 2147483999, jax.devices()[:1])
    drv.setup()
    drv.release()
    limits = cell.config["check"]["limits"]
    assert check.judge(drv.reading(), limits)
    assert not check.judge(drv.reading(dtype=jnp.bfloat16), limits)


DP_SCRIPT = """
import json, pathlib, sys
sys.path[:0] = [{root!r}, {src!r}, {here!r}]
import jax
if {drop_exchange}:
    jax.lax.pmean = lambda x, axes: x
import bench_tiny
from bench import run
root = bench_tiny.make_root(pathlib.Path({tmp!r}))
run.main(["--workload", "tiny-dp4-hidden", *{args!r}], root=root,
         chips=lambda n: jax.devices()[:n], cache=lambda r: None)
"""


@pytest.mark.parametrize("drop_exchange", [False, True],
                         ids=["sound", "exchange-left-out"])
def test_data_parallel_cell(tmp_path, drop_exchange):
    script = DP_SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"),
                              here=str(HERE), tmp=str(tmp_path),
                              drop_exchange=drop_exchange, args=ARGS)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is (not drop_exchange), res["checks"]


def test_refuses_to_run_off_the_chip(capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "stl10-hidden", *ARGS])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_refuses_to_run_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's files
    fails past the chip check, when it needs the program, and prints no
    result."""
    import shutil

    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    script = ("import sys; sys.path.insert(0, '.'); import jax; "
              "from bench import run; run.main(['--workload', 'mnist-hidden', "
              f"*{ARGS!r}], chips=lambda n: jax.devices()[:n], "
              "cache=lambda r: None)")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "repro" in proc.stderr
