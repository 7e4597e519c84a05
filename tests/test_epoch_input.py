"""Phase input staging: a host dataset is placed on the device once a phase
and every shuffled epoch is gathered there (``ScanPlan.stages`` /
``ScanPlan.stage``, ``program._stage``).  The states are bit-identical to the
host gather's; the history shows each staging and where each epoch gathered;
a device without room keeps the host gather; the activation store stays keyed
on the caller's array."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DenseLayer,
    ExecutionConfig,
    Network,
    StructuralPlasticityLayer,
    UnitLayout,
    onehot_layout,
)
from repro.data import complementary_code, mnist_like
from repro.runtime import plans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H1, H2 = UnitLayout(4, 8), UnitLayout(3, 4)


@pytest.fixture(scope="module")
def dataset():
    ds = mnist_like(n_train=200, n_test=32, n_features=16, seed=0)
    x, layout = complementary_code(ds.x_train)
    return np.asarray(x, np.float32), np.asarray(ds.y_train), layout


def _net(layout, depth=1):
    net = Network(seed=0)
    pre = layout
    for post in (H1, H2)[:depth]:
        net.add(StructuralPlasticityLayer(pre, post, fan_in=8, lam=0.05,
                                          init_jitter=1.0, gain=4.0))
        pre = post
    return net.add(DenseLayer(pre, onehot_layout(10), lam=0.05))


def _leaves(compiled):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(compiled.state)]


def _assert_same(a, b):
    for u, v in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(u, v)


@pytest.fixture
def no_room(monkeypatch):
    """A device that reports no free memory."""
    monkeypatch.setattr(plans, "free_device_bytes", lambda devices: 0)


FIT = dict(epochs_hidden=2, epochs_readout=2, batch_size=64)


def _fit(layout, x, y, readout="bcpnn", **config):
    compiled = _net(layout).compile(ExecutionConfig(**config))
    res = compiled.fit((x, y), readout=readout, **FIT)
    return compiled, res


@pytest.mark.parametrize("readout", ["bcpnn", "sgd"])
@pytest.mark.parametrize("cache", [True, False])
def test_staged_states_bit_identical(dataset, monkeypatch, readout, cache):
    x, y, layout = dataset
    staged, _ = _fit(layout, x, y, readout, cache_activations=cache)
    on_device, _ = _fit(layout, jnp.asarray(x), jnp.asarray(y), readout,
                        cache_activations=cache)
    monkeypatch.setattr(plans, "free_device_bytes", lambda devices: 0)
    host, res = _fit(layout, x, y, readout, cache_activations=cache)
    assert {h["input"] for h in res.history if "epoch" in h} == {"host"}
    _assert_same(staged, on_device)
    _assert_same(staged, host)


@pytest.mark.parametrize("cache", [True, False])
def test_history_has_one_stage_per_host_array(dataset, cache):
    x, y, layout = dataset
    _, res = _fit(layout, x, y, cache_activations=cache)
    stages = [h for h in res.history if h["phase"] == "stage"]
    # hidden0 stages x; the readout stages its x (level 0 only on the fused
    # path: the cached path reads the device-resident level-1 projection)
    # and y.
    want = [(0, "x", x.nbytes)]
    want += [(0, "x", x.nbytes)] if not cache else []
    want += [(0, "y", y.nbytes)]
    assert [(h["level"], h["array"], h["bytes"]) for h in stages] == want
    for h in stages:
        assert h["seconds"] == pytest.approx(
            h["host_s"] + h["device_wait_s"], rel=1e-6, abs=1e-9)
    epochs = [h for h in res.history if "epoch" in h]
    assert len(epochs) == 4
    assert {h["input"] for h in epochs} == {"device"}
    # the stage precedes its phase's epochs
    phases = [h["phase"] for h in res.history]
    assert phases.index("stage") < phases.index("hidden0")


def test_partial_fit_stages_its_chunk(dataset):
    x, y, layout = dataset
    compiled = _net(layout).compile(ExecutionConfig())
    for chunk in (x[:128], x[128:]):
        res = compiled.partial_fit((chunk, None), batch_size=64)
        stages = [h for h in res.history if h["phase"] == "stage"]
        assert [h["bytes"] for h in stages] == [chunk.nbytes]
        epochs = [h for h in res.history if "epoch" in h]
        assert [h["input"] for h in epochs] == ["device"]


def test_partial_fit_matches_host_gather(dataset, monkeypatch):
    x, y, layout = dataset

    def run():
        compiled = _net(layout).compile(ExecutionConfig())
        for lo in (0, 128):
            compiled.partial_fit((x[lo:lo + 128], y[lo:lo + 128]),
                                 batch_size=64, readout="bcpnn")
        return compiled

    staged = run()
    monkeypatch.setattr(plans, "free_device_bytes", lambda devices: 0)
    _assert_same(staged, run())


def test_no_room_keeps_the_host_gather(dataset, no_room):
    x, y, layout = dataset
    _, res = _fit(layout, x, y)
    assert not [h for h in res.history if h["phase"] == "stage"]
    assert {h["input"] for h in res.history if "epoch" in h} == {"host"}


def test_guard_counts_the_compiled_gather_in_half_the_free_memory(monkeypatch):
    plan = plans.ScanPlan([])
    arr = np.zeros((100, 10), np.float32)  # 4,000 bytes
    need = plan._gather_bytes(arr, 96, 32)
    # at least the staged copy, the indices and one stacked epoch
    assert need >= arr.nbytes + 96 * 4 + 96 * 10 * 4
    monkeypatch.setattr(plans, "free_device_bytes", lambda devices: 2 * need)
    assert plan.stages(arr, 96, 32)
    monkeypatch.setattr(plans, "free_device_bytes",
                        lambda devices: 2 * need - 1)
    assert not plan.stages(arr, 96, 32)
    monkeypatch.setattr(plans, "free_device_bytes", lambda devices: None)
    assert plan.stages(arr, 96, 32)
    assert not plan.stages(jnp.asarray(arr), 96, 32)


def test_device_input_and_batch_plan_are_not_staged(dataset):
    x, y, layout = dataset
    _, res = _fit(layout, jnp.asarray(x), jnp.asarray(y))
    assert not [h for h in res.history if h["phase"] == "stage"]
    assert {h["input"] for h in res.history if "epoch" in h} == {"device"}
    _, res = _fit(layout, x, y, engine="batch")
    assert not [h for h in res.history if h["phase"] == "stage"]
    assert {h["input"] for h in res.history if "epoch" in h} == {"host"}


def test_projection_cache_keyed_on_the_callers_array(dataset):
    x, y, layout = dataset
    compiled = _net(layout, depth=2).compile(ExecutionConfig())
    kw = dict(epochs_hidden=[0, 1], epochs_readout=0, batch_size=64)
    compiled.fit((x, y), **kw)
    store = compiled.activations
    hits, projections = store.stats["hits"], store.stats["projections"]
    res = compiled.fit((x, y), **kw)
    # layer 0 did not train, so the level-1 projection of this x still holds
    assert store.stats["hits"] == hits + 1
    assert store.stats["projections"] == projections
    assert store.datasets == 1
    assert {h["input"] for h in res.history if "epoch" in h} == {"device"}


def test_data_parallel_staging_matches_host_gather():
    """shard_map data parallelism on 8 forced CPU devices (a (4, 2) mesh):
    rows sharded over ``data`` (256 rows, cached path), replicated where
    they do not divide (250 rows, fused path: the activation store cannot
    row-shard its level-1 cache of 250 rows either); both bit-identical to
    the host gather.  The guard counts what the compiled gather holds on a
    device, more than the row shard and the stack's share of it, so it
    refuses free memory that a count of the shard would have passed."""
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.core import (DenseLayer, ExecutionConfig, Network,
                                StructuralPlasticityLayer, UnitLayout,
                                onehot_layout)
        from repro.core.distributed import DataParallelTrainer
        from repro.data import complementary_code, mnist_like
        from repro.runtime import plans

        ds = mnist_like(n_train=256, n_test=32, n_features=16, seed=0)
        x, layout = complementary_code(ds.x_train)
        mesh = jax.make_mesh((4, 2), ("data", "model"))

        def fit(n, cache):
            hidden = UnitLayout(4, 8)
            net = Network(seed=0)
            net.add(StructuralPlasticityLayer(layout, hidden, fan_in=8,
                                              lam=0.05, init_jitter=1.0))
            net.add(DenseLayer(hidden, onehot_layout(10), lam=0.05))
            tr = DataParallelTrainer(mesh, mode="shard_map")
            compiled = net.compile(ExecutionConfig(trainer=tr,
                                                   cache_activations=cache))
            res = compiled.fit((x[:n], ds.y_train[:n]), epochs_hidden=2,
                               epochs_readout=1, batch_size=64)
            leaves = [np.asarray(jax.device_get(a))
                      for a in jax.tree_util.tree_leaves(compiled.state)]
            return leaves, res.history

        for n, cache in ((256, True), (250, False)):
            staged, hist = fit(n, cache)
            arrays = [h["array"] for h in hist if h["phase"] == "stage"]
            assert arrays == (["x", "y"] if cache else ["x", "x", "y"]), hist
            assert {h["input"] for h in hist if "epoch" in h} == {"device"}
            real = plans.free_device_bytes
            plans.free_device_bytes = lambda devices: 0
            host, hist = fit(n, cache)
            plans.free_device_bytes = real
            assert {h["input"] for h in hist if "epoch" in h} == {"host"}
            for a, b in zip(staged, host):
                np.testing.assert_array_equal(a, b)
            print(n, "OK")

        tr = DataParallelTrainer(mesh, mode="shard_map")
        plan = Network(seed=0).add(DenseLayer(layout, onehot_layout(10))) \
            .compile(ExecutionConfig(trainer=tr)).plan
        x = np.asarray(x, np.float32)
        need = plan._gather_bytes(x, 256, 64)
        shard = x.nbytes // 4
        assert need > 2 * shard, (need, shard)
        plans.free_device_bytes = lambda devices: 2 * need - 1
        assert not plan.stages(x, 256, 64)
        assert 2 * shard <= (2 * need - 1) // 2
        plans.free_device_bytes = lambda devices: 2 * need
        assert plan.stages(x, 256, 64)
        print("guard OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert out.stdout.split() == ["256", "OK", "250", "OK", "guard", "OK"]


def test_spilled_level_stays_with_the_store(dataset):
    """A level-1 projection past the activation store's budget stays on the
    host, where the store put it: the store, not the staging, decides where
    a level-k array lives."""
    x, y, layout = dataset
    compiled = _net(layout, depth=2).compile(
        ExecutionConfig(activation_budget_mb=0.001))
    res = compiled.fit((x, y), epochs_hidden=1, epochs_readout=0,
                       batch_size=64)
    assert [h["array"] for h in res.history if h["phase"] == "stage"] == ["x"]
    inputs = {h["phase"]: h["input"] for h in res.history if "epoch" in h}
    assert inputs == {"hidden0": "device", "hidden1": "host"}
