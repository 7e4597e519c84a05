"""Training spans: the ``train.*`` names a JAX profile and the Tracer ring
both carry, the trace-cache count an epoch reports, and the promise that
spans only observe (results bit-identical with the tracer on and off)."""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import devtrace  # noqa: E402
from repro.core import (  # noqa: E402
    DenseLayer,
    ExecutionConfig,
    Network,
    StructuralPlasticityLayer,
    UnitLayout,
    onehot_layout,
)
from repro.data import complementary_code, mnist_like  # noqa: E402
from repro.runtime import TraceConfig  # noqa: E402

EPOCH = ("train.gather", "train.upload", "train.dispatch", "train.fence")
STAGE = ("train.upload", "train.fence")


def _network(trace=None):
    ds = mnist_like(n_train=128, n_test=32, n_features=32, seed=0)
    x, layout = complementary_code(ds.x_train)
    net = Network(seed=0).add(
        StructuralPlasticityLayer(layout, UnitLayout(4, 8), fan_in=16, lam=0.05)
    )
    return net.compile(ExecutionConfig(trace=trace)), np.asarray(x, np.float32)


def _fit(compiled, x):
    return compiled.fit((x, None), epochs_hidden=2, epochs_readout=0,
                        batch_size=64)


def _inside(events, outer):
    return [ev for ev in events if outer[0] <= ev[0] and ev[1] <= outer[1]]


def test_profile_nests_the_epoch_spans(tmp_path):
    compiled, x = _network()
    with jax.profiler.trace(str(tmp_path)):
        _fit(compiled, x)
    host = [ev for ev in devtrace.load(str(tmp_path))["host"]
            if ev[2].startswith("train.")]
    (fit,) = [ev for ev in host if ev[2] == "train.fit"]
    (stage,) = [ev for ev in _inside(host, fit) if ev[2] == "train.stage"]
    inner = [ev[2] for ev in _inside(host, stage) if ev is not stage]
    assert inner == list(STAGE)
    epochs = [ev for ev in _inside(host, fit) if ev[2] == "train.hidden0"]
    assert len(epochs) == 2
    assert all(stage[1] <= epoch[0] for epoch in epochs)
    for epoch in epochs:
        inner = [ev[2] for ev in _inside(host, epoch) if ev is not epoch]
        assert inner == list(EPOCH)


def test_tracer_ring_holds_the_same_spans():
    compiled, x = _network(TraceConfig())
    res = _fit(compiled, x)
    # recorded as each span closes, innermost first
    names = [s.name for s in compiled.tracer.spans()]
    assert names == [*STAGE, "train.stage",
                     *[*EPOCH, "train.hidden0"] * 2, "train.fit"]
    assert {s.trace_id for s in compiled.tracer.spans()} == {0}
    hidden = compiled.tracer.spans("train.hidden0")
    assert [s.attrs["host_s"] for s in hidden] == [
        h["host_s"] for h in res.history if h["phase"] == "hidden0"]


def test_traces_counts_compiles_per_epoch():
    compiled, x = _network(TraceConfig())
    res = _fit(compiled, x)
    epochs = [h for h in res.history if h["phase"] == "hidden0"]
    assert epochs[0]["traces"] >= 1 and epochs[1]["traces"] == 0
    dispatch = compiled.tracer.spans("train.dispatch")
    assert [s.attrs["traces"] for s in dispatch] == [1, 0]
    assert "hidden_epoch_cached[0]" in compiled.plan.jit_cache_sizes()


def test_states_bit_identical_with_tracer_on_and_off():
    (off, x), (on, _) = _network(), _network(TraceConfig())
    _fit(off, x)
    _fit(on, x)
    for a, b in zip(jax.tree_util.tree_leaves(off.state),
                    jax.tree_util.tree_leaves(on.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("cache,names", [
    (True, {"hidden_epoch_cached", "readout_epoch_cached"}),
    (False, {"hidden_epoch", "readout_epoch"}),
])
def test_hidden_and_readout_scans_have_their_own_names(cache, names):
    ds = mnist_like(n_train=128, n_test=32, n_features=32, seed=0)
    x, layout = complementary_code(ds.x_train)
    hidden = UnitLayout(4, 8)
    net = Network(seed=0).add(
        StructuralPlasticityLayer(layout, hidden, fan_in=16, lam=0.05)
    ).add(DenseLayer(hidden, onehot_layout(10), lam=0.05))
    compiled = net.compile(ExecutionConfig(cache_activations=cache))
    compiled.fit((np.asarray(x, np.float32), ds.y_train), epochs_hidden=1,
                 epochs_readout=1, batch_size=64)
    # jax.jit names the program jit_<__name__>
    assert {fn.__name__ for fn in compiled.plan.jitted.values()} == names
