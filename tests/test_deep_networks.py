"""Deep (3-hidden-layer) greedy stacks through the phase program.

Tier-1 previously had ZERO multi-hidden-layer coverage; this suite pins the
project-once pipeline on the configuration it was built for: scan-vs-batch
parity, bit-exactness with a greedy reference that recomputes the frozen
prefix every batch, per-layer epoch schedules, history
wall-times, activation-store residency/invalidation, distributed
(shard_map) parity, and a whole-network checkpoint round-trip.
"""
import tempfile

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
from repro.core.compiled import build_forward
from repro.core.network import sgd_readout_setup
from repro.data import complementary_code, mnist_like
from repro.runtime.epoch_engine import forward_stack

H1, H2, H3 = UnitLayout(4, 4), UnitLayout(3, 4), UnitLayout(2, 4)


@pytest.fixture(scope="module")
def dataset():
    ds = mnist_like(n_train=256, n_test=64, n_features=16, seed=0)
    x, layout = complementary_code(ds.x_train)
    x_te, _ = complementary_code(ds.x_test)
    return ds, x, x_te, layout


def build_deep(layout, seed=0, readout=True):
    """input -> 3 greedy plasticity layers (sparse fan-in, so structural
    rewires fire in every layer) -> BCPNN readout."""
    net = Network(seed=seed)
    net.add(StructuralPlasticityLayer(layout, H1, fan_in=8, lam=0.05,
                                      init_jitter=1.0, gain=4.0))
    net.add(StructuralPlasticityLayer(H1, H2, fan_in=3, lam=0.05,
                                      init_jitter=1.0, gain=4.0))
    net.add(StructuralPlasticityLayer(H2, H3, fan_in=2, lam=0.05,
                                      init_jitter=1.0, gain=4.0))
    if readout:
        net.add(DenseLayer(H3, onehot_layout(10), lam=0.05))
    return net


KW = dict(epochs_hidden=2, epochs_readout=2, batch_size=64)


def assert_states_equal(states_a, states_b, exact=True):
    cmp = (
        np.testing.assert_array_equal
        if exact
        else lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    )
    for sa, sb in zip(states_a, states_b):
        cmp(np.asarray(sa.w), np.asarray(sb.w))
        cmp(np.asarray(sa.b), np.asarray(sb.b))
        cmp(np.asarray(sa.marginals.cij), np.asarray(sb.marginals.cij))
        if sa.plast is not None:
            np.testing.assert_array_equal(
                np.asarray(sa.plast.hcu_mask), np.asarray(sb.plast.hcu_mask)
            )
        assert int(sa.step) == int(sb.step)


def greedy_reference(compiled, x, y, epochs_hidden, epochs_readout, readout,
                     rng, shuffle=True, batch_size=64, states=None):
    """Alg. 1's greedy schedule written out, with no activation store: each
    hidden layer trains on batches forwarded through the frozen layers
    below it (``forward_stack``) inside every batch, then the readout on
    batches forwarded through the whole hidden stack — one jitted scan an
    epoch, over the batches ``compiled`` draws (``rng``).  Starts from
    ``compiled``'s untrained states (or ``states``); returns the trained
    layer states and the SGD head (None for the BCPNN readout)."""
    layers = compiled.layers
    states = list(compiled.state.layers if states is None else states)
    n_hidden = len(compiled.hidden_layers)
    n = x.shape[0] // batch_size * batch_size

    def batches():
        idx = rng.permutation(x.shape[0])[:n] if shuffle else np.arange(n)
        idx = idx.reshape(-1, batch_size)
        return jnp.asarray(x[idx]), jnp.asarray(y[idx])

    for li in range(n_hidden):
        below = forward_stack(layers[:li])

        @jax.jit
        def hidden_epoch(state, frozen, xs, _layer=layers[li], _below=below):
            def body(s, xb):
                return _layer.train_batch(s, _below(frozen, xb))[0], None

            return jax.lax.scan(body, state, xs)[0]

        for _ in range(epochs_hidden):
            states[li] = hidden_epoch(states[li], states[:li], batches()[0])

    below = forward_stack(layers[:n_hidden])
    if readout == "bcpnn":
        @jax.jit
        def readout_epoch(state, frozen, xs, ys, _layer=layers[-1]):
            def body(s, batch):
                xb, yb = batch
                return _layer.train_batch(s, below(frozen, xb), yb)[0], None

            return jax.lax.scan(body, state, (xs, ys))[0]

        for _ in range(epochs_readout):
            states[-1] = readout_epoch(states[-1], states[:n_hidden],
                                       *batches())
        return states, None

    params, opt, opt_state, loss_fn = sgd_readout_setup(
        compiled.network.seed, layers[n_hidden - 1].spec.n_post, y, 1e-3,
        n_classes=layers[-1].spec.n_post,
    )

    @jax.jit
    def sgd_epoch(carry, frozen, xs, ys):
        def body(carry, batch):
            p, s = carry
            xb, yb = batch
            _, g = jax.value_and_grad(loss_fn)(p, below(frozen, xb), yb)
            updates, s = opt.update(g, s, p)
            return (jax.tree_util.tree_map(lambda a, u: a + u, p, updates),
                    s), None

        return jax.lax.scan(body, carry, (xs, ys))[0]

    carry = (params, opt_state)
    for _ in range(epochs_readout):
        carry = sgd_epoch(carry, states[:n_hidden], *batches())
    return states, carry[0]


class TestCachedFusedBitExact:
    """Projecting the dataset once through each frozen prefix must be
    bit-identical to recomputing the prefix every batch (the acceptance
    contract of the activation store), checked against
    :func:`greedy_reference`."""

    @pytest.mark.parametrize("readout", ["bcpnn", "sgd"])
    def test_fit_bitexact(self, dataset, readout):
        ds, x, x_te, layout = dataset
        compiled = build_deep(layout).compile(ExecutionConfig())
        ref = build_deep(layout).compile(ExecutionConfig())
        compiled.fit((x, ds.y_train), readout=readout, **KW)
        states, head = greedy_reference(
            ref, x, ds.y_train, KW["epochs_hidden"], KW["epochs_readout"],
            readout, np.random.default_rng(ref.network.seed),
        )
        assert_states_equal(compiled.state.layers, states)
        if readout == "sgd":
            np.testing.assert_array_equal(
                np.asarray(compiled.state.readout["w"]), np.asarray(head["w"])
            )
        scores = build_forward(ref.layers)(states, head, jnp.asarray(x_te))
        np.testing.assert_array_equal(
            np.asarray(compiled.predict(x_te)), np.asarray(scores)
        )
        accuracy = float(np.mean(np.argmax(scores, -1) == ds.y_test))
        assert compiled.evaluate((x_te, ds.y_test)) == accuracy

    def test_partial_fit_bitexact(self, dataset):
        ds, x, _, layout = dataset
        compiled = build_deep(layout).compile(ExecutionConfig())
        ref = build_deep(layout).compile(ExecutionConfig())
        states = None
        for i in (0, 128):
            chunk = (x[i : i + 128], ds.y_train[i : i + 128])
            compiled.partial_fit(chunk, batch_size=64, readout="bcpnn")
            states, _ = greedy_reference(ref, *chunk, 1, 1, "bcpnn", None,
                                         shuffle=False, states=states)
        assert_states_equal(compiled.state.layers, states)
        assert int(compiled.state.layers[0].step) == 4  # 2 chunks x 2 batches

    def test_host_spill_bitexact(self, dataset):
        """A ~0 activation budget forces every cached level to host memory;
        the epoch gathers fall back transparently and numerics are
        unchanged."""
        ds, x, x_te, layout = dataset
        tiny = build_deep(layout).compile(
            ExecutionConfig(activation_budget_mb=1e-4)
        )
        roomy = build_deep(layout).compile(ExecutionConfig())
        tiny.fit((x, ds.y_train), **KW)
        roomy.fit((x, ds.y_train), **KW)
        assert tiny.activations.stats["spills"] > 0
        assert_states_equal(tiny.state.layers, roomy.state.layers)
        np.testing.assert_array_equal(
            np.asarray(tiny.predict(x_te)), np.asarray(roomy.predict(x_te))
        )
        # The spilled entries really live on host.
        assert tiny.activations.resident(3) == "host"
        assert roomy.activations.resident(3) == "device"


class TestEngineParity:
    def test_scan_matches_batch_on_deep_stack(self, dataset):
        """Both engines route gathers through the store; the deep greedy
        stack (rewires at three depths) must agree across them."""
        ds, x, _, layout = dataset
        scan = build_deep(layout).compile(ExecutionConfig(engine="scan"))
        batch = build_deep(layout).compile(ExecutionConfig(engine="batch"))
        scan.fit((x, ds.y_train), **KW)
        batch.fit((x, ds.y_train), **KW)
        assert_states_equal(scan.state.layers, batch.state.layers, exact=False)


class TestPhaseProgram:
    def test_per_layer_epoch_schedule(self, dataset):
        """epochs_hidden=[3, 2, 1] gives each greedy stage its own budget —
        step counters must reflect exactly that many epochs of 4 batches."""
        ds, x, _, layout = dataset
        net = build_deep(layout).compile(ExecutionConfig())
        net.fit((x, ds.y_train), epochs_hidden=[3, 2, 1], epochs_readout=1,
                batch_size=64)
        n_batches = 256 // 64
        assert int(net.state.layers[0].step) == 3 * n_batches
        assert int(net.state.layers[1].step) == 2 * n_batches
        assert int(net.state.layers[2].step) == 1 * n_batches
        assert int(net.state.layers[3].step) == 1 * n_batches

    def test_schedule_length_must_match(self, dataset):
        ds, x, _, layout = dataset
        net = build_deep(layout).compile(ExecutionConfig())
        with pytest.raises(ValueError, match="schedule"):
            net.fit((x, ds.y_train), epochs_hidden=[2, 2], epochs_readout=0)

    def test_history_has_wall_times(self, dataset):
        """Every epoch entry carries a seconds field; projection entries
        appear at each deep phase boundary; the sum is coarsely bounded by
        the fit wall time."""
        ds, x, _, layout = dataset
        net = build_deep(layout).compile(ExecutionConfig())
        res = net.fit((x, ds.y_train), **KW)
        epochs = [h for h in res.history if "epoch" in h]
        assert len(epochs) == 3 * 2 + 2  # 3 hidden layers x 2 + readout x 2
        assert all(h["seconds"] >= 0 for h in epochs)
        projections = [h for h in res.history if h["phase"] == "project"]
        assert [p["level"] for p in projections] == [1, 2, 3]
        total = sum(h["seconds"] for h in res.history if "seconds" in h)
        assert total <= res.wall_time_s

    def test_compile_program_shapes(self):
        from repro.runtime.program import (
            BcpnnReadoutPhase,
            HiddenPhase,
            SgdReadoutPhase,
            compile_program,
        )

        p = compile_program(3, [2, 0, 1], 4, "bcpnn")
        assert p.phases == (
            HiddenPhase(0, 2), HiddenPhase(2, 1), BcpnnReadoutPhase(4)
        )
        assert p.total_epochs == 7
        assert "hidden0 x2" in p.describe()
        # sgd with zero epochs still gets a phase (head initialization).
        p = compile_program(1, 2, 0, "sgd", readout_lr=0.01)
        assert p.phases == (HiddenPhase(0, 2), SgdReadoutPhase(0, lr=0.01))
        with pytest.raises(ValueError, match="non-negative"):
            compile_program(1, -1, 0, "bcpnn")


class TestActivationStore:
    def test_projection_reuse_and_invalidation(self, dataset):
        """Within one fit each level projects once; training an upstream
        layer (or a new dataset) invalidates exactly the levels above it."""
        ds, x, x_te, layout = dataset
        net = build_deep(layout).compile(ExecutionConfig())
        net.fit((x, ds.y_train), **KW)
        store = net.activations
        assert store.stats["projections"] == 3  # levels 1, 2, 3 — once each
        # predict on the SAME (train) array reuses the cached level-3 code.
        hits = store.stats["hits"]
        net.predict(x)
        assert store.stats["hits"] == hits + 1
        # A different dataset replaces the entries (one more projection).
        net.predict(x_te)
        assert store.stats["projections"] == 4
        # Streaming adoption publishes a new layer-0 state -> all stale.
        sess = net.streaming(layer=0, max_batch=16)
        for row in x[:16]:
            sess.feed(row)
        sess.close()
        before = store.stats["projections"]
        net.predict(x_te)
        assert store.stats["projections"] == before + 1

    def test_level_zero_is_raw_input(self, dataset):
        _, x, _, layout = dataset
        net = build_deep(layout).compile(ExecutionConfig())
        assert net.activations.level(0, list(net.state.layers), x, 64) is x

    def test_multi_dataset_entries_coexist(self, dataset):
        """Alternating fit(train)/evaluate(test) keeps BOTH projections
        cached under one budget — no per-level thrash."""
        ds, x, x_te, layout = dataset
        net = build_deep(layout).compile(ExecutionConfig())
        net.fit((x, ds.y_train), **KW)
        store = net.activations
        net.predict(x_te)  # projects the test set once
        p, h = store.stats["projections"], store.stats["hits"]
        net.predict(x)  # train level-3 STILL cached (old store evicted it)
        net.predict(x_te)  # test level-3 cached too
        assert store.stats["projections"] == p
        assert store.stats["hits"] == h + 2
        assert store.datasets == 2
        # The alternation the ROADMAP item named, repeated: zero re-projects.
        net.evaluate((x_te, ds.y_test))
        net.evaluate((x, ds.y_train))
        assert store.stats["projections"] == p

    def test_host_budget_bounds_spilled_bytes(self, dataset):
        """Host-spilled entries are bounded too: LRU host entries are
        dropped (recomputable) instead of growing host memory forever."""
        ds, x, x_te, layout = dataset
        net = build_deep(layout).compile(
            ExecutionConfig(activation_budget_mb=1e-4)
        )
        net.fit((x, ds.y_train), **KW)
        net.predict(x_te)
        store = net.activations
        # Bounded up to one working entry: the just-inserted level is never
        # dropped, so a budget smaller than a single entry keeps exactly it.
        largest = max(e.nbytes for e in store._entries.values())
        assert store.host_bytes <= max(store.host_budget_bytes, largest)
        assert store.stats["evictions"] > 0
        # Numerics unaffected by the churn.
        roomy = build_deep(layout).compile(ExecutionConfig())
        roomy.fit((x, ds.y_train), **KW)
        np.testing.assert_array_equal(
            np.asarray(net.predict(x_te)), np.asarray(roomy.predict(x_te))
        )


class TestCheckpointRoundTrip:
    @pytest.mark.parametrize("readout", ["bcpnn", "sgd"])
    def test_deep_save_load_bitexact(self, dataset, readout):
        ds, x, x_te, layout = dataset
        src = build_deep(layout).compile(ExecutionConfig())
        src.fit((x, ds.y_train), readout=readout, **KW)
        with tempfile.TemporaryDirectory() as d:
            path = src.save(d, step=3)
            dst = build_deep(layout).compile(ExecutionConfig())
            dst.load(path)
            assert_states_equal(src.state.layers, dst.state.layers)
            np.testing.assert_array_equal(
                np.asarray(src.predict(x_te)), np.asarray(dst.predict(x_te))
            )
            assert src.evaluate((x_te, ds.y_test)) == dst.evaluate(
                (x_te, ds.y_test)
            )
            # The restored network keeps training through the phase program.
            dst.partial_fit((x[:128], ds.y_train[:128]), batch_size=64)
            assert int(dst.state.layers[0].step) == int(
                src.state.layers[0].step
            ) + 2


class TestDeepServing:
    def test_streaming_serve_targets_deep_layer(self, dataset):
        """ServiceConfig(layer=...) streams online updates into a non-zero
        hidden layer of a deep stack through the unified front door."""
        from repro.runtime.service import ServiceConfig

        ds, x, _, layout = dataset
        net = build_deep(layout).compile(ExecutionConfig())
        net.fit((x, ds.y_train), **KW)
        step1 = int(net.state.layers[1].step)
        svc = net.serve(ServiceConfig(plan="streaming", max_batch=8, layer=1))
        # Layer 1 consumes level-1 codes: feed projected activations.
        h1 = net.activations.level(1, list(net.state.layers), x, 64)
        for row in np.asarray(h1[:16]):
            svc.feed(row)
        svc.close()
        assert int(net.state.layers[1].step) == step1 + 2  # 16/8 flushes
        assert int(net.state.layers[0].step) == 8  # untouched
        with pytest.raises(ValueError, match="layer"):
            ServiceConfig(layer=-1)


def test_deep_trainer_shard_map_parity():
    """Data-parallel (shard_map) deep training == single-device on 4 fake
    devices (subprocess: jax locks the device count)."""
    from tests.test_distributed import run_with_devices

    run_with_devices("""
        import jax, numpy as np
        from repro.core import (DenseLayer, ExecutionConfig, Network,
                                StructuralPlasticityLayer, UnitLayout,
                                onehot_layout)
        from repro.core.distributed import DataParallelTrainer
        from repro.data import complementary_code, mnist_like

        H1, H2, H3 = UnitLayout(4, 4), UnitLayout(3, 4), UnitLayout(2, 4)

        def build(layout):
            net = Network(seed=0)
            net.add(StructuralPlasticityLayer(layout, H1, fan_in=8, lam=0.05,
                                              init_jitter=1.0, gain=4.0))
            net.add(StructuralPlasticityLayer(H1, H2, fan_in=3, lam=0.05,
                                              init_jitter=1.0, gain=4.0))
            net.add(StructuralPlasticityLayer(H2, H3, fan_in=2, lam=0.05,
                                              init_jitter=1.0, gain=4.0))
            net.add(DenseLayer(H3, onehot_layout(10), lam=0.05))
            return net

        ds = mnist_like(n_train=256, n_test=64, n_features=16, seed=0)
        x, layout = complementary_code(ds.x_train)
        kw = dict(epochs_hidden=2, epochs_readout=2, batch_size=64,
                  shuffle=False)

        ref = build(layout).compile(ExecutionConfig())
        ref.fit((x, ds.y_train), **kw)

        mesh = jax.make_mesh((4,), ("data",))
        tr = DataParallelTrainer(mesh, mode="shard_map")
        dp = build(layout).compile(ExecutionConfig(trainer=tr))
        dp.fit((x, ds.y_train), **kw)
        for sa, sb in zip(dp.state.layers, ref.state.layers):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(sa.w)), np.asarray(sb.w),
                rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(
                np.asarray(jax.device_get(sa.marginals.cij)),
                np.asarray(sb.marginals.cij), rtol=2e-4, atol=1e-7)
            assert int(sa.step) == int(sb.step)
        print("OK")
    """, n=4)
