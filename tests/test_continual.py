"""Continual-learning serving tier (PR 8).

Covers the online-update lifecycle end to end: online-vs-offline bit
parity of the jitted micro-batch updates, tenant adapter isolation,
merge-strategy math and convergence under shift, the drift safety loop
(detect -> snapshot -> rollback with every future resolved) on the async
engine path, strict-mode cleanliness of the interleaved update path, the
streaming-adoption ActivationStore invalidation regression, Router
affinity + shed-on-drift, and the adapter checkpoint round trip.
"""
import os

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
from repro.runtime import (
    ContinualConfig,
    DriftDetected,
    DriftWindow,
    Feedback,
    ServiceConfig,
)
from repro.runtime.epoch_engine import forward_stack

N_CLASSES = 4


def _easy_ds(seed=0):
    """Separable 4-class data: the fitted base reaches accuracy 1.0, so a
    label flip is an unambiguous drift signal."""
    ds = mnist_like(
        n_train=256, n_test=64, n_features=32, seed=seed,
        n_classes=N_CLASSES, prototypes_per_class=2, noise=0.05,
        informative_fraction=1.0,
    )
    x, layout = complementary_code(ds.x_train)
    return np.asarray(x, np.float32), np.asarray(ds.y_train), layout


def _fitted(seed=0, hidden=(4, 8)):
    """A small supervised BCPNN stack (hidden SPL + DenseLayer readout),
    fitted to convergence on the easy data."""
    xs, ys, layout = _easy_ds(seed)
    net = Network(seed=seed).add(
        StructuralPlasticityLayer(
            layout, UnitLayout(*hidden), fan_in=16, lam=0.05, gain=4.0
        )
    ).add(DenseLayer(UnitLayout(*hidden), onehot_layout(N_CLASSES), lam=0.05))
    compiled = net.compile(ExecutionConfig())
    compiled.fit((xs, ys), epochs_hidden=4, epochs_readout=4, batch_size=64)
    return compiled, xs, ys


def _cc(**kw):
    base = dict(
        update_batch=4, update_budget=16, merge_every=2, drift_window=16,
        drift_min_samples=8, drift_threshold=0.4, merge_strategy="replace",
    )
    base.update(kw)
    return ContinualConfig(**base)


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _offline_adapter(compiled, xs_rows, ys_rows, li, update_batch,
                     start_state=None):
    """Replay the online update path offline: same jit construction, same
    micro-batch grouping, starting from a fork of ``start_state`` (default:
    the live base — pass the pre-merge base explicitly when a merge already
    adopted).  Returns the final adapter LayerState (partial tail batches
    dropped, mirroring the plan's only-full-micro-batches rule)."""
    layer = compiled.layers[li]
    prefix = jax.jit(forward_stack(compiled.layers[:li])) if li > 0 else None
    update = jax.jit(lambda s, xk, yb: layer.train_batch(s, xk, yb)[0])
    if start_state is None:
        start_state = compiled.state.layers[li]
    state = jax.tree_util.tree_map(jnp.array, start_state)
    n_full = (len(xs_rows) // update_batch) * update_batch
    for i in range(0, n_full, update_batch):
        xd = jnp.asarray(np.stack(xs_rows[i:i + update_batch]))
        yd = jnp.asarray(ys_rows[i:i + update_batch], jnp.int32)
        xk = xd if prefix is None else prefix(
            tuple(compiled.state.layers[:li]), xd
        )
        state = update(state, xk, yd)
    return state


# ----------------------------------------------------------- drift window
class TestDriftWindow:
    def test_baseline_freeze_and_drift(self):
        dw = DriftWindow(window=8, min_samples=4, threshold=0.3)
        for _ in range(8):
            dw.observe(True, 0.9)
        assert not dw.drifted()  # no baseline yet
        dw.freeze_baseline()
        assert dw.baseline_samples == 8
        assert dw.samples == 0  # freeze resets the current window
        for _ in range(4):
            dw.observe(False, 0.5)
        assert dw.drifted()
        snap = dw.snapshot()
        assert snap["drifted"] and snap["baseline_accuracy"] == 1.0
        assert snap["accuracy"] == 0.0 and snap["samples"] == 4

    def test_min_samples_gates_drift(self):
        dw = DriftWindow(window=8, min_samples=4, threshold=0.1)
        for _ in range(4):
            dw.observe(True, 0.9)
        dw.freeze_baseline()
        dw.observe(False, 0.5)  # 1 < min_samples
        assert not dw.drifted()

    def test_validation(self):
        with pytest.raises(ValueError):
            DriftWindow(window=0)
        with pytest.raises(ValueError):
            DriftWindow(window=4, min_samples=8)
        with pytest.raises(ValueError):
            DriftWindow(threshold=0.0)


# ----------------------------------------------------------------- config
class TestContinualConfig:
    def test_validation(self):
        with pytest.raises(ValueError, match="update_batch"):
            ContinualConfig(update_batch=0)
        with pytest.raises(ValueError, match="drift_min_samples"):
            ContinualConfig(drift_window=8, drift_min_samples=16)
        with pytest.raises(ValueError, match="merge_strategy"):
            ContinualConfig(merge_strategy="nope")

    def test_layer_out_of_range_at_bind(self):
        compiled, xs, ys = _fitted()
        with pytest.raises(ValueError, match="out of range"):
            compiled.serve(ServiceConfig(continual=_cc(layer=5)))

    def test_plan_name_conflict_rejected(self):
        with pytest.raises(ValueError, match="plan"):
            ServiceConfig(plan="batched", continual=_cc())


# ----------------------------------------- disabled => bit-identical serving
class TestDisabledBitIdentical:
    def test_default_serve_unchanged(self):
        compiled, xs, _ = _fitted()
        svc = compiled.serve(ServiceConfig())
        assert svc.plan.name == "batched"
        np.testing.assert_array_equal(
            np.asarray(svc.predict(xs[:16])),
            np.asarray(compiled.predict(xs[:16])),
        )

    def test_frozen_inference_identical_before_first_merge(self):
        # Until a merge adopts, learning happens only in adapters — the
        # served base scores stay bit-identical to a frozen twin.
        compiled_a, xs, ys = _fitted(seed=0)
        compiled_b, _, _ = _fitted(seed=0)
        svc = compiled_a.serve(
            ServiceConfig(continual=_cc(merge_every=10_000))
        )
        for k in range(8):
            svc.plan.learn(Feedback(xs[k], int(ys[k])))
        np.testing.assert_array_equal(
            np.asarray(svc.predict(xs[:16])),
            np.asarray(compiled_b.predict(xs[:16])),
        )


# ------------------------------------------------- online/offline parity
class TestOnlineOfflineParity:
    def test_adapter_updates_bit_match_offline_replay(self):
        compiled, xs, ys = _fitted()
        svc = compiled.serve(
            ServiceConfig(continual=_cc(merge_every=10_000))
        )
        plan = svc.plan
        rows_x, rows_y = [], []
        for k in range(13):  # 3 full micro-batches + 1 dropped tail sample
            svc.plan.learn(Feedback(xs[k], int(ys[k])))
            rows_x.append(xs[k])
            rows_y.append(int(ys[k]))
        expect = _offline_adapter(
            compiled, rows_x, rows_y, plan._li, plan.cc.update_batch
        )
        _leaves_equal(plan._adapters["default"].state, expect)

    def test_partial_buffers_dropped_on_close(self):
        compiled, xs, ys = _fitted()
        svc = compiled.serve(ServiceConfig(continual=_cc()))
        svc.plan.learn(Feedback(xs[0], int(ys[0])))  # 1 of 4: stays buffered
        assert len(svc.plan._adapters["default"].buf_x) == 1
        svc.close()
        assert svc.plan._adapters["default"].buf_x == []


# ------------------------------------------------ masked weights invariant
class TestMaskedWeights:
    def test_adapter_updates_and_merges_keep_silent_blocks_zero(self):
        """Adapter updates of the hidden layer (rewires included) and the
        merges into the base leave w zero on every silent (input HCU,
        hidden HCU) block: the jnp forward reads w unmasked."""
        compiled, xs, ys = _fitted()
        svc = compiled.serve(
            ServiceConfig(continual=_cc(layer=0, update_batch=4, merge_every=3))
        )
        plan = svc.plan
        spec = compiled.layers[0].spec
        pre, post = spec.pre, spec.post

        def assert_masked(state):
            w = np.asarray(state.w).reshape(
                pre.n_hcu, pre.n_mcu, post.n_hcu, post.n_mcu
            ).transpose(0, 2, 1, 3)
            silent = np.asarray(state.plast.hcu_mask) == 0.0
            assert silent.any() and not w[silent].any()
            assert w[~silent].any()

        before = np.asarray(compiled.state.layers[0].plast.hcu_mask)
        swapped = merged = 0
        for k in range(48):
            ack = plan.learn(Feedback(xs[k], int(ys[k])))
            state = plan._adapters["default"].state
            after = np.asarray(state.plast.hcu_mask)
            swapped += bool((after != before).any())
            before = after
            merged += ack["merged"]
            assert_masked(state)
            assert_masked(compiled.state.layers[0])
        assert swapped and merged


# --------------------------------------------------------- tenant isolation
class TestTenantIsolation:
    def test_one_tenant_learning_never_touches_another(self):
        compiled, xs, ys = _fitted()
        svc = compiled.serve(
            ServiceConfig(continual=_cc(merge_every=10_000))
        )
        plan = svc.plan
        base = compiled.state.layers[plan._li]
        plan.learn(Feedback(xs[0], int(ys[0]), tenant="b"))  # buffered only
        for k in range(8):  # two applied micro-batches for tenant a
            plan.learn(Feedback(xs[k], int(ys[k]), tenant="a"))
        assert plan._adapters["a"].applied == 2
        # a's adapter moved; b's is still a bit-exact fork of the base.
        assert int(plan._adapters["a"].state.step) > int(base.step)
        _leaves_equal(plan._adapters["b"].state, base)
        # Pre-merge, the shared base object itself is untouched.
        assert compiled.state.layers[plan._li] is base


# ------------------------------------------------------- merge strategies
class TestMergeStrategies:
    def _drive_to_first_merge(self, strategy):
        compiled, xs, ys = _fitted()
        svc = compiled.serve(
            ServiceConfig(continual=_cc(merge_strategy=strategy))
        )
        plan = svc.plan
        base0 = compiled.state.layers[plan._li]
        w0 = plan._base_weight
        rows_x, rows_y = [], []
        merged = False
        k = 0
        while not merged:
            ack = plan.learn(Feedback(xs[k], int(ys[k])))
            rows_x.append(xs[k])
            rows_y.append(int(ys[k]))
            merged = ack["merged"]
            k += 1
        adapter = _offline_adapter(
            compiled, rows_x, rows_y, plan._li, plan.cc.update_batch,
            start_state=base0,
        )
        return plan, compiled, base0, w0, adapter

    def test_replace_single_tenant_is_bit_exact_adoption(self):
        plan, compiled, _, _, adapter = self._drive_to_first_merge("replace")
        _leaves_equal(compiled.state.layers[plan._li].marginals,
                      adapter.marginals)
        np.testing.assert_array_equal(
            np.asarray(compiled.state.layers[plan._li].w),
            np.asarray(adapter.w),
        )

    @pytest.mark.parametrize("strategy", ["trace", "mean"])
    def test_weighted_marginal_average(self, strategy):
        plan, compiled, base0, w0, adapter = (
            self._drive_to_first_merge(strategy)
        )
        n_applied = plan.cc.merge_every  # one tenant, merge_every updates
        if strategy == "trace":
            wb, wa = max(w0, 1.0), float(n_applied)
        else:
            wb, wa = 1.0, 1.0
        merged = compiled.state.layers[plan._li].marginals
        for got, b, a in zip(
            jax.tree_util.tree_leaves(merged),
            jax.tree_util.tree_leaves(base0.marginals),
            jax.tree_util.tree_leaves(adapter.marginals),
        ):
            want = (wb * np.asarray(b) + wa * np.asarray(a)) / (wb + wa)
            np.testing.assert_allclose(
                np.asarray(got), want, rtol=1e-6, atol=1e-7
            )

    def test_adapters_refork_from_merged_base(self):
        plan, compiled, _, _, _ = self._drive_to_first_merge("trace")
        ad = plan._adapters["default"]
        assert ad.applied == 0
        _leaves_equal(ad.state, compiled.state.layers[plan._li])

    def test_update_budget_sheds_excess_micro_batches(self):
        compiled, xs, ys = _fitted()
        svc = compiled.serve(
            ServiceConfig(
                continual=_cc(update_budget=1, merge_every=10_000)
            )
        )
        plan = svc.plan
        acks = [plan.learn(Feedback(xs[k], int(ys[k]))) for k in range(8)]
        assert sum(a["applied"] for a in acks) == 1
        assert sum(a["shed"] for a in acks) == 1
        assert plan.metrics.updates_shed.value == 1


# --------------------------------------------------- adaptation under shift
class TestAdaptationUnderShift:
    def test_merges_recover_accuracy_on_shifted_labels(self):
        # Frozen serving scores 0 on flipped labels; with the continual
        # tier (rollback off: the shift is the new truth) merges adapt the
        # base and the prequential window recovers.
        compiled, xs, ys = _fitted()
        svc = compiled.serve(
            ServiceConfig(
                continual=_cc(
                    merge_strategy="replace", rollback=False,
                    drift_threshold=10.0,  # detection off: pure adaptation
                )
            )
        )
        plan = svc.plan
        flipped = (ys + 1) % N_CLASSES
        hits = [
            plan.learn(Feedback(xs[k % 256], int(flipped[k % 256])))["correct"]
            for k in range(96)
        ]
        early, late = np.mean(hits[:16]), np.mean(hits[-16:])
        assert early < 0.5 and late > 0.8, (early, late)
        assert plan.stats["merges"] > 0


# ------------------------------------------- drift -> snapshot -> rollback
class TestDriftRollback:
    def test_drift_snapshot_rollback_all_futures_resolve(self, tmp_path):
        compiled, xs, ys = _fitted()
        snap_dir = str(tmp_path / "snaps")
        svc = compiled.serve(
            ServiceConfig(
                async_mode=True,
                continual=_cc(snapshot_dir=snap_dir, snapshot_retain=3),
            )
        )
        flipped = (ys + 1) % N_CLASSES
        futures = []
        for k in range(32):  # clean: baseline freezes, merges confirm
            futures.append(svc.submit(Feedback(xs[k], int(ys[k]))))
        for k in range(16):  # injected label shift
            futures.append(svc.submit(Feedback(xs[k], int(flipped[k]))))
        for k in range(32):  # clean again: recovery
            futures.append(svc.submit(Feedback(xs[32 + k], int(ys[32 + k]))))
            futures.append(svc.submit(xs[32 + k]))  # interleaved inference
        acks = [f.result(timeout=60) for f in futures]
        svc.drain_and_stop()
        # EVERY future resolved, across the rollback.
        assert len(acks) == 32 + 16 + 64
        learn_acks = [a for a in acks if isinstance(a, dict)]
        assert len(learn_acks) == 80
        assert any(a["rolled_back"] for a in learn_acks)
        snap = svc.stats["telemetry"]
        assert snap["drift_events"] >= 1
        assert snap["rollbacks"] >= 1
        assert snap["merges"] >= 2
        # Snapshots were written through the checkpoint manifest, bounded
        # by retain.
        ckpts = sorted(os.listdir(snap_dir))
        assert 1 <= len(ckpts) <= 3
        # The stream ended on clean traffic: the window measured healthy
        # again after the rollback.
        assert snap["drift"]["accuracy"] >= 0.8

    def test_rollback_restores_last_good_bit_exact(self):
        compiled, xs, ys = _fitted()
        svc = compiled.serve(ServiceConfig(continual=_cc()))
        plan = svc.plan
        flipped = (ys + 1) % N_CLASSES
        for k in range(32):
            plan.learn(Feedback(xs[k], int(ys[k])))
        last_good_base = plan._last_good[0]
        rolled = False
        k = 0
        while not rolled and k < 64:
            rolled = plan.learn(
                Feedback(xs[k % 256], int(flipped[k % 256]))
            )["rolled_back"]
            k += 1
        assert rolled
        # Adoption republished the exact last-good object, and every
        # adapter re-forked from it.
        assert compiled.state.layers[plan._li] is last_good_base
        _leaves_equal(plan._adapters["default"].state, last_good_base)
        assert plan.metrics.rollbacks.value == 1

    def test_rollback_disabled_only_counts(self):
        compiled, xs, ys = _fitted()
        svc = compiled.serve(
            ServiceConfig(continual=_cc(rollback=False))
        )
        plan = svc.plan
        flipped = (ys + 1) % N_CLASSES
        for k in range(32):
            plan.learn(Feedback(xs[k], int(ys[k])))
        for k in range(24):
            ack = plan.learn(Feedback(xs[k % 256], int(flipped[k % 256])))
            assert not ack["rolled_back"]
        assert plan.metrics.drift_events.value >= 1
        assert plan.metrics.rollbacks.value == 0


# ------------------------------------------------------------- strict mode
class TestStrictMode:
    def test_full_lifecycle_strict_clean(self):
        compiled, xs, ys = _fitted()
        svc = compiled.serve(
            ServiceConfig(strict=True, continual=_cc())
        )
        plan = svc.plan
        for k in range(24):  # updates + merges + interleaved inference
            plan.learn(Feedback(xs[k], int(ys[k])))
            if k % 3 == 0:
                plan.infer(xs[k])
        reg = plan._strict_registry()
        assert {"continual_update", "continual_view",
                "continual_prefix"} <= set(reg)
        assert any(n.startswith("continual_merge[") for n in reg)


# ------------------------------------- streaming adoption store invalidation
class TestStreamingAdoptionInvalidation:
    def test_adoption_drops_cached_levels_above_and_recompute_is_exact(self):
        from repro.runtime.activations import ActivationStore

        xs, ys, layout = _easy_ds()
        net = Network(seed=0).add(
            StructuralPlasticityLayer(
                layout, UnitLayout(4, 8), fan_in=16, lam=0.05, gain=4.0
            )
        ).add(
            StructuralPlasticityLayer(
                UnitLayout(4, 8), UnitLayout(4, 4), fan_in=16, lam=0.05,
                gain=4.0,
            )
        ).add(DenseLayer(UnitLayout(4, 4), onehot_layout(N_CLASSES),
                         lam=0.05))
        compiled = net.compile(ExecutionConfig())
        compiled.fit((xs, ys), epochs_hidden=2, epochs_readout=2,
                     batch_size=64)
        store = compiled.activations
        assert store is not None
        # Populate cached projections above hidden layer 0 for a second
        # dataset (a serving batch) on top of the training set's.
        probe = np.array(xs[:32])
        svc = compiled.serve(ServiceConfig(plan="batched"))
        svc.predict(probe)
        assert any(lvl > 0 for _, lvl in store._entries)
        ev0 = store.stats["evictions"]

        sess = compiled.streaming(layer=0, max_batch=8)
        for row in xs[:16]:
            sess.feed(row)
        sess.close()  # adopts the trained layer-0 state

        # Every cached level above the adopted layer was dropped eagerly,
        # at the adoption itself.
        assert all(lvl <= 0 for _, lvl in store._entries)
        assert store.stats["evictions"] > ev0
        # And the recomputed projection under the NEW states bit-matches a
        # fresh store built from scratch — no stale value survives.
        got = store.level(2, list(compiled.state.layers), probe,
                          chunk=probe.shape[0])
        fresh = ActivationStore(compiled.layers).level(
            2, list(compiled.state.layers), probe, chunk=probe.shape[0]
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(fresh))


# ------------------------------------------------------- service front door
class TestServiceFrontDoor:
    def test_sync_drain_serves_mixed_traffic_in_order(self):
        compiled, xs, ys = _fitted()
        svc = compiled.serve(
            ServiceConfig(plan="continual", continual=_cc())
        )
        assert svc.submit(Feedback(xs[0], int(ys[0])))
        assert svc.submit(xs[1])
        assert svc.submit(Feedback(xs[2], int(ys[2])))
        out = svc.drain()
        assert isinstance(out[0], dict) and isinstance(out[2], dict)
        assert np.asarray(out[1]).shape[0] == N_CLASSES

    def test_continual_config_requires_continual_plan(self):
        compiled, _, _ = _fitted()
        svc = compiled.serve(ServiceConfig(continual=_cc()))
        assert svc.plan.name == "continual"


# ------------------------------------------------------------- checkpoints
class TestAdapterCheckpoints:
    def test_snapshot_round_trip(self, tmp_path):
        from repro.checkpoint import load_adapters
        from repro.checkpoint.store import latest_checkpoint

        compiled, xs, ys = _fitted()
        snap_dir = str(tmp_path / "snaps")
        svc = compiled.serve(
            ServiceConfig(continual=_cc(snapshot_dir=snap_dir))
        )
        plan = svc.plan
        merged = False
        k = 0
        while not merged:
            merged = plan.learn(Feedback(xs[k], int(ys[k])))["merged"]
            k += 1
        _, path = latest_checkpoint(snap_dir)
        template = compiled.state.layers[plan._li]
        adapters = load_adapters(path, template)
        assert sorted(adapters) == ["default"]
        _leaves_equal(adapters["default"], plan._adapters["default"].state)

    def test_unsafe_tenant_name_rejected(self, tmp_path):
        from repro.checkpoint.network import save_network

        compiled, _, _ = _fitted()
        with pytest.raises(ValueError, match="checkpoint-safe"):
            save_network(
                str(tmp_path), 0, compiled.state,
                adapters={"../evil": compiled.state.layers[-1]},
                adapter_layer=1,
            )


# ------------------------------------------------------------------ router
class TestRouterContinual:
    def _router(self, n_engines=2, **router_kw):
        from repro.runtime import Router, RouterConfig

        engines = []

        def make_factory():
            compiled, xs, ys = _fitted()
            engines.append(compiled)

            def factory(config, metrics):
                from repro.runtime.continual import ContinualPlan

                return ContinualPlan(compiled, config, metrics)

            return factory

        router = Router(RouterConfig(routing="round_robin", **router_kw))
        cfg = ServiceConfig(continual=_cc(merge_every=10_000))
        for i in range(n_engines):
            router.add_engine(f"cl{i}", make_factory(), cfg)
        return router

    def test_tenant_affinity_pins_continual_engine(self):
        router = self._router(n_engines=2)
        _, xs, ys = _fitted()
        router.start()
        futs = [
            router.submit(Feedback(xs[k], int(ys[k]), tenant="t1"),
                          tenant="t1", pool="continual")
            for k in range(8)
        ]
        for f in futs:
            assert isinstance(f.result(timeout=60), dict)
        router.drain_and_stop()
        with router._cv:
            tenants_per_engine = [
                slot.engine.plan.stats["tenants"]
                for slot in router._slots.values()
            ]
        served = [t for t in tenants_per_engine if "t1" in t]
        assert len(served) == 1  # all eight landed on ONE engine
        assert ("continual", "t1") in router._affinity

    def test_shed_on_drift_refuses_with_typed_exception(self):
        router = self._router(n_engines=1)
        _, xs, ys = _fitted()
        router.start()
        # Prime: one served feedback records the affinity pin.
        router.submit(
            Feedback(xs[0], int(ys[0]), tenant="t1"),
            tenant="t1", pool="continual",
        ).result(timeout=60)
        with router._cv:
            slot = next(iter(router._slots.values()))
            plan = slot.engine.plan
        dw = plan.metrics.drift
        for _ in range(8):
            dw.observe(True, 0.9)
        dw.freeze_baseline()
        with plan._lock:
            plan._drifting = True
        fut = router.submit(
            Feedback(xs[1], int(ys[1]), tenant="t1"),
            tenant="t1", pool="continual",
        )
        with pytest.raises(DriftDetected):
            fut.result(timeout=60)
        assert router.metrics.tenant("t1").shed_drift.value >= 1
        with plan._lock:
            plan._drifting = False
        # Healthy again: the same tenant is served normally.
        assert isinstance(
            router.submit(
                Feedback(xs[2], int(ys[2]), tenant="t1"),
                tenant="t1", pool="continual",
            ).result(timeout=60),
            dict,
        )
        router.drain_and_stop()

    def test_shed_on_drift_opt_out(self):
        router = self._router(n_engines=1, shed_on_drift=False)
        _, xs, ys = _fitted()
        router.start()
        with router._cv:
            plan = next(iter(router._slots.values())).engine.plan
        with plan._lock:
            plan._drifting = True
        out = router.submit(
            Feedback(xs[0], int(ys[0]), tenant="t1"),
            tenant="t1", pool="continual",
        ).result(timeout=60)
        assert isinstance(out, dict)
        router.drain_and_stop()
