"""Bring-up check on the chip: the paper's STL-10 BCPNN network at full width.

    python chip_smoke.py [--seed N]        # one chip: phases A and B
    python chip_smoke.py --four-chips      # four chips: data-parallel epochs

Phase A trains 27,648 features, complementary-coded to 55,296 inputs ->
StructuralPlasticityLayer 20x150 (fan-in 1024) -> DenseLayer 10 through
``Network -> compile(ExecutionConfig()) -> fit -> partial_fit -> evaluate``
and answers a few requests through ``serve(ServiceConfig(plan="batched"))``.
Phase B trains the same network on the same batches with the fused Pallas
phase kernel (``fused_phase=True, strict=True``) and compares its hidden
state with Phase A's.  ``--four-chips`` runs only the hidden layer's scan
epoch under ``DataParallelTrainer`` on a (4, 1) data mesh and a (2, 2)
data x model mesh, each against one device on the same global batch.

Weights are random from ``--seed``; data is ``stl10_like(seed=--seed)``.
The last line of standard output is one JSON object naming the device.
Off a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    DenseLayer,
    ExecutionConfig,
    Network,
    StructuralPlasticityLayer,
    UnitLayout,
    onehot_layout,
)
from repro.core.distributed import DataParallelTrainer  # noqa: E402
from repro.data import complementary_code, stl10_like  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.runtime import ServiceConfig  # noqa: E402

N_CLASSES = 10
LAM = 0.05
GAIN = 4.0


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_features: int  # raw features; complementary coding doubles them
    n_hcu: int
    n_mcu: int
    fan_in: int  # active input HCUs per hidden HCU
    n_train: int
    n_test: int
    batch: int


# The paper's STL-10 network (Sec. 4.3): 96x96x3 features, 20 HCUs x 150
# MCUs, fan-in 1,024 input HCUs.  The epoch budget is cut to a smoke run: 16
# batches of 128 per epoch.
STL10 = Sizes(
    n_features=96 * 96 * 3, n_hcu=20, n_mcu=150, fan_in=1024,
    n_train=2048, n_test=256, batch=128,
)

# Hidden-state tolerances, fused kernel (Phase B) against the jnp path
# (Phase A) after the same batches.  See TOLERANCE_REASON.
FUSED_TOL = {
    "ci": dict(rtol=1e-5, atol=1e-7),
    "cj": dict(rtol=1e-3, atol=1e-5),
    "cij": dict(rtol=1e-3, atol=1e-6),
    "w": dict(rtol=1e-2, atol=1e-3),
}
TOLERANCE_REASON = (
    "c_i is a mean of the inputs alone, so only reassociation separates the "
    "paths.  At default precision both paths multiply float32 operands in "
    "one bf16 pass on v5e and sum in float32 in different orders, so c_j, "
    "C_ij and w differ where that order tips a gain-4 softmax near-tie, "
    "and the tip grows over the batches that follow."
)
# A data-parallel step against one device: the same jnp math with the batch
# means all-reduced, so only the reduction order differs (the tolerances of
# tests/test_distributed.py).
DP_TOL = {
    "ci": dict(rtol=2e-4, atol=1e-7),
    "cj": dict(rtol=2e-4, atol=1e-7),
    "cij": dict(rtol=2e-4, atol=1e-7),
    "w": dict(rtol=2e-4, atol=2e-5),
}


class CompileClock:
    """Sums JAX's backend-compile durations while active (a persistent
    cache hit counts its retrieval time)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def make_data(sizes: Sizes, seed: int):
    ds = stl10_like(
        n_train=sizes.n_train, n_test=sizes.n_test, seed=seed,
        n_features=sizes.n_features,
    )
    x, layout = complementary_code(ds.x_train)
    x_te, _ = complementary_code(ds.x_test)
    return x, ds.y_train, x_te, ds.y_test, layout


def build_network(sizes: Sizes, layout: UnitLayout, seed: int) -> Network:
    hidden = UnitLayout(sizes.n_hcu, sizes.n_mcu)
    net = Network(seed=seed)
    net.add(StructuralPlasticityLayer(
        layout, hidden, fan_in=sizes.fan_in, lam=LAM, init_jitter=1.0,
        gain=GAIN,
    ))
    net.add(DenseLayer(hidden, onehot_layout(N_CLASSES), lam=LAM))
    return net


def hidden_snapshot(compiled) -> dict:
    """Host copy of the hidden layer's learned state."""
    st = compiled.state.layers[0]
    return {
        name: np.asarray(jax.device_get(a))
        for name, a in (("ci", st.marginals.ci), ("cj", st.marginals.cj),
                        ("cij", st.marginals.cij), ("w", st.w))
    }


def compare(got: dict, want: dict, tol: dict, what: str) -> dict:
    """Max abs error per field; raises if any field is outside ``tol``."""
    errs = {k: float(np.max(np.abs(got[k] - want[k]))) for k in tol}
    log(f"{what}: max abs error {errs}")
    for k, t in tol.items():
        np.testing.assert_allclose(got[k], want[k], **t, err_msg=f"{what}: {k}")
    return errs


def phase_a(sizes: Sizes, data, seed: int) -> dict:
    """Default config (scan engine, jnp path): fit, a second fit and a
    partial_fit on the same compiled network, evaluate, then serve."""
    x, y, x_te, y_te, layout = data
    net = build_network(sizes, layout, seed).compile(ExecutionConfig())
    with CompileClock() as clock:
        net.fit((x, y), epochs_hidden=1, epochs_readout=0,
                batch_size=sizes.batch)
        ref = hidden_snapshot(net)  # Phase B's reference
        net.fit((x, y), epochs_hidden=1, epochs_readout=1,
                batch_size=sizes.batch)
        half = (sizes.n_train // 2 // sizes.batch) * sizes.batch
        net.partial_fit((x[:half], y[:half]), batch_size=sizes.batch,
                        readout="bcpnn")
        acc = net.evaluate((x_te, y_te))
    log(f"phase A: compile {clock.seconds:.3f} s, accuracy {acc:.4f} "
        f"(chance {1 / N_CLASSES})")
    if not np.isfinite(acc) or acc <= 2.0 / N_CLASSES:
        raise AssertionError(f"phase A accuracy {acc} is not above chance")

    svc = net.serve(ServiceConfig(plan="batched"))
    n_req, k = 3, 8
    try:
        served = [np.asarray(svc.predict(x_te[i * k:(i + 1) * k]))
                  for i in range(n_req)]
    finally:
        svc.close()
    served = np.concatenate(served)
    direct = np.asarray(net.predict(x_te[:n_req * k]))
    if served.shape != (n_req * k, N_CLASSES) or not np.all(np.isfinite(served)):
        raise AssertionError(f"served scores: shape {served.shape}, "
                             f"finite {np.all(np.isfinite(served))}")
    # Same weights on row blocks of another size: the compiler may tile the
    # float32 sums differently, a few ulps of probabilities <= 1.
    np.testing.assert_allclose(served, direct, rtol=0, atol=1e-5,
                               err_msg="served vs predict")
    log(f"phase A: served {n_req} requests of {k}, max |served - predict| "
        f"{float(np.max(np.abs(served - direct)))}")
    return {"accuracy": acc, "reference": ref}


def phase_b(sizes: Sizes, data, seed: int, reference: dict) -> dict:
    """The fused phase kernel on Phase A's first epoch of batches; returns
    the errors against Phase A and the compiled epoch program's text."""
    x, y, _, _, layout = data
    net = build_network(sizes, layout, seed).compile(
        ExecutionConfig(fused_phase=True, strict=True)
    )
    with CompileClock() as clock:
        net.fit((x, y), epochs_hidden=1, epochs_readout=0,
                batch_size=sizes.batch)
    log(f"phase B: compile {clock.seconds:.3f} s")
    log(f"phase B tolerance: {TOLERANCE_REASON}")
    errs = compare(hidden_snapshot(net), reference, FUSED_TOL,
                   "phase B fused vs phase A")
    n_batches = sizes.n_train // sizes.batch
    xs = jax.ShapeDtypeStruct(
        (n_batches, sizes.batch, 2 * sizes.n_features), np.float32
    )
    epoch = net.plan.jitted["hidden_epoch[0]"]
    hlo = epoch.lower(net.state.layers[0], xs).compile().as_text()
    return {"errors": errs, "epoch_hlo": hlo}


def four_chip_phase(sizes: Sizes, data, seed: int) -> dict:
    """The hidden layer's scan epoch under DataParallelTrainer on a (4, 1)
    and a (2, 2) mesh, each against the one-device step on the same global
    batch.

    One batch, because training amplifies any difference in summation
    order: the gain-4 softmax tips near-ties, tipped winners move C_ij, and
    C_ij sets the next batch's weights.  After 16 batches a (4, 1) mesh and
    one device differ by up to 3.3 in w on v5e, after one by
    reassociation only."""
    x, y, _, _, layout = data
    batch = (x[:sizes.batch], y[:sizes.batch])

    def epoch(config):
        net = build_network(sizes, layout, seed).compile(config)
        net.fit(batch, epochs_hidden=1, epochs_readout=0,
                batch_size=sizes.batch)
        return hidden_snapshot(net)

    one = epoch(ExecutionConfig())
    errs = {}
    for shape in ((4, 1), (2, 2)):
        mesh = make_mesh(shape, ("data", "model"), devices=jax.devices()[:4])
        trainer = DataParallelTrainer(mesh, mode="shard_map")
        with CompileClock() as clock:
            got = epoch(ExecutionConfig(trainer=trainer))
        log(f"mesh {shape}: compile {clock.seconds:.3f} s")
        errs[shape] = compare(got, one, DP_TOL, f"mesh {shape} vs one device")
    return errs


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel path on four chips")
    args = ap.parse_args(argv)

    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (backend "
                 f"{jax.default_backend()!r}); this check runs on the chip")
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        sys.exit(f"chip_smoke: needs {need} chips, found {len(devices)}")
    log(f"device {devices[0].device_kind} x{len(devices)}, "
        f"compile cache {enable_compile_cache()}")

    t0 = time.perf_counter()
    data = make_data(STL10, args.seed)
    log(f"data: {data[0].shape[0]} x {data[0].shape[1]} inputs in "
        f"{time.perf_counter() - t0:.3f} s")
    if args.four_chips:
        four_chip_phase(STL10, data, args.seed)
    else:
        a = phase_a(STL10, data, args.seed)
        b = phase_b(STL10, data, args.seed, a["reference"])
        if "tpu_custom_call" not in b["epoch_hlo"]:
            raise AssertionError("fused epoch program holds no TPU kernel")
    log(f"peak bytes in use on device 0: {peak_bytes()}")
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
