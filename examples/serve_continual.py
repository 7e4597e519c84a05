"""Continual-learning serving: online Hebbian updates under live traffic.

    PYTHONPATH=src python examples/serve_continual.py --smoke
    PYTHONPATH=src python examples/serve_continual.py --smoke --strict

BCPNN learning is a cheap local EWMA update — no backward pass — so the
same jitted ``train_batch`` the phase programs run offline can interleave
with inference on the serving thread.  This example drives that tier end
to end through the async engine:

1. Fit a small supervised BCPNN stack (hidden layer + DenseLayer readout).
2. Serve it with ``ServiceConfig(continual=ContinualConfig(...))``: labeled
   ``Feedback`` submits route to ``learn()`` (prequential drift evaluation,
   per-tenant adapter micro-batch updates, periodic adapter->base merges),
   plain rows route to ``infer()`` — mixed traffic, one engine thread.
3. Two tenants: ``store-a`` streams clean labels throughout; ``store-b``
   suffers an injected label shift mid-stream.  The drift window detects
   the degradation, a merge snapshot exists through the checkpoint
   manifest, and the safety loop rolls base + adapters back to last-good
   — while every submitted future still resolves.
4. Recovery: clean traffic refills the window; the final telemetry line
   shows updates / merges / rollbacks / drift events.

``--strict`` runs the whole stream under the transfer guard with the
recompile sentinel proving the interleaved update path compiles once.
"""
import argparse
import tempfile
import time

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
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
    Feedback,
    ServiceConfig,
    format_latency_line,
)

N_CLASSES = 4


def build_fitted(seed=0):
    ds = mnist_like(
        n_train=256, n_test=64, n_features=32, seed=seed,
        n_classes=N_CLASSES, prototypes_per_class=2, noise=0.05,
        informative_fraction=1.0,
    )
    x, layout = complementary_code(ds.x_train)
    xs = np.asarray(x, np.float32)
    net = Network(seed=seed).add(
        StructuralPlasticityLayer(
            layout, UnitLayout(4, 8), fan_in=16, lam=0.05, gain=4.0
        )
    ).add(DenseLayer(UnitLayout(4, 8), onehot_layout(N_CLASSES), lam=0.05))
    compiled = net.compile(ExecutionConfig())
    compiled.fit((xs, ds.y_train), epochs_hidden=4, epochs_readout=4,
                 batch_size=64)
    return compiled, xs, np.asarray(ds.y_train)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced stream for CI (default sizes are small "
                    "anyway; --smoke halves them)")
    ap.add_argument("--strict", action="store_true",
                    help="transfer guard + recompile sentinel on the "
                    "interleaved update path")
    ap.add_argument("--samples", type=int, default=None,
                    help="feedback samples per phase (overrides --smoke)")
    args = ap.parse_args()
    n = args.samples if args.samples is not None else (24 if args.smoke else 48)

    compiled, xs, ys = build_fitted()
    flipped = (ys + 1) % N_CLASSES
    snap_dir = tempfile.mkdtemp(prefix="continual_snaps_")
    service = compiled.serve(
        ServiceConfig(
            async_mode=True,
            strict=args.strict,
            continual=ContinualConfig(
                update_batch=4, merge_every=2, update_budget=16,
                drift_window=16, drift_min_samples=8, drift_threshold=0.4,
                merge_strategy="replace", snapshot_dir=snap_dir,
            ),
        )
    )

    futures = []
    t0 = time.perf_counter()
    # Phase 1 — both tenants clean: baseline freezes, merges confirm.
    for k in range(n):
        futures.append(service.submit(
            Feedback(xs[k], int(ys[k]), tenant="store-a")))
        futures.append(service.submit(
            Feedback(xs[k + n], int(ys[k + n]), tenant="store-b")))
    # Phase 2 — store-b's labels shift (a broken upstream labeler);
    # store-a stays clean and keeps serving.
    for k in range(n // 2):
        futures.append(service.submit(
            Feedback(xs[k], int(ys[k]), tenant="store-a")))
        futures.append(service.submit(
            Feedback(xs[k], int(flipped[k]), tenant="store-b")))
        futures.append(service.submit(xs[k]))  # interleaved inference
    # Phase 3 — clean again: the rolled-back base recovers the window.
    for k in range(n):
        futures.append(service.submit(
            Feedback(xs[k], int(ys[k]), tenant="store-b")))

    acks = [f.result(timeout=120) for f in futures]
    service.drain_and_stop()
    dt = time.perf_counter() - t0

    learn_acks = [a for a in acks if isinstance(a, dict)]
    n_rollback_acks = sum(a["rolled_back"] for a in learn_acks)
    snap = service.stats["telemetry"]
    drift = snap["drift"]
    print(
        f"[continual] {len(learn_acks)} feedback + "
        f"{len(acks) - len(learn_acks)} inference in {dt:.2f}s "
        f"({len(acks) / dt:.0f} items/s), tenants "
        f"{service.stats['tenants']}"
    )
    print(
        f"[safety]    drift events={int(snap['drift_events'])} "
        f"rollbacks={int(snap['rollbacks'])} "
        f"(rolled-back acks resolved: {n_rollback_acks}); final window "
        f"acc={drift['accuracy']:.3f}"
        + (f" baseline={drift['baseline_accuracy']:.3f}"
           if drift["baseline_accuracy"] is not None else "")
    )
    print("[telemetry] " + format_latency_line(
        snap, "queue_wait_s", "update_s", "e2e_s"))
    assert len(acks) == len(futures), "every future must resolve"
    assert snap["merges"] >= 1, "expected at least one adapter merge"
    if snap["drift_events"] >= 1:
        print(f"[snapshots] base+adapter manifests in {snap_dir}")


if __name__ == "__main__":
    main()
