"""Depth sweep: project-once (phase program) vs fused frozen-stack training.

The fused path recomputes the frozen stack below the training layer inside
every scan body, so a depth-D STL-10-shaped network pays O(D^2 * epochs)
passes of the dominant 55296-unit first-layer GEMM; the project-once
activation store pays each frozen prefix exactly once per phase.  This
bench sweeps depth 1..3 on the STL-10-shaped proxy (27648 raw features,
complementary-coded to 55296 units) and reports whole-fit wall-clock for
both paths plus the per-phase split at depth 3 — the ISSUE-4 acceptance
criterion is >= 2x on the hidden+readout phases at depth 3 (CPU).

Wall-times come from ``FitResult.history`` ``seconds`` entries (blocked on
the epoch result), so compile/trace time of the first epoch of each phase
is included for BOTH paths — the fused path traces bigger programs, which
is part of what it costs.
"""
from __future__ import annotations

from benchmarks.bench_common import emit
from repro.core import (
    DenseLayer,
    ExecutionConfig,
    Network,
    StructuralPlasticityLayer,
    UnitLayout,
    onehot_layout,
)
from repro.data import complementary_code, stl10_like

WIDTHS = [(20, 50), (20, 40), (20, 30)]  # hidden UnitLayouts by depth
EPOCHS = 6


def build_deep(layout, depth, seed=0):
    net = Network(seed=seed)
    pre = layout
    for n_hcu, n_mcu in WIDTHS[:depth]:
        post = UnitLayout(n_hcu, n_mcu)
        net.add(
            StructuralPlasticityLayer(
                pre, post, fan_in=min(512, pre.n_hcu), lam=0.05,
                init_jitter=1.0, gain=4.0,
            )
        )
        pre = post
    net.add(DenseLayer(pre, onehot_layout(10), lam=0.05))
    return net


def phase_split(history):
    """{phase: seconds} over training epochs + projections.  A ``stage``
    entry (a host array placed on the device for a phase) is charged to the
    phase whose epochs follow it."""
    agg = {}
    staged = 0.0
    for h in history:
        if "seconds" not in h:
            continue
        if h["phase"] == "stage":
            staged += h["seconds"]
            continue
        agg[h["phase"]] = agg.get(h["phase"], 0.0) + h["seconds"] + staged
        staged = 0.0
    return agg


def frozen_phase_seconds(split):
    """Seconds spent on phases that consume frozen-stack representations
    (everything except hidden0, whose input is the raw dataset in BOTH
    paths).  The cached side is charged its phase-boundary projections."""
    return sum(v for k, v in split.items() if k != "hidden0")


def main():
    ds = stl10_like(n_train=256, n_test=64, seed=0)
    x, layout = complementary_code(ds.x_train)

    # The cached path runs FIRST (cold allocator/trace caches), so shared-CPU
    # warm-up bias — if any — works against the project-once numbers.
    for depth in (1, 2, 3):
        split = {}
        for cached in (True, False):
            tag = "cached" if cached else "fused"
            net = build_deep(layout, depth).compile(
                ExecutionConfig(cache_activations=cached)
            )
            res = net.fit(
                (x, ds.y_train), epochs_hidden=EPOCHS,
                epochs_readout=EPOCHS, batch_size=64,
            )
            split[tag] = phase_split(res.history)
            total = sum(split[tag].values())
            emit(
                f"deep_d{depth}_{tag}_train_s", total, "s",
                f"{EPOCHS} epochs/phase; history-sum incl. trace",
            )
        total_speedup = sum(split["fused"].values()) / max(
            sum(split["cached"].values()), 1e-9
        )
        emit(
            f"deep_d{depth}_total_speedup", total_speedup, "x",
            "fused / project-once, whole fit (incl. the shared hidden0 phase)",
        )
        if depth > 1:
            frozen = frozen_phase_seconds(split["fused"]) / max(
                frozen_phase_seconds(split["cached"]), 1e-9
            )
            emit(
                f"deep_d{depth}_frozen_phases_speedup", frozen, "x",
                "hidden1+/readout phases (frozen-stack inputs); projections "
                "charged to the cached side",
            )
        if depth == 3:
            for phase in sorted(set(split["fused"]) | set(split["cached"])):
                emit(
                    f"deep_d3_phase_{phase}_s",
                    split["cached"].get(phase, 0.0), "s",
                    f"fused={split['fused'].get(phase, 0.0):.2f}s",
                )


if __name__ == "__main__":
    main()
