"""Deep greedy BCPNN on an STL-10-shaped pipeline (the phase program).

    PYTHONPATH=src python examples/deep_stl10.py [--smoke]

StreamBrain's headline scale claim is BCPNN at STL-10 size (27648 input
features, Sec. V); follow-on work stacks the same greedy pipeline deeper.
This example trains a THREE-hidden-layer stack with a per-layer epoch
schedule — each ``fit`` compiles into an explicit phase program
(hidden0 -> hidden1 -> hidden2 -> readout), and at every phase boundary the
dataset is projected ONCE through the newly-frozen prefix and cached
(project-once activation store), so upper layers train on cached hidden
codes instead of re-running the frozen stack per batch.  The per-phase
wall-times printed at the end come straight from ``FitResult.history``.

``--smoke`` shrinks every dimension for CI; the default sizes exercise the
real 27648-feature STL-10 shape on CPU in a few minutes.
"""
import argparse
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.core import (
    DenseLayer,
    ExecutionConfig,
    Network,
    StructuralPlasticityLayer,
    UnitLayout,
    onehot_layout,
)
from repro.data import complementary_code, stl10_like


def build_deep(input_layout, widths, fan_in, seed=0):
    """input -> greedy plasticity stack (one layer per width) -> readout."""
    net = Network(seed=seed)
    pre = input_layout
    for n_hcu, n_mcu in widths:
        post = UnitLayout(n_hcu, n_mcu)
        net.add(
            StructuralPlasticityLayer(
                pre, post, fan_in=min(fan_in, pre.n_hcu), lam=0.05,
                init_jitter=1.0, gain=4.0,
            )
        )
        pre = post
    net.add(DenseLayer(pre, onehot_layout(10), lam=0.05))
    return net


def phase_seconds(history):
    """Aggregate FitResult.history into ordered per-phase wall-times."""
    agg = {}
    for h in history:
        if "seconds" not in h:
            continue
        key = h["phase"] if h["phase"] != "project" else f"project->{h['level']}"
        agg[key] = agg.get(key, 0.0) + h["seconds"]
    return agg


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny dimensions for CI (seconds, not minutes)")
    ap.add_argument("--strict", action="store_true",
                    help="strict verification: transfer guard on every "
                         "dispatch, recompile sentinel, finite-value checks")
    ap.add_argument("--fused-phase", action="store_true",
                    help="one-dispatch training: each hidden batch runs as a "
                         "single fused Pallas mega-kernel (interpret mode "
                         "off-TPU; bit-exact with the unfused kernel path)")
    args = ap.parse_args()

    if args.smoke:
        ds = stl10_like(n_train=512, n_test=128, n_features=256, seed=0,
                        informative_fraction=0.5)
        widths = [(10, 16), (8, 16), (6, 16)]
        schedule, epochs_readout, fan_in = [4, 2, 2], 4, 128
    else:
        ds = stl10_like(n_train=512, n_test=128, seed=0)  # full 27648 feats
        widths = [(20, 50), (20, 40), (20, 30)]
        schedule, epochs_readout, fan_in = [4, 3, 2], 4, 512

    x_tr, layout = complementary_code(ds.x_train)
    x_te, _ = complementary_code(ds.x_test)

    model = build_deep(layout, widths, fan_in)
    # project-once by default; --strict layers the hot-path guards on top
    compiled = model.compile(
        ExecutionConfig(strict=args.strict, fused_phase=args.fused_phase)
    )

    t0 = time.perf_counter()
    res = compiled.fit(
        (x_tr, ds.y_train),
        epochs_hidden=schedule,       # per-layer budget: deep greedy stacks
        epochs_readout=epochs_readout,  # want more epochs at the bottom
        batch_size=64,
        verbose=True,
    )
    acc = compiled.evaluate((x_te, ds.y_test))

    print(f"\ntrained in {time.perf_counter() - t0:.1f}s — "
          f"test accuracy {acc:.3f} (chance 0.1)")
    print("per-phase wall-time (from FitResult.history):")
    for phase, sec in phase_seconds(res.history).items():
        print(f"  {phase:>12s}: {sec:7.2f}s")
    print("activation store:", compiled.activations.stats)


if __name__ == "__main__":
    main()
