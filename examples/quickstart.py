"""Quickstart: the paper's Listing 1 — build, train, evaluate a BCPNN.

    PYTHONPATH=src python examples/quickstart.py

Trains the three-layer network (input -> hidden HCUs -> readout) with the
unsupervised Hebbian rule + supervised readout on an MNIST-shaped synthetic
dataset, then reports accuracy and shows the structural-plasticity mask.
The model description is purely declarative; everything about execution
(engine, distribution, precision) binds in the compile step.
"""
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
from repro.core.plasticity import fan_in
from repro.data import complementary_code, mnist_like


def main():
    enable_compile_cache()
    # 1. Data: continuous features in [0,1], complementary-coded into 2-MCU
    #    input hypercolumns (x, 1-x).
    ds = mnist_like(n_train=4096, n_test=1024, n_features=64, seed=0)
    x_train, input_layout = complementary_code(ds.x_train)
    x_test, _ = complementary_code(ds.x_test)

    # 2. Create the network (Listing 1 of the paper).
    hidden = UnitLayout(n_hcu=16, n_mcu=16)  # 256 hidden minicolumns
    model = Network(seed=0)
    model.add(
        StructuralPlasticityLayer(
            input_layout, hidden,
            fan_in=32,          # sparse receptive fields (of 64 input HCUs)
            lam=0.02,           # EWMA learning rate
            gain=4.0,           # soft-WTA sharpness
            init_jitter=1.0,    # symmetry-breaking marginal jitter
        )
    )
    model.add(DenseLayer(hidden, onehot_layout(10), lam=0.02))

    # 3. Compile: bind the declarative model to an execution strategy (the
    #    scan epoch engine by default; add trainer=/precision= to deploy the
    #    same model distributed or on the reduced-precision datapath).
    compiled = model.compile(ExecutionConfig(engine="scan"))

    # 4. Train (phase 1: unsupervised hidden; phase 2: supervised readout)
    #    and evaluate.
    res = compiled.fit(
        (x_train, ds.y_train), epochs_hidden=5, epochs_readout=5,
        batch_size=128, verbose=True,
    )
    acc = compiled.evaluate((x_test, ds.y_test))
    print(f"\ntrained in {res.wall_time_s:.1f}s — test accuracy: {acc:.3f}")

    state0 = compiled.state.layers[0]
    mask = state0.plast.hcu_mask
    print(f"receptive-field fan-in per hidden HCU: {np.asarray(fan_in(state0.plast))}")
    print(f"mask shape {mask.shape}, active fraction {float(np.asarray(mask).mean()):.2f}")


if __name__ == "__main__":
    main()
