"""Variable-precision study (paper Fig. 3) as a runnable example.

    PYTHONPATH=src python examples/precision_study.py

ONE declarative model description, compiled once per FloPoCo-style bfloat
format: the precision policy binds at compile time (a deployment choice,
like the paper's FPGA datapath), not in the layer declarations.  Sweeps
BF14..BF28 through the full BCPNN datapath and prints the accuracy curve —
reproducing the paper's finding that BCPNN tolerates BF16 with minor loss
while BF14 collapses to chance.
"""
from repro.launch.compile_cache import enable_compile_cache
from repro.core import (
    DenseLayer, ExecutionConfig, Network, StructuralPlasticityLayer,
    UnitLayout, onehot_layout,
)
from repro.data import complementary_code, mnist_like
from repro.precision import FORMATS


def main():
    enable_compile_cache()
    ds = mnist_like(n_train=2048, n_test=512, n_features=64, seed=0)
    x_tr, layout = complementary_code(ds.x_train)
    x_te, _ = complementary_code(ds.x_test)
    hidden = UnitLayout(16, 16)

    # The model is declared ONCE, with no precision anywhere in it.
    net = Network(seed=0)
    net.add(StructuralPlasticityLayer(
        layout, hidden, fan_in=32, lam=0.02, gain=4.0, init_jitter=1.0,
    ))
    net.add(DenseLayer(hidden, onehot_layout(10), lam=0.02))

    print(f"{'format':8s} {'mantissa':>8s} {'accuracy':>9s}")
    for name in ("fp32", "bf28", "bf24", "bf20", "bf16", "bf15", "bf14"):
        # compile() binds the datapath format; "fp32" means no emulation.
        cfg = ExecutionConfig() if name == "fp32" else ExecutionConfig(precision=name)
        compiled = net.compile(cfg)
        compiled.fit((x_tr, ds.y_train), epochs_hidden=4, epochs_readout=4,
                     batch_size=128)
        acc = compiled.evaluate((x_te, ds.y_test))
        mb = FORMATS[name].mantissa_bits
        print(f"{name:8s} {mb:8d} {acc:9.3f}")


if __name__ == "__main__":
    main()
