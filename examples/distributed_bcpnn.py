"""Data-parallel BCPNN training — the paper's MPI backend on a JAX mesh.

    PYTHONPATH=src python examples/distributed_bcpnn.py

Runs on 8 fake host devices (set before jax import).  ONE declarative model
description is compiled three ways — (a) single device, (b) shard_map with
explicit pmean (the paper's MPI_Allreduce), (c) sharding-annotated pjit —
by swapping only the ExecutionConfig's trainer decoration, and all three
fits produce identical weights.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
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
from repro.data import complementary_code, mnist_like  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def build(layout):
    hidden = UnitLayout(8, 16)
    net = Network(seed=0)
    net.add(StructuralPlasticityLayer(layout, hidden, fan_in=32, lam=0.05,
                                      init_jitter=1.0))
    net.add(DenseLayer(hidden, onehot_layout(10), lam=0.05))
    return net


def main():
    enable_compile_cache()
    print(f"devices: {len(jax.devices())}")
    ds = mnist_like(n_train=512, n_test=64, n_features=64, seed=0)
    x, layout = complementary_code(ds.x_train)
    kw = dict(epochs_hidden=2, epochs_readout=2, batch_size=128)

    # (a) single-device reference: default ExecutionConfig.
    ref = build(layout).compile(ExecutionConfig())
    ref.fit((x, ds.y_train), **kw)
    w_ref = np.asarray(jax.device_get(ref.state.layers[0].w))

    # (b)+(c) same model, 4-way data x 2-way model mesh — only the config
    # changes; the trainer decorates the execution plan.
    mesh = make_mesh((4, 2), ("data", "model"))
    for mode in ("shard_map", "pjit"):
        trainer = DataParallelTrainer(mesh, mode=mode)
        compiled = build(layout).compile(ExecutionConfig(trainer=trainer))
        compiled.fit((x, ds.y_train), **kw)
        w = np.asarray(jax.device_get(compiled.state.layers[0].w))
        err = float(jnp.max(jnp.abs(w - w_ref)))
        print(f"{mode:10s}: max |w - w_ref| = {err:.2e} "
              f"({'OK' if err < 1e-3 else 'MISMATCH'})")


if __name__ == "__main__":
    main()
