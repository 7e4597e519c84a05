"""Paper Fig. 2d: strong scaling of the data-parallel (MPI) backend.

Runs the STL-10-shaped proxy workload on meshes over the first 1, 2, 4, 8
of this process's devices (those that exist) and reports speedup relative
to one device.  Everything runs in this one process: a chip belongs to the
process that first touches JAX, so a child started after the import could
not reach it.  On the CPU, ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
set before the import gives eight host devices; on one physical core the
time speedup is flat there.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.bench_common import emit
from repro.core import StructuralPlasticityLayer, UnitLayout
from repro.core.distributed import DataParallelTrainer
from repro.data import complementary_code, stl10_like
from repro.launch.mesh import make_mesh


def _step_seconds(n_dev: int, x) -> float:
    layout = UnitLayout(2048, 2)
    hidden = UnitLayout(20, 150)  # paper: 3000 MCUs / 20 HCUs for STL-10
    layer = StructuralPlasticityLayer(
        layout, hidden, fan_in=512, lam=0.02, init_jitter=1.0
    )
    mesh = make_mesh((n_dev, 1), ("data", "model"),
                     devices=jax.devices()[:n_dev])
    tr = DataParallelTrainer(mesh, mode="shard_map")
    step = tr.hidden_step(layer)
    st = tr.place_state(layer, layer.init(jax.random.PRNGKey(0)))
    xb = jax.device_put(jnp.asarray(x[:512]), tr.batch_sharding())
    jax.block_until_ready(step(st, xb))
    t0 = time.perf_counter()
    for _ in range(3):
        st = step(st, xb)
    jax.block_until_ready(st.w)
    return (time.perf_counter() - t0) / 3


def main():
    ds = stl10_like(n_train=512, n_test=8, seed=0)
    x, _ = complementary_code(ds.x_train[:, :2048])
    dev = jax.devices()[0]
    where = f"{dev.platform}/{dev.device_kind}"
    times = {}
    for n in (1, 2, 4, 8):
        if n > len(jax.devices()):
            break
        times[n] = _step_seconds(n, x)
        emit(f"fig2d_scaling_n{n}_step", times[n], "s/step", where)
    for n, t in times.items():
        emit(f"fig2d_speedup_n{n}", times[1] / t, "x", where)


if __name__ == "__main__":
    main()
