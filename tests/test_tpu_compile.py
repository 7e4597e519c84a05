"""Compile the training kernels, the jnp train step and the hidden epoch's
scan for one TPU v5e.

The ``v5e:2x2`` topology is described, not attached: the TPU compiler runs
here and refuses what the chip would refuse (a block shape off the (8, 128)
tiling, a lane-splitting reshape, scoped VMEM overrun, a program too big
for HBM), with no chip.  Widths are the paper's: MNIST (B=256, 1,568
inputs, 30x100 hidden) and STL-10 (B=128, 55,296 inputs, 20x150 hidden).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and pytest-xdist workers
import every test file.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.core import StructuralPlasticityLayer, UnitLayout
from repro.kernels.bcpnn_phase import bcpnn_phase_fused
from repro.kernels.bcpnn_update import bcpnn_update_fused
from repro.kernels.hcu_softmax import hcu_softmax
from repro.kernels.masked_matmul import masked_matmul
from repro.runtime import hidden_epoch_fn

# name -> (B, F, n_hcu, n_mcu)
WIDTHS = {
    "mnist": (256, 1568, 30, 100),
    "stl10": (128, 55296, 20, 150),
}
# Active input HCUs per hidden HCU in the benchmark's cells.
FAN_IN = {"mnist": 78, "stl10": 1024}
HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # Otherwise the TPU compiler writes its logs under the system temp dir.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip; keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shapes(sharding):
    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    return s


def _compile_kernel(kernel, one_chip, width):
    B, F, n_hcu, n_mcu = WIDTHS[width]
    H = n_hcu * n_mcu
    s = _shapes(one_chip)
    if kernel == "bcpnn_phase":
        lowered = bcpnn_phase_fused.lower(
            s(B, F), s(F, H), s(H), s(F, H), s(F), s(H), s(F, H),
            lam=0.01, k_b=1.0, gain=4.0, n_hcu=n_hcu, n_mcu=n_mcu,
            interpret=False,
        )
    elif kernel == "bcpnn_update":
        lowered = bcpnn_update_fused.lower(
            s(B, F), s(B, H), s(F, H), s(F), s(H), s(F, H),
            lam=0.01, interpret=False,
        )
    elif kernel == "masked_matmul":
        lowered = masked_matmul.lower(
            s(B, F), s(F, H), s(H), s(F, H), interpret=False
        )
    else:
        lowered = hcu_softmax.lower(
            s(B, H), n_hcu=n_hcu, n_mcu=n_mcu, interpret=False
        )
    return lowered.compile()


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize(
    "kernel", ["bcpnn_phase", "bcpnn_update", "masked_matmul", "hcu_softmax"]
)
def test_kernel_compiles_for_v5e(one_chip, kernel, width):
    compiled = _compile_kernel(kernel, one_chip, width)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_jnp_train_batch_compiles_for_v5e_at_stl10_width(one_chip):
    B, F, n_hcu, n_mcu = WIDTHS["stl10"]
    layer = StructuralPlasticityLayer(
        UnitLayout(F // 2, 2), UnitLayout(n_hcu, n_mcu), fan_in=1024,
        lam=0.05, init_jitter=1.0, gain=4.0,
    )
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0))),
    )
    x = _shapes(one_chip)(B, F)
    compiled = jax.jit(layer.train_batch).lower(state, x).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def _computations(hlo: str) -> dict:
    """Optimized HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) ", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and " = " in line:
            comps[name].append(line.strip())
    return comps


def _loop_outside_rewire(hlo: str) -> list:
    """Instruction lines of the epoch scan's while body and of every branch
    of its conditionals but the last: lax.cond's true branch, the rewire."""
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY (%[\w.\-]+) ", hlo, re.M).group(1)
    bodies = [
        m.group(1)
        for line in comps[entry]
        for m in [re.search(r" while\(.*body=(%[\w.\-]+)", line)]
        if m
    ]
    assert len(bodies) == 1, bodies
    lines = list(comps[bodies[0]])
    for line in comps[bodies[0]]:
        branches = re.search(r"branch_computations=\{([^}]*)\}", line)
        if branches:
            names = [b.strip() for b in branches.group(1).split(",")]
            for b in names[:-1]:
                lines += comps[b]
    return lines


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_hidden_epoch_loop_builds_no_unit_mask(one_chip, width):
    """Outside the rewire branch the epoch scan builds and moves no
    unit-granular (F×H) array: the receptive fields reach w at HCU
    granularity inside the update's fusion, and the forward reads w alone.
    A broadcast, reshape, copy, transpose or gather of F×H elements is the
    per-batch mask build (or a relayout of w) this guards against."""
    B, F, n_hcu, n_mcu = WIDTHS[width]
    layer = StructuralPlasticityLayer(
        UnitLayout(F // 2, 2), UnitLayout(n_hcu, n_mcu),
        fan_in=FAN_IN[width], lam=0.05, init_jitter=1.0, gain=4.0,
    )
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0))),
    )
    xs = _shapes(one_chip)(4, B, F)
    hlo = hidden_epoch_fn(layer).lower(state, xs).compile().as_text()
    unit_elems = F * n_hcu * n_mcu
    moves = ("broadcast", "reshape", "copy", "copy-start", "transpose", "gather")
    found = []
    for line in _loop_outside_rewire(hlo):
        m = re.match(r"(%[\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(", line)
        if not m or m.group(3) not in moves:
            continue
        for dims in re.findall(r"[a-z]+\d*\[([\d,]+)\]", m.group(2)):
            n = 1
            for d in dims.split(","):
                n *= int(d)
            if n == unit_elems:
                found.append(f"{m.group(1)} {m.group(3)} {m.group(2)[:60]}")
    assert not found, found
