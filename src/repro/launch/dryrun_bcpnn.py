import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

DOC = """Pod-scale dry-run of the PAPER'S technique: a BCPNN layer two orders
of magnitude beyond the paper's largest run (STL-10: 3000 hidden units),
lowered + compiled on the production mesh with the shard_map data-parallel
step (the MPI backend) plus beyond-paper hidden-axis model parallelism.

  bcpnn_xl: N_F = 55,296 input units (complementary-coded 96x96x3),
            hidden = 512 HCUs x 256 MCUs = 131,072 units,
            C_ij = 7.25e9 marginals (29 GB f32), global batch 16,384.

No layer scan -> compiled.cost_analysis() is exact (no probe correction
needed).  Writes experiments/dryrun/bcpnn_xl__train__{pod,multipod}.json.
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp


def run(multi_pod: bool, out_dir: str, n_f=55296, n_hcu=512, n_mcu=256,
        batch=16384, lam=0.01, fan_in=None):
    from repro.core import StructuralPlasticityLayer, UnitLayout
    from repro.core.distributed import DataParallelTrainer
    from repro.launch.dryrun import collective_bytes
    from repro.launch.mesh import make_production_mesh
    from repro.runtime.plans import BatchPlan

    mesh = make_production_mesh(multi_pod=multi_pod)
    pre = UnitLayout(n_f // 2, 2)
    post = UnitLayout(n_hcu, n_mcu)
    # Dense mask for the lowered hot step (the greedy rewire runs as its own
    # small program every N_HCU batches and is excluded from the roofline,
    # exactly as the paper treats it: "not the primary candidate for
    # performance optimization").
    layer = StructuralPlasticityLayer(
        pre, post, fan_in=fan_in or pre.n_hcu, lam=lam, init_jitter=1.0,
        gain=4.0,
    )
    # The trainer decorates an ExecutionPlan (the compile-step route); the
    # plan's per-batch hidden step is the lowering/analysis surface.
    tr = DataParallelTrainer(mesh, mode="shard_map")
    plan = tr.decorate(BatchPlan([layer]))
    step = plan.hidden_step(0)

    state_sds = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0)))
    x_sds = jax.ShapeDtypeStruct((batch, n_f), jnp.float32)

    # Shardings mirror place_state / batch_sharding.
    spec = tr._state_spec(layer, tr._can_shard_hidden(layer))
    from jax.sharding import NamedSharding

    s_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec,
        is_leaf=lambda x: x is None or isinstance(x, jax.sharding.PartitionSpec),
    )
    state_sds = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state_sds, s_shard,
    )
    x_in = jax.ShapeDtypeStruct(
        x_sds.shape, x_sds.dtype, sharding=tr.batch_sharding()
    )

    t0 = time.perf_counter()
    with mesh:
        # the trainer returns a (possibly wrapped) jitted fn; unwrap for
        # lower() by jitting the raw shard_map step directly
        lowered = step.lower(state_sds, x_in) if hasattr(step, "lower") else None
        if lowered is None:
            raise RuntimeError("hidden_step is wrapped; use mask-free layer")
        compiled = lowered.compile()
    dt = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    # Model FLOPs (per step, global): forward GEMM + outer-product GEMM.
    n_h = n_hcu * n_mcu
    model_flops = 2.0 * batch * n_f * n_h * 2
    rec = {
        "arch": "bcpnn_xl",
        "shape": f"train_b{batch}",
        "kind": "train",
        "mesh": list(mesh.devices.shape),
        "chips": int(mesh.devices.size),
        "compile_s": round(dt, 2),
        "flops_per_device": float(cost.get("flops", -1)),
        "bytes_per_device": float(cost.get("bytes accessed", -1)),
        "collectives": coll,
        "model_flops": model_flops,
        "n_f": n_f,
        "n_hidden": n_h,
        "cij_gb": n_f * n_h * 4 / 1e9,
    }
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                rec[attr] = int(v)
    # Roofline terms (no scans -> direct), projected for a v5e pod.
    from repro.launch.mesh import V5E, peaks
    from repro.launch.roofline import WIRE_WEIGHT

    chip = peaks(V5E)
    wire = sum(coll.get(op, 0.0) * w for op, w in WIRE_WEIGHT.items())
    rec["compute_term_s"] = rec["flops_per_device"] / chip.bf16_flops
    rec["memory_term_s"] = rec["bytes_per_device"] / chip.hbm_bw
    rec["collective_term_s"] = wire / chip.ici_bw
    rec["useful_flop_ratio"] = model_flops / (
        rec["flops_per_device"] * rec["chips"]
    )
    tag = "multipod" if multi_pod else "pod"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"bcpnn_xl__train__{tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    print(
        f"[bcpnn-dryrun] {tag} compile={rec['compile_s']}s "
        f"flops/dev={rec['flops_per_device']:.3e} "
        f"compute={rec['compute_term_s']:.4f}s "
        f"mem={rec['memory_term_s']:.4f}s coll={rec['collective_term_s']:.4f}s "
        f"useful={rec['useful_flop_ratio']:.3f}",
        flush=True,
    )
    return rec


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"), default="both")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--batch", type=int, default=16384)
    args = ap.parse_args()
    for mp in {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]:
        run(mp, args.out, batch=args.batch)
    return 0


if __name__ == "__main__":
    sys.exit(main())
