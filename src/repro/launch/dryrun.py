import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST precede every other import (jax locks the
# device count at first initialization), which is why the module docstring
# and __future__ imports are sacrificed below.

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices stand in for two v5e pods, every
step function is jit-lowered with production shardings, compiled, and the
compiled artifact's memory/cost/collective footprint recorded to JSON for
the roofline analysis (launch/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun

Flags:
  --mesh pod|multipod|both    16x16 (256 chips) and/or 2x16x16 (512)
  --moe-impl psum|a2a         override the MoE dispatch scheme (perf study)
  --no-remat                  disable activation checkpointing (perf study)
  --micro N                   grad-accumulation microbatches (perf study)
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache
from repro.configs import SHAPES, batch_specs, decode_specs, get_config, shape_applicable
from repro.configs.registry import ARCH_NAMES
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.optim import AdamW
from repro.sharding.rules import ShardCtx

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


# Per-device WIRE bytes as a multiple of the op's RESULT bytes (ring/
# bidirectional-ring algorithms on a 1D slice of the mesh):
#   all-gather        receives result*(n-1)/n        ~ 1x result
#   all-reduce (ring) moves 2x the tensor            ~ 2x result
#   reduce-scatter    receives input*(n-1)/n; result is the 1/n shard,
#                     so wire ~ (n-1)x result — approximated by the mean
#                     partition count below
#   all-to-all        receives result*(n-1)/n        ~ 1x result
#   collective-permute 1x result
_WIRE_WEIGHT = {
    "all-gather": 1.0,
    "all-reduce": 2.0,
    "reduce-scatter": 15.0,  # n-1 for the 16-way axes used here
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device collective wire bytes of a (per-device) HLO module.

    Parses every collective op's result shape and applies the ring-algorithm
    wire weight above.  Fusion computations are skipped (collectives are
    never fused).  Raw per-op result-byte sums are kept alongside under
    ``raw_<op>`` for the perf-iteration analysis.
    """
    out: Dict[str, float] = {k: 0.0 for k in COLLECTIVE_OPS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped or not re.match(r"^%?[\w.\-]+\s*=", stripped):
            continue
        for op in COLLECTIVE_OPS:
            # match " op(" or " op-start(" etc.
            if re.search(rf"\b{op}(?:-start|-done)?\(", stripped):
                if f"{op}-done(" in stripped:
                    break  # counted at -start
                # XLA's collective combiner emits VARIADIC collectives with
                # TUPLE results — sum every dtype[dims] element in the
                # result type (the text before the opcode name).
                head = stripped.split("=", 1)[1].split(f"{op}", 1)[0]
                nbytes = 0.0
                for dt, dims in re.findall(r"([a-z0-9]+)\[([0-9,]*)\]", head):
                    b = float(_DTYPE_BYTES.get(dt, 4))
                    for d in dims.split(","):
                        if d:
                            b *= int(d)
                    nbytes += b
                out[op] += nbytes
                out["count"] += 1
                break
    out["total"] = sum(
        out[k] * _WIRE_WEIGHT[k] for k in COLLECTIVE_OPS
    )
    return out


def _spec_tree(ctx: ShardCtx, shapes_tree, logical_tree):
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map(
        lambda s, lg: NamedSharding(ctx.mesh, ctx.spec(lg.names, s.shape)),
        shapes_tree,
        logical_tree,
    )


def _batch_shardings(ctx: ShardCtx, specs):
    from jax.sharding import NamedSharding

    def one(s):
        if s.shape and s.shape[0] > 1:
            return NamedSharding(
                ctx.mesh,
                ctx.spec(("batch",) + (None,) * (len(s.shape) - 1), s.shape),
            )
        return NamedSharding(ctx.mesh, ctx.spec((None,) * len(s.shape)))

    return jax.tree_util.tree_map(one, specs)


def _to_bf16(tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype
        ),
        tree,
    )


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    moe_impl: Optional[str] = None,
    remat: Optional[bool] = None,
    micro: Optional[int] = None,
    print_hlo: bool = False,
    probe: Optional[Dict] = None,
    rule_overrides: Optional[Dict] = None,
) -> Dict:
    """Lower+compile one cell; returns the roofline-input record.

    probe: cost-accounting mode — {"n_layers", "n_dec_layers", "seq",
    "batch"} overrides with every scan unrolled, so compiled.cost_analysis()
    counts ALL iterations (XLA costs a while body once; launch/roofline fits
    f(L,S) from these probes and extrapolates the production cell).
    """
    import dataclasses
    from repro.configs.base import ShapeConfig

    cfg = get_config(arch)
    if moe_impl is not None:
        cfg = dataclasses.replace(cfg, moe_impl=moe_impl)
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    if micro is not None:
        cfg = dataclasses.replace(cfg, n_micro=micro)
    if rule_overrides and rule_overrides.pop("__cast_once__", None):
        cfg = dataclasses.replace(cfg, cast_params_once=True)
    if rule_overrides:
        ph = rule_overrides.pop("__pad_heads__", None)
        if ph:
            cfg = dataclasses.replace(cfg, pad_heads_to=int(ph))
        if rule_overrides.pop("__sharded_xent__", None):
            cfg = dataclasses.replace(cfg, sharded_xent=True)
        if rule_overrides.pop("__rs_grads__", None):
            cfg = dataclasses.replace(cfg, constrain_grads=True)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}

    if probe is not None:
        reps = {"n_micro": probe.get("micro", 1)}
        if "n_layers" in probe:
            reps["n_layers"] = probe["n_layers"]
        if "n_dec_layers" in probe and cfg.family == "encdec":
            reps["n_dec_layers"] = probe["n_dec_layers"]
        if cfg.family == "hybrid":
            # probe depth counts groups; convert to mamba layers
            reps["n_layers"] = probe["n_layers"] * cfg.attn_every
        if cfg.family == "moe":
            # keep first_dense_layers=fd; probe n_layers includes it
            pass
        cfg = dataclasses.replace(cfg, **reps)
        shape = ShapeConfig(
            name=f"probe_{shape.name}",
            seq_len=probe.get("seq", shape.seq_len),
            global_batch=probe.get("batch", shape.global_batch),
            kind=shape.kind,
        )
        if probe.get("micro", 1) > 1:
            # micro-marginal probes keep the scan (measuring its per-
            # iteration collectives requires trip>1 handled by caller diff)
            pass

    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = ShardCtx(mesh=mesh, unroll=probe is not None)
    if rule_overrides:
        ctx = ctx.with_rules(**rule_overrides)
    model = build_model(cfg, ctx)
    key = jax.random.PRNGKey(0)

    t0 = time.perf_counter()
    param_sds = jax.eval_shape(lambda: model.init(key))
    logical = model.logical()
    p_shard = _spec_tree(ctx, param_sds, logical)

    if shape.kind == "train":
        opt = AdamW(learning_rate=1e-4, weight_decay=0.1)
        opt_sds = jax.eval_shape(opt.init, param_sds)
        from repro.optim.adamw import AdamWState
        from jax.sharding import NamedSharding, PartitionSpec as P

        o_shard = AdamWState(
            step=NamedSharding(mesh, P()),
            mu=_spec_tree(ctx, opt_sds.mu, logical),
            nu=_spec_tree(ctx, opt_sds.nu, logical),
        )
        batch_sds = batch_specs(cfg, shape)
        b_shard = _batch_shardings(ctx, batch_sds)
        step = model.make_train_step(opt)
        jitted = jax.jit(
            step,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
        )
        args = (param_sds, opt_sds, batch_sds)
    elif shape.kind == "prefill":
        param_sds = _to_bf16(param_sds)  # serving: bf16 weights
        p_shard = _spec_tree(ctx, param_sds, logical)
        batch_sds = batch_specs(cfg, shape)
        b_shard = _batch_shardings(ctx, batch_sds)
        jitted = jax.jit(
            model.prefill, in_shardings=(p_shard, b_shard), out_shardings=None
        )
        args = (param_sds, batch_sds)
    else:  # decode
        param_sds = _to_bf16(param_sds)
        p_shard = _spec_tree(ctx, param_sds, logical)
        dspec = decode_specs(cfg, shape, model)
        c_shard = _spec_tree(ctx, dspec["cache"], model.cache_logical())
        from jax.sharding import NamedSharding, PartitionSpec as P

        t_shard = NamedSharding(
            mesh, ctx.spec(("batch", None), dspec["token"].shape)
        )
        l_shard = NamedSharding(mesh, P())
        jitted = jax.jit(
            model.decode_step,
            in_shardings=(p_shard, c_shard, t_shard, l_shard),
            out_shardings=(None, c_shard),
            donate_argnums=(1,),
        )
        args = (param_sds, dspec["cache"], dspec["token"], dspec["cur_len"])

    with mesh:
        lowered = jitted.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    if print_hlo:
        print(hlo[:100000])

    rec = {
        "arch": arch,
        "shape": shape_name,
        "probe": probe,
        "kind": shape.kind,
        "mesh": list(mesh.devices.shape),
        "chips": mesh.devices.size,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device": float(cost.get("flops", -1.0)) if cost else None,
        "bytes_per_device": float(cost.get("bytes accessed", -1.0)) if cost else None,
        "collectives": coll,
        "params": int(cfg.param_count()),
        "active_params": int(cfg.active_param_count()),
        "tokens_per_step": shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1),
        "moe_impl": cfg.moe_impl if cfg.n_experts else None,
        "remat": cfg.remat,
        "n_micro": cfg.n_micro if shape.kind == "train" else None,
        "probe_layers": cfg.n_layers if probe is not None else None,
        "probe_seq": shape.seq_len if probe is not None else None,
        "probe_batch": shape.global_batch if probe is not None else None,
    }
    if mem is not None:
        for attr in (
            "temp_size_in_bytes",
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            v = getattr(mem, attr, None)
            if v is not None:
                rec[attr] = int(v)
    return rec


def probe_suite(arch: str, shape_name: str):
    """The (depth, seq) probe grid for cost extrapolation (see roofline.py).

    Train probes run the FULL global batch with n_micro=1 so flops/collective
    volumes equal the production step exactly (microbatching only re-reads
    weights — added analytically in roofline.py).
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, _ = shape_applicable(cfg, shape)
    if not ok:
        return []
    if shape.kind == "decode":
        seqs = (4096, 8192, 16384)
    else:
        seqs = (1024, 2048, 4096)
    if cfg.family == "moe":
        la, lb = cfg.first_dense_layers + 1, cfg.first_dense_layers + 2
    else:
        la, lb = 1, 2  # hybrid: groups
    if cfg.family == "encdec":
        grid = []
        for s in seqs:
            grid += [
                {"n_layers": 1, "n_dec_layers": 1, "seq": s},
                {"n_layers": 2, "n_dec_layers": 1, "seq": s},
                {"n_layers": 1, "n_dec_layers": 2, "seq": s},
            ]
        return grid
    # Three sequence points so the per-layer fit can carry a CONSTANT term
    # (S-independent weight gathers) next to the linear and quadratic terms.
    return [
        {"n_layers": nl, "seq": s} for s in seqs for nl in (la, lb)
    ]


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"), default="pod")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--moe-impl", default=None)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--micro", type=int, default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--print-hlo", action="store_true")
    ap.add_argument(
        "--probes", action="store_true",
        help="run the unrolled cost-probe grid instead of production cells",
    )
    ap.add_argument(
        "--sp-attn", action="store_true",
        help="perf lever: padded head-group attention parallelism",
    )
    ap.add_argument(
        "--cast-once", action="store_true",
        help="perf lever: bf16 param cast hoisted out of the microbatch loop",
    )
    ap.add_argument(
        "--pad-heads", type=int, default=None,
        help="perf lever: zero-pad q heads to N so projections+attention shard",
    )
    ap.add_argument(
        "--sharded-xent", action="store_true",
        help="perf lever: vocab-shard-local label pick in the loss",
    )
    ap.add_argument(
        "--rs-grads", action="store_true",
        help="perf lever: constrain grads to param shardings (reduce-scatter)",
    )
    ap.add_argument(
        "--fsdp-only", action="store_true",
        help="perf lever: no TP — batch over ALL axes, weights 256-way FSDP "
             "(kills per-layer TP activation all-reduces; right-sizes "
             "parallelism for <=15B dense models)",
    )
    args = ap.parse_args()
    rule_overrides = {}
    if args.sp_attn:
        rule_overrides["q_groups"] = "model"
    if args.cast_once:
        rule_overrides["__cast_once__"] = True
    if args.pad_heads:
        rule_overrides["__pad_heads__"] = args.pad_heads
    if args.sharded_xent:
        rule_overrides["__sharded_xent__"] = True
    if args.rs_grads:
        rule_overrides["__rs_grads__"] = True
    if args.fsdp_only:
        rule_overrides.update({
            "batch": ("pod", "data", "model"),
            "cache_batch": ("pod", "data", "model"),
            "d_fsdp": ("data", "model"),
            "mlp": None,
            "heads": None,
            "kv_heads": None,
            "ssm_heads": None,
        })

    cells = []
    if args.all:
        for a in ARCH_NAMES:
            for s in SHAPES:
                cells.append((a, s))
    else:
        archs = [args.arch] if args.arch else list(ARCH_NAMES)
        shapes = [args.shape] if args.shape else list(SHAPES)
        cells = [(a, s) for a in archs for s in shapes]

    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)
    failures = 0

    if args.probes:
        for arch, shape in cells:
            for i, probe in enumerate(probe_suite(arch, shape)):
                tag = f"{arch}__{shape}__probe{i}"
                if args.tag:
                    tag += f"__{args.tag}"
                out_path = os.path.join(args.out, tag + ".json")
                if os.path.exists(out_path):
                    continue  # incremental
                try:
                    rec = run_cell(
                        arch, shape, False, probe=probe,
                        moe_impl=args.moe_impl,
                        remat=False if args.no_remat else None,
                        rule_overrides=dict(rule_overrides),
                    )
                    print(
                        f"[probe] ok {tag} L={probe.get('n_layers')} "
                        f"S={probe.get('seq')} compile={rec.get('compile_s')}s "
                        f"flops={rec.get('flops_per_device')}",
                        flush=True,
                    )
                except Exception as e:  # noqa: BLE001
                    failures += 1
                    rec = {
                        "arch": arch, "shape": shape, "probe": probe,
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-2000:],
                    }
                    print(f"[probe] FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                with open(out_path, "w") as f:
                    json.dump(rec, f, indent=2)
        return 1 if failures else 0

    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'multipod' if mp else 'pod'}"
            if args.tag:
                tag += f"__{args.tag}"
            out_path = os.path.join(args.out, tag + ".json")
            if os.path.exists(out_path):
                continue  # incremental sweep
            try:
                rec = run_cell(
                    arch, shape, mp,
                    moe_impl=args.moe_impl,
                    remat=False if args.no_remat else None,
                    micro=args.micro,
                    print_hlo=args.print_hlo,
                    rule_overrides=dict(rule_overrides),
                )
            except Exception as e:  # noqa: BLE001 — record and continue
                failures += 1
                rec = {
                    "arch": arch, "shape": shape, "mesh_multipod": mp,
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:],
                }
                print(f"[dryrun] FAIL {tag}: {type(e).__name__}: {e}", flush=True)
            else:
                status = rec.get("skipped") and "SKIP" or "ok"
                print(
                    f"[dryrun] {status:4s} {tag} "
                    f"compile={rec.get('compile_s', '-')}s "
                    f"flops/dev={rec.get('flops_per_device', '-')} "
                    f"coll={rec.get('collectives', {}).get('total', '-')}",
                    flush=True,
                )
            with open(out_path, "w") as f:
                json.dump(rec, f, indent=2)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
