"""Roofline analysis from dry-run artifacts.

Combines, per (arch x shape) cell on the single-pod mesh:

* the PRODUCTION record (scan-over-layers program): compile proof,
  ``memory_analysis`` (peak per-device memory — scans make this exact);
* the PROBE records (fully unrolled, reduced depth/seq): exact per-iteration
  costs, because XLA's cost analysis counts a while-loop body ONCE — raw
  cost_analysis on the production program undercounts flops/bytes/collective
  volume by every scan trip count (layers, q/kv chunks, SSD chunks,
  microbatches).

Extrapolation model, fitted exactly from the probe grid:

    f(L, S) = base(S) + L * layer(S)
    base(S)  = delta + gamma * S          (embed/unembed/loss/optimizer)
    layer(S) = alpha * S + beta * S**2    (linear matmuls + quadratic attn)

with probes at two depths x two sequence lengths (enc-dec: three depth
combinations to separate encoder and decoder layers).  Train probes run the
full global batch with n_micro=1, so flops/collective volume equal the
production step exactly; the microbatch loop's extra weight re-reads are
added analytically to the bytes term.

Terms are projected for a TPU v5e chip (``peaks(V5E)`` in launch/mesh.py):
compute = flops / bf16 peak, memory = bytes / HBM bandwidth, collective =
collective_bytes / interconnect bandwidth.  All per-device (equivalent to
the global-total / (chips x rate) form for uniform sharding).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, Optional, Tuple

from repro.configs import SHAPES, get_config
from repro.launch.mesh import V5E, peaks

METRICS = ("flops_per_device", "bytes_per_device", "coll_total")


# Per-device wire bytes per RESULT byte (ring algorithms; 16-way axes):
# all-reduce moves 2x the tensor; reduce-scatter receives (n-1)x its (1/n)
# result; gather/all-to-all/permute receive ~1x their result.
WIRE_WEIGHT = {
    "all-gather": 1.0,
    "all-reduce": 2.0,
    "reduce-scatter": 15.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _metric(rec: Dict, name: str) -> float:
    if name == "coll_total":
        coll = rec.get("collectives", {})
        return float(sum(coll.get(op, 0.0) * w for op, w in WIRE_WEIGHT.items()))
    return float(rec.get(name) or 0.0)


def _nonneg_basis_fit(ss, vs, basis) -> List[float]:
    """Least-squares fit of vs(ss) over the given basis functions with all
    coefficients constrained nonnegative (costs live in the physical cone;
    unconstrained extrapolation from noisy probes explodes).

    Tiny exhaustive NNLS: tries every basis subset, keeps the feasible
    (all-nonnegative) solution with the smallest residual.
    """
    import itertools

    import numpy as np

    ss = np.asarray(ss, np.float64)
    vs = np.maximum(np.asarray(vs, np.float64), 0.0)
    best, best_res = None, None
    nb = len(basis)
    for r in range(nb, 0, -1):
        for subset in itertools.combinations(range(nb), r):
            a = np.stack([basis[i](ss) for i in subset], axis=1)
            coef, *_ = np.linalg.lstsq(a, vs, rcond=None)
            if (coef < -1e-12).any():
                continue
            res = float(np.sum((a @ coef - vs) ** 2))
            if best_res is None or res < best_res - 1e-9:
                full = [0.0] * nb
                for i, c in zip(subset, coef):
                    full[i] = max(float(c), 0.0)
                best, best_res = full, res
        if best is not None and best_res <= 1e-12 * float(np.sum(vs**2) + 1.0):
            break
    return best if best is not None else [0.0] * nb


def _fit_linear(ss, vs) -> Tuple[float, float]:
    """base(S) = delta + gamma*S (nonneg least squares over >=2 points)."""
    c = _nonneg_basis_fit(ss, vs, [lambda s: s * 0 + 1.0, lambda s: s])
    return c[0], c[1]


def _fit_layer(ss, ls) -> Tuple[float, float, float]:
    """layer(S) = w + alpha*S + beta*S^2 (nonneg LS; w captures the
    S-independent per-layer cost — e.g. FSDP weight gathers — which a
    constant-free fit would misattribute to alpha*S and inflate ~S_real/S_probe
    times under extrapolation)."""
    c = _nonneg_basis_fit(
        ss, ls, [lambda s: s * 0 + 1.0, lambda s: s, lambda s: s * s]
    )
    return c[0], c[1], c[2]


def extrapolate(
    probes: List[Dict], cfg, shape, metric: str
) -> Optional[float]:
    """Fit f(L,S) from probes and evaluate at the production (L, S)."""
    if not probes or any("error" in p for p in probes):
        return None
    if cfg.family == "encdec":
        return _extrapolate_encdec(probes, cfg, shape, metric)
    by = {}
    for p in probes:
        by[(p["probe"]["n_layers"], p["probe"]["seq"])] = _metric(p, metric)
    depths = sorted({k[0] for k in by})
    seqs = sorted({k[1] for k in by if (depths[0], k[1]) in by and (depths[-1], k[1]) in by})
    if len(depths) < 2 or len(seqs) < 2:
        return None
    la, lb = depths[0], depths[1]
    lays = [max((by[(lb, s)] - by[(la, s)]) / (lb - la), 0.0) for s in seqs]
    bases = [max(by[(la, s)] - la * lay, 0.0) for s, lay in zip(seqs, lays)]
    delta, gamma = _fit_linear(seqs, bases)
    w, alpha, beta = _fit_layer(seqs, lays)

    s_real = shape.seq_len
    if cfg.family == "hybrid":
        l_real = cfg.n_layers // cfg.attn_every  # probe unit = group
    else:
        l_real = cfg.n_layers
    return (
        delta + gamma * s_real
        + l_real * (w + alpha * s_real + beta * s_real**2)
    )


def _extrapolate_encdec(probes, cfg, shape, metric):
    by = {}
    for p in probes:
        key = (p["probe"]["n_layers"], p["probe"]["n_dec_layers"], p["probe"]["seq"])
        by[key] = _metric(p, metric)
    seqs = sorted({k[2] for k in by})
    if len(seqs) < 2:
        return None
    encs, decs, bases = [], [], []
    for s in seqs:
        f11, f21, f12 = by[(1, 1, s)], by[(2, 1, s)], by[(1, 2, s)]
        enc = max(f21 - f11, 0.0)
        dec = max(f12 - f11, 0.0)
        encs.append(enc)
        decs.append(dec)
        bases.append(max(f11 - enc - dec, 0.0))
    delta, gamma = _fit_linear(seqs, bases)
    we, ae, be = _fit_layer(seqs, encs)
    wd, ad, bd = _fit_layer(seqs, decs)
    s_real = shape.seq_len
    return (
        delta + gamma * s_real
        + cfg.n_layers * (we + ae * s_real + be * s_real**2)
        + cfg.n_dec_layers * (wd + ad * s_real + bd * s_real**2)
    )


def analytic_hbm_bytes(cfg, shape, chips: int, n_micro: int, arg_bytes) -> float:
    """First-order per-chip HBM traffic model.

    XLA's `bytes accessed` counts every (unfused) op's operands — a gross
    upper bound on real HBM traffic (TPU fuses elementwise chains).  The
    dominance decision therefore uses this analytic lower-bound-style model;
    the HLO number is reported alongside as `memory_hlo_upper_s`.

      train:   n_micro x bf16 weight reads (TP-sharded) + f32 optimizer
               states/params r/w + remat-era activation traffic
               (~64 B/token/layer/d_model: ~16 bf16 tensors written+read,
               x2 for the recompute pass)
      prefill: one weight read + fwd activation traffic (~32 B/token/layer/d)
      decode:  every argument byte (params shard + cache shard) read once —
               the canonical decode bound.
    """
    tp = 16
    n = cfg.param_count()
    d = cfg.d_model
    layers = cfg.n_layers + (cfg.n_dec_layers if cfg.family == "encdec" else 0)
    if shape.kind == "decode":
        return float(arg_bytes or 2.0 * n / chips)
    tokens_local = shape.global_batch * shape.seq_len / chips
    if shape.kind == "train":
        w = n_micro * 2.0 * n / tp
        opt = 16.0 * n / chips
        act = tokens_local * d * layers * 64.0
        return w + opt + act
    return 2.0 * n / tp + tokens_local * d * layers * 32.0


def analyze_cell(dryrun_dir: str, arch: str, shape_name: str, tag: str = "") -> Optional[Dict]:
    suffix = f"__{tag}" if tag else ""
    prod_path = os.path.join(dryrun_dir, f"{arch}__{shape_name}__pod{suffix}.json")
    if not os.path.exists(prod_path):
        return None
    with open(prod_path) as f:
        prod = json.load(f)
    if "skipped" in prod and prod.get("skipped"):
        return {"arch": arch, "shape": shape_name, "skipped": prod["skipped"]}
    if "error" in prod:
        return {"arch": arch, "shape": shape_name, "error": prod["error"]}

    import re as _re

    probes = []
    pat = _re.compile(
        _re.escape(f"{arch}__{shape_name}__probe") + r"\d+"
        + _re.escape(suffix) + r"\.json$"
    )
    for p in sorted(
        glob.glob(os.path.join(dryrun_dir, f"{arch}__{shape_name}__probe*.json"))
    ):
        if not pat.search(os.path.basename(p)):
            continue  # don't mix probe sets from other perf-tag variants
        with open(p) as f:
            probes.append(json.load(f))

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    chips = prod.get("chips", 256)
    chip = peaks(V5E)

    flops = extrapolate(probes, cfg, shape, "flops_per_device")
    bytes_ = extrapolate(probes, cfg, shape, "bytes_per_device")
    coll = extrapolate(probes, cfg, shape, "coll_total")

    # Microbatch weight re-reads (train): the probe ran n_micro=1; the
    # production program re-reads the (bf16-cast) weights every microbatch.
    n_micro = prod.get("n_micro") or 1
    if shape.kind == "train" and bytes_ is not None and n_micro > 1:
        local_param_bytes = 2.0 * cfg.param_count() / chips  # bf16 cast reads
        bytes_ += (n_micro - 1) * local_param_bytes

    rec = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "chips": chips,
        "compile_s": prod.get("compile_s"),
        "flops_per_device": flops,
        "bytes_per_device": bytes_,
        "coll_bytes_per_device": coll,
        "raw_prod_flops_per_device": prod.get("flops_per_device"),
        "temp_bytes": prod.get("temp_size_in_bytes"),
        "arg_bytes": prod.get("argument_size_in_bytes"),
        "n_probes": len(probes),
        "probe_errors": sum(1 for p in probes if "error" in p),
    }
    analytic_mem = analytic_hbm_bytes(
        cfg, shape, chips, n_micro, rec.get("arg_bytes")
    )
    rec["analytic_hbm_bytes"] = analytic_mem
    if flops is not None:
        rec["compute_term_s"] = flops / chip.bf16_flops
    rec["memory_term_s"] = analytic_mem / chip.hbm_bw
    if bytes_ is not None:
        rec["memory_hlo_upper_s"] = bytes_ / chip.hbm_bw
    if coll is not None:
        rec["collective_term_s"] = coll / chip.ici_bw
    terms = {
        k: rec.get(k)
        for k in ("compute_term_s", "memory_term_s", "collective_term_s")
        if rec.get(k) is not None
    }
    if terms:
        dom = max(terms, key=terms.get)
        rec["dominant"] = dom.replace("_term_s", "")
        step_time = terms[dom]  # no-overlap lower bound on the dominant term
        rec["bound_step_s"] = step_time
        # MODEL_FLOPS = 6 * N(_active) * tokens (assignment's definition).
        n = cfg.active_param_count() if cfg.n_experts else cfg.param_count()
        tokens = shape.global_batch * (
            shape.seq_len if shape.kind != "decode" else 1
        )
        factor = 6.0 if shape.kind == "train" else 2.0  # inference: fwd only
        rec["model_flops"] = factor * n * tokens
        if flops:
            rec["useful_flop_ratio"] = rec["model_flops"] / (flops * chips)
        if shape.kind == "decode":
            # Decode is bandwidth-bound by construction: efficiency = how
            # close the step is to the read-everything-once bound.
            rec["roofline_fraction"] = (
                rec["memory_term_s"] / step_time if step_time else None
            )
        else:
            # Achievable-model-compute time / dominant-term bound.
            model_compute_s = rec["model_flops"] / (chips * chip.bf16_flops)
            rec["roofline_fraction"] = (
                model_compute_s / step_time if step_time else None
            )
    return rec


def markdown_table(records: List[Dict]) -> str:
    hdr = (
        "| arch | shape | compute s | memory s | collective s | dominant | "
        "MODEL_FLOPS | useful ratio | roofline frac |\n"
        "|---|---|---|---|---|---|---|---|---|\n"
    )
    rows = []
    for r in records:
        if r.get("skipped"):
            rows.append(
                f"| {r['arch']} | {r['shape']} | — | — | — | skipped | — | — | — |"
            )
            continue
        if r.get("error") or r.get("compute_term_s") is None:
            rows.append(
                f"| {r['arch']} | {r['shape']} | ? | ? | ? | error | ? | ? | ? |"
            )
            continue
        rows.append(
            "| {arch} | {shape} | {c:.4f} | {m:.4f} | {k:.4f} | {dom} | "
            "{mf:.3e} | {ur:.3f} | {rf:.3f} |".format(
                arch=r["arch"], shape=r["shape"],
                c=r["compute_term_s"], m=r["memory_term_s"],
                k=r["collective_term_s"], dom=r["dominant"],
                mf=r["model_flops"], ur=r.get("useful_flop_ratio") or -1,
                rf=r.get("roofline_fraction") or -1,
            )
        )
    return hdr + "\n".join(rows) + "\n"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--out", default="experiments/roofline.json")
    ap.add_argument("--tag", default="")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    args = ap.parse_args()

    from repro.configs.registry import ARCH_NAMES

    records = []
    archs = [args.arch] if args.arch else list(ARCH_NAMES)
    shapes = [args.shape] if args.shape else list(SHAPES)
    for arch in archs:
        for shape in shapes:
            rec = analyze_cell(args.dir, arch, shape, tag=args.tag)
            if rec is not None:
                records.append(rec)
    with open(args.out, "w") as f:
        json.dump(records, f, indent=2)
    print(markdown_table(records))


if __name__ == "__main__":
    main()
