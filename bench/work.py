"""Operations and bytes that one hidden-layer batch update requires.

Counted from the shapes of the algorithm (the paper's Alg. 1), not from what
an implementation happens to move, so that no implementation can read above
100 % of its roofline:

Bytes per batch (float32 storage, 4 bytes an element):
- the batch's inputs, read once: ``B * F``;
- C_ij read once and written once: ``2 * F * H``;
- the structural-plasticity rewire reads C_ij once every
  ``mask_update_every`` batches (the hidden HCU count): ``F * H / every``;
- the marginals c_i and c_j, read once and written once: ``2 * (F + H)``.
Not counted: w and b.  Both follow from the marginals and the mask, so an
implementation need neither store nor read them.  Nor the mask, which is
``n_in_hcu * n_hcu`` values, a rounding error beside C_ij.

Operations per batch (multiply and add count one each):
- the forward over active connections only: ``2 * B * fan_in_units * H``,
  with ``fan_in_units = fan_in * n_mcu_in`` inputs per hidden unit;
- the outer product ``a_i^T a_j`` over every pair: ``2 * B * F * H``,
  because C_ij tracks every pair, active or not, for the rewire;
- the per-batch elementwise update, per element of C_ij: three for the EWMA
  ``(1 - lam) * C + lam * M`` and four for the weight
  ``log C - log c_i - log c_j`` under the mask: ``7 * F * H``;
- the rewire's mutual-information score, five per element of C_ij
  (clamp, log, two subtractions, product), once every ``every`` batches.
Softmax, batch means and the marginal vectors are below 0.1 % and left out.
Nothing recomputed is counted: a replicated update on several chips counts
once per global batch.
"""
from __future__ import annotations

import dataclasses

BYTES = 4  # float32


@dataclasses.dataclass(frozen=True)
class Work:
    flops: int  # per global batch
    bytes: int  # per global batch
    batch: int  # samples per global batch

    @property
    def flops_per_sample(self) -> float:
        return self.flops / self.batch

    def least_seconds(self, peak) -> float:
        """The least time the chip could take for one batch."""
        return max(self.flops / peak.bf16_flops, self.bytes / peak.hbm_bw)


def hidden_batch(cfg: dict) -> Work:
    """Work of one hidden-layer batch update of configuration ``cfg``."""
    b = cfg["batch"]
    f = cfg["n_features"] * cfg["n_mcu_in"]  # coded inputs
    h = cfg["n_hcu"] * cfg["n_mcu"]
    every = cfg["n_hcu"]  # Alg. 1: rewire every N_HCU batches
    fan_in_units = cfg["fan_in"] * cfg["n_mcu_in"]
    flops = (2 * b * fan_in_units * h + 2 * b * f * h + 7 * f * h
             + 5 * f * h // every)
    nbytes = BYTES * (b * f + 2 * f * h + f * h // every + 2 * (f + h))
    return Work(flops=flops, bytes=nbytes, batch=b)
