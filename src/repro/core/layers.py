"""Functional BCPNN layers (the DSL's building blocks).

Each layer is a pure-functional object: `init(key) -> LayerState` plus
`forward(state, x)` / `train_batch(state, x, [y])` transition functions that
jit/scan/shard_map cleanly.  The Keras-like imperative API in
``repro.core.network`` is a thin veneer over these.

Two layer types, matching the paper's Listing 1:

* :class:`StructuralPlasticityLayer` — input -> hidden, unsupervised Hebbian
  learning with a dynamic receptive-field mask (Alg. 1).
* :class:`DenseLayer` — hidden -> output, supervised readout: identical
  marginal learning but with the post-activations clamped to one-hot labels.

`use_kernels=True` routes the hot ops through the Pallas TPU kernels
(interpret-mode on CPU); False uses the pure-jnp reference path. Both paths
are numerically validated against each other in tests.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import learning, plasticity
from repro.core.learning import MarginalState
from repro.core.plasticity import PlasticityState
from repro.core.units import UnitLayout


class LayerState(NamedTuple):
    """Learnable state of a BCPNN layer (a pytree).

    w/b are *derived* from marginals each cycle but cached here because
    inference uses them without touching marginals.

    Invariant: where `plast` is set, `w` is zero on every silent (input HCU,
    hidden HCU) block.  Every writer of `w` masks it (`init`, the learning
    cycle, the rewire, `ContinualPlan`'s merge), so the jnp forward reads `w`
    alone.
    """

    marginals: MarginalState
    w: jnp.ndarray
    b: jnp.ndarray
    plast: Optional[PlasticityState]
    step: jnp.ndarray  # int32 scalar, counts train batches seen


@dataclasses.dataclass(frozen=True)
class BCPNNLayerSpec:
    """Hyperparameters shared by both layer types.

    precision: optional repro.precision.PrecisionPolicy — routes the whole
    datapath through the reduced-mantissa emulation (the paper's FPGA
    BF14..BF28 study).  Mutually composable with use_kernels (bf_round is
    itself a Pallas kernel).
    """

    pre: UnitLayout
    post: UnitLayout
    lam: float = 0.001
    k_b: float = 1.0
    n_cycles: int = 1
    use_kernels: bool = False
    dtype: jnp.dtype = jnp.float32
    precision: object = None
    gain: float = 1.0  # softmax inverse temperature (soft-WTA sharpness)
    # One-dispatch training: forward + softmax + EWMA + weights in a single
    # Pallas mega-kernel (repro.kernels.bcpnn_phase).  Requires use_kernels;
    # composes with the quantized *state* tier but not with a reduced
    # *datapath* (the per-stage rounding of the bf emulation cannot run
    # inside the fused kernel).
    fused_phase: bool = False

    def __post_init__(self):
        if self.fused_phase:
            if not self.use_kernels:
                raise ValueError("fused_phase=True requires use_kernels=True")
            if _datapath_policy(self) is not None:
                raise ValueError(
                    "fused_phase is incompatible with a reduced-precision "
                    "datapath (precision fmt "
                    f"{self.precision.fmt.name!r}); only the quantized state "
                    "tier (state_format=) composes with the fused kernel"
                )

    @property
    def n_pre(self) -> int:
        return self.pre.n_units

    @property
    def n_post(self) -> int:
        return self.post.n_units


def _datapath_policy(spec: "BCPNNLayerSpec"):
    """The PrecisionPolicy if it actually reduces the *datapath* (non-identity
    fmt) — a policy carrying only a ``state_format`` is not a datapath."""
    p = spec.precision
    if p is None or p.fmt.is_identity:
        return None
    return p


def _state_format(spec: "BCPNNLayerSpec"):
    """The storage format of the quantized state tier, if any."""
    p = spec.precision
    if p is not None and getattr(p, "has_state_tier", False):
        return p.state_format
    return None


def _jnp_path(spec: BCPNNLayerSpec) -> bool:
    """Neither the kernels nor a reduced-precision datapath."""
    return not spec.use_kernels and _datapath_policy(spec) is None


def _unit_mask(spec: BCPNNLayerSpec, state: LayerState) -> Optional[jnp.ndarray]:
    """The F×H mask operand of the kernel and reduced-datapath paths."""
    if state.plast is None:
        return None
    return state.plast.unit_mask(spec.pre, spec.post)


def _forward(spec: BCPNNLayerSpec, state: LayerState, x: jnp.ndarray) -> jnp.ndarray:
    """s = x @ w + b; softmax per HCU. Kernel or reference path.

    `w` is already masked (LayerState); the kernel and reduced-datapath
    paths still take the F×H mask as an operand."""
    if _jnp_path(spec):
        return learning.forward(x, state.w, state.b, spec.post, gain=spec.gain)
    mask = _unit_mask(spec, state)
    if _datapath_policy(spec) is not None:
        from repro.precision.policy import quantized_forward

        return quantized_forward(
            x, state.w, state.b, spec.post, spec.precision, mask, gain=spec.gain
        )
    from repro.kernels import ops as kops

    s = kops.masked_matmul(x, state.w, state.b, mask=mask)
    if spec.gain != 1.0:
        s = s * spec.gain
    return kops.hcu_softmax(s, n_hcu=spec.post.n_hcu, n_mcu=spec.post.n_mcu)


def _learn(
    spec: BCPNNLayerSpec, state: LayerState, ai: jnp.ndarray, aj: jnp.ndarray
) -> LayerState:
    """n_cycles of the EWMA marginal -> weight update (Alg.1 L10-16).

    The jnp path applies the receptive fields with `mask_weights`, in the
    pass that writes w; the kernel and reduced-datapath paths take the F×H
    mask."""
    mask = None if _jnp_path(spec) else _unit_mask(spec, state)

    marg, w, b = state.marginals, state.w, state.b
    sfmt = _state_format(spec)
    for _ in range(spec.n_cycles):
        if _datapath_policy(spec) is not None:
            from repro.precision.policy import quantized_learning_cycle

            marg, w, b = quantized_learning_cycle(
                marg, ai, aj, spec.lam, spec.precision, spec.k_b, mask=mask
            )
        elif spec.use_kernels:
            from repro.kernels import ops as kops

            marg, w, b = kops.bcpnn_update(
                marg, ai, aj, lam=spec.lam, k_b=spec.k_b, mask=mask,
                state_format=sfmt,
            )
        else:
            if sfmt is not None:
                # Traces may be stored bf16; upcast so the EWMA runs in f32
                # (bf16 * python-float would weak-promote to bf16 arithmetic).
                marg = MarginalState(
                    ci=marg.ci.astype(jnp.float32),
                    cj=marg.cj.astype(jnp.float32),
                    cij=marg.cij.astype(jnp.float32),
                )
            marg, w, b = learning.learning_cycle(marg, ai, aj, spec.lam, spec.k_b)
            if sfmt is not None:
                from repro.precision.policy import state_quantized_cycle

                marg, w, b = state_quantized_cycle(marg, spec.precision, k_b=spec.k_b)
            if state.plast is not None:
                w = state.plast.mask_weights(w, spec.pre, spec.post)
    return LayerState(
        marginals=marg, w=w, b=b, plast=state.plast, step=state.step + 1
    )


def _fused_train_batch(
    spec: BCPNNLayerSpec, state: LayerState, x: jnp.ndarray
) -> Tuple[LayerState, jnp.ndarray]:
    """The one-dispatch training path: the whole Alg.1 batch iteration
    (forward + HCU softmax + EWMA marginals + weight/bias epilogue) in a
    single `bcpnn_phase` Pallas call."""
    from repro.kernels import ops as kops

    marg, w, b, aj = kops.bcpnn_phase(
        state.marginals, x, state.w, state.b, spec.post,
        lam=spec.lam, k_b=spec.k_b, gain=spec.gain, mask=_unit_mask(spec, state),
        n_cycles=spec.n_cycles, state_format=_state_format(spec),
    )
    new_state = LayerState(
        marginals=marg, w=w, b=b, plast=state.plast, step=state.step + 1
    )
    return new_state, aj


class StructuralPlasticityLayer:
    """Unsupervised BCPNN layer with dynamic receptive fields (Alg. 1)."""

    def __init__(
        self,
        pre: UnitLayout,
        post: UnitLayout,
        fan_in: Optional[int] = None,
        lam: float = 0.001,
        k_b: float = 1.0,
        n_cycles: int = 1,
        mask_update_every: Optional[int] = None,
        use_kernels: bool = False,
        precision=None,
        init_jitter: float = 1.0,
        gain: float = 1.0,
        fused_phase: bool = False,
    ):
        self.spec = BCPNNLayerSpec(
            pre=pre, post=post, lam=lam, k_b=k_b, n_cycles=n_cycles,
            use_kernels=use_kernels, precision=precision, gain=gain,
            fused_phase=fused_phase,
        )
        self.init_jitter = init_jitter
        self.fan_in = fan_in if fan_in is not None else pre.n_hcu
        # Alg.1 L4: "if i_B % N_HCU == 0: update plasticity mask"
        self.mask_update_every = (
            mask_update_every if mask_update_every is not None else post.n_hcu
        )

    def init(self, key: jax.Array) -> LayerState:
        k_marg, key = jax.random.split(key)
        marg = learning.init_marginals(
            self.spec.n_pre, self.spec.n_post, self.spec.pre, self.spec.post,
            dtype=self.spec.dtype, key=k_marg, jitter=self.init_jitter,
        )
        if self.fan_in < self.spec.pre.n_hcu:
            plast = plasticity.init_random_mask(
                key, self.spec.pre, self.spec.post, self.fan_in
            )
        else:
            plast = plasticity.full_mask(self.spec.pre, self.spec.post)
        w, b = learning.weights_from_marginals(marg, self.spec.k_b)
        w = plast.mask_weights(w, self.spec.pre, self.spec.post)
        return LayerState(
            marginals=marg, w=w, b=b, plast=plast, step=jnp.zeros((), jnp.int32)
        )

    def forward(self, state: LayerState, x: jnp.ndarray) -> jnp.ndarray:
        return _forward(self.spec, state, x)

    def train_batch(self, state: LayerState, x: jnp.ndarray) -> Tuple[LayerState, jnp.ndarray]:
        """One Alg.1 batch iteration: (maybe) rewire, forward, learn."""
        state = self.maybe_update_mask(state)
        if self.spec.fused_phase:
            return _fused_train_batch(self.spec, state, x)
        aj = _forward(self.spec, state, x)
        new_state = _learn(self.spec, state, x, aj)
        return new_state, aj

    def maybe_update_mask(self, state: LayerState) -> LayerState:
        """Rewire every `mask_update_every` batches (Alg.1 L4-6), under lax.cond
        so the whole train step remains a single jitted program."""
        if self.fan_in >= self.spec.pre.n_hcu:
            return state  # dense: nothing to rewire

        def rewire(s: LayerState) -> PlasticityState:
            return plasticity.update_mask(
                s.plast, s.marginals, self.spec.pre, self.spec.post
            )

        do = (state.step % self.mask_update_every) == 0
        plast = jax.lax.cond(do, rewire, lambda s: s.plast, state)
        # Re-apply the (possibly changed) mask to the cached weights.  Out of
        # the branch, the product fuses into the forward's read of w; a
        # branch that returned w would copy it on every batch.
        w = plast.mask_weights(state.w, self.spec.pre, self.spec.post)
        return state._replace(w=w, plast=plast)


class DenseLayer:
    """Supervised BCPNN readout layer: marginal learning against one-hot
    targets (the paper's output layer; "training of the output layer is
    similar" to Alg. 1, with a_k := onehot(y))."""

    def __init__(
        self,
        pre: UnitLayout,
        post: UnitLayout,
        lam: float = 0.001,
        k_b: float = 1.0,
        n_cycles: int = 1,
        use_kernels: bool = False,
        precision=None,
        gain: float = 1.0,
    ):
        self.spec = BCPNNLayerSpec(
            pre=pre, post=post, lam=lam, k_b=k_b, n_cycles=n_cycles,
            use_kernels=use_kernels, precision=precision, gain=gain,
        )

    def init(self, key: jax.Array) -> LayerState:
        del key
        marg = learning.init_marginals(
            self.spec.n_pre, self.spec.n_post, self.spec.pre, self.spec.post,
            dtype=self.spec.dtype,
        )
        w, b = learning.weights_from_marginals(marg, self.spec.k_b)
        return LayerState(
            marginals=marg, w=w, b=b, plast=None, step=jnp.zeros((), jnp.int32)
        )

    def forward(self, state: LayerState, x: jnp.ndarray) -> jnp.ndarray:
        return _forward(self.spec, state, x)

    def train_batch(
        self, state: LayerState, x: jnp.ndarray, y: jnp.ndarray
    ) -> Tuple[LayerState, jnp.ndarray]:
        """Supervised batch: targets (int labels or already-one-hot) become
        the post-activations for the marginal update."""
        if y.ndim == x.ndim - 1:  # integer labels -> one-hot over output units
            aj = jax.nn.one_hot(y, self.spec.n_post, dtype=x.dtype)
        else:
            aj = y
        new_state = _learn(self.spec, state, x, aj)
        return new_state, aj
