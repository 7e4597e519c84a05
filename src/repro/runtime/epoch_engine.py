"""Device-resident epoch engine: one jitted ``lax.scan`` per training epoch.

The seed ``Network.fit`` drives every batch from Python — a fresh
host->device transfer plus a jitted-call dispatch per batch — so on small
BCPNN layers the dispatch overhead, not the MXU, dominates (the BLAS2->BLAS3
aggregation problem StreamBrain solves with resident-state streaming).  This
module keeps the whole Alg. 1 inner loop resident on the device:

* :func:`stack_epoch` gathers a pre-shuffled epoch in one step and
  reshapes it to ``(n_batches, B, ...)``: on the device with ``jnp.take``
  from an array already there (the dataset the plan staged once for the
  phase, a ``jax.Array`` input, an activation cache), or on the host with
  one transfer where the dataset stays on the host;
* the ``*_epoch_fn`` builders wrap a per-batch transition into a single
  jitted, buffer-donated ``lax.scan`` over the leading batch axis — the
  hidden Hebbian phase, the BCPNN readout phase, and the SGD readout phase
  each get a scan body;
* the ``*_epoch_cached_fn`` builders are the project-once variants: their
  inputs are pre-projected level-k representations from the
  :class:`repro.runtime.activations.ActivationStore`, so the scan bodies
  contain no frozen-stack forward at all (the fused builders stay as the
  bit-exact parity reference).

Numerics are bit-identical to the per-batch loop modulo reduction order:
the scan body runs exactly the per-batch transition (including the
``lax.cond``-guarded structural-plasticity rewire, which keys on
``state.step`` carried through the scan), just without returning to Python
between batches.  ``tests/test_epoch_engine.py`` asserts parity for both the
reference and Pallas-kernel paths.

Distributed training threads through unchanged: a
:class:`repro.core.distributed.DataParallelTrainer` step (shard_map or pjit)
is itself a traceable function, so it becomes the scan body and the stacked
epoch is placed with the batch axes sharded (leading scan axis replicated).

The epoch *driver* (shuffle, stack, thread states through phases) lives in
:class:`repro.runtime.plans.ScanPlan`, consumed by
``repro.core.compiled.CompiledNetwork``; this module only builds the jitted
epoch functions.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.runtime.trace import span


@functools.partial(jax.jit, static_argnums=(2, 3))
def _take_epoch(arr, idx, shape, sharding):
    """One epoch gathered on the device and laid out with ``sharding`` inside
    the program.  A ``device_put`` of the gathered array to another sharding
    goes through the host instead: 0.34–0.45 s an epoch at STL-10 width on
    four v5e chips, more than the gather it follows.  The indices take the
    stack's ``(n_batches, B)`` shape first: from a set replicated on every
    chip that gathers straight into the epoch's layout, with half the
    temporaries of a flat gather and a reshape (STL-10 width, v5e 2x2)."""
    stacked = jnp.take(arr, idx.reshape(shape[:2]), axis=0)
    if sharding is not None:
        stacked = jax.lax.with_sharding_constraint(stacked, sharding)
    return stacked


def stack_epoch(
    arr,
    idx: np.ndarray,
    batch_size: int,
    sharding: Optional[NamedSharding] = None,
    tracer=None,
) -> jnp.ndarray:
    """Gather a shuffled epoch and reshape to ``(n_batches, B, ...)``.

    Arrays on the device — the dataset ``ScanPlan`` staged once for the
    phase, a ``jax.Array`` input, a device-resident activation cache —
    gather with ``jnp.take`` in one program that also lays the stack out
    with ``sharding``, so the epoch never round-trips through host memory
    (its ``train.upload`` span is then empty).  Host arrays (a dataset too
    large to stage, a host-spilled cache level) take one contiguous
    host-side gather and one device transfer — versus one transfer per
    batch in the per-batch loop.
    ``idx`` must already be trimmed to a multiple of ``batch_size``.

    The two steps run under the ``train.gather`` and ``train.upload`` spans
    (:func:`repro.runtime.trace.span`; ``tracer`` may be None).
    """
    n = idx.shape[0]
    if n % batch_size != 0:
        raise ValueError(f"epoch of {n} samples is not a multiple of B={batch_size}")
    shape = (n // batch_size, batch_size, *arr.shape[1:])
    on_device = isinstance(arr, jax.Array)
    with span(tracer, "train.gather"):
        if on_device:
            stacked = _take_epoch(arr, jnp.asarray(idx), shape, sharding)
        else:
            stacked = np.ascontiguousarray(arr[idx]).reshape(shape)
    with span(tracer, "train.upload"):
        if on_device:
            return stacked
        if sharding is not None:
            return jax.device_put(stacked, sharding)
        return jnp.asarray(stacked)


def gather_batch(arr, sel: np.ndarray) -> jnp.ndarray:
    """One batch gather for the per-batch reference loop: ``jnp.take`` when
    ``arr`` is device-resident, host fancy-indexing otherwise."""
    if isinstance(arr, jax.Array):
        return jnp.take(arr, jnp.asarray(sel), axis=0)
    return jnp.asarray(arr[sel])


def epoch_sharding(trainer, ndim: int) -> Optional[NamedSharding]:
    """Sharding for a stacked ``(n_batches, B, ...)`` epoch under a trainer.

    The scan axis (leading) is replicated; the per-batch axis is sharded over
    the trainer's batch mesh axes, so each scan slice is exactly the global
    batch layout the trainer's shard_map/pjit step expects.
    """
    if trainer is None:
        return None
    return NamedSharding(
        trainer.mesh, P(None, trainer.baxes, *(None,) * (ndim - 2))
    )


# --------------------------------------------------------------------------
# Epoch-scan builders.  Each returns a jitted function closed over the layer
# *structure* (static) and taking all traced state explicitly, with the
# mutable carry donated — re-running an epoch reuses the same compiled
# program.  The stacked epoch inputs are not donated: no output has their
# shape, so the buffer could not be reused and jax would warn at every
# compile.  Each inner function has a name of its own, so each program
# compiles, caches and profiles as ``jit_<name>`` (``jit_hidden_epoch``,
# ``jit_readout_epoch_cached``, ...).
# --------------------------------------------------------------------------
def _donate(enabled: bool, *argnums: int) -> dict:
    """donate_argnums kwargs, suppressed on CPU (donation unsupported there
    and jax warns per-call) or when the ExecutionConfig opts out."""
    if not enabled or jax.default_backend() == "cpu":
        return {}
    return {"donate_argnums": argnums}


def forward_stack(layers: Sequence[Any]) -> Callable:
    """``(states, xb) -> xb`` through a frozen layer stack — the ONE
    frozen-forward loop, shared by the scan bodies here and by
    BatchPlan's per-batch reference loop."""
    def fwd(states, xb):
        for layer, state in zip(layers, states):
            xb = layer.forward(state, xb)
        return xb

    return fwd


def hidden_epoch_fn(
    layer,
    below_layers: Sequence[Any],
    step_fn: Optional[Callable] = None,
    donate: bool = True,
) -> Callable:
    """Jitted ``(state, below_states, xs) -> state`` for one Hebbian epoch.

    ``xs``: stacked input epoch ``(n_batches, B, F)``.  ``below_states`` are
    the frozen lower hidden layers (passed as traced args, not baked-in
    constants, so the compiled epoch is reusable).  ``step_fn`` overrides the
    per-batch transition — e.g. a DataParallelTrainer.hidden_step.
    """
    below = forward_stack(below_layers)
    step = step_fn if step_fn is not None else (
        lambda s, xb: layer.train_batch(s, xb)[0]
    )

    def hidden_epoch(state, below_states, xs):
        def body(carry, xb):
            return step(carry, below(below_states, xb)), None

        state, _ = jax.lax.scan(body, state, xs)
        return state

    return jax.jit(hidden_epoch, **_donate(donate, 0))


def readout_epoch_fn(
    layer,
    hidden_layers: Sequence[Any],
    step_fn: Optional[Callable] = None,
    donate: bool = True,
) -> Callable:
    """Jitted ``(state, hidden_states, xs, ys) -> state`` for one supervised
    BCPNN-readout epoch (post-activations clamped to one-hot labels)."""
    below = forward_stack(hidden_layers)
    step = step_fn if step_fn is not None else (
        lambda s, hb, yb: layer.train_batch(s, hb, yb)[0]
    )

    def readout_epoch(state, hidden_states, xs, ys):
        def body(carry, batch):
            xb, yb = batch
            return step(carry, below(hidden_states, xb), yb), None

        state, _ = jax.lax.scan(body, state, (xs, ys))
        return state

    return jax.jit(readout_epoch, **_donate(donate, 0))


def sgd_epoch_fn(
    opt, hidden_layers: Sequence[Any], loss_fn: Callable, donate: bool = True
) -> Callable:
    """Jitted ``(params, opt_state, hidden_states, xs, ys) ->
    (params, opt_state, losses)`` for one hybrid-readout (AdamW) epoch."""
    below = forward_stack(hidden_layers)

    def sgd_epoch(params, opt_state, hidden_states, xs, ys):
        def body(carry, batch):
            p, s = carry
            xb, yb = batch
            hb = below(hidden_states, xb)
            loss, g = jax.value_and_grad(loss_fn)(p, hb, yb)
            updates, s = opt.update(g, s, p)
            p = jax.tree_util.tree_map(lambda a, u: a + u, p, updates)
            return (p, s), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), (xs, ys)
        )
        return params, opt_state, losses

    return jax.jit(sgd_epoch, **_donate(donate, 0, 1))


# --------------------------------------------------------------------------
# Cached-input (project-once) variants.  ``xs`` is already the layer's own
# input representation — gathered from the ActivationStore's cached level-k
# array — so the scan bodies contain NO frozen-stack forward.  This is the
# phase-program fast path; the fused builders above remain the parity
# reference (ExecutionConfig(cache_activations=False)).
# --------------------------------------------------------------------------
def hidden_epoch_cached_fn(
    layer, step_fn: Optional[Callable] = None, donate: bool = True
) -> Callable:
    """Jitted ``(state, xs) -> state``: one Hebbian epoch on pre-projected
    inputs ``(n_batches, B, F_level)``."""
    step = step_fn if step_fn is not None else (
        lambda s, xb: layer.train_batch(s, xb)[0]
    )

    def hidden_epoch_cached(state, xs):
        def body(carry, xb):
            return step(carry, xb), None

        state, _ = jax.lax.scan(body, state, xs)
        return state

    return jax.jit(hidden_epoch_cached, **_donate(donate, 0))


def readout_epoch_cached_fn(
    layer, step_fn: Optional[Callable] = None, donate: bool = True
) -> Callable:
    """Jitted ``(state, hs, ys) -> state``: one supervised BCPNN-readout
    epoch on pre-projected hidden codes."""
    step = step_fn if step_fn is not None else (
        lambda s, hb, yb: layer.train_batch(s, hb, yb)[0]
    )

    def readout_epoch_cached(state, hs, ys):
        def body(carry, batch):
            hb, yb = batch
            return step(carry, hb, yb), None

        state, _ = jax.lax.scan(body, state, (hs, ys))
        return state

    return jax.jit(readout_epoch_cached, **_donate(donate, 0))


def sgd_epoch_cached_fn(opt, loss_fn: Callable, donate: bool = True) -> Callable:
    """Jitted ``(params, opt_state, hs, ys) -> (params, opt_state, losses)``:
    one hybrid-readout (AdamW) epoch on pre-projected hidden codes."""

    def sgd_epoch_cached(params, opt_state, hs, ys):
        def body(carry, batch):
            p, s = carry
            hb, yb = batch
            loss, g = jax.value_and_grad(loss_fn)(p, hb, yb)
            updates, s = opt.update(g, s, p)
            p = jax.tree_util.tree_map(lambda a, u: a + u, p, updates)
            return (p, s), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), (hs, ys)
        )
        return params, opt_state, losses

    return jax.jit(sgd_epoch_cached, **_donate(donate, 0, 1))
