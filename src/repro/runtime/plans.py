"""ExecutionPlan strategies: how a compiled network's epochs execute.

``Network.compile(ExecutionConfig(...))`` binds a declarative layer stack to
exactly one ExecutionPlan; every training phase (hidden Hebbian, BCPNN
readout, SGD readout) and every single-batch step then routes through that
plan.  Two strategies exist:

* :class:`ScanPlan` ("scan", the default) — each epoch is one jitted,
  buffer-donated ``lax.scan`` over a device-resident ``(n_batches, B, F)``
  stack (:mod:`repro.runtime.epoch_engine`), the paper's resident-state
  streaming posture.
* :class:`BatchPlan` ("batch") — the per-batch reference loop: one jitted
  dispatch and one host->device transfer per batch.  Kept as the numerical
  reference; parity is asserted in tests.

A :class:`repro.core.distributed.DataParallelTrainer` is a plan *decorator*:
``trainer.decorate(plan)`` swaps the per-batch transition for the sharded
shard_map/pjit step (the paper's MPI backend) without changing the driver.
Both plans cache their jitted epoch/step callables, so repeated ``fit`` /
``partial_fit`` calls on one CompiledNetwork never rebuild or re-trace.

Epoch-runner calling convention (the phase's input in, new state out):

    hidden_epoch(li)(state, x, idx, batch_size) -> state
    readout_epoch()(state, h, y, idx, batch_size) -> state
    sgd_epoch(opt, loss_fn)(params, opt_state, h, y, idx, batch_size)
        -> (params, opt_state, last_loss)

``x``/``h``/``y`` are the full arrays a phase gathers from, each already the
layer's own input: the raw dataset for the first hidden layer (the caller's
host array, or the device copy :meth:`ScanPlan.stage` made of it once for
the phase) and above it the level-k projection the activation store holds;
``idx`` is the (already length-trimmed) shuffled index vector for this
epoch.
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.analysis.strict import dispatch_guard
from repro.runtime.epoch_engine import (
    _take_epoch,
    epoch_sharding,
    gather_batch,
    hidden_epoch_fn,
    readout_epoch_fn,
    sgd_epoch_fn,
    stack_epoch,
)
from repro.runtime.trace import span


def free_device_bytes(devices) -> Optional[int]:
    """The least ``bytes_limit - bytes_in_use`` over ``devices``, or None
    where the backend reports no memory statistics (the CPU)."""
    free = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return None
        free.append(stats["bytes_limit"] - stats.get("bytes_in_use", 0))
    return min(free)


class ExecutionPlan:
    """Base strategy: owns the bound layers, the optional trainer decoration,
    and the cache of compiled callables."""

    name: str = "?"

    def __init__(self, layers: Sequence[Any], donate: bool = True,
                 strict: bool = False):
        from repro.core.layers import DenseLayer, StructuralPlasticityLayer

        self.layers: List[Any] = list(layers)
        self.donate = donate
        self.strict = strict
        # name -> jitted callable, for the strict-mode recompile sentinel.
        # Every compiled callable this plan builds registers here, so
        # CompiledNetwork can assert each one compiles exactly once.
        self.jitted: dict = {}
        self.trainer = None
        # The compiled network's Tracer (or None): the scan plan records its
        # train.gather / upload / dispatch spans there.
        self.tracer = None
        self._hidden_cache: dict = {}
        self._hidden_step_cache: dict = {}
        self._readout_cache: Optional[Callable] = None
        self._gather_bytes_cache: dict = {}
        self._plastic_cls = StructuralPlasticityLayer
        self._dense_cls = DenseLayer

    # ------------------------------------------------------------ structure
    @property
    def hidden_layers(self) -> List[Any]:
        return [la for la in self.layers if isinstance(la, self._plastic_cls)]

    @property
    def readout_layer(self) -> Optional[Any]:
        last = self.layers[-1] if self.layers else None
        return last if isinstance(last, self._dense_cls) else None

    # --------------------------------------------------------- observability
    def jit_cache_sizes(self) -> dict:
        """``name -> trace-cache size`` for every compiled callable this
        plan registered — the observability view of the compile-once
        contract (the strict sentinel asserts over the same registry)."""
        return {
            name: fn._cache_size()
            for name, fn in self.jitted.items()
            if hasattr(fn, "_cache_size")
        }

    # ----------------------------------------------------------- decoration
    def bind_trainer(self, trainer) -> "ExecutionPlan":
        """Called by DataParallelTrainer.decorate; must precede compilation
        of any cached callable (they close over the trainer's steps)."""
        if (
            self._hidden_cache
            or self._hidden_step_cache
            or self._readout_cache
        ):
            raise RuntimeError(
                "cannot bind a trainer to a plan that already compiled steps"
            )
        self.trainer = trainer
        return self

    def place_state(self, layer, state):
        """Device placement for a layer state entering this plan's epochs."""
        return state

    # -------------------------------------------------------- phase input
    def stages(self, arr, n: int, batch_size: int) -> bool:
        """Whether a phase places the host array ``arr`` its epochs gather
        ``n`` rows from on the device once (:meth:`ScanPlan.stage`).  The
        base plan never does: BatchPlan's per-batch loop is the numerical
        reference."""
        return False

    # ------------------------------------------------------- single steps
    def hidden_step(self, li: int) -> Callable:
        """Jitted per-batch ``(state, xb) -> state`` for hidden layer li —
        the lowering/analysis surface (see launch/dryrun_bcpnn.py) and
        BatchPlan's per-batch transition."""
        fn = self._hidden_step_cache.get(li)
        if fn is None:
            layer = self.hidden_layers[li]
            if self.trainer is not None:
                fn = self.trainer.hidden_step(layer)
            else:
                fn = jax.jit(lambda s, xb, _l=layer: _l.train_batch(s, xb)[0])
            self._hidden_step_cache[li] = fn
            self.jitted[f"hidden_step[{li}]"] = fn
        return fn

    # ----------------------------------------------------------- interface
    def hidden_epoch(self, li: int) -> Callable:
        raise NotImplementedError

    def readout_epoch(self) -> Callable:
        raise NotImplementedError

    def sgd_epoch(self, opt, loss_fn: Callable) -> Callable:
        raise NotImplementedError


class ScanPlan(ExecutionPlan):
    """Device-resident epochs: stack once, scan once (engine="scan")."""

    name = "scan"

    def _stack(self, arr, idx, batch_size):
        return stack_epoch(
            arr, idx, batch_size, epoch_sharding(self.trainer, arr.ndim + 1),
            tracer=self.tracer,
        )

    def _dispatch(self, epoch_fn, *args):
        """``epoch_fn(*args)`` under the ``train.dispatch`` span, whose
        ``traces`` attr counts the entries the call added to the callable's
        trace cache (0 once it is compiled)."""
        before = epoch_fn._cache_size()
        with span(self.tracer, "train.dispatch") as attrs:
            with dispatch_guard(self.strict):
                out = epoch_fn(*args)
            attrs["traces"] = epoch_fn._cache_size() - before
        return out

    def place_state(self, layer, state):
        if self.trainer is not None:
            return self.trainer.place_state(layer, state)
        return state

    def _stage_sharding(self, arr) -> Optional[NamedSharding]:
        """Where a staged array lives: the default device on one chip, the
        trainer's row placement (``trainer.rows_sharding``, as the
        activation store's levels) under a trainer."""
        if self.trainer is None:
            return None
        return self.trainer.rows_sharding(arr.shape)

    def stages(self, arr, n: int, batch_size: int) -> bool:
        """Host arrays are staged whole, so every epoch of the phase gathers
        its shuffled stack of ``n`` rows on the device (``stack_epoch``'s
        ``jnp.take``) and the data crosses to the device once a phase, not
        once an epoch — where the device reports room: the bytes the compiled
        epoch gather holds on each chip (:meth:`_gather_bytes`) within half
        of its free memory.  A backend that reports none (the CPU) stages."""
        if isinstance(arr, jax.Array):
            return False
        sharding = self._stage_sharding(arr)
        devices = jax.devices()[:1] if sharding is None else sharding.device_set
        free = free_device_bytes(devices)
        return free is None or self._gather_bytes(arr, n, batch_size) <= free // 2

    def _gather_bytes(self, arr, n: int, batch_size: int) -> int:
        """Per-chip bytes of the epoch gather from a staged ``arr`` at its
        peak — the staged copy, the index vector, the stacked epoch and
        XLA's temporaries — from the compiled program's memory analysis,
        once a shape."""
        key = (arr.shape, arr.dtype, n, batch_size)
        if key not in self._gather_bytes_cache:
            shape = (n // batch_size, batch_size) + arr.shape[1:]
            src = jax.ShapeDtypeStruct(
                arr.shape, jax.dtypes.canonicalize_dtype(arr.dtype),
                sharding=self._stage_sharding(arr),
            )
            idx = jax.ShapeDtypeStruct(
                (n,), jax.dtypes.canonicalize_dtype(np.int64))
            mem = _take_epoch.lower(
                src, idx, shape, epoch_sharding(self.trainer, arr.ndim + 1)
            ).compile().memory_analysis()
            self._gather_bytes_cache[key] = (
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes
            )
        return self._gather_bytes_cache[key]

    def stage(self, arr):
        """``arr`` on the device, under ``train.upload``.  One chip takes the
        default device uncommitted, as the host gather's upload did, so the
        epoch programs see the argument placement they always saw."""
        with span(self.tracer, "train.upload"):
            if self.trainer is None:
                return jnp.asarray(arr)
            return jax.device_put(arr, self._stage_sharding(arr))

    def hidden_epoch(self, li: int) -> Callable:
        run = self._hidden_cache.get(li)
        if run is None:
            layer = self.hidden_layers[li]
            step = self.trainer.hidden_step(layer) if self.trainer else None
            epoch_fn = hidden_epoch_fn(layer, step_fn=step, donate=self.donate)
            self.jitted[f"hidden_epoch[{li}]"] = epoch_fn

            def run(state, x, idx, batch_size):
                xs = self._stack(x, idx, batch_size)
                return self._dispatch(epoch_fn, state, xs)

            self._hidden_cache[li] = run
        return run

    def readout_epoch(self) -> Callable:
        if self._readout_cache is None:
            layer = self.readout_layer
            step = self.trainer.readout_step(layer) if self.trainer else None
            epoch_fn = readout_epoch_fn(layer, step_fn=step, donate=self.donate)
            self.jitted["readout_epoch"] = epoch_fn

            def run(state, h, y, idx, batch_size):
                hs = self._stack(h, idx, batch_size)
                ys = self._stack(y, idx, batch_size)
                return self._dispatch(epoch_fn, state, hs, ys)

            self._readout_cache = run
        return self._readout_cache

    def sgd_epoch(self, opt, loss_fn: Callable) -> Callable:
        epoch_fn = sgd_epoch_fn(opt, loss_fn, donate=self.donate)
        self.jitted["sgd_epoch"] = epoch_fn

        def run(params, opt_state, h, y, idx, batch_size):
            hs = self._stack(h, idx, batch_size)
            ys = self._stack(y, idx, batch_size)
            params, opt_state, losses = self._dispatch(
                epoch_fn, params, opt_state, hs, ys
            )
            return params, opt_state, losses[-1]

        return run


class BatchPlan(ExecutionPlan):
    """Per-batch reference loop (engine="batch"): numerically interchangeable
    with ScanPlan modulo reduction order; each batch pays a dispatch and a
    host->device transfer."""

    name = "batch"

    def hidden_epoch(self, li: int) -> Callable:
        run = self._hidden_cache.get(li)
        if run is None:
            step = self.hidden_step(li)

            def run(state, x, idx, batch_size):
                with dispatch_guard(self.strict):
                    for b in range(0, idx.shape[0], batch_size):
                        state = step(
                            state, gather_batch(x, idx[b : b + batch_size])
                        )
                return state

            self._hidden_cache[li] = run
        return run

    def readout_epoch(self) -> Callable:
        if self._readout_cache is None:
            layer = self.readout_layer
            if self.trainer is not None:
                step = self.trainer.readout_step(layer)
            else:
                step = jax.jit(
                    lambda s, hb, yb, _l=layer: _l.train_batch(s, hb, yb)[0]
                )
            self.jitted["readout_step"] = step

            def run(state, h, y, idx, batch_size):
                with dispatch_guard(self.strict):
                    for b in range(0, idx.shape[0], batch_size):
                        sel = idx[b : b + batch_size]
                        state = step(
                            state, gather_batch(h, sel), gather_batch(y, sel)
                        )
                return state

            self._readout_cache = run
        return self._readout_cache

    def sgd_epoch(self, opt, loss_fn: Callable) -> Callable:
        @jax.jit
        def step(p, s, hb, yb):
            loss, g = jax.value_and_grad(loss_fn)(p, hb, yb)
            updates, s = opt.update(g, s, p)
            p = jax.tree_util.tree_map(lambda a, u: a + u, p, updates)
            return p, s, loss

        self.jitted["sgd_step"] = step

        def run(params, opt_state, h, y, idx, batch_size):
            loss = jnp.zeros(())
            with dispatch_guard(self.strict):
                for b in range(0, idx.shape[0], batch_size):
                    sel = idx[b : b + batch_size]
                    params, opt_state, loss = step(
                        params, opt_state,
                        gather_batch(h, sel), gather_batch(y, sel),
                    )
            return params, opt_state, loss

        return run


PLANS = {ScanPlan.name: ScanPlan, BatchPlan.name: BatchPlan}


def make_plan(engine: str, layers: Sequence[Any], donate: bool = True,
              strict: bool = False) -> ExecutionPlan:
    try:
        cls = PLANS[engine]
    except KeyError:
        raise ValueError(
            f"Unknown engine {engine!r} (want one of {sorted(PLANS)})"
        ) from None
    return cls(layers, donate=donate, strict=strict)
