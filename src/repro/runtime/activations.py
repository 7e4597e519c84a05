"""Project-once activation store for phase-program training.

The paper's training scheme is explicitly staged: greedy layer-by-layer
Hebbian epochs, then a supervised readout on *frozen* representations.
Recomputing the frozen stack below the training layer inside every batch
would cost a depth-D network O(D^2 * epochs) redundant frozen forwards and
re-transfer the raw input every epoch even when the layer's true input is a
much smaller hidden code.

:class:`ActivationStore` exploits the staging instead: at each phase
boundary the dataset is projected ONCE through the newly-frozen prefix with
a single jitted batched ``lax.scan`` and the level-k representation is
cached.  Epoch shuffles then gather rows from the cached level-k array
(`jnp.take` on device), so the per-epoch scan bodies contain no frozen
forward at all (the ``*_epoch_fn`` builders in
:mod:`repro.runtime.epoch_engine`).

Residency is governed by a byte budget (``ExecutionConfig(
activation_budget_mb=...)``): cached levels live on device until the budget
is exceeded, then the least-recently-used level is spilled to host memory
(the epoch gather transparently falls back to the host path).  Projection
chunking uses the caller's batch size and pads the ragged tail to a full
chunk, so every row is produced by a GEMM of exactly the shape a training
batch has — this is what keeps projecting once bit-exact with recomputing
the frozen prefix every batch (asserted in ``tests/test_deep_networks.py``).

Invalidation is by object identity: an entry records the exact
``LayerState`` objects (and the dataset array) it was projected from, and is
valid only while ``states[:k]`` still *are* those objects.  Training a
layer, ``partial_fit`` on a new chunk, a checkpoint ``load()``, or a
streaming session adopting state on close all publish new state objects, so
upstream changes invalidate exactly the levels above them — no version
counters to keep in sync.

Entries are keyed ``(dataset, level)`` — the dataset anchor is the array
object itself (the entry holds it, so its identity stays stable) — under
ONE shared byte budget.  Alternating ``fit(train)`` / ``evaluate(test)``
therefore caches both projections instead of thrashing one slot per level,
and serving request batches (``BatchedPlan``) coexist with the training
set's levels.  Device residency spills LRU to host as before; host-spilled
bytes are themselves bounded (``host_budget_bytes``, default 4x the device
budget) by dropping LRU host entries entirely — they are recomputable.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.epoch_engine import forward_stack


@dataclasses.dataclass
class _Entry:
    """One cached level-k representation."""

    value: Any  # jnp.ndarray (device) or np.ndarray (host-spilled)
    states: Tuple[Any, ...]  # the frozen states[:k] it was projected from
    x: Any  # the dataset array it was projected from (identity anchor)
    nbytes: int
    on_host: bool
    tick: int  # LRU clock

    def valid_for(self, states: Sequence[Any]) -> bool:
        return len(self.states) <= len(states) and all(
            a is b for a, b in zip(self.states, states)
        )


class ActivationStore:
    """Cached frozen-prefix projections, keyed by ``(dataset, level)``.

    ``level(k, states, x, chunk)`` returns the representation of ``x`` after
    the first ``k`` layers (level 0 is ``x`` itself, returned as-is).  The
    projection starts from the deepest still-valid cached level of ``x``
    below ``k``, so a phase boundary costs one pass through only the
    newly-frozen layers.

    Entries for several datasets coexist under the shared byte budget, so
    alternating ``fit(train)``/``evaluate(test)`` (or serving request
    batches) no longer thrash one slot per level; the dataset key is the
    array object's identity, anchored by the strong reference the entry
    itself holds.
    """

    def __init__(
        self,
        layers: Sequence[Any],
        budget_bytes: int = 512 << 20,
        place: Optional[Callable] = None,
        host_budget_bytes: Optional[int] = None,
    ):
        self.layers = list(layers)
        self.budget_bytes = int(budget_bytes)
        self.host_budget_bytes = (
            int(host_budget_bytes)
            if host_budget_bytes is not None
            else 4 * self.budget_bytes
        )
        self._place = place  # device placement hook (trainer rows_sharding)
        self._entries: Dict[Tuple[int, int], _Entry] = {}  # (id(x), level)
        self._proj_scan: Dict[Tuple[int, int], Callable] = {}
        self._proj_chunk: Dict[Tuple[int, int], Callable] = {}
        self._tick = 0
        self.stats = {"projections": 0, "hits": 0, "spills": 0, "evictions": 0}

    # ------------------------------------------------------------- interface
    def level(self, k: int, states: Sequence[Any], x, chunk: int):
        """Representation of ``x`` at level ``k`` under frozen ``states[:k]``."""
        if k == 0:
            return x
        if not 0 < k <= len(self.layers):
            raise ValueError(f"level {k} out of range for {len(self.layers)} layers")
        self._purge(states)
        # Each entry holds a strong reference to its dataset array, so the
        # id() in its key stays reserved for the entry's lifetime — a key
        # hit always means THIS x.
        key = (id(x), k)
        entry = self._entries.get(key)
        if entry is not None:
            self.stats["hits"] += 1
            entry.tick = self._next_tick()
            return entry.value
        # Deepest still-cached level of THIS dataset below k.
        base, j = x, 0
        for (aid, lvl), e in self._entries.items():
            if aid == id(x) and j < lvl < k:
                base, j = e.value, lvl
        value = self._project(base, j, k, states, chunk)
        self._insert(key, value, states, x)
        return self._entries[key].value

    def invalidate(self) -> None:
        """Drop every cached level (e.g. before freeing the network)."""
        self._entries.clear()

    def invalidate_above(self, level: int) -> int:
        """Eagerly drop every cached level strictly above ``level`` — for
        every dataset — returning the number of entries dropped.

        Identity purging (:meth:`_purge`) already guarantees correctness
        lazily: an entry projected from superseded state objects can never
        be *served* again.  But it only runs at the next :meth:`level` call,
        so a state adoption (streaming-session close, continual merge or
        rollback) would otherwise leave the dead projections pinning device/
        host bytes until someone happens to ask for a level.  Adoption paths
        call this to release those bytes at the adoption itself.
        """
        stale = [k for k in self._entries if k[1] > level]
        for k in stale:
            del self._entries[k]
            self.stats["evictions"] += 1
        return len(stale)

    @property
    def device_bytes(self) -> int:
        return sum(e.nbytes for e in self._entries.values() if not e.on_host)

    @property
    def host_bytes(self) -> int:
        return sum(e.nbytes for e in self._entries.values() if e.on_host)

    @property
    def datasets(self) -> int:
        """Distinct dataset anchors currently cached."""
        return len({aid for aid, _ in self._entries})

    def resident(self, k: int, x=None) -> Optional[str]:
        """'device' / 'host' for a cached level, None when not cached.
        With ``x`` given, answers for that dataset's entry; without, for
        the most-recently-used entry at level ``k``."""
        if x is not None:
            e = self._entries.get((id(x), k))
            if e is None:
                return None
            return "host" if e.on_host else "device"
        hits = [e for (_, lvl), e in self._entries.items() if lvl == k]
        if not hits:
            return None
        e = max(hits, key=lambda e: e.tick)
        return "host" if e.on_host else "device"

    # -------------------------------------------------------------- plumbing
    def _next_tick(self) -> int:
        self._tick += 1
        return self._tick

    def _purge(self, states: Sequence[Any]) -> None:
        """Drop entries invalidated by upstream state changes — for EVERY
        cached dataset (all project through the same frozen states) — so
        stale entries never pin superseded buffers."""
        stale = [k for k, e in self._entries.items() if not e.valid_for(states)]
        for k in stale:
            del self._entries[k]
            self.stats["evictions"] += 1

    def _project(self, base, j: int, k: int, states: Sequence[Any], chunk: int):
        """One batched pass of ``base`` (level j) through layers[j:k].

        Full chunks run as ONE jitted scan over a ``(n_full, chunk, F)``
        stack; the ragged tail is zero-padded to a full chunk and sliced, so
        every row sees the same GEMM shape as a training batch — the
        bit-exactness contract with recomputing the prefix per batch.
        """
        self.stats["projections"] += 1
        frozen = tuple(states[j:k])
        n = base.shape[0]
        chunk = min(chunk, n)
        n_full, rem = divmod(n, chunk)
        parts = []
        if n_full:
            # Stage host chunks explicitly: the jitted scan must never be
            # the implicit h2d boundary (strict mode's transfer guard
            # disallows it; a no-op for device-resident bases).
            xs = jnp.asarray(self._as_chunks(base, n_full, chunk))
            ys = self._scan_fn(j, k)(frozen, xs)
            parts.append(ys.reshape(n_full * chunk, *ys.shape[2:]))
        if rem:
            tail = base[n_full * chunk :]
            pad = jnp.zeros if isinstance(tail, jax.Array) else np.zeros
            padded = jnp.concatenate(
                [jnp.asarray(tail), pad((chunk - rem, *tail.shape[1:]), tail.dtype)]
            )
            parts.append(self._chunk_fn(j, k)(frozen, padded)[:rem])
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)

    @staticmethod
    def _as_chunks(base, n_full: int, chunk: int):
        head = base[: n_full * chunk]
        shape = (n_full, chunk, *base.shape[1:])
        if isinstance(head, jax.Array):
            return head.reshape(shape)
        return np.ascontiguousarray(head).reshape(shape)

    def _scan_fn(self, j: int, k: int) -> Callable:
        fn = self._proj_scan.get((j, k))
        if fn is None:
            fwd = forward_stack(self.layers[j:k])

            def project(frozen, xs):
                def body(_, xb):
                    return None, fwd(frozen, xb)

                _, ys = jax.lax.scan(body, None, xs)
                return ys

            fn = jax.jit(project)
            self._proj_scan[(j, k)] = fn
        return fn

    def _chunk_fn(self, j: int, k: int) -> Callable:
        fn = self._proj_chunk.get((j, k))
        if fn is None:
            fn = jax.jit(forward_stack(self.layers[j:k]))
            self._proj_chunk[(j, k)] = fn
        return fn

    def _insert(self, key: Tuple[int, int], value, states: Sequence[Any], x) -> None:
        k = key[1]
        nbytes = int(value.nbytes)
        on_host = nbytes > self.budget_bytes
        if not on_host:
            # Spill least-recently-used device levels until this one fits.
            while self.device_bytes + nbytes > self.budget_bytes:
                victims = [
                    (e.tick, vk)
                    for vk, e in self._entries.items()
                    if not e.on_host
                ]
                if not victims:
                    break
                _, vk = min(victims)
                entry = self._entries[vk]
                entry.value = np.asarray(entry.value)
                entry.on_host = True
                self.stats["spills"] += 1
        if on_host:
            value = np.asarray(value)
            self.stats["spills"] += 1
        else:
            value = jnp.asarray(value)
            if self._place is not None:
                value = self._place(value)
        self._entries[key] = _Entry(
            value=value,
            states=tuple(states[:k]),
            x=x,
            nbytes=nbytes,
            on_host=on_host,
            tick=self._next_tick(),
        )
        # Host-spilled bytes are bounded too (they are recomputable): drop
        # LRU host entries beyond the host budget — multi-dataset serving
        # traffic must not grow host memory without limit.
        while self.host_bytes > self.host_budget_bytes:
            victims = [
                (e.tick, vk)
                for vk, e in self._entries.items()
                if e.on_host and vk != key
            ]
            if not victims:
                break  # only the just-inserted entry remains; keep it
            _, vk = min(victims)
            del self._entries[vk]
            self.stats["evictions"] += 1


def store_for(layers: Sequence[Any], config, trainer=None) -> "ActivationStore":
    """Build the store an :class:`ExecutionConfig` sizes.  With a
    DataParallelTrainer, device-resident levels are placed by the trainer's
    one row rule (``trainer.rows_sharding``), as a staged dataset is."""
    place = None
    if trainer is not None:
        place = lambda a: jax.device_put(a, trainer.rows_sharding(a.shape))  # noqa: E731
    budget = int(float(config.activation_budget_mb) * (1 << 20))
    return ActivationStore(layers, budget_bytes=budget, place=place)


__all__ = ["ActivationStore", "store_for"]
