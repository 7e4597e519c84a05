"""Unified serving subsystem: ``ServiceConfig -> InferenceService``.

The inference-side mirror of the PR 2 compile step.  Training binds a
declarative Network to one :class:`ExecutionPlan` via
``network.compile(ExecutionConfig(...))``; serving binds a compiled model to
one :class:`ServePlan` via::

    service = compiled.serve(ServiceConfig(max_batch=64, buckets=(16, 64)))
    scores  = service.predict(x)             # BCPNN classification (BatchedPlan)

    service = serve_model(model, params, ServiceConfig(max_batch=8, max_seq=256))
    done    = service.generate(requests)     # LM zoo decode (DecodePlan, fused)

Three strategies, analogous to ScanPlan/BatchPlan on the training side:

* :class:`BatchedPlan` — BCPNN classification through the compiled network's
  *shared* jitted level-H projection and readout head (the same
  ``build_head`` definition ``compiled.predict`` uses), with padding-bucket
  selection on the batch axis so a service facing arbitrary request sizes
  compiles a bounded number of shapes.  Repeated request batches hit the
  activation store's cached projection (content-addressed
  canonicalization) and pay only the head.  Zero-padding rows
  never change real outputs (the forward is row-independent;
  property-tested).
* :class:`DecodePlan` — prefill + continuous slot-batched decode for the LM
  zoo.  The hot path is ONE jitted, shape-stable step over a fused slot axis:
  per-slot ``(1, ...)`` caches live stacked in a single ``(max_batch, ...)``
  pytree and every active slot advances through one ``vmap``'d
  ``decode_step`` with per-slot positions — no per-slot Python-loop dispatch
  (the seed ``ServeSession`` paid one jit call per slot per token).
  The admit/evict/step machinery lives in :class:`DecodeSession`, which
  both the synchronous ``generate()`` loop and the async engine
  (:mod:`repro.runtime.engine`) drive — ONE slot schedule, so the two
  surfaces are token-identical under deterministic arrivals.
  Prompt-length padding buckets bound prefill traces for attention families;
  prefill gathers last-position logits at the *true* prompt end
  (``last_pos``), so bucketing is token-exact.  SSM/hybrid state caches are
  position-dependent, so those families prefill at exact length (per-length
  cells LRU-bounded by ``cache_size``).
* :class:`StreamingPlan` — the latency-oriented online path: wraps the
  compiled network's :class:`StreamingSession` (host-side coalescing,
  LRU-bounded per-shape cells, state adoption on close) behind the same
  front door.

:class:`InferenceService` owns the request queue (admission control via
``max_queue``, ordering via ``policy``: "fcfs" arrival order or "sjf"
shortest-prompt-first — decode plans only; other plans reject it at bind
time) and delegates execution to its plan.  Slot admission/eviction — free
slot -> prefill -> decode -> EOS/limit -> refill — lives inside
DecodeSession, at step granularity (continuous batching).

``service.start()`` (or ``ServiceConfig(async_mode=True)``) hands the queue
to a dedicated executor thread: ``submit()`` then returns a
``concurrent.futures.Future`` and new requests are admitted into freed
decode slots *mid-flight*, between jitted steps — see
:mod:`repro.runtime.engine`.  Every plan records latency telemetry
(queue-wait / prefill / per-token decode histograms,
:mod:`repro.runtime.metrics`) surfaced via ``service.stats["telemetry"]``.

``pad_cache_like`` is the structural replacement for the seed's name-list
cache-padding heuristic: every leaf grows to its template shape (from
``jax.eval_shape`` of ``init_cache``), so new cache layouts (MLA latents,
hybrid ssm+kv, enc-dec cross kv) pad correctly without name registration.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.strict import RecompileSentinel, dispatch_guard
from repro.core.streaming import _LRUCells
from repro.runtime.metrics import ServiceMetrics

POLICIES = ("fcfs", "sjf")

# Families whose decode cache is a position-dependent recurrent state: a
# right-padded prefill would fold pad tokens into the state, so prompt
# bucketing is disabled and prefill runs at exact length.
_STATEFUL_FAMILIES = ("ssm", "hybrid")


# --------------------------------------------------------------- requests
@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (len,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    # Trace correlation: minted by the fabric front door (Router/engine)
    # when tracing is on, so plan-level spans (prefill, per-token decode)
    # join the same trace as the scheduling hops.  None when tracing is off.
    trace_id: Optional[int] = None


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: np.ndarray  # generated tokens
    prefill_len: int
    steps: int


# ---------------------------------------------------------- cache padding
def pad_cache_like(cache, template):
    """Grow every leaf of ``cache`` to its ``template`` shape (trailing
    zero-pad per axis).  ``template`` is typically
    ``jax.eval_shape(lambda: model.init_cache(batch, max_seq))`` — purely
    structural, so any cache pytree (GQA k/v, MLA latents, SSM states,
    enc-dec cross kv) pads without a leaf-name registry."""

    def pad(a, t):
        if tuple(a.shape) == tuple(t.shape):
            return a
        if a.ndim != len(t.shape) or any(
            s > ts for s, ts in zip(a.shape, t.shape)
        ):
            raise ValueError(
                f"cache leaf of shape {tuple(a.shape)} cannot grow to "
                f"template shape {tuple(t.shape)}"
            )
        return jnp.pad(a, [(0, ts - s) for s, ts in zip(a.shape, t.shape)])

    return jax.tree_util.tree_map(pad, cache, template)


# ------------------------------------------------------------------ config
@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Everything about *how* a model serves, none of *what* it serves.

    max_batch:  concurrent capacity — decode slots (DecodePlan), padding
                chunk cap (BatchedPlan), coalescing micro-batch
                (StreamingPlan).
    max_seq:    decode cache length (prompt + generated), DecodePlan only.
    buckets:    ascending padding buckets — prompt lengths for DecodePlan
                prefill, batch sizes for BatchedPlan predict.  None = exact
                shapes (jit traces per distinct size, LRU-bounded).
    policy:     queue admission order: "fcfs" (arrival) or "sjf"
                (shortest-prompt-first; decode plans only).
    cache_size: LRU bound on per-shape jitted callables (prefill cells /
                streaming cells).
    plan:       "batched" | "decode" | "streaming"; None lets the entry
                point pick its default (serve() -> batched, serve_model()
                -> decode).
    max_wait_s: micro-batch aggregation deadline: the async engine (and
                StreamingPlan's coalescing buffer) waits at most this long
                to fill ``max_batch`` before dispatching a partial batch.
    max_queue:  admission control — submit() beyond this depth is rejected
                (None = unbounded).  The async engine's inbox is bounded by
                the same knob (backpressure).
    layer:      StreamingPlan's target hidden layer (deep greedy stacks can
                stream online updates into any level, matching
                ``compiled.streaming(layer=...)``).
    async_mode: start the dedicated executor thread at bind time —
                ``submit()`` returns a ``Future`` and decode slots admit
                new requests mid-flight (continuous batching).  For
                streaming plans the async surface serves per-item
                INFERENCE (sync submit+drain feeds training samples).
    strict:     runtime hot-path verification (repro.analysis.strict): the
                fused decode step and the batched head dispatch run under
                jax.transfer_guard("disallow"), and a recompile sentinel
                asserts the plan's jitted callables compile exactly once
                across repeated submit/predict/generate rounds (new prefill
                buckets get their own baseline).
    router:     a ``repro.runtime.router.RouterConfig`` enabling the fleet
                front door — ``serve_fleet()`` builds N engines over shared
                params behind one Router (per-tenant queues, deadlines, hot
                restart).  None = single-engine serving, unchanged.
    continual:  a ``repro.runtime.continual.ContinualConfig`` enabling the
                online-learning tier — the bound plan becomes
                :class:`~repro.runtime.continual.ContinualPlan` (inference
                unchanged; labeled ``Feedback`` items drive jitted Hebbian
                adapter updates, merges, drift detection and rollback).
                None = frozen serving, bit-identical to before.
    trace:      a ``repro.runtime.trace.TraceConfig`` enabling per-request
                tracing + the structured event journal: every hop (queue
                wait, inbox, prefill, per-token decode, learn) records a
                span keyed by the request's ``trace_id``, exportable as
                Chrome trace JSON.  None (the default) constructs no
                tracer at all — zero allocation, zero lock traffic,
                bit-identical results.
    """

    max_batch: int = 4
    max_seq: int = 256
    buckets: Optional[Tuple[int, ...]] = None
    policy: str = "fcfs"
    cache_size: int = 8
    plan: Optional[str] = None
    max_wait_s: float = 0.0
    max_queue: Optional[int] = None
    layer: int = 0
    async_mode: bool = False
    strict: bool = False
    router: Optional[Any] = None
    continual: Optional[Any] = None
    trace: Optional[Any] = None

    def __post_init__(self):
        if self.continual is not None or self.plan == "continual":
            # Lazy circular-import break (continual -> service for the plan
            # base); importing registers ContinualPlan in SERVE_PLANS before
            # the plan-name validation below runs.
            from repro.runtime.continual import ContinualConfig

            if self.continual is not None and not isinstance(
                self.continual, ContinualConfig
            ):
                raise ValueError(
                    f"continual must be a ContinualConfig, got "
                    f"{type(self.continual).__name__}"
                )
            if self.plan not in (None, "continual"):
                raise ValueError(
                    f"continual learning serves through plan='continual', "
                    f"got plan={self.plan!r}"
                )
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.layer < 0:
            raise ValueError(f"layer must be >= 0, got {self.layer}")
        if self.max_seq < 1:
            raise ValueError(f"max_seq must be >= 1, got {self.max_seq}")
        if self.policy not in POLICIES:
            raise ValueError(
                f"Unknown policy {self.policy!r} (want one of {POLICIES})"
            )
        # Validate against the plan registry — the single source of truth —
        # so registering a new ServePlan automatically extends configs.
        if self.plan is not None and self.plan not in SERVE_PLANS:
            raise ValueError(
                f"Unknown plan {self.plan!r} (want one of {sorted(SERVE_PLANS)})"
            )
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {self.max_queue}")
        if self.max_wait_s < 0:
            raise ValueError(f"max_wait_s must be >= 0, got {self.max_wait_s}")
        if self.buckets is not None:
            b = tuple(int(x) for x in self.buckets)
            if not b or any(x <= 0 for x in b) or list(b) != sorted(set(b)):
                raise ValueError(
                    f"buckets must be strictly ascending positive ints, got "
                    f"{self.buckets!r}"
                )
            object.__setattr__(self, "buckets", b)
        if self.router is not None:
            # Lazy import: router -> service for Request/ServiceConfig, so
            # the validation (not the module top) pulls the router in.
            from repro.runtime.router import RouterConfig

            if not isinstance(self.router, RouterConfig):
                raise ValueError(
                    f"router must be a RouterConfig, got "
                    f"{type(self.router).__name__}"
                )
        if self.trace is not None:
            from repro.runtime.trace import TraceConfig

            if not isinstance(self.trace, TraceConfig):
                raise ValueError(
                    f"trace must be a TraceConfig, got "
                    f"{type(self.trace).__name__}"
                )

    def bucket_for(self, n: int) -> int:
        """Smallest configured bucket >= n, or n itself when none fits."""
        if self.buckets is not None:
            for b in self.buckets:
                if b >= n:
                    return b
        return n


# ------------------------------------------------------------------- plans
class ServePlan:
    """Base serving strategy.  Subclasses implement the capability they
    serve; calling an unsupported capability raises with the plan name.
    Every plan owns a :class:`ServiceMetrics` bundle (shared with the
    service front door and the async engine) and a ``_lock`` guarding its
    stat counters — the async engine's executor thread mutates them while
    caller threads read ``stats`` (the same discipline ``metrics.py``
    follows, enforced by jaxlint JL004)."""

    name: str = "?"

    def __init__(self, config: ServiceConfig,
                 metrics: Optional[ServiceMetrics] = None):
        self.config = config
        self.metrics = metrics if metrics is not None else ServiceMetrics()
        self._lock = threading.Lock()
        # Strict-mode recompile sentinel over this plan's jitted callables
        # (repro.analysis.strict); None unless ``config.strict``.
        self._sentinel = RecompileSentinel() if config.strict else None
        # Per-request tracer (repro.runtime.trace), attached by the fabric
        # owner via bind_tracer(); None keeps every span site a dead check.
        self.tracer = None

    def bind_tracer(self, tracer) -> None:
        """Attach the fabric's Tracer so plan-level spans (prefill,
        per-token decode, learn/merge) join request traces; also hooks the
        strict-mode sentinel's rebaseline into the event journal."""
        with self._lock:
            self.tracer = tracer
        if self._sentinel is not None and tracer is not None:
            def _journal_rebaseline(sizes, _t=tracer):
                from repro.runtime.trace import RecompileRebaseline

                _t.emit(RecompileRebaseline(sizes=dict(sizes)))

            self._sentinel.on_rebaseline = _journal_rebaseline

    def _strict_registry(self) -> Dict[str, Any]:
        """name -> jitted callable, re-collected at every check (registries
        grow: new prefill buckets, lazily-built heads)."""
        return {}

    def _strict_check(self, where: str) -> None:
        if self._sentinel is None:
            return
        for name, fn in self._strict_registry().items():
            self._sentinel.watch(name, fn)
        self._sentinel.check(where)

    def _unsupported(self, what: str):
        raise NotImplementedError(
            f"{type(self).__name__} ({self.name!r}) does not serve {what}"
        )

    # capability surface -------------------------------------------------
    def predict(self, x):
        self._unsupported("predict()")

    def generate(self, requests: List[Request]) -> List[Completion]:
        self._unsupported("generate()")

    def feed(self, sample) -> None:
        self._unsupported("feed()")

    def infer(self, sample):
        self._unsupported("infer()")

    def flush(self) -> None:  # default no-op: batch plans have no buffer
        pass

    def close(self) -> None:
        pass

    @property
    def stats(self) -> Dict[str, Any]:
        return {}


class BatchedPlan(ServePlan):
    """BCPNN classification through the compiled network's shared head.

    ``predict`` chunks the input along the batch axis (chunk cap =
    ``max_batch`` or the largest bucket), pads each chunk up to its bucket
    with zero rows, projects it through the SAME jitted frozen-stack
    projection ``compiled.predict``/``evaluate`` use, then applies the ONE
    shared ``build_head`` definition.  Padded chunks are
    content-canonicalized (a small LRU maps chunk bytes -> one anchor
    array), so repeated request batches hit the store's cached level-H
    projection and pay only the readout head.  A network with no hidden
    layer runs the shared full-network forward instead."""

    name = "batched"

    _CANON_CAPACITY = 32  # distinct padded chunks remembered for reuse

    def __init__(self, compiled, config: ServiceConfig,
                 metrics: Optional[ServiceMetrics] = None):
        super().__init__(config, metrics)
        self.compiled = compiled
        self._fwd = compiled._forward_fn()  # shared forward (no hidden layer)
        self._requests = 0
        self._rows = 0
        self._padded_rows = 0
        # Content-addressed canonicalization: digest -> the first array
        # object seen with those bytes.  The activation store anchors cache
        # validity on array identity, so resubmitted batches must map onto
        # ONE object to hit its projection.
        self._canon: "OrderedDict[tuple, np.ndarray]" = OrderedDict()
        self._reuse_hits = 0

    def _chunk_cap(self) -> int:
        if self.config.buckets is not None:
            return self.config.buckets[-1]
        return self.config.max_batch

    def _canonical(self, xb: np.ndarray) -> np.ndarray:
        key = (
            xb.shape,
            str(xb.dtype),
            hashlib.blake2b(np.ascontiguousarray(xb).tobytes(),
                            digest_size=16).digest(),
        )
        hit = self._canon.get(key)
        if hit is not None:
            self._canon.move_to_end(key)
            with self._lock:
                self._reuse_hits += 1
            return hit
        # Anchor a PRIVATE copy, never a view of the caller's array: the
        # digest->anchor mapping (and the store's identity-keyed projection)
        # must survive the caller mutating their buffer in place.
        # jaxlint: allow[JL001] reason=private host-side anchor copy for the digest cache; no device involved
        anchor = np.array(xb, copy=True)
        self._canon[key] = anchor
        while len(self._canon) > self._CANON_CAPACITY:
            self._canon.popitem(last=False)
        return anchor

    def _strict_registry(self) -> Dict[str, Any]:
        reg: Dict[str, Any] = {"forward": self._fwd}
        if self.compiled._head is not None:
            reg["head"] = self.compiled._head
        store = self.compiled.activations
        for (j, k), fn in store._proj_scan.items():
            reg[f"proj_scan[{j}->{k}]"] = fn
        for (j, k), fn in store._proj_chunk.items():
            reg[f"proj_chunk[{j}->{k}]"] = fn
        return reg

    def _scores(self, xb: np.ndarray) -> jnp.ndarray:
        """One padded chunk -> class scores, through the shared head."""
        compiled = self.compiled
        state = compiled.state
        if compiled.hidden_layers:
            xb = self._canonical(xb)
            n_hidden = len(compiled.hidden_layers)
            h = compiled.activations.level(
                n_hidden, list(state.layers), xb, chunk=xb.shape[0]
            )
            head = compiled._head_fn()
            hd = jnp.asarray(h)
            with dispatch_guard(self.config.strict):
                return head(state.layers, state.readout, hd)
        xd = jnp.asarray(xb)
        with dispatch_guard(self.config.strict):
            return self._fwd(state.layers, state.readout, xd)

    def predict(self, x) -> jnp.ndarray:
        # jaxlint: allow[JL001] reason=host-side input normalization before bucket padding; the h2d boundary is _scores
        x = np.asarray(x)
        if x.ndim == 1:
            x = x[None, :]
        cap = self._chunk_cap()
        outs = []
        for i in range(0, x.shape[0], cap):
            xb = x[i : i + cap]
            n = xb.shape[0]
            m = self.config.bucket_for(n)
            if m > n:
                xb = np.concatenate(
                    [xb, np.zeros((m - n,) + xb.shape[1:], xb.dtype)], axis=0
                )
                with self._lock:
                    self._padded_rows += m - n
            t0 = time.perf_counter()
            # jaxlint: allow[JL001] reason=per-chunk latency telemetry blocks once at the dispatch boundary
            scores = jax.block_until_ready(self._scores(xb))
            self.metrics.batch_s.observe(time.perf_counter() - t0)
            outs.append(scores[:n])
            with self._lock:
                self._rows += n
        with self._lock:
            self._requests += 1
        self._strict_check("predict")
        return jnp.concatenate(outs, axis=0)

    @property
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "requests": self._requests,
                "rows": self._rows,
                "padded_rows": self._padded_rows,
                "projection_reuse_hits": self._reuse_hits,
            }


class DecodeSession:
    """Mutable slot state for one continuously-batched decode run.

    The admit / evict / fused-step cycle lives HERE, so the synchronous
    whole-queue ``DecodePlan.generate`` and the async engine's mid-flight
    admission loop drive literally the same schedule: admission fills free
    slots in slot order, eviction retires finished slots, and one jitted
    dispatch advances every active slot.  ``tag`` is an opaque caller
    handle (the engine keys futures on it); completions come back as
    ``(tag, Completion)`` pairs."""

    def __init__(self, plan: "DecodePlan"):
        self.plan = plan
        S = plan.config.max_batch
        self.S = S
        self.active: List[Optional[Dict]] = [None] * S
        self.caches = jax.tree_util.tree_map(
            lambda t: jnp.zeros((S,) + tuple(t.shape), t.dtype),
            plan._cache_template,
        )

    def free_slots(self) -> int:
        return sum(a is None for a in self.active)

    def has_active(self) -> bool:
        return any(a is not None for a in self.active)

    def admit(self, req: Request, tag: Any = None) -> bool:
        """Prefill ``req`` into the lowest free slot; False when full."""
        slot = next(
            (s for s in range(self.S) if self.active[s] is None), None
        )
        if slot is None:
            return False
        plan = self.plan
        t0 = time.perf_counter()
        first, cache_one = plan._prefill_one(req.prompt)
        self.caches = plan._write(
            self.caches, cache_one, jnp.asarray(slot, jnp.int32)
        )
        if plan.tracer is not None:
            tid = getattr(req, "trace_id", None)
            if tid is not None:
                plan.tracer.record(
                    tid, "plan.prefill", t0, time.perf_counter(),
                    slot=slot, prompt_len=len(req.prompt),
                )
        self.active[slot] = {
            "req": req,
            "cur_len": len(req.prompt),
            "tokens": [first],
            "steps": 1,
            "tag": tag,
        }
        plan._count_admit()
        plan._strict_check("prefill/admit")
        return True

    def step(self) -> List[Tuple[Any, Completion]]:
        """One engine cycle minus admission: retire finished slots, then
        advance every remaining active slot through ONE fused dispatch.
        Returns the ``(tag, Completion)`` pairs retired this call."""
        plan = self.plan
        cfg = plan.config
        done: List[Tuple[Any, Completion]] = []

        # Eviction: retire finished slots (freed slots refill on the next
        # admission pass, i.e. continuous batching at step granularity —
        # same schedule as the per-slot reference loop).
        advancing = []
        for slot in range(self.S):
            st = self.active[slot]
            if st is None:
                continue
            req = st["req"]
            if (
                len(st["tokens"]) >= req.max_new_tokens
                or (req.eos_id is not None and st["tokens"][-1] == req.eos_id)
                or st["cur_len"] + 1 >= cfg.max_seq
            ):
                done.append(
                    (
                        st["tag"],
                        Completion(
                            rid=req.rid,
                            # jaxlint: allow[JL001] reason=token list is host data already; no device transfer
                            tokens=np.asarray(st["tokens"], np.int32),
                            prefill_len=len(req.prompt),
                            steps=st["steps"],
                        ),
                    )
                )
                plan._count_retired(len(st["tokens"]))
                self.active[slot] = None
                continue
            advancing.append(slot)

        if not advancing:
            return done

        # The fused hot path: ONE jitted dispatch advances every slot.
        # Idle slots ride along with position 0 and a dead cache — their
        # outputs are discarded and their cache is overwritten at the
        # next admission, so the step stays shape-stable at (S, ...).
        tokens = np.zeros(self.S, np.int32)
        cur_lens = np.zeros(self.S, np.int32)
        for slot in advancing:
            tokens[slot] = self.active[slot]["tokens"][-1]
            cur_lens[slot] = self.active[slot]["cur_len"]
        t0 = time.perf_counter()
        toks_d = jnp.asarray(tokens)
        lens_d = jnp.asarray(cur_lens)
        with dispatch_guard(plan.config.strict):
            nxt, self.caches = plan._fused(
                plan.params, self.caches, toks_d, lens_d
            )
        # jaxlint: allow[JL001] reason=greedy tokens steer EOS/admission host-side; ONE d2h per fused step by design
        nxt = np.asarray(nxt)
        t1 = time.perf_counter()
        plan.metrics.decode_step_s.observe(t1 - t0)
        if plan.tracer is not None:
            # One span per advancing request per token (inter-token
            # latency, trace-correlated); the fused dispatch is shared, so
            # concurrent slots show identical span bounds — by design.
            for slot in advancing:
                tid = getattr(self.active[slot]["req"], "trace_id", None)
                if tid is not None:
                    plan.tracer.record(
                        tid, "plan.decode_step", t0, t1, slot=slot,
                        token=self.active[slot]["steps"],
                    )
        for slot in advancing:
            st = self.active[slot]
            st["tokens"].append(int(nxt[slot]))
            st["cur_len"] += 1
            st["steps"] += 1
        plan._count_step(len(advancing))
        plan._strict_check("decode step")
        return done


class DecodePlan(ServePlan):
    """Continuous slot-batched LM serving with a fused decode step.

    Slots are admission units (one request each); their ``(1, ...)`` caches
    live stacked on the leading axis of ONE ``(max_batch, ...)`` cache
    pytree.  Every step, all slots advance together through a single jitted
    ``vmap``'d ``decode_step`` with per-slot write positions — token-exact
    vs the per-slot reference loop (parity-tested), one dispatch per token
    instead of ``max_batch``.  :meth:`session` exposes the admit/step
    machinery for continuous callers (the async engine)."""

    name = "decode"

    def __init__(self, model, params, config: ServiceConfig,
                 metrics: Optional[ServiceMetrics] = None):
        super().__init__(config, metrics)
        if getattr(model.cfg, "family", None) == "encdec":
            raise ValueError(
                "DecodePlan serves decoder-only models; enc-dec serving "
                "needs a cross-attention prefill path"
            )
        if config.buckets is not None and config.buckets[-1] > config.max_seq:
            raise ValueError(
                f"prompt buckets {config.buckets} exceed max_seq="
                f"{config.max_seq}: a bucketed prefill cache could not fit "
                "the decode cache"
            )
        self.model = model
        self.params = params
        self._family = model.cfg.family
        self._cache_template = jax.eval_shape(
            lambda: model.init_cache(1, config.max_seq)
        )
        # Per-padded-length prefill cells, LRU-bounded like streaming cells.
        self._prefill_cells = _LRUCells(config.cache_size)
        self._fused = jax.jit(self._fused_step)
        self._write = jax.jit(self._write_slot)
        self._fused_steps = 0
        self._slot_steps = 0
        self._requests = 0
        self._tokens = 0

    # ------------------------------------------------------- stat counters
    # DecodeSession (driven by the engine's executor thread) counts through
    # these, so every mutation shares one lock with the ``stats`` reader.
    def _count_admit(self) -> None:
        with self._lock:
            self._requests += 1

    def _count_retired(self, n_tokens: int) -> None:
        with self._lock:
            self._tokens += n_tokens

    def _count_step(self, n_slots: int) -> None:
        with self._lock:
            self._fused_steps += 1
            self._slot_steps += n_slots

    def _strict_registry(self) -> Dict[str, Any]:
        reg: Dict[str, Any] = {
            "fused_step": self._fused,
            "write_slot": self._write,
        }
        # Per-bucket prefill cells are separate callables: a NEW bucket gets
        # its own baseline (expected trace), the SAME bucket re-tracing is a
        # violation.
        for m, cell in self._prefill_cells.items():
            reg[f"prefill[{m}]"] = cell
        return reg

    # ---------------------------------------------------------- jit bodies
    def _fused_step(self, params, caches, tokens, cur_lens):
        """One decode step for ALL slots: (S,...) caches, (S,) tokens and
        per-slot positions -> ((S,) next greedy tokens, new caches)."""

        def one(cache, tok, cur_len):
            logits, new_cache = self.model.decode_step(
                params, cache, tok[None, None], cur_len
            )
            return logits[0], new_cache

        logits, caches = jax.vmap(one)(caches, tokens, cur_lens)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    def _write_slot(self, caches, cache_one, slot):
        """Install one admitted request's (1, ...) cache at slot index."""
        return jax.tree_util.tree_map(
            lambda f, c: jax.lax.dynamic_update_index_in_dim(f, c, slot, 0),
            caches,
            cache_one,
        )

    # ------------------------------------------------------------- prefill
    def _prompt_bucket(self, n: int) -> int:
        if self._family in _STATEFUL_FAMILIES:
            return n  # recurrent state would absorb pad tokens
        return self.config.bucket_for(n)

    def _prefill_one(self, prompt: np.ndarray):
        """(first greedy token, structurally padded (1, max_seq) cache)."""
        n = len(prompt)
        if n < 1:
            raise ValueError("empty prompt")
        if n > self.config.max_seq:
            raise ValueError(
                f"prompt length {n} exceeds max_seq={self.config.max_seq}"
            )
        t0 = time.perf_counter()
        m = self._prompt_bucket(n)
        cell = self._prefill_cells.get(m)
        if cell is None:
            # Close over the MODEL only (cells outlive trace eviction).
            cell = jax.jit(
                lambda params, batch, _m=self.model: _m.prefill(params, batch)
            )
            self._prefill_cells.put(m, cell)
        tokens = np.zeros((1, m), np.int32)
        tokens[0, :n] = prompt
        # last_pos gathers logits at the true prompt end: causal attention
        # makes positions <= last_pos independent of right-padding, so the
        # bucketed prefill is bit-identical to an exact-length one.
        batch = {"tokens": jnp.asarray(tokens),
                 "last_pos": jnp.asarray(n - 1, jnp.int32)}
        with dispatch_guard(self.config.strict):
            logits, cache = cell(self.params, batch)
        cache = pad_cache_like(cache, self._cache_template)
        # jaxlint: allow[JL001] reason=first token steers admission host-side; one sync per prefill
        first = int(jnp.argmax(logits[0]))
        self.metrics.prefill_s.observe(time.perf_counter() - t0)
        return first, cache

    # ------------------------------------------------------------ generate
    def session(self) -> DecodeSession:
        """A fresh slot-state for continuous admission (the async engine's
        substrate; ``generate`` opens one per call)."""
        return DecodeSession(self)

    def generate(self, requests: List[Request]) -> List[Completion]:
        """Whole-queue continuous batching: admit into free slots, advance
        all active slots through the fused step, evict on EOS/limits,
        refill — the same DecodeSession schedule the async engine drives."""
        sess = self.session()
        pending: Deque[Request] = deque(requests)
        done: List[Completion] = []
        while pending or sess.has_active():
            while pending and sess.admit(pending[0]):
                pending.popleft()
            done.extend(c for _, c in sess.step())
        return done

    @property
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "requests": self._requests,
                "tokens_generated": self._tokens,
                "fused_steps": self._fused_steps,
                "slot_steps": self._slot_steps,
                "mean_occupancy": (
                    self._slot_steps / self._fused_steps
                    if self._fused_steps
                    else 0.0
                ),
                "prefill_cells": len(self._prefill_cells),
                "prefill_cell_evictions": self._prefill_cells.evictions,
            }


class StreamingPlan(ServePlan):
    """The latency path: online BCPNN updates/inference via the compiled
    network's StreamingSession (coalescing buffer, shared LRU-bounded cells,
    state adoption on close) behind the service front door."""

    name = "streaming"

    def __init__(self, compiled, config: ServiceConfig,
                 layer: Optional[int] = None,
                 metrics: Optional[ServiceMetrics] = None):
        super().__init__(config, metrics)
        self.session = compiled.streaming(
            layer=config.layer if layer is None else layer,
            max_batch=config.max_batch,
            max_wait_s=config.max_wait_s,
            cache_size=config.cache_size,
        )

    def feed(self, sample) -> None:
        self.session.feed(sample)

    def infer(self, sample):
        t0 = time.perf_counter()
        out = self.session.infer(sample)
        self.metrics.batch_s.observe(time.perf_counter() - t0)
        return out

    def flush(self) -> None:
        self.session.flush()

    def close(self) -> None:
        self.session.close()

    @property
    def stats(self) -> Dict[str, Any]:
        return self.session.stats


SERVE_PLANS = {
    BatchedPlan.name: BatchedPlan,
    DecodePlan.name: DecodePlan,
    StreamingPlan.name: StreamingPlan,
}


# ----------------------------------------------------------------- service
class InferenceService:
    """The serving front door: a request queue with admission control and
    ordering policy, delegating execution to one bound ServePlan.

    Two execution surfaces share the queue semantics:

    * the synchronous parity path — ``submit()`` returns bool, ``drain()``
      runs everything queued through the plan in one call;
    * the async path — ``start()`` hands the plan to a dedicated
      executor thread (:class:`repro.runtime.engine.AsyncEngine`) and
      ``submit()`` returns a ``concurrent.futures.Future``; decode slots
      admit new requests mid-flight between jitted steps.
    """

    def __init__(self, plan: ServePlan, config: ServiceConfig):
        if config.policy == "sjf" and plan.name != "decode":
            raise ValueError(
                f"policy='sjf' orders decode Requests by prompt length; "
                f"the {plan.name!r} plan has no request length to order by "
                "(use policy='fcfs')"
            )
        self.plan = plan
        self.config = config
        self.metrics = plan.metrics
        # Single-engine tracing: the service owns the Tracer (fleet serving
        # puts it on the Router instead) and binds it to the plan so
        # prefill / per-token spans join the engine's inbox spans.
        from repro.runtime.trace import build_tracer

        self.tracer = build_tracer(config.trace)
        if self.tracer is not None:
            plan.bind_tracer(self.tracer)
        self.engine = None  # set by start()
        self._queue: Deque = deque()
        self._queue_t: Deque[float] = deque()

    # --------------------------------------------------------------- async
    def start(self, run: bool = True):
        """Bind (and by default start) the async engine; ``submit()``
        afterwards returns Futures.  Idempotent while the engine is live.

        ``run=False`` binds the engine without launching its thread:
        submits queue into the bounded inbox and execute when ``start()``
        (or ``drain_and_stop()``) runs it — deterministic arrival order
        for tests and pre-warmed startup.

        Items already in the SYNC queue have no Future to resolve into, so
        they cannot migrate: ``start()`` refuses while the sync queue is
        non-empty (``drain()`` it first)."""
        from repro.runtime.engine import AsyncEngine

        if self._queue:
            raise RuntimeError(
                f"{len(self._queue)} item(s) in the sync queue have no "
                "Future to resolve into; drain() before start()"
            )
        if self.engine is not None and not self.engine.stopped:
            if run:
                self.engine.start()
            return self.engine
        self.engine = AsyncEngine(self.plan, self.config, tracer=self.tracer)
        if run:
            self.engine.start()
        return self.engine

    def drain_and_stop(self):
        """Finish all in-flight/queued async work, then stop the engine.
        No-op when the engine was never started."""
        if self.engine is not None:
            self.engine.drain_and_stop()

    # --------------------------------------------------------------- queue
    def submit(self, item):
        """Queue one work item (a Request for decode plans, a sample for
        batched/streaming).

        Synchronous mode: returns True, or False when ``max_queue`` rejects
        the item.  Once ``start()`` has bound the async engine, delegates
        to it and returns a ``concurrent.futures.Future`` (backpressure
        raises ``QueueFull``; a stopped engine raises ``EngineStopped``
        rather than silently reverting to the sync queue).

        NOTE the streaming-plan semantics differ by surface: sync
        ``submit``+``drain`` FEEDS samples (online training, matching the
        paper's streaming-update mode), while async submits run INFERENCE
        per item (futures resolve to scores — the latency-serving path).
        Keep training feeds on the sync surface / ``feed()``."""
        if self.engine is not None:
            return self.engine.submit(item)
        if (
            self.config.max_queue is not None
            and len(self._queue) >= self.config.max_queue
        ):
            self.metrics.rejected.inc()
            return False
        self._queue.append(item)
        self._queue_t.append(time.perf_counter())
        self.metrics.submitted.inc()
        self.metrics.queue_depth.set(len(self._queue))
        return True

    def _ordered(self, requests: List[Request]) -> List[Request]:
        if self.config.policy == "sjf":
            return sorted(requests, key=lambda r: len(r.prompt))  # stable
        return list(requests)

    def drain(self):
        """Run everything queued through the plan: completions (decode),
        stacked scores (batched), or a flush (streaming)."""
        if self.engine is not None and not self.engine.stopped:
            raise RuntimeError(
                "the async engine owns this service's queue; submit() "
                "returns Futures — use them, or drain_and_stop() first"
            )
        items = list(self._queue)
        stamps = list(self._queue_t)
        self._queue.clear()
        self._queue_t.clear()
        self.metrics.queue_depth.set(0)
        now = time.perf_counter()
        for t in stamps:
            self.metrics.queue_wait_s.observe(now - t)
        if not items:
            self.plan.flush()
            # Decode plans always answer with completions, even for an
            # empty queue (callers iterate the result).
            return [] if self.plan.name == "decode" else None
        if isinstance(items[0], Request):
            out = self.plan.generate(self._ordered(items))
        elif self.plan.name == "streaming":
            for s in items:
                self.plan.feed(s)
            self.plan.flush()
            out = None
        elif self.plan.name == "continual":
            # Mixed traffic in arrival order: Feedback learns, anything
            # else infers — one result per item, mirroring the async path.
            from repro.runtime.continual import Feedback

            out = [
                self.plan.learn(s) if isinstance(s, Feedback)
                else self.plan.infer(s)
                for s in items
            ]
        else:
            # jaxlint: allow[JL001] reason=submitted items are host objects; staging them is the h2d boundary
            out = self.plan.predict(np.stack([np.asarray(s) for s in items]))
        end = time.perf_counter()
        for t in stamps:
            self.metrics.e2e_s.observe(end - t)
        self.metrics.completed.inc(len(items))
        return out

    # -------------------------------------------------- direct conveniences
    def predict(self, x):
        return self.plan.predict(x)

    def generate(self, requests: List[Request]) -> List[Completion]:
        return self.plan.generate(self._ordered(requests))

    def feed(self, sample) -> None:
        self.plan.feed(sample)

    def infer(self, sample):
        return self.plan.infer(sample)

    def flush(self) -> None:
        self.plan.flush()

    def close(self) -> None:
        if self.engine is not None:
            self.engine.drain_and_stop()
        self.plan.close()

    @property
    def stats(self) -> Dict[str, Any]:
        engine_live = self.engine is not None and not self.engine.stopped
        out = {
            "plan": self.plan.name,
            # Queued = the sync queue plus the engine inbox: callers sizing
            # backpressure see every waiting item wherever it waits.
            "queued": len(self._queue)
            + (self.engine.inbox_depth if engine_live else 0),
            "rejected": self.metrics.rejected.value,
            **self.plan.stats,
            "telemetry": self.metrics.snapshot(),
        }
        if self.engine is not None:
            out["engine"] = self.engine.stats
        return out


def serve_model(model, params, config: Optional[ServiceConfig] = None) -> InferenceService:
    """Bind an LM (CausalLM + params) to an InferenceService — the LM-zoo
    twin of ``CompiledNetwork.serve``.  Only the decode plan applies.
    ``ServiceConfig(async_mode=True)`` starts the executor thread at bind
    time (submit() then returns Futures)."""
    config = config if config is not None else ServiceConfig()
    plan_name = config.plan or "decode"
    if plan_name != "decode":
        raise ValueError(
            f"serve_model() serves token decoding; plan {plan_name!r} needs "
            "a CompiledNetwork (use compiled.serve)"
        )
    service = InferenceService(DecodePlan(model, params, config), config)
    if config.async_mode:
        service.start()
    return service


def serve_fleet(model, params, config: Optional[ServiceConfig] = None,
                *, fleet: int = 2):
    """Bind an LM to a started :class:`~repro.runtime.router.Router`
    fronting ``fleet`` decode engines over SHARED params — the multi-engine
    twin of ``serve_model``.  One set of weights, N independent decode
    loops; ``router.submit(request, tenant=..., deadline_s=...)`` returns
    a Future exactly like the single-engine async path.

    ``config.router`` (a RouterConfig) carries the scheduling knobs
    (tenants, routing policy, restart budgets); the rest of the
    ServiceConfig applies per engine.  Engine inboxes are kept shallow
    (``max_queue`` defaults to ``max_batch`` here) so queueing — and
    therefore tenant/deadline policy — lives in the Router.
    """
    from repro.runtime.router import Router, RouterConfig

    config = config if config is not None else ServiceConfig()
    if fleet < 1:
        raise ValueError(f"fleet must be >= 1, got {fleet}")
    plan_name = config.plan or "decode"
    if plan_name != "decode":
        raise ValueError(
            f"serve_fleet() serves token decoding; plan {plan_name!r} needs "
            "a CompiledNetwork front door"
        )
    router_config = config.router
    if router_config is None:
        router_config = RouterConfig()
    if router_config.trace is None and config.trace is not None:
        # The fleet shares ONE tracer, owned by the Router: promote the
        # service-level trace config so engine/plan spans correlate with
        # the router's sched-wait spans under one trace_id space.
        router_config = dataclasses.replace(router_config, trace=config.trace)
    if config.max_queue is None:
        engine_config = dataclasses.replace(
            config, max_queue=config.max_batch, router=None
        )
    else:
        engine_config = dataclasses.replace(config, router=None)

    def factory(cfg, metrics):
        # Closes over (model, params) only — called again on hot restart,
        # and the rebuilt plan shares the same params (no re-upload).
        return DecodePlan(model, params, cfg, metrics=metrics)

    router = Router(router_config)
    for i in range(fleet):
        router.add_engine(f"decode{i}", factory, engine_config)
    router.start()
    return router


__all__ = [
    "POLICIES",
    "Request",
    "Completion",
    "pad_cache_like",
    "ServiceConfig",
    "ServePlan",
    "BatchedPlan",
    "DecodeSession",
    "DecodePlan",
    "StreamingPlan",
    "SERVE_PLANS",
    "InferenceService",
    "serve_model",
    "serve_fleet",
]
