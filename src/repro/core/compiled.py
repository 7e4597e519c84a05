"""The compile step: bind a declarative Network to one ExecutionPlan.

Keras' real power is ``compile()`` — one place where execution strategy
(backend, precision, distribution) binds to a declarative model.  Here:

::

    model = Network(seed=0)
    model.add(StructuralPlasticityLayer(...))
    model.add(DenseLayer(...))
    compiled = model.compile(ExecutionConfig(
        engine="scan",                       # or "batch" (reference loop)
        trainer=DataParallelTrainer(mesh),   # the paper's MPI backend
        precision=PrecisionPolicy.named("bf20"),  # FPGA datapath emulation
    ))
    compiled.fit((x, y), epochs_hidden=5, epochs_readout=5)
    compiled.fit((x, y), epochs_hidden=[20, 10, 5])  # per-layer schedule
    compiled.evaluate((x_test, y_test))
    compiled.save("ckpts")                   # whole-network checkpoint
    sess = compiled.streaming()              # online updates, same jit cells
    svc = compiled.serve(ServiceConfig(...)) # serving front door (ServePlan)

Everything execution-strategic lives in :class:`ExecutionConfig`; the
``Network`` holds only the model description.  :class:`CompiledNetwork` owns
a pure-functional :class:`NetworkState` pytree plus cached jitted callables
for fit / partial_fit / predict / evaluate — nothing re-traces across calls
unless the input schema changes (jit's own cache handles shape/structure
variation within one cached callable).

Training executes as a *phase program* (:mod:`repro.runtime.program`):
fit/partial_fit arguments compile into an ordered list of hidden/readout
phases, and at each phase boundary the dataset is projected ONCE through
the newly-frozen prefix and cached (:mod:`repro.runtime.activations`) so
epochs never recompute the frozen stack — the paper's staged greedy
training made explicit.

The legacy ``Network.fit(engine=..., trainer=...)`` signature survives as a
deprecated shim that compiles on the fly and copies learned state back;
parity is asserted in tests/test_compile_api.py.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from copy import copy as _shallow_copy
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.layers import DenseLayer, LayerState, StructuralPlasticityLayer
from repro.runtime.plans import PLANS, ExecutionPlan, make_plan
from repro.runtime.trace import span

READOUTS = ("bcpnn", "sgd")


def build_head(layers) -> Callable:
    """The readout head ``(states, readout_params, hb) -> scores`` over
    level-H hidden codes.  ONE definition of the head branch logic — the
    optional SGD head is an *argument* (jit's trace cache handles the
    bcpnn<->sgd switch), and it was trained on the output of the FULL
    hidden stack, so only a trailing DenseLayer is skipped when it is
    active — shared by :func:`build_forward` (full-stack predict) and
    ``CompiledNetwork._head_fn`` (predict on projected codes) so the two
    surfaces cannot diverge.
    """
    n_hidden = len(layers) - 1 if isinstance(layers[-1], DenseLayer) else len(layers)

    def head(states, readout_params, hb):
        if readout_params is not None:
            return hb @ readout_params["w"] + readout_params["b"]
        if n_hidden < len(layers):
            return layers[-1].forward(states[-1], hb)
        return hb

    return head


def build_forward(layers) -> Callable:
    """One jitted full-network forward ``(states, readout_params, xb)``.

    Shared by CompiledNetwork's predict on a network with no hidden layer,
    the legacy Network.predict shim, and the serving BatchedPlan — a single
    definition keeps the surfaces bit-identical.
    """
    n_hidden = len(layers) - 1 if isinstance(layers[-1], DenseLayer) else len(layers)
    head = build_head(layers)

    def fwd(states, readout_params, xb):
        h = xb
        for layer, state in zip(layers[:n_hidden], states[:n_hidden]):
            h = layer.forward(state, h)
        return head(states, readout_params, h)

    return jax.jit(fwd)


class NetworkState(NamedTuple):
    """The whole network's learnable state — one pytree.

    ``layers``: per-layer :class:`LayerState`; ``readout``: the hybrid SGD
    readout params (``{"w", "b"}``) or None when the BCPNN DenseLayer readout
    is in use.  Host-side RNG state rides along in checkpoints (manifest
    metadata), not in the pytree.
    """

    layers: Tuple[LayerState, ...]
    readout: Optional[dict]


@dataclasses.dataclass(frozen=True)
class ExecutionConfig:
    """Everything about *how* a network executes, none of *what* it is.

    engine:      "scan" (device-resident epoch scans, default) or "batch"
                 (per-batch reference loop).
    trainer:     optional repro.core.distributed.DataParallelTrainer — the
                 paper's MPI backend as a plan decorator.
    precision:   optional PrecisionPolicy (or format name str, e.g. "bf20")
                 bound to EVERY layer's datapath at compile time — the
                 paper's deployment-time FPGA precision choice.
    use_kernels: optional bool overriding every layer's Pallas-kernel flag
                 (None leaves the declared per-layer setting).
    fused_phase: one-dispatch training — every hidden layer's per-batch
                 Alg.1 cycle (forward + HCU softmax + EWMA + weights) runs
                 as a single fused Pallas mega-kernel
                 (repro.kernels.bcpnn_phase) instead of the three-kernel
                 composition; bit-exact with the unfused kernel path in
                 interpret mode.  Implies use_kernels=True (auto-enabled
                 when left None; an explicit False raises).  Composes with
                 the quantized state tier (state_format=) but not with a
                 reduced-precision *datapath* policy.
    donate:      donate scan carries/epoch buffers on accelerators.
    activation_budget_mb: device-memory budget for the level-k activations
                 the frozen prefix is projected to once a phase
                 (repro.runtime.activations); levels beyond it are spilled
                 to host memory (epoch gathers fall back to the host path
                 transparently).
    strict:      runtime hot-path verification (repro.analysis.strict):
                 epoch dispatches run under jax.transfer_guard("disallow"),
                 a recompile sentinel asserts every jitted callable compiles
                 exactly once across repeated fit/partial_fit/predict calls,
                 and checkify finite-value guards run on the BCPNN state
                 after every epoch.  Guards sit at phase entry/exit only, so
                 steady-state throughput is unchanged.
    trace:       optional repro.runtime.trace.TraceConfig — the compiled
                 network owns a Tracer and training records its spans
                 there on the shared training trace id: ``train.fit``,
                 ``train.<phase>`` per epoch (host vs device-wait
                 attribution), and inside each epoch ``train.gather``,
                 ``train.upload``, ``train.dispatch`` and ``train.fence``.
                 The same spans are always profiler annotations; None
                 (default) records nothing else.
    profile_dir: when set, ``fit()`` runs its whole phase program under
                 ``jax.profiler.trace(profile_dir)`` — a device-level
                 profile (TensorBoard/Perfetto) in which the ``train.*``
                 spans appear beside the device's ops.
    """

    engine: str = "scan"
    trainer: Any = None
    precision: Any = None
    use_kernels: Optional[bool] = None
    fused_phase: bool = False
    donate: bool = True
    activation_budget_mb: float = 512.0
    strict: bool = False
    trace: Any = None
    profile_dir: Optional[str] = None

    def __post_init__(self):
        if self.trace is not None:
            from repro.runtime.trace import TraceConfig

            if not isinstance(self.trace, TraceConfig):
                raise TypeError(
                    f"trace must be a TraceConfig, got {type(self.trace).__name__}"
                )
        # Validate against the plan registry — the single source of truth —
        # so registering a new ExecutionPlan automatically extends configs.
        if self.engine not in PLANS:
            raise ValueError(
                f"Unknown engine {self.engine!r} (want one of {sorted(PLANS)})"
            )
        if self.activation_budget_mb <= 0:
            raise ValueError("activation_budget_mb must be positive")
        if isinstance(self.precision, str):
            from repro.precision.policy import PrecisionPolicy

            object.__setattr__(
                self, "precision", PrecisionPolicy.named(self.precision)
            )
        if self.fused_phase:
            if self.use_kernels is False:
                raise ValueError(
                    "fused_phase=True requires the Pallas kernels; drop "
                    "use_kernels=False (or leave it None — fused_phase "
                    "auto-enables it)"
                )
            if self.use_kernels is None:
                object.__setattr__(self, "use_kernels", True)
            if self.precision is not None and not self.precision.fmt.is_identity:
                raise ValueError(
                    "fused_phase is incompatible with a reduced-precision "
                    f"datapath (precision fmt {self.precision.fmt.name!r}); "
                    "use PrecisionPolicy.named('fp32', state_format=...) for "
                    "the quantized state tier, which does compose"
                )

    def bind_layer(self, layer):
        """A copy of ``layer`` with this config's precision/kernel choices
        bound into its spec (the declarative layer is never mutated)."""
        overrides = {}
        if self.precision is not None:
            overrides["precision"] = self.precision
        if self.use_kernels is not None:
            overrides["use_kernels"] = self.use_kernels
        # Only hidden layers get the fused phase: the supervised readout's
        # post-activations are clamped to labels, so there is no forward +
        # softmax to fuse into its update.
        if self.fused_phase and isinstance(layer, StructuralPlasticityLayer):
            overrides["fused_phase"] = True
        if not overrides:
            return layer
        bound = _shallow_copy(layer)
        bound.spec = dataclasses.replace(layer.spec, **overrides)
        return bound


class CompiledNetwork:
    """A Network bound to one ExecutionPlan, owning state + jitted callables."""

    def __init__(self, network, config: Optional[ExecutionConfig] = None,
                 rng: Optional[np.random.Generator] = None):
        self.network = network
        self.config = config if config is not None else ExecutionConfig()
        network.build()
        self.layers = [self.config.bind_layer(layer) for layer in network.layers]
        # Copy the initial states: the scan plan donates its state carry on
        # accelerators, so aliasing network.states here would invalidate the
        # declarative Network's buffers on the first fit (breaking repeated
        # compiles of one Network, e.g. the precision-sweep pattern).
        self.state = NetworkState(
            layers=tuple(
                jax.tree_util.tree_map(jnp.array, s) for s in network.states
            ),
            readout=None,
        )
        # Quantized state tier: cast the initial marginals into the storage
        # dtype at compile time, so jitted epoch scans carry a type-stable
        # state from the very first batch (bf16-in -> bf16-out).
        if any(
            getattr(b.spec.precision, "has_state_tier", False)
            for b in self.layers
        ):
            from repro.precision.policy import quantize_marginals

            self.state = NetworkState(
                layers=tuple(
                    s._replace(
                        marginals=quantize_marginals(s.marginals, b.spec.precision)
                    )
                    for b, s in zip(self.layers, self.state.layers)
                ),
                readout=self.state.readout,
            )
        self.plan: ExecutionPlan = make_plan(
            self.config.engine, self.layers, donate=self.config.donate,
            strict=self.config.strict,
        )
        if self.config.trainer is not None:
            self.plan = self.config.trainer.decorate(self.plan)
        # Project-once activation store: each frozen level's projection.
        from repro.runtime.activations import store_for

        self.activations = store_for(
            self.layers, self.config, trainer=self.config.trainer
        )
        self._rng = rng if rng is not None else np.random.default_rng(network.seed)
        # Cached jitted callables (satellite: predict used to re-jit per call).
        self._fwd: Optional[Callable] = None
        self._head: Optional[Callable] = None
        # Hybrid-readout machinery cached across fit/partial_fit calls.
        self._sgd_cache: dict = {}
        self._sgd_opt_state = None
        # Per-layer LRU of per-shape streaming cells, shared by every session
        # this compiled network opens (see streaming()).
        self._stream_train_cells: dict = {}
        self._stream_infer_cells: dict = {}
        # Strict-mode verification (repro.analysis.strict): a recompile
        # sentinel over every jitted callable and a checkify finite guard
        # the program runners call after each epoch.
        self._sentinel = None
        self._finite_check = None
        if self.config.strict:
            from repro.analysis.strict import RecompileSentinel, finite_checker

            self._sentinel = RecompileSentinel()
            self._finite_check = finite_checker()
        # Training-side tracing (repro.runtime.trace): the phase programs
        # read this and record train.* spans; None keeps them zero-cost.
        from repro.runtime.trace import build_tracer

        self.tracer = build_tracer(self.config.trace)
        self.plan.tracer = self.tracer

    # ------------------------------------------------------------ structure
    @property
    def hidden_layers(self) -> List[StructuralPlasticityLayer]:
        return self.plan.hidden_layers

    @property
    def readout_layer(self) -> Optional[DenseLayer]:
        return self.plan.readout_layer

    # -------------------------------------------------------------- forward
    def _strict_check(self, where: str) -> None:
        """Strict-mode recompile audit: (re)watch every jitted callable this
        network owns — the plan's registry grows as phases compile — then
        assert none re-traced.  No-op unless ``config.strict``."""
        if self._sentinel is None:
            return
        self._sentinel.watch_all(self.plan.jitted, prefix="plan.")
        self._sentinel.watch("forward", self._fwd)
        self._sentinel.watch("head", self._head)
        for (j, k), fn in self.activations._proj_scan.items():
            self._sentinel.watch(f"proj_scan[{j}->{k}]", fn)
        for (j, k), fn in self.activations._proj_chunk.items():
            self._sentinel.watch(f"proj_chunk[{j}->{k}]", fn)
        self._sentinel.check(where)

    def _forward_fn(self) -> Callable:
        """The jitted full-network forward, built exactly once per compile
        (see :func:`build_forward`)."""
        if self._fwd is None:
            self._fwd = build_forward(self.layers)
        return self._fwd

    def _head_fn(self) -> Callable:
        """Jitted readout head over pre-projected level-H hidden codes —
        the project-once mirror of :func:`build_forward`, sharing the ONE
        :func:`build_head` definition (the hidden stack is replaced by the
        ActivationStore projection)."""
        if self._head is None:
            self._head = jax.jit(build_head(self.layers))
        return self._head

    def predict(self, x, batch_size: int = 1024) -> jnp.ndarray:
        """Class scores for a batch of inputs (cached jit).

        The hidden stack runs through the SAME level-H projection training
        used — so repeated predict/evaluate on one dataset (and predict
        right after fit on the train set) skip the frozen stack entirely;
        only the readout head runs per call."""
        from repro.analysis.strict import dispatch_guard

        outs = []
        if self.hidden_layers:
            n_hidden = len(self.hidden_layers)
            h = self.activations.level(
                n_hidden, list(self.state.layers), x, chunk=batch_size
            )
            head = self._head_fn()
            for i in range(0, h.shape[0], batch_size):
                hb = jnp.asarray(h[i : i + batch_size])
                with dispatch_guard(self.config.strict):
                    outs.append(
                        head(self.state.layers, self.state.readout, hb)
                    )
            self._strict_check("predict")
            return jnp.concatenate(outs, axis=0)
        fwd = self._forward_fn()
        for i in range(0, x.shape[0], batch_size):
            xb = jnp.asarray(x[i : i + batch_size])
            with dispatch_guard(self.config.strict):
                outs.append(fwd(self.state.layers, self.state.readout, xb))
        self._strict_check("predict")
        return jnp.concatenate(outs, axis=0)

    def evaluate(self, dataset, batch_size: int = 1024) -> float:
        """Classification accuracy (argmax over output units)."""
        x, y = dataset
        scores = self.predict(x, batch_size=batch_size)
        # jaxlint: allow[JL001] reason=accuracy is a host-side API result; one readback per evaluate
        pred = np.asarray(jnp.argmax(scores, axis=-1))
        return float(np.mean(pred == np.asarray(y)))  # jaxlint: allow[JL001] reason=labels are compared host-side once per evaluate

    # ------------------------------------------------------------- training
    def fit(
        self,
        dataset,
        epochs_hidden=10,
        epochs_readout: int = 10,
        batch_size: int = 128,
        readout: str = "bcpnn",
        readout_lr: float = 1e-3,
        shuffle: bool = True,
        verbose: bool = False,
    ):
        """Phase-program BCPNN training (Alg. 1 + supervised readout)
        through the compiled plan.  Engine, trainer, precision, and the
        project-once activation cache were fixed at compile time; only
        training-objective knobs remain here.

        ``epochs_hidden`` is either one epoch count for every hidden layer
        or a per-layer schedule (``epochs_hidden=[20, 10, 5]`` for a
        three-layer greedy stack); the arguments compile into a
        :class:`repro.runtime.program.TrainProgram` executed phase by
        phase, with per-epoch wall-time recorded in the result's
        ``history`` (``seconds`` field)."""
        from repro.core.network import FitResult

        t0 = time.perf_counter()
        history: List[dict] = []
        profile = (
            jax.profiler.trace(self.config.profile_dir)
            if self.config.profile_dir is not None
            else contextlib.nullcontext()
        )
        with profile, span(self.tracer, "train.fit"):
            self._run(
                dataset, epochs_hidden, epochs_readout, batch_size, readout,
                readout_lr, shuffle, verbose, history, reset_readout=True,
            )
            self._strict_check("fit")
        return FitResult(
            epochs_hidden=epochs_hidden,
            epochs_readout=epochs_readout,
            batch_size=min(batch_size, dataset[0].shape[0]),
            wall_time_s=time.perf_counter() - t0,
            history=history,
        )

    def partial_fit(
        self,
        dataset,
        batch_size: int = 128,
        readout: Optional[str] = None,
        readout_lr: float = 1e-3,
        shuffle: bool = False,
        verbose: bool = False,
    ):
        """One incremental pass over a data chunk: each hidden layer gets one
        Hebbian epoch on the chunk, plus one readout epoch when ``readout``
        is given.  SGD-readout params and optimizer state persist across
        calls, so repeated partial_fit converges like a streamed fit; all
        jitted epoch callables are shared with fit().

        Shape-stable execution trains ``(len(chunk) // batch_size) *
        batch_size`` samples per call: a ragged tail is dropped (reported as
        a ``ragged_tail_dropped`` history entry) — size chunks as multiples
        of ``batch_size`` to train on everything."""
        from repro.core.network import FitResult

        t0 = time.perf_counter()
        history: List[dict] = []
        with span(self.tracer, "train.fit"):
            self._run(
                dataset, 1, 1 if readout is not None else 0, batch_size,
                readout or "bcpnn", readout_lr, shuffle, verbose, history,
                reset_readout=False,
            )
            self._strict_check("partial_fit")
        return FitResult(
            epochs_hidden=1,
            epochs_readout=1 if readout is not None else 0,
            batch_size=min(batch_size, dataset[0].shape[0]),
            wall_time_s=time.perf_counter() - t0,
            history=history,
        )

    # The one training driver: fit and partial_fit both compile their
    # arguments into a TrainProgram (repro.runtime.program) and hand it to
    # the phase-program executor, which routes each phase through the bound
    # plan's epoch runners.
    def _run(
        self, dataset, epochs_hidden, epochs_readout, batch_size, readout,
        readout_lr, shuffle, verbose, history, reset_readout,
    ) -> None:
        from repro.runtime.program import (
            HiddenPhase,
            compile_program,
            run_program,
        )

        x, y = dataset
        n_total = x.shape[0]
        if n_total == 0:
            raise ValueError("fit() called with an empty dataset")
        if readout not in READOUTS:
            raise ValueError(
                f"Unknown readout {readout!r} (want one of {READOUTS})"
            )
        # A batch size larger than the dataset would round n down to zero and
        # silently train on nothing — clamp to the dataset size instead.
        batch_size = min(batch_size, n_total)
        # Keep step functions shape-stable under jit: each epoch uses n
        # samples (a multiple of B).  _epoch_indices permutes the FULL
        # dataset before truncating, so a different ragged tail is left out
        # each epoch and no sample is permanently excluded.  partial_fit
        # makes exactly one pass, so its dropped tail is deterministic —
        # surface it rather than lose data silently.
        n = (n_total // batch_size) * batch_size
        if not reset_readout and n < n_total:
            history.append(
                {"phase": "ragged_tail_dropped", "samples": n_total - n}
            )

        program = compile_program(
            len(self.hidden_layers), epochs_hidden, epochs_readout, readout,
            readout_lr=readout_lr, reset_readout=reset_readout,
        )
        if y is None and any(
            not isinstance(p, HiddenPhase) for p in program.phases
        ):
            raise ValueError(
                "readout training requires labels: pass (x, y), or run "
                "hidden-only with epochs_readout=0 (fit) / readout=None "
                "(partial_fit)"
            )
        if verbose:
            print(f"[fit/{self.plan.name}] program: {program.describe()}")

        result = run_program(
            self, program, x, y, n, n_total, batch_size, shuffle, verbose,
            history,
        )

        # Readout-head bookkeeping.  A stale SGD head is only dropped AFTER
        # a BCPNN readout actually trains a replacement — never
        # unconditionally, which would leave headless networks (or
        # epochs_readout=0 fits) with no classifier at all.
        readout_params = self.state.readout
        if result.bcpnn_trained and self.readout_layer is not None:
            # Training the BCPNN readout makes the DenseLayer authoritative
            # — drop any SGD head so predict() sees the work just done.
            readout_params = None
        if result.sgd_ran:
            readout_params = result.sgd_params
        self.state = NetworkState(
            layers=self.state.layers, readout=readout_params
        )

    def _sgd_setup(self, y, lr: float, reset: bool):
        """Hybrid-readout machinery for one SgdReadoutPhase: (params,
        opt_state, epoch runner) — AdamW + cross-entropy on frozen hidden
        reps, the paper's 97.5%+ MNIST configuration.  The runner takes the
        level-H projection as its input and is cached across
        fit/partial_fit calls."""
        from repro.core.network import sgd_readout_setup

        n_hidden = self.hidden_layers[-1].spec.n_post
        # Size the head from the declared output layout, not this batch's
        # labels: a partial_fit chunk missing the high classes must not lock
        # the head too narrow (later labels would silently clamp under jit).
        if self.readout_layer is not None:
            n_classes = self.readout_layer.spec.n_post
        elif not reset and self.state.readout is not None:
            # Headless network resuming an existing head: the head width is
            # fixed; out-of-range labels must fail loudly, not clamp.
            n_classes = int(self.state.readout["w"].shape[1])
            y_max = int(np.max(y))
            if y_max >= n_classes:
                raise ValueError(
                    f"label {y_max} exceeds the SGD head's {n_classes} "
                    "classes (a headless network's head is sized by its "
                    "first fit); declare a DenseLayer readout or run a full "
                    "fit() covering the label range"
                )
        else:
            n_classes = int(np.max(y)) + 1
        key = (n_hidden, n_classes, lr)
        resume = not reset and self.state.readout is not None
        cached = self._sgd_cache.get(key)
        if cached is None:
            # Resume paths only need opt/loss_fn — skip the random head init.
            params, opt, opt_state, loss_fn = sgd_readout_setup(
                self.network.seed, n_hidden, y, lr, n_classes=n_classes,
                init_params=not resume,
            )
            run_epoch = self.plan.sgd_epoch(opt, loss_fn)
            self._sgd_cache[key] = (opt, loss_fn, run_epoch)
        else:
            opt, loss_fn, run_epoch = cached
            params = opt_state = None
        if resume:
            # Resume the stored head (fresh moments if none survive, e.g.
            # right after a checkpoint load).  The scan plan donates the
            # params/opt_state carries, so hand it copies, not the stored
            # buffers themselves.
            params = self._donation_safe(self.state.readout)
            opt_state = (
                self._donation_safe(self._sgd_opt_state)
                if self._sgd_opt_state is not None
                else opt.init(params)
            )
        elif params is None:
            # Cached epoch fn but a fresh trajectory: re-init params/moments.
            params, _, opt_state, _ = sgd_readout_setup(
                self.network.seed, n_hidden, y, lr, n_classes=n_classes
            )
        return params, opt_state, run_epoch

    def _donation_safe(self, state):
        """A copy of ``state`` when the plan will donate its carry, so the
        buffers still referenced by ``self.state`` (and by any failed-run
        survivor) are never deleted.  Applies with or without a trainer:
        place_state's device_put is an aliasing no-op once the state already
        carries the target sharding (e.g. on a second fit).  No-op wherever
        donation is inert (CPU, batch plan, donate=False)."""
        if (
            self.plan.name == "scan"
            and self.config.donate
            and jax.default_backend() != "cpu"
        ):
            return jax.tree_util.tree_map(jnp.array, state)
        return state

    def _epoch_indices(self, n: int, n_total: int, shuffle: bool) -> np.ndarray:
        """First `n` indices of a full-dataset permutation (rotates which
        ragged-tail samples sit out each epoch)."""
        if not shuffle:
            return np.arange(n)
        return self._rng.permutation(n_total)[:n]

    # ------------------------------------------------------------ streaming
    def streaming(
        self,
        layer: int = 0,
        max_batch: int = 16,
        max_wait_s: float = 0.0,
        cache_size: int = 8,
    ):
        """A StreamingSession over hidden layer ``layer`` whose per-shape
        jitted cells live in this compiled network's own LRU (so several
        sessions share one bounded trace cache — each distinct micro-batch
        size is a separate jit wrapper, and eviction really frees its traces)
        and whose learned state is written back into ``self.state`` on
        close()."""
        from repro.core.streaming import StreamingSession, _LRUCells

        bound = self.hidden_layers[layer]
        li = self.layers.index(bound)
        # The session gets its own copy of the layer state: a later fit()
        # donates self.state.layers[li] on accelerators, which would delete
        # the buffer out from under a live session if it were shared.
        session_state = jax.tree_util.tree_map(jnp.array, self.state.layers[li])
        train_lru = self._stream_train_cells.setdefault(li, _LRUCells(cache_size))
        infer_lru = self._stream_infer_cells.setdefault(li, _LRUCells(cache_size))
        # The shared LRUs are handed to the session as ITS caches (no
        # session-private copy), so the latest cache_size governs the one
        # real bound and stats/eviction behavior agree across sessions.
        train_lru.set_capacity(cache_size)
        infer_lru.set_capacity(cache_size)

        base_step = int(self.state.layers[li].step)  # for conflict detection

        def adopt(state):
            # Compare step COUNTERS, not object identity: fit republishes
            # value-identical copies of untouched layers (donation safety),
            # which must not read as a conflict.
            if int(self.state.layers[li].step) != base_step:
                import warnings

                warnings.warn(
                    "StreamingSession.close(): this layer trained elsewhere "
                    "(another session or a fit) since the session opened; "
                    "overwriting those updates with this session's result",
                    RuntimeWarning,
                    stacklevel=3,
                )
            layers = list(self.state.layers)
            layers[li] = state
            self.state = NetworkState(tuple(layers), self.state.readout)
            # Identity purging would drop the now-stale cached levels above
            # this layer lazily at the next level() call; invalidate them
            # eagerly so the adoption itself releases their device/host
            # bytes (and a served evaluate() right after close() can never
            # race a stale entry).
            self.activations.invalidate_above(li)

        # The session's default factories already build exactly the cells we
        # want from `bound`; only the shared LRUs and adoption are injected.
        return StreamingSession(
            bound,
            session_state,
            max_batch=max_batch,
            max_wait_s=max_wait_s,
            cache_size=cache_size,
            train_cells=train_lru,
            infer_cells=infer_lru,
            on_close=adopt,
        )

    # -------------------------------------------------------------- serving
    def serve(self, config=None):
        """Bind this compiled network to an :class:`InferenceService` — the
        serving mirror of the compile step.  ``ServiceConfig(plan=...)``
        picks the strategy: "batched" (default — bucket-padded
        classification through the SAME cached jitted forward ``predict``
        uses, so service and library calls share one trace cache) or
        "streaming" (the latency path: wraps :meth:`streaming` with its
        coalescing buffer and state adoption).  Token decoding
        (plan="decode") belongs to the LM zoo — use
        ``repro.runtime.service.serve_model``.

        ``ServiceConfig(async_mode=True)`` starts the dedicated executor
        thread at bind time: ``submit()`` then returns
        ``concurrent.futures.Future``s and batched requests aggregate
        under the ``max_wait_s`` deadline (see
        :mod:`repro.runtime.engine`)."""
        from repro.runtime.service import (
            BatchedPlan,
            InferenceService,
            ServiceConfig,
            StreamingPlan,
        )

        config = config if config is not None else ServiceConfig()
        plan_name = config.plan or (
            "continual" if config.continual is not None else "batched"
        )
        if plan_name == "batched":
            plan = BatchedPlan(self, config)
        elif plan_name == "streaming":
            plan = StreamingPlan(self, config)
        elif plan_name == "continual":
            from repro.runtime.continual import ContinualPlan

            plan = ContinualPlan(self, config)
        else:
            raise ValueError(
                f"CompiledNetwork.serve supports plans 'batched'/'streaming'"
                f"/'continual'; {plan_name!r} serves token decoding (use "
                "serve_model)"
            )
        service = InferenceService(plan, config)
        if config.async_mode:
            service.start()
        return service

    # ----------------------------------------------------------- checkpoint
    def save(self, directory: str, step: int = 0, retain: int = 3) -> str:
        """Whole-network checkpoint: layer states + sgd-readout params + the
        host shuffle RNG, atomically via repro.checkpoint.store."""
        from repro.checkpoint.network import save_network

        return save_network(
            directory, step, self.state, self._rng.bit_generator.state,
            retain=retain,
        )

    def load(self, path: str) -> "CompiledNetwork":
        """Restore a whole-network checkpoint written by :meth:`save` into
        this compiled network (architectures must match)."""
        from repro.checkpoint.network import load_network

        layer_states, readout, rng_state = load_network(
            path, list(self.state.layers),
            readout_in_features=self.hidden_layers[-1].spec.n_post
            if self.hidden_layers else None,
        )
        self.state = NetworkState(layers=tuple(layer_states), readout=readout)
        # Optimizer moments belong to the pre-load trajectory; a resumed
        # SGD-readout fit must re-initialize them.
        self._sgd_opt_state = None
        if rng_state is not None:
            self._rng.bit_generator.state = rng_state
        return self
