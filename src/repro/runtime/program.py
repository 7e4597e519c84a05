"""Phase programs: training as an explicit, inspectable schedule.

The paper's training scheme is staged — greedy layer-by-layer Hebbian
training, then a supervised readout on frozen representations.
``CompiledNetwork.fit``/``partial_fit`` compile their arguments into a
:class:`TrainProgram` — an ordered tuple of :class:`HiddenPhase`,
:class:`BcpnnReadoutPhase`, :class:`SgdReadoutPhase` — and ONE driver
(:func:`run_program`) executes it.  Making the schedule a value rather than
control flow buys three things:

* **per-layer epoch schedules** — ``fit(epochs_hidden=[20, 10, 5])`` gives
  each greedy stage its own budget, which deep stacking wants (lower layers
  need more epochs; upper layers converge on already-clustered codes);
* **project-once execution** — each phase boundary is exactly where a layer
  freezes, so the driver projects the dataset once through the newly-frozen
  prefix (:class:`repro.runtime.activations.ActivationStore`) and every
  epoch of the phase gathers from the cached level-k array instead of
  re-running the frozen stack per batch;
* **stage-once input** — a host array a phase's epochs gather from is
  placed on the device once at the phase's start (the plan's
  :meth:`~repro.runtime.plans.ScanPlan.stage`, where the device has
  room), so each shuffled epoch is gathered there rather than on the host
  and shipped again;
* **observability** — every history entry carries a ``seconds`` field
  (epoch wall-time, blocked on the result) plus explicit ``project`` and
  ``stage`` entries, and each epoch says where its input was gathered
  (``input``: ``"device"`` or ``"host"``), so the phase-program speedup is
  measurable from the API; each runs under a ``train.<phase>`` span
  (:func:`repro.runtime.trace.span`) that a JAX profile shows beside the
  device's ops.

The driver is engine-agnostic: it calls the bound
:class:`repro.runtime.plans.ExecutionPlan`'s cached epoch runners when the
compiled network owns an ActivationStore (``ExecutionConfig(
cache_activations=True)``, the default) and the fused runners otherwise —
the two paths are bit-exact (``tests/test_deep_networks.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro.runtime.trace import span


# --------------------------------------------------------------------------
# Phases and the program.
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class HiddenPhase:
    """Unsupervised Hebbian epochs for hidden layer ``li`` (greedy stage)."""

    li: int
    epochs: int


@dataclasses.dataclass(frozen=True)
class BcpnnReadoutPhase:
    """Supervised BCPNN DenseLayer readout on frozen hidden codes."""

    epochs: int


@dataclasses.dataclass(frozen=True)
class SgdReadoutPhase:
    """Hybrid AdamW cross-entropy readout on frozen hidden codes.

    ``reset=False`` resumes the stored head/optimizer moments
    (partial_fit's streamed-readout semantics).  ``epochs=0`` still
    initializes the head, matching the legacy fit path.
    """

    epochs: int
    lr: float = 1e-3
    reset: bool = True


Phase = Union[HiddenPhase, BcpnnReadoutPhase, SgdReadoutPhase]


@dataclasses.dataclass(frozen=True)
class TrainProgram:
    """An ordered, immutable training schedule."""

    phases: Tuple[Phase, ...]

    @property
    def total_epochs(self) -> int:
        return sum(p.epochs for p in self.phases)

    def describe(self) -> str:
        """One line per phase, e.g. ``hidden0 x20 -> readout(bcpnn) x10``."""
        parts = []
        for p in self.phases:
            if isinstance(p, HiddenPhase):
                parts.append(f"hidden{p.li} x{p.epochs}")
            elif isinstance(p, BcpnnReadoutPhase):
                parts.append(f"readout(bcpnn) x{p.epochs}")
            else:
                parts.append(f"readout(sgd,lr={p.lr:g}) x{p.epochs}")
        return " -> ".join(parts) if parts else "(empty)"


def compile_program(
    n_hidden: int,
    epochs_hidden: Union[int, Sequence[int]],
    epochs_readout: int,
    readout: str,
    readout_lr: float = 1e-3,
    reset_readout: bool = True,
) -> TrainProgram:
    """Compile fit/partial_fit arguments into a :class:`TrainProgram`.

    ``epochs_hidden`` is either one epoch count for every hidden layer or a
    per-layer schedule (length must equal the hidden-layer count).
    """
    if isinstance(epochs_hidden, (int, np.integer)):
        schedule = [int(epochs_hidden)] * n_hidden
    else:
        schedule = [int(e) for e in epochs_hidden]
        if len(schedule) != n_hidden:
            raise ValueError(
                f"epochs_hidden schedule has {len(schedule)} entries for "
                f"{n_hidden} hidden layers"
            )
    if any(e < 0 for e in schedule) or epochs_readout < 0:
        raise ValueError("epoch counts must be non-negative")

    phases: List[Phase] = [
        HiddenPhase(li, e) for li, e in enumerate(schedule) if e > 0
    ]
    if readout == "bcpnn":
        if epochs_readout > 0:
            phases.append(BcpnnReadoutPhase(epochs_readout))
    elif readout == "sgd":
        # epochs=0 still initializes the head (legacy-fit semantics).
        phases.append(
            SgdReadoutPhase(epochs_readout, lr=readout_lr, reset=reset_readout)
        )
    else:
        raise ValueError(f"Unknown readout {readout!r} (want one of ('bcpnn', 'sgd'))")
    return TrainProgram(tuple(phases))


class ProgramResult(NamedTuple):
    """What the driver learned beyond the layer states it already published."""

    sgd_params: Optional[dict]
    sgd_ran: bool
    bcpnn_trained: bool


# --------------------------------------------------------------------------
# The one driver.
# --------------------------------------------------------------------------
def run_program(
    net,
    program: TrainProgram,
    x,
    y,
    n: int,
    n_total: int,
    batch_size: int,
    shuffle: bool,
    verbose: bool,
    history: List[dict],
) -> ProgramResult:
    """Execute ``program`` against a CompiledNetwork.

    Layer states are published onto ``net.state`` as each phase completes
    (so a failure mid-program leaves only live buffers referenced); the
    readout-head bookkeeping is returned for the caller to finalize.
    """
    sgd_params: Optional[dict] = None
    sgd_ran = False
    bcpnn_trained = False
    for phase in program.phases:
        if isinstance(phase, HiddenPhase):
            _run_hidden_phase(
                net, phase, x, n, n_total, batch_size, shuffle, verbose, history
            )
        elif isinstance(phase, BcpnnReadoutPhase):
            bcpnn_trained |= _run_bcpnn_phase(
                net, phase, x, y, n, n_total, batch_size, shuffle, verbose,
                history,
            )
        else:
            sgd_params = _run_sgd_phase(
                net, phase, x, y, n, n_total, batch_size, shuffle, verbose,
                history,
            )
            sgd_ran = True
    return ProgramResult(sgd_params, sgd_ran, bcpnn_trained)


@contextlib.contextmanager
def _timed(net, history: List[dict], entry: dict):
    """Times one epoch (or projection) as a history entry and as a
    ``train.<phase>`` span (:func:`repro.runtime.trace.span`; recorded in
    the network's tracer when it has one).

    The body does the work and ends with ``fence(result)``, which blocks on
    the result under the ``train.fence`` span.  The entry's wall-time is
    split into the host-side span (``host_s``: body start to the fence) and
    the device wait at the fence (``device_wait_s``); ``seconds`` is the
    total and ``traces`` the number of programs the body traced."""
    tracer = getattr(net, "tracer", None)
    with span(tracer, f"train.{entry['phase']}") as attrs:
        sizes = net.plan.jit_cache_sizes
        traces = sum(sizes().values())
        t0 = time.perf_counter()

        def fence(result) -> None:
            t1 = time.perf_counter()
            with span(tracer, "train.fence"):
                # jaxlint: allow[JL001] reason=phase timing telemetry must block once at the phase boundary
                jax.block_until_ready(result)
            t2 = time.perf_counter()
            entry["host_s"] = t1 - t0
            entry["device_wait_s"] = t2 - t1
            entry["seconds"] = t2 - t0
            entry["traces"] = sum(sizes().values()) - traces

        yield fence
        history.append(entry)
        attrs.update(
            (k, v) for k, v in entry.items() if k not in ("phase", "seconds")
        )


def check_finite(net, tree, where: str) -> None:
    """Strict-mode checkify guard on a freshly-updated state pytree — the
    BCPNN EWMA traces and log-ratio weights are where a runaway learning
    rate or zero marginal first shows up as NaN/Inf.  No-op unless the
    network was compiled with ``ExecutionConfig(strict=True)``.

    Public because every *driver* of partial-fit updates shares it: the
    phase runners below and the continual tier's online micro-batch
    updates (:mod:`repro.runtime.continual`)."""
    if getattr(net, "_finite_check", None) is not None:
        net._finite_check(tree, where=where)


# The phase runners predate the public name.
_check_finite = check_finite


def _phase_input(net, level: int, states, x, n, batch_size, history,
                 stage: bool = True):
    """The array a phase's epochs gather from, and whether it is the cached
    level-k projection (project-once) rather than the raw dataset (level 0,
    and the fused path).  The raw dataset is staged on the device once for
    the phase where the plan stages it (``stage=False`` for a phase that
    runs no epoch); a projection stays where the activation store put it,
    on the device or, past the store's budget, on the host."""
    store = net.activations
    if store is None or level == 0:
        if stage:
            x = _stage(net, x, "x", n, batch_size, history)
        return x, store is not None
    with _timed(net, history, {"phase": "project", "level": level}) as fence:
        # The store keys on the caller's own x, never on a staged copy.
        xk = store.level(level, states, x, chunk=batch_size)
        fence(xk)
    return xk, True


def _stage(net, arr, name: str, n: int, batch_size: int, history):
    """``arr`` placed on the device once for a phase's epochs, timed as a
    ``stage`` history entry; ``arr`` itself where the plan keeps the host
    gather (a device array, BatchPlan, or too little free device memory)."""
    if not net.plan.stages(arr, n, batch_size):
        return arr
    entry = {"phase": "stage", "level": 0, "array": name,
             "bytes": int(arr.nbytes)}
    with _timed(net, history, entry) as fence:
        staged = net.plan.stage(arr)
        fence(staged)
    return staged


def _input(*arrays) -> str:
    """Where an epoch gathers its stack: on the device when every array it
    gathers from is a ``jax.Array``, else on the host."""
    return "device" if all(isinstance(a, jax.Array) for a in arrays) else "host"


def _run_hidden_phase(
    net, phase, x, n, n_total, batch_size, shuffle, verbose, history
) -> None:
    li = phase.li
    layer = net.hidden_layers[li]
    states = list(net.state.layers)
    state = net._donation_safe(net.plan.place_state(layer, states[li]))
    xk, cached = _phase_input(net, li, states, x, n, batch_size, history)
    if cached:
        run_epoch = net.plan.hidden_epoch_cached(li)
        step = lambda st, idx: run_epoch(st, xk, idx, batch_size)  # noqa: E731
    else:
        run_epoch = net.plan.hidden_epoch(li)
        below = states[:li]
        step = lambda st, idx: run_epoch(st, below, xk, idx, batch_size)  # noqa: E731
    where = _input(xk)
    for epoch in range(phase.epochs):
        entry = {"phase": f"hidden{li}", "epoch": epoch, "input": where}
        with _timed(net, history, entry) as fence:
            idx = net._epoch_indices(n, n_total, shuffle)
            state = step(state, idx)
            _check_finite(net, state, f"hidden layer {li}, epoch {epoch}")
            fence(state)
        if verbose:
            print(
                f"[fit/{net.plan.name}] hidden layer {li} epoch "
                f"{epoch + 1}/{phase.epochs}"
            )
    states[li] = state
    # Publish each finished layer immediately so an exception in a later
    # phase leaves net.state referencing only live buffers (the scan plan
    # donates its carries on accelerators).
    net.state = net.state._replace(layers=tuple(states))


def _run_bcpnn_phase(
    net, phase, x, y, n, n_total, batch_size, shuffle, verbose, history
) -> bool:
    layer = net.readout_layer
    if layer is None:
        return False
    li = len(net.layers) - 1
    states = list(net.state.layers)
    state = net._donation_safe(net.plan.place_state(layer, states[li]))
    hk, cached = _phase_input(net, li, states, x, n, batch_size, history)
    ys = _stage(net, y, "y", n, batch_size, history)
    if cached:
        run_epoch = net.plan.readout_epoch_cached()
        step = lambda st, idx: run_epoch(st, hk, ys, idx, batch_size)  # noqa: E731
    else:
        run_epoch = net.plan.readout_epoch()
        hidden_states = states[:li]
        step = lambda st, idx: run_epoch(  # noqa: E731
            st, hidden_states, hk, ys, idx, batch_size
        )
    where = _input(hk, ys)
    for epoch in range(phase.epochs):
        entry = {"phase": "readout", "epoch": epoch, "input": where}
        with _timed(net, history, entry) as fence:
            idx = net._epoch_indices(n, n_total, shuffle)
            state = step(state, idx)
            _check_finite(net, state, f"bcpnn readout epoch {epoch}")
            fence(state)
        if verbose:
            print(
                f"[fit/{net.plan.name}] readout epoch {epoch + 1}/{phase.epochs}"
            )
    states[li] = state
    net.state = net.state._replace(layers=tuple(states))
    return True


def _run_sgd_phase(
    net, phase, x, y, n, n_total, batch_size, shuffle, verbose, history
) -> dict:
    params, opt_state, run_epoch = net._sgd_setup(y, phase.lr, phase.reset)
    states = list(net.state.layers)
    n_hidden = len(net.hidden_layers)
    # epochs=0 only initializes the head: nothing to gather, nothing to stage
    hk, cached = _phase_input(net, n_hidden, states, x, n, batch_size,
                              history, stage=phase.epochs > 0)
    ys = _stage(net, y, "y", n, batch_size, history) if phase.epochs else y
    if cached:
        step = lambda p, s, idx: run_epoch(p, s, hk, ys, idx, batch_size)  # noqa: E731
    else:
        hidden_states = states[:n_hidden]
        step = lambda p, s, idx: run_epoch(  # noqa: E731
            p, s, hidden_states, hk, ys, idx, batch_size
        )
    where = _input(hk, ys)
    for epoch in range(phase.epochs):
        entry = {"phase": "sgd_readout", "epoch": epoch, "input": where}
        with _timed(net, history, entry) as fence:
            idx = net._epoch_indices(n, n_total, shuffle)
            params, opt_state, loss = step(params, opt_state, idx)
            _check_finite(net, params, f"sgd readout epoch {epoch}")
            fence(params)
        if verbose:
            print(
                f"[fit/{net.plan.name}] sgd readout epoch "
                f"{epoch + 1}/{phase.epochs} loss={float(loss):.4f}"
            )
    net._sgd_opt_state = opt_state
    return params


__all__ = [
    "HiddenPhase",
    "BcpnnReadoutPhase",
    "SgdReadoutPhase",
    "TrainProgram",
    "ProgramResult",
    "compile_program",
    "run_program",
    "check_finite",
]
