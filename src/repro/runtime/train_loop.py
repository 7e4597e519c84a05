"""Fault-tolerant training loop.

The 1000+-node posture, expressed at the loop level:

* **checkpoint/restart**: async checkpoints every `ckpt_every` steps; on ANY
  step failure the loop restores the latest checkpoint and replays.  A
  restart may land on a different device count — restore is mesh-agnostic
  (see repro.checkpoint), so elastic shrink/grow is the same code path.
* **bounded retries**: `max_retries` failures within one step window abort
  (a hard fault, not a transient), surfacing the original exception.
* **straggler mitigation**: per-step wall times feed an EMA; steps slower
  than `straggler_factor x EMA` increment a counter and invoke an optional
  callback (on real pods this is where you'd report the slow host for
  replacement / trigger rebalancing — on a single host we record and expose
  the telemetry so the policy is testable).
* **data replay determinism**: the batch iterator is (re)constructed from
  (seed, step), so a restored run consumes exactly the batches it would
  have — no double-consumption after restart.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax

from repro.checkpoint import (
    AsyncCheckpointer,
    latest_checkpoint,
    restore_checkpoint,
)


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    ckpt_retain: int = 3
    max_retries: int = 3
    straggler_factor: float = 3.0
    ema_alpha: float = 0.1
    log_every: int = 10


@dataclasses.dataclass
class TrainLoopResult:
    steps_done: int
    restarts: int
    straggler_events: int
    metrics: List[Dict[str, float]]
    mean_step_s: float


def train_loop(
    step_fn: Callable,  # (params, opt_state, batch) -> (params, opt_state, metrics)
    params: Any,
    opt_state: Any,
    batch_fn: Callable[[int], Any],  # step -> batch (deterministic replay)
    cfg: TrainLoopConfig,
    on_straggler: Optional[Callable[[int, float, float], None]] = None,
    fail_injector: Optional[Callable[[int], None]] = None,
) -> TrainLoopResult:
    """Run `total_steps` with checkpoint/restart + straggler telemetry.

    fail_injector(step) may raise to simulate node failures (tests).
    """
    ckpt = AsyncCheckpointer(cfg.ckpt_dir, cfg.ckpt_retain) if cfg.ckpt_dir else None
    start_step = 0

    # Resume from the latest checkpoint if one exists.
    if cfg.ckpt_dir:
        latest = latest_checkpoint(cfg.ckpt_dir)
        if latest is not None:
            start_step, path = latest
            state = restore_checkpoint(path, {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]

    # Snapshot the true step-`start_step` state: a failure before the first
    # checkpoint lands must replay from HERE, not from the already-mutated
    # live params (which would double-apply the replayed batches).  Copies
    # guard against step_fn donating/aliasing the live buffers.
    def _copy_tree(tree):
        return jax.tree_util.tree_map(
            lambda a: a.copy() if hasattr(a, "copy") else a, tree
        )

    initial_snapshot = _copy_tree({"params": params, "opt": opt_state})

    metrics_hist: List[Dict[str, float]] = []
    restarts = 0
    straggler_events = 0
    ema: Optional[float] = None
    # Per-step failure budget: a step that keeps failing after max_retries
    # restore+replay attempts is a hard fault, not a transient (prevents the
    # restore-to-checkpoint / fail-again livelock).
    fail_counts: Dict[int, int] = {}
    step = start_step
    t_total0 = time.perf_counter()
    steps_timed = 0

    while step < cfg.total_steps:
        batch = batch_fn(step)
        t0 = time.perf_counter()
        try:
            if fail_injector is not None:
                fail_injector(step)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            metrics = jax.tree_util.tree_map(float, jax.device_get(metrics))
        except Exception:
            restarts += 1
            fail_counts[step] = fail_counts.get(step, 0) + 1
            if ckpt:
                # A write still in flight may be replacing the very
                # checkpoint the restore below would read (a replayed step
                # saves its step again).
                ckpt.wait()
            if fail_counts[step] > cfg.max_retries or not cfg.ckpt_dir:
                raise
            latest = latest_checkpoint(cfg.ckpt_dir)
            if latest is not None:
                ckpt_step, path = latest
                state = restore_checkpoint(path, {"params": params, "opt": opt_state})
                params, opt_state = state["params"], state["opt"]
                step = ckpt_step
            else:
                # No checkpoint on disk yet: rewind to the pristine initial
                # state, not to step 0 with the current (mutated) params.
                state = _copy_tree(initial_snapshot)
                params, opt_state = state["params"], state["opt"]
                step = start_step
            # Drop metrics from the rolled-back steps so the history stays
            # monotonic in `step` (the replay re-records them).
            metrics_hist = [m for m in metrics_hist if m["step"] < step]
            continue

        dt = time.perf_counter() - t0
        steps_timed += 1
        if ema is not None and dt > cfg.straggler_factor * ema:
            straggler_events += 1
            if on_straggler is not None:
                on_straggler(step, dt, ema)
        ema = dt if ema is None else (1 - cfg.ema_alpha) * ema + cfg.ema_alpha * dt

        metrics = dict(metrics)
        metrics["step"] = step
        metrics["step_time_s"] = dt
        metrics_hist.append(metrics)
        step += 1

        if ckpt and (step % cfg.ckpt_every == 0 or step == cfg.total_steps):
            ckpt.save(step, {"params": params, "opt": opt_state})

    if ckpt:
        ckpt.wait()
    wall = time.perf_counter() - t_total0
    return TrainLoopResult(
        steps_done=step - start_step,
        restarts=restarts,
        straggler_events=straggler_events,
        metrics=metrics_hist,
        mean_step_s=wall / max(steps_timed, 1),
    )
