"""Closed-loop training: each step of the window is one ``CompiledNetwork.fit``.

The traffic mix gives ``epochs_hidden``, ``epochs_readout`` and ``shuffle``;
the configuration gives the network, the data and, for several chips, the
``data x model`` mesh of a ``DataParallelTrainer``.  The system is reached
only through its public API.

Set-up builds one compiled network and drives it through its first epoch
with the window's own call (``fit`` on the same host array, one epoch); that
epoch's hidden state is what the check compares with the reference, and the
same object then trains in the window.
"""
from __future__ import annotations

import gc
import sys
import time

import jax
import numpy as np

from bench import check, data, reference, work


def build(cfg: dict, seed: int, devices):
    """The network of ``cfg`` from ``seed``, compiled for ``devices``."""
    from repro.core import (DenseLayer, ExecutionConfig, Network,
                            StructuralPlasticityLayer, UnitLayout,
                            onehot_layout)

    hidden = UnitLayout(cfg["n_hcu"], cfg["n_mcu"])
    net = Network(seed=seed)
    net.add(StructuralPlasticityLayer(
        UnitLayout(cfg["n_features"], cfg["n_mcu_in"]), hidden,
        fan_in=cfg["fan_in"], lam=cfg["lam"], init_jitter=cfg["init_jitter"],
        gain=cfg["gain"],
    ))
    net.add(DenseLayer(hidden, onehot_layout(cfg["n_classes"]), lam=cfg["lam"]))
    mesh = cfg.get("mesh")
    if mesh is None:
        return net.compile(ExecutionConfig())
    from jax.sharding import Mesh

    from repro.core.distributed import DataParallelTrainer

    grid = np.asarray(devices[: int(np.prod(mesh["shape"]))]).reshape(
        mesh["shape"])
    trainer = DataParallelTrainer(Mesh(grid, tuple(mesh["axes"])),
                                  mode=mesh["mode"])
    return net.compile(ExecutionConfig(trainer=trainer))


def hidden_state(net) -> dict:
    """Host copy of the hidden layer's learned state, by leaf name."""
    st = net.state.layers[0]
    leaves = dict(ci=st.marginals.ci, cj=st.marginals.cj, cij=st.marginals.cij,
                  w=st.w, b=st.b, mask=st.plast.hcu_mask)
    return {k: np.asarray(v) for k, v in jax.device_get(leaves).items()}


class Runner:
    def __init__(self, cell, seed: int, devices):
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.devices = devices
        self.batch = self.cfg["batch"]
        self.net = None
        self.prog = None  # host copy of the state after the checked epoch
        self.x = self.y = None
        self._ref = None  # the reference's (initial, after) states

    @property
    def batches_per_epoch(self) -> int:
        return self.cfg["n_train"] // self.batch

    def batches_per_step(self) -> int:
        return self.traffic["epochs_hidden"] * self.batches_per_epoch

    def work(self) -> work.Work:
        return work.hidden_batch(self.cfg)

    def setup(self) -> float:
        """Builds and warms up; returns the seconds spent on the check
        alone (the host copy of the checked state), not set-up."""
        t = [time.perf_counter()]
        self.x, self.y = data.make_inputs(self.cfg, self.seed)
        t.append(time.perf_counter())
        self.net = build(self.cfg, self.seed, self.devices)
        jax.block_until_ready(self.net.state)
        t.append(time.perf_counter())
        self.net.fit((self.x, self.y), epochs_hidden=1, epochs_readout=0,
                     batch_size=self.batch, shuffle=self.traffic["shuffle"])
        t.append(time.perf_counter())
        self.prog = hidden_state(self.net)
        t.append(time.perf_counter())
        print("bench: set-up steps: inputs {:.3f} s, network {:.3f} s, "
              "first epoch {:.3f} s; check copy {:.3f} s".format(
                  *(b - a for a, b in zip(t, t[1:]))), file=sys.stderr)
        return t[-1] - t[-2]

    def call(self) -> dict:
        """One step of the closed loop."""
        t = self.traffic
        res = self.net.fit((self.x, self.y), epochs_hidden=t["epochs_hidden"],
                           epochs_readout=t["epochs_readout"],
                           batch_size=self.batch, shuffle=t["shuffle"])
        return dict(samples=self.batches_per_step() * self.batch,
                    host_s=sum(h["host_s"] for h in res.history))

    def end_to_end(self, samples: int, seconds: float) -> dict:
        return {"train_samples_per_s": samples / seconds}

    def finite(self) -> bool:
        return all(np.isfinite(np.asarray(jax.device_get(jax.numpy.sum(a))))
                   for a in jax.tree_util.tree_leaves(self.net.state.layers[0]))

    def release(self) -> None:
        """Frees the program's device state before the reference runs."""
        self.net = None
        gc.collect()

    def n_shards(self) -> int:
        mesh = self.cfg.get("mesh")
        return 1 if mesh is None else int(mesh["shape"][0])

    def reading(self, prog=None, dtype=None, fault=None) -> dict:
        """The compared numbers for the program's checked epoch, or for a
        stand-in put in its place: ``prog="init"`` (a state left unchanged),
        or the reference run at ``dtype`` or with ``fault``."""
        if self._ref is None:
            self._ref = reference.run(self.cfg, self.seed, self.x, self.batch,
                                      n_shards=self.n_shards())
        init, ref = self._ref
        if dtype is not None or fault is not None:
            prog = reference.run(self.cfg, self.seed, self.x, self.batch,
                                 dtype=dtype or jax.numpy.float32, fault=fault,
                                 n_shards=self.n_shards())[1]
        elif prog == "init":
            prog = init
        else:
            prog = self.prog
        self.last = (init, ref, prog)
        return check.numbers(init, ref, prog, self.cfg["n_hcu"])
