"""Keras-like DSL for BCPNN networks (the paper's Listing 1).

::

    model = Network()
    model.add(StructuralPlasticityLayer(...))   # input -> hidden, unsupervised
    model.add(DenseLayer(...))                  # hidden -> output, supervised
    compiled = model.compile(ExecutionConfig(engine="scan"))
    compiled.fit(dataset=(x, y), ...)
    compiled.evaluate(dataset=(x_test, y_test))

``Network`` is purely declarative: layers plus a seed.  Everything about
*execution* — scan vs per-batch engine, data/model-parallel trainer,
reduced-precision datapath, Pallas kernels, buffer donation — binds in the
compile step (:mod:`repro.core.compiled`), exactly as the paper treats
backend and precision as a deployment choice rather than a call-site choice.

Training is the paper's two-phase scheme: (1) unsupervised Hebbian epochs on
every hidden (plasticity) layer, in order, each trained on the activations of
the already-frozen stack below it; (2) supervised readout training of the
final DenseLayer on frozen hidden representations.  A *hybrid* readout
(``fit(readout="sgd")``) replaces phase 2 with AdamW cross-entropy training of
a linear softmax readout — the configuration the paper reports at 97.5%+.

The legacy imperative surface (``Network.fit(engine=..., trainer=...)``,
``Network.predict/evaluate``) survives as a deprecated shim that compiles on
the fly and copies learned state back; tests assert it is bit-compatible
with the explicit compile path.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.layers import DenseLayer, LayerState, StructuralPlasticityLayer


@dataclasses.dataclass
class FitResult:
    """Bookkeeping returned by ``fit``/``partial_fit``.

    ``epochs_hidden`` echoes the request: one int for every hidden layer or
    a per-layer schedule list.  ``history`` holds one entry per executed
    epoch (``{"phase", "epoch", "input", "seconds"}``, ``input`` saying
    whether the epoch was gathered on the ``"device"`` or the ``"host"``)
    plus ``project`` entries for each phase-boundary activation projection
    and ``stage`` entries for each host array placed on the device for a
    phase, so per-phase wall-time is observable from the API.
    """

    epochs_hidden: Any
    epochs_readout: int
    batch_size: int
    wall_time_s: float
    history: List[dict]


def sgd_readout_setup(
    seed: int, n_hidden: int, y: np.ndarray, lr: float,
    n_classes: Optional[int] = None,
    init_params: bool = True,
):
    """Hybrid-readout initialization shared by both execution plans.

    Returns (params, opt, opt_state, loss_fn) for the AdamW cross-entropy
    readout.  Single source of truth for the hyperparameters — the per-batch
    loop and the scan engine must stay numerically interchangeable.
    n_classes defaults to the labels' range; pass the declared output width
    when the batch at hand may not contain every class (partial_fit chunks).
    init_params=False skips the random head/moment initialization (params
    and opt_state come back None) for resume paths that only need
    opt/loss_fn.
    """
    from repro.optim import adamw  # local import: optim is a sibling package

    if n_classes is None:
        n_classes = int(np.max(y)) + 1
    opt = adamw.AdamW(learning_rate=lr, weight_decay=1e-4)
    params = None
    if init_params:
        key = jax.random.PRNGKey(seed + 1)
        params = {
            "w": jax.random.normal(key, (n_hidden, n_classes), jnp.float32)
            * (1.0 / np.sqrt(n_hidden)),
            "b": jnp.zeros((n_classes,), jnp.float32),
        }

    def loss_fn(p, hb, yb):
        logits = hb @ p["w"] + p["b"]
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - ll)

    opt_state = opt.init(params) if params is not None else None
    return params, opt, opt_state, loss_fn


class Network:
    """A sequential BCPNN network (hidden plasticity layers + one readout).

    Declarative only: add layers, then :meth:`compile` with an
    :class:`repro.core.compiled.ExecutionConfig` to get a
    :class:`repro.core.compiled.CompiledNetwork` that trains and serves.
    """

    def __init__(self, seed: int = 0, precision=None):
        self.layers: List[Any] = []
        self.states: List[LayerState] = []
        self.seed = seed
        self.precision = precision  # Optional repro.precision.PrecisionPolicy
        self._rng = np.random.default_rng(seed)
        self._built = False
        # Legacy-shim state (populated by the deprecated fit()).
        self._sgd_readout: Optional[dict] = None
        self._fwd_jit: Optional[Callable] = None

    # ------------------------------------------------------------------ DSL
    def add(self, layer) -> "Network":
        if self._built:
            raise RuntimeError("Cannot add layers after the network is built")
        if self.layers and not isinstance(self.layers[-1], StructuralPlasticityLayer):
            raise ValueError(
                "Only the final layer may be a DenseLayer readout; hidden "
                "layers must be StructuralPlasticityLayer"
            )
        self.layers.append(layer)
        return self

    def build(self) -> "Network":
        """Initialize all layer states (idempotent)."""
        if self._built:
            return self
        if not self.layers:
            raise ValueError("Network has no layers")
        key = jax.random.PRNGKey(self.seed)
        keys = jax.random.split(key, len(self.layers))
        self.states = [layer.init(k) for layer, k in zip(self.layers, keys)]
        self._built = True
        return self

    def compile(self, config=None):
        """Bind this model description to an execution strategy.

        config: :class:`repro.core.compiled.ExecutionConfig` (or None for the
        defaults: scan engine, single device, declared per-layer precision).
        Returns a :class:`repro.core.compiled.CompiledNetwork` owning a
        functional NetworkState pytree and cached jitted callables for
        fit / partial_fit / predict / evaluate / save / load / streaming.
        """
        from repro.core.compiled import CompiledNetwork

        return CompiledNetwork(self, config)

    @property
    def hidden_layers(self) -> List[StructuralPlasticityLayer]:
        return [la for la in self.layers if isinstance(la, StructuralPlasticityLayer)]

    @property
    def readout_layer(self) -> Optional[DenseLayer]:
        return self.layers[-1] if isinstance(self.layers[-1], DenseLayer) else None

    # ---------------------------------------------------- legacy (deprecated)
    def predict(self, x: jnp.ndarray, batch_size: int = 1024) -> jnp.ndarray:
        """Class scores for a batch of inputs (runs the whole stack).

        The jitted forward is built once and cached on the instance (it takes
        the states and the optional SGD head as arguments, so state updates
        and the bcpnn<->sgd readout switch reuse the same callable).
        """
        self.build()
        if self._fwd_jit is None:
            from repro.core.compiled import build_forward

            self._fwd_jit = build_forward(self.layers)
        outs = []
        for i in range(0, x.shape[0], batch_size):
            outs.append(
                self._fwd_jit(
                    tuple(self.states), self._sgd_readout,
                    jnp.asarray(x[i : i + batch_size]),
                )
            )
        return jnp.concatenate(outs, axis=0)

    def fit(
        self,
        dataset: Tuple[np.ndarray, np.ndarray],
        epochs_hidden: int = 10,
        epochs_readout: int = 10,
        batch_size: int = 128,
        readout: str = "bcpnn",
        readout_lr: float = 1e-3,
        shuffle: bool = True,
        verbose: bool = False,
        trainer=None,
        engine: str = "scan",
    ) -> FitResult:
        """DEPRECATED shim over the compile step.

        Equivalent to ``self.compile(ExecutionConfig(engine=engine,
        trainer=trainer)).fit(...)``, with the learned state copied back onto
        this Network so the legacy ``states``/``predict``/``evaluate``
        surface keeps working.  Parity with the explicit compile path is
        bit-exact (tests/test_compile_api.py).
        """
        warnings.warn(
            "Network.fit(engine=..., trainer=...) is deprecated; use "
            "network.compile(ExecutionConfig(engine=..., trainer=...)).fit(...)",
            DeprecationWarning,
            stacklevel=2,
        )
        from repro.core.compiled import CompiledNetwork, ExecutionConfig

        config = ExecutionConfig(engine=engine, trainer=trainer)
        self.build()
        # Share this Network's RNG stream so consecutive legacy fit() calls
        # consume shuffles exactly as the pre-compile implementation did.
        compiled = CompiledNetwork(self, config, rng=self._rng)
        result = compiled.fit(
            dataset,
            epochs_hidden=epochs_hidden,
            epochs_readout=epochs_readout,
            batch_size=batch_size,
            readout=readout,
            readout_lr=readout_lr,
            shuffle=shuffle,
            verbose=verbose,
        )
        self.states = list(compiled.state.layers)
        self._sgd_readout = compiled.state.readout
        return result

    # ------------------------------------------------------------ evaluation
    def evaluate(
        self, dataset: Tuple[np.ndarray, np.ndarray], batch_size: int = 1024
    ) -> float:
        """Classification accuracy (argmax over output units)."""
        x, y = dataset
        scores = self.predict(x, batch_size=batch_size)
        pred = np.asarray(jnp.argmax(scores, axis=-1))
        return float(np.mean(pred == np.asarray(y)))
