"""Distributed BCPNN training — the paper's MPI backend, JAX-native.

The paper's scheme (Sec. 3, "MPI Backend"): each rank takes a sub-batch,
computes *local batch means* of the activation statistics, then a single
``MPI_Allreduce`` derives the global means before the EWMA marginal update is
applied locally (hence identically) on every rank.  OpenMP parallelizes
inside each rank.

Mapping onto JAX:

* MPI rank        -> device along the ``data`` (and optionally ``pod``) mesh axes
* sub-batch       -> batch shard (``P(('pod','data'), ...)``)
* MPI_Allreduce   -> ``jax.lax.pmean`` inside ``shard_map`` (explicit,
                     paper-faithful) or the all-reduce XLA inserts for
                     ``jnp.mean`` over a sharded axis (pjit, implicit)
* OpenMP          -> XLA intra-device parallelism

Both formulations are provided; they are bitwise-identical in exact
arithmetic and validated against the single-device path in tests.  The
*beyond-paper* extension is hidden-axis model parallelism: ``C_ij``/``w`` are
sharded over the ``model`` axis on the hidden-unit dimension (HCUs are never
split — enforced by ``UnitLayout.validate_divisible_by``), which the paper's
flat MPI scheme cannot express.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import learning
from repro.core.layers import DenseLayer, LayerState, StructuralPlasticityLayer
from repro.core.learning import MarginalState


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes the batch is sharded over (pod+data when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def model_axis(mesh: Mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


# --------------------------------------------------------------------------
# shard_map formulation: explicit pmean == the paper's MPI_Allreduce
# --------------------------------------------------------------------------
def dp_learning_cycle(
    state: MarginalState,
    ai: jnp.ndarray,
    aj: jnp.ndarray,
    lam: float,
    k_b: float,
    axes: Sequence[str],
):
    """One learning cycle on a *local* sub-batch inside shard_map.

    Local batch means are pmean-ed over `axes` (the paper's allreduce of
    <a_i>, <a_j>, <a_i (x) a_j>), then the EWMA/weight update runs locally.
    Equal shard sizes make mean-of-means == global mean exactly.  Returns
    the unmasked w; the caller applies the receptive fields.
    """
    mi, mj, mij = learning.batch_means(ai, aj)
    mi = jax.lax.pmean(mi, axes)
    mj = jax.lax.pmean(mj, axes)
    mij = jax.lax.pmean(mij, axes)
    new_state = learning.update_marginals(state, mi, mj, mij, lam)
    w, b = learning.weights_from_marginals(new_state, k_b)
    return new_state, w, b


class DataParallelTrainer:
    """Builds sharded per-batch step functions for Network.fit.

    mode="shard_map": paper-faithful explicit collectives.
    mode="pjit":      sharding-annotated jit; XLA derives the same allreduce.
    Model-axis sharding of the hidden dimension is applied when the mesh has
    a 'model' axis and the layer's post layout divides evenly.
    """

    def __init__(self, mesh: Mesh, mode: str = "shard_map"):
        if mode not in ("shard_map", "pjit"):
            raise ValueError(f"mode must be shard_map|pjit, got {mode}")
        # The steps write global math and leave the all-reduce to the
        # compiler (implicit sharding), so every mesh axis must be Auto;
        # jax.make_mesh's default Explicit axes refuse the batch-contracting
        # einsum.  Same devices, same layout, Auto axes.
        self.mesh = Mesh(
            mesh.devices, mesh.axis_names,
            axis_types=(AxisType.Auto,) * len(mesh.axis_names),
        )
        self.mode = mode
        self.baxes = batch_axes(mesh)
        if not self.baxes:
            raise ValueError(f"mesh {mesh.axis_names} has no pod/data axis")

    # ------------------------------------------------------- plan decoration
    def decorate(self, plan):
        """Bind this trainer into an ExecutionPlan (repro.runtime.plans):
        every per-batch transition the plan compiles becomes the sharded
        shard_map/pjit step, and (for the scan plan) states and stacked
        epochs are placed with this trainer's shardings.  Invoked by
        ``Network.compile(ExecutionConfig(trainer=...))``."""
        return plan.bind_trainer(self)

    # -------------------------------------------------------------- helpers
    def _state_spec(self, layer, shard_hidden: bool) -> LayerState:
        """PartitionSpec pytree for a LayerState."""
        m = model_axis(self.mesh) if shard_hidden else None
        marg = MarginalState(ci=P(None), cj=P(m), cij=P(None, m))
        from repro.core.plasticity import PlasticityState

        # StructuralPlasticityLayer always carries a mask state (full mask
        # when dense); DenseLayer has none — the spec must mirror the state.
        has_plast = isinstance(layer, StructuralPlasticityLayer)
        pl_spec = PlasticityState(hcu_mask=P(None, m)) if has_plast else None
        return LayerState(
            marginals=marg, w=P(None, m), b=P(m), plast=pl_spec, step=P()
        )

    def _can_shard_hidden(self, layer) -> bool:
        m = model_axis(self.mesh)
        if m is None:
            return False
        n_shards = self.mesh.shape[m]
        return layer.spec.post.n_hcu % n_shards == 0

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.baxes, None))

    def cache_sharding(self, ndim: int = 2) -> NamedSharding:
        """Placement for a cached ``(n_samples, ...)`` level-k activation
        array (repro.runtime.activations): rows sharded over the batch mesh
        axes, so project-once caches live distributed and the per-epoch
        ``jnp.take`` gather + epoch_sharding placement never funnel the
        whole level through one device."""
        return NamedSharding(self.mesh, P(self.baxes, *(None,) * (ndim - 1)))

    def place_state(self, layer, state: LayerState) -> LayerState:
        """Device-put a layer state with the trainer's shardings."""
        spec = self._state_spec(layer, self._can_shard_hidden(layer))
        return jax.tree_util.tree_map(
            lambda x, s: x if x is None
            else jax.device_put(x, NamedSharding(self.mesh, s)),
            state,
            spec,
            is_leaf=lambda x: x is None,
        )

    # ---------------------------------------------------------- step builders
    def hidden_step(self, layer: StructuralPlasticityLayer) -> Callable:
        if self.mode == "pjit":
            return self._pjit_step(layer, supervised=False)
        return self._shard_map_step(layer, supervised=False)

    def readout_step(self, layer: DenseLayer) -> Callable:
        if self.mode == "pjit":
            return self._pjit_step(layer, supervised=True)
        return self._shard_map_step(layer, supervised=True)

    def _pjit_step(self, layer, supervised: bool) -> Callable:
        """Sharding-annotated jit: write the *global* math, let GSPMD insert
        the allreduce over the sharded batch axis."""
        sspec = self._state_spec(layer, self._can_shard_hidden(layer))
        s_shard = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), sspec,
            is_leaf=lambda x: isinstance(x, P),
        )
        x_shard = self.batch_sharding()
        y_shard = NamedSharding(self.mesh, P(self.baxes))

        if supervised:

            def step(state, xb, yb):
                return layer.train_batch(state, xb, yb)[0]

            return jax.jit(
                step,
                in_shardings=(s_shard, x_shard, y_shard),
                out_shardings=s_shard,
            )

        def step(state, xb):
            return layer.train_batch(state, xb)[0]

        return jax.jit(step, in_shardings=(s_shard, x_shard), out_shardings=s_shard)

    def _shard_map_step(self, layer, supervised: bool) -> Callable:
        """Explicit-collective step: forward + dp_learning_cycle under
        shard_map.  The plasticity-mask rewire runs on replicated marginals
        (identical on all shards), preserving the single-device semantics."""
        spec = layer.spec
        baxes = self.baxes
        shard_hidden = self._can_shard_hidden(layer)
        if shard_hidden:
            spec.post.validate_divisible_by(self.mesh.shape["model"])
        sspec = self._state_spec(layer, shard_hidden)
        x_spec = P(baxes, None)

        def local_step(state: LayerState, xb, yb=None):
            post_layout = _local_post(spec.post, state.w)
            # Forward on the local hidden shard; softmax is HCU-local so no
            # collective is needed (HCUs never straddle shards).  `w` is
            # already masked (LayerState).  The soft-WTA gain must scale the
            # support exactly as learning.forward does — omitting it
            # silently diverged shard_map training from the single-device
            # and pjit paths for any gain != 1 layer (caught by the
            # deep-network parity test).
            s = xb @ state.w + state.b
            if spec.gain != 1.0:
                s = s * spec.gain
            aj = learning.hcu_softmax(s, post_layout)
            if supervised:
                aj = jax.nn.one_hot(yb, state.w.shape[1], dtype=xb.dtype)
            marg, w, b = state.marginals, state.w, state.b
            for _ in range(spec.n_cycles):
                marg, w, b = dp_learning_cycle(
                    marg, xb, aj, spec.lam, spec.k_b, baxes
                )
                if state.plast is not None:
                    w = state.plast.mask_weights(w, spec.pre, post_layout)
            return LayerState(marg, w, b, state.plast, state.step + 1)

        if supervised:
            fn = jax.shard_map(
                local_step,
                mesh=self.mesh,
                in_specs=(sspec, x_spec, P(baxes)),
                out_specs=sspec,
                check_vma=False,
            )
        else:
            fn = jax.shard_map(
                lambda s, xb: local_step(s, xb),
                mesh=self.mesh,
                in_specs=(sspec, x_spec),
                out_specs=sspec,
                check_vma=False,
            )

        if (
            not supervised
            and getattr(layer, "fan_in", None) is not None
            and layer.fan_in < layer.spec.pre.n_hcu
        ):
            # Rewire outside shard_map on the replicated view (cheap,
            # infrequent), exactly as Alg.1 interleaves it.
            rewire = jax.jit(layer.maybe_update_mask)

            def stepper(state, xb):
                state = rewire(state)
                return jax.jit(fn)(state, xb)

            return stepper
        return jax.jit(fn)


def _local_post(post, w):
    """Local-view UnitLayout for a (possibly model-sharded) hidden dim."""
    from repro.core.units import UnitLayout

    n_local = w.shape[1]
    if n_local == post.n_units:
        return post
    assert n_local % post.n_mcu == 0, "shard split an HCU — forbidden"
    return UnitLayout(n_hcu=n_local // post.n_mcu, n_mcu=post.n_mcu)
