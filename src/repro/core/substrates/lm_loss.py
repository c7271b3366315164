"""LM-loss evaluation backend: the engine's fitness IS a model forward.

This is the ROADMAP's flagship scenario (DESIGN.md §11): every fitness
evaluation is a real forward + cross-entropy of a ``models/`` network on a
fixed synthetic batch, with the parameters perturbed along a k-dimensional
``SubspaceProjection`` (``core/subspace.py`` — shared with the in-process
subspace-Newton optimizer).  An engine candidate point is a (k,) vector of
subspace coefficients; the backend lifts it to θ0 + c·V leaf-by-leaf and
returns the loss.  Six orders of magnitude more expensive than the SDSS
quadratics, which is exactly the regime where the paper's volunteer-grid
economics bind — and the ``EvalBackend`` seam must not care.

Two evaluation modes, one class:

  * ``mesh=None`` — in-process: ``lax.map`` over the bucket's lanes on
    the local device (the parity reference);
  * ``mesh=make_production_mesh()`` — pod: the bucket's lanes are
    ``shard_map``'d over the ``data`` axis while θ0 and the basis enter
    SHARDED OVER ``model`` with the model's own ``param_specs``
    (``enforce_divisible``'d — a smoke config's 4 heads cannot split 16
    ways and must fall back explicitly), and each shard all-gathers the
    full leaves before evaluating its local lanes.

Why gather-at-use instead of Megatron-style partitioned compute: a TP
matmul splits a contraction across the ``model`` axis and psums partials,
which changes the f32 summation order — and bit-identical iterates
between pod and in-process evaluation are a hard contract of this seam.
Tiled all-gathers reconstruct exactly the original leaf, so every lane
runs the SAME per-lane program both ways; the ``model`` axis contributes
parameter/basis STORAGE scaling (the basis is k× the model's size — at
real scale it is the thing that must shard), lanes scale on ``data``.

Why ``lax.map`` over lanes instead of ``vmap``: a vmapped forward fuses
the lane axis into every matmul, so a lane's numerics could depend on the
bucket width it rides in (the pod_mesh backend needs a 4-rows-per-shard
floor for exactly that reason).  Sequential per-lane evaluation makes
each lane's program width-independent BY CONSTRUCTION — sync, pipelined,
pod, coalesced multi-search buckets and quorum replicas all compute any
given point with the identical instruction sequence.  See DESIGN.md §11.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import numpy as np

from repro.core.substrates.eval_backend import (DEFAULT_MIN_BUCKET,
                                                EvalBackend, bucket_size)


@dataclasses.dataclass(frozen=True)
class LmWorkload:
    """One frozen LM fitness problem: smoke config + synthetic batch +
    subspace chart, plus the engine-facing search box.  Everything is
    derived deterministically from (arch, seed), so two processes given
    the same fields build bit-identical fitness functions — the
    work-server restore path and every parity baseline depend on it."""
    arch: str
    cfg: Any                       # ModelConfig (smoke, use_kernels routed)
    batch: Dict[str, np.ndarray]   # fixed synthetic tokens/labels
    proj: Any                      # SubspaceProjection (theta0, basis, ...)
    k: int
    coeff_bound: float
    seed: int

    # -- the engine-facing search space: subspace coefficients ------------
    @property
    def x0(self) -> np.ndarray:
        return np.zeros(self.k, np.float64)          # θ0 itself

    @property
    def lo(self) -> np.ndarray:
        return np.full(self.k, -self.coeff_bound, np.float64)

    @property
    def hi(self) -> np.ndarray:
        return np.full(self.k, self.coeff_bound, np.float64)

    @property
    def step(self) -> np.ndarray:
        return np.full(self.k, 0.2 * self.coeff_bound, np.float64)


def make_lm_workload(arch: str, *, k: int = 8, batch_size: int = 2,
                     seq_len: int = 32, seed: int = 0,
                     coeff_bound: float = 1.0,
                     use_kernels: bool = True) -> LmWorkload:
    """Build the LM fitness problem for one smoke config.

    The ``configs/`` smoke reductions ARE the workload definitions: any
    registered arch name works, and ``use_kernels=True`` routes its
    attention/wkv6 hot paths through ``kernels/ops.py`` (Pallas on TPU,
    ref fallback on CPU — compat.route_pallas) inside the traced ladder.
    """
    import jax

    from repro.configs import get_smoke_config
    from repro.core.subspace import SubspaceProjection

    cfg = dataclasses.replace(get_smoke_config(arch),
                              use_kernels=use_kernels)
    rng = np.random.default_rng(seed * 7919 + 11)
    batch = {
        "tokens": rng.integers(0, cfg.vocab_size, (batch_size, seq_len),
                               dtype=np.int64).astype(np.int32),
        "labels": rng.integers(0, cfg.vocab_size, (batch_size, seq_len),
                               dtype=np.int64).astype(np.int32),
    }
    init_key, basis_key = jax.random.split(jax.random.key(seed), 2)
    from repro.models import transformer as T
    params0 = T.init_params(cfg, init_key)
    proj = SubspaceProjection.create(params0, k, basis_key)
    return LmWorkload(arch=arch, cfg=cfg, batch=batch, proj=proj, k=k,
                      coeff_bound=coeff_bound, seed=seed)


class LmLossEvalBackend(EvalBackend):
    """``EvalBackend`` whose ``_raw_eval`` lifts each lane's (k,) subspace
    coefficients to model parameters and returns the forward/CE loss on
    the workload's fixed batch.

    ``mesh=None``: local single-device evaluation.  ``mesh`` given: lanes
    shard over ``data``, θ0/basis storage shards over ``model`` (see
    module docstring).  The async submit/collect framing, staging rings,
    malicious-lane corruption and pad masking are all inherited — so the
    backend composes unchanged with ``CachingSubmitter``, the coalescing
    orchestrator and the work server, which only ever see the seam.
    """

    def __init__(self, workload: LmWorkload, mesh=None, *,
                 data_axis: str = "data", model_axis: str = "model",
                 n_dims: Optional[int] = None,
                 max_bucket: Optional[int] = None):
        import jax
        import jax.numpy as jnp

        from repro.models import transformer as T

        self.workload = workload
        self.mesh = mesh
        loss_fn = T.make_loss_fn(workload.cfg)
        batch = {k_: jnp.asarray(v) for k_, v in workload.batch.items()}
        theta0, basis_tree = workload.proj.theta0, workload.proj.basis_tree

        from repro.core.subspace import tree_lift

        def lanes(pts, theta, basis, batch_):
            # The bucket's lanes, one at a time.  The barrier pins the
            # lift's operands as materialized arrays: without it XLA may
            # fuse the k-contraction with an all_gather (pod) or a
            # constant (in-process) and lower it with different FMA
            # contraction — a last-ulp split that breaks the pod ==
            # in-process bit-identity contract.  With it, every path
            # compiles the same lift-then-forward program per lane.
            theta, basis = jax.lax.optimization_barrier((theta, basis))

            def lane(c):
                return loss_fn(tree_lift(theta, basis, c), batch_)[0]
            return jax.lax.map(lane, pts)

        if mesh is None:
            self._lane_eval = lambda pts: lanes(pts, theta0, basis_tree,
                                                batch)
            min_bucket = DEFAULT_MIN_BUCKET
            self.n_shards = 1
        else:
            from jax.sharding import PartitionSpec as P

            from repro.models.sharding import enforce_divisible

            self.n_shards = int(mesh.shape[data_axis])
            if self.n_shards & (self.n_shards - 1):
                raise ValueError(
                    f"data axis must be a power of two to divide the "
                    f"power-of-two buckets, got {self.n_shards}")
            # the model's own sharding rules, with every non-dividing
            # entry downgraded EXPLICITLY (smoke dims vs model=16)
            pspecs, self.spec_fallbacks = enforce_divisible(
                workload.cfg, mesh)
            # basis leaves mirror the param leaves with a leading k axis
            bspecs = jax.tree.map(lambda s: P(*((None,) + tuple(s))), pspecs,
                                  is_leaf=lambda x: isinstance(x, P))
            # every lane's loss is over the WHOLE batch, so the batch is
            # replicated: split over ``data`` (as a training step would),
            # each shard would score its lanes on its slice of the tokens
            in_pspecs = jax.tree.map(lambda _: P(), batch)

            def _gather_full(tree, specs):
                # tiled all-gather over the model axis reconstructs each
                # sharded leaf exactly (concatenation in axis order) —
                # deterministic, so per-lane numerics match in-process
                def g(leaf, spec):
                    for dim, e in enumerate(spec):
                        axes = e if isinstance(e, tuple) else (e,)
                        if e is not None and model_axis in axes:
                            return jax.lax.all_gather(
                                leaf, model_axis, axis=dim, tiled=True)
                    return leaf
                return jax.tree.map(g, tree, specs,
                                    is_leaf=lambda x: isinstance(x, P))

            def shard_body(pts, theta_sh, basis_sh, batch_sh):
                theta_f = _gather_full(theta_sh, pspecs)
                basis_f = _gather_full(basis_sh, bspecs)
                return lanes(pts, theta_f, basis_f, batch_sh)

            self._sharded = jax.shard_map(
                shard_body, mesh=mesh,
                in_specs=(P(data_axis, None), pspecs, bspecs, in_pspecs),
                out_specs=P(data_axis), check_vma=False)
            # device_put with the enforced specs: θ0 and the basis are
            # STORED model-sharded (the tentpole's storage-scaling claim),
            # and shard_map consumes them without a relayout
            from repro.models.sharding import to_named
            self._theta = jax.device_put(theta0, to_named(pspecs, mesh))
            self._basis = jax.device_put(basis_tree, to_named(bspecs, mesh))
            self._batch = jax.device_put(
                batch, to_named(in_pspecs, mesh))
            self._lane_eval = lambda pts: self._sharded(
                pts, self._theta, self._basis, self._batch)
            # lanes are evaluated sequentially per shard (lax.map), so —
            # unlike the vectorized pod_mesh f_batch — ANY rows-per-shard
            # count is width-stable; the floor is just even division
            min_bucket = bucket_size(self.n_shards)
        super().__init__(min_bucket)
        if n_dims is not None and max_bucket is not None:
            self.warm(n_dims, max_bucket)

    def _raw_eval(self, pts):
        return self._lane_eval(pts)
