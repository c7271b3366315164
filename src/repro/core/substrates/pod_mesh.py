"""Pod-mesh evaluation backend: shard_map workunit buckets over the pod.

This is the ROADMAP's "wire the batched grid to the pod mesh" step: instead
of evaluating each tick's workunit block with one local ``f_batch`` call,
``PodMeshEvalBackend`` partitions the padded bucket over the ``data`` axis
of the production mesh (``launch/mesh.py::make_production_mesh``, 16×16 =
256 devices when 512 host devices are forced) and lets every
data shard evaluate its ``kp / n_shards`` rows in parallel.  The ``model``
axis is left for the fitness function itself (a replicated closure today;
a model-sharded likelihood slots in without touching the grid).

The backend speaks the shared async ``submit``/``collect`` protocol
(DESIGN.md §7): the shard_map'd evaluation is traced inside the base
class's jitted bucket finalization, so corruption lanes and pad-NaN
masking happen on-device here exactly as in-process, and the bucket
ladder is warmed at construction when ``n_dims``/``max_bucket`` are given.

Key properties (DESIGN.md §6):

  * buckets are powers of two with a floor at the shard count, so every
    shard gets the same whole number of rows and XLA still compiles
    O(log k_max) shapes — shapes depend on the block size and shard count,
    never on the grid's host count;
  * remainder lanes (k < bucket) are padded with the last real point and
    come back NaN-masked by the shared on-device framing — never dropped;
  * rows are evaluated by the SAME per-row computation as in-process
    (``f_batch`` is row-independent), so a given engine seed commits
    bit-identical iterates on either backend — pinned by
    tests/test_substrates_pod_mesh.py, and on the 16×16 mesh by
    tests/test_sigkill_restore.py.
"""
from __future__ import annotations

from typing import Callable, Optional

from repro.core.substrates.eval_backend import EvalBackend, bucket_size


def make_data_mesh():
    """Best evaluation mesh for the visible devices: the production pod
    when enough devices exist (e.g. under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=512``, as
    ``tests/test_sigkill_restore.py`` runs it), else the largest
    power-of-two data-parallel mesh that fits
    — down to a degenerate (1, 1) mesh on a single-device CPU, which keeps
    the shard_map path importable and testable anywhere."""
    import jax
    from repro.launch.mesh import make_production_mesh
    try:
        return make_production_mesh()
    except RuntimeError:
        n = len(jax.devices())
        d = 1 << (n.bit_length() - 1)
        return jax.make_mesh((d, 1), ("data", "model"),
                             devices=jax.devices()[:d])


class PodMeshEvalBackend(EvalBackend):
    """Evaluate buckets with ``shard_map`` over the mesh's ``data`` axis.

    f_batch: (rows, n) -> (rows,) fitness, jit-friendly and row-independent
    (each shard calls it on its local rows).  ``mesh`` defaults to
    ``make_data_mesh()``.
    """

    def __init__(self, f_batch: Callable, mesh=None, data_axis: str = "data",
                 *, n_dims: Optional[int] = None,
                 max_bucket: Optional[int] = None):
        import jax
        from jax.sharding import PartitionSpec as P

        self.mesh = make_data_mesh() if mesh is None else mesh
        self.data_axis = data_axis
        self.n_shards = int(self.mesh.shape[data_axis])
        if self.n_shards & (self.n_shards - 1):
            raise ValueError(
                f"data axis must be a power of two to divide the "
                f"power-of-two buckets, got {self.n_shards}")
        self.f_batch = f_batch
        self._sharded = jax.shard_map(
            f_batch, mesh=self.mesh,
            in_specs=P(data_axis, None), out_specs=P(data_axis))
        # floor of 4 rows per shard: XLA CPU picks a different (last-ulp
        # divergent) vectorization for 2-row sub-batches (observed on jax
        # 0.4.37 — every other width is bitwise-stable), and bit-identical
        # iterates vs the in-process backend are a hard contract of this
        # seam.  The parity tests exist to catch any future regression
        # of this property.
        super().__init__(bucket_size(4 * self.n_shards))
        if n_dims is not None and max_bucket is not None:
            self.warm(n_dims, max_bucket)

    def _raw_eval(self, pts):
        return self._sharded(pts)
