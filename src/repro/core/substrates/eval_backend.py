"""Evaluation backends for the batched-grid substrate (DESIGN.md §6–§7).

The batched grid decides WHICH points a tick evaluates; a backend decides
HOW that block of points turns into fitness values.  Since the pipelined
refactor the seam is an asynchronous two-call protocol:

    handle = backend.submit(pts, mal_u)    # frame + dispatch, returns now
    ys = backend.collect(handle)           # wait for the values, unpad

``submit`` leans on JAX async dispatch: it returns as soon as the bucket
is enqueued on the device, so the caller overlaps host simulation work
(fleet physics, speculative work generation) with the evaluation and only
pays for the device when ``collect`` materializes the result.  ``submit``
also requests the bucket's device-to-host copy right after the dispatch
(``copy_to_host_async``): the device queues it behind the evaluation, so
a bucket collected ticks later finds its values already in host memory,
and ``collect`` only waits for a copy already under way.  Every collected
bucket is read back anyway, so no transfer is added; only its start
moves.  There is no ``block_until_ready`` before the read-back: it adds
a round trip a bucket and lost on the chip (PERF.md).  When a bucket is
collected stays invisible to the engines: the values are the same bits.
The synchronous form ``backend(pts, mal_u)`` remains
``collect(submit(...))``, so non-pipelined callers are unchanged.

Framing.  Every block of ``k`` points is written into a PERSISTENT
per-bucket staging buffer padded up to a power-of-two bucket ``kp``: one
``(kp, n + 1)`` float32 array, the points in columns ``0..n-1`` (pad
lanes repeat the last real point) and each lane's malicious draw in
column ``n`` (NaN for an honest lane, ``PAD_MARK`` for a pad lane).  A
dispatch thus hands the device ONE host array and pays one
host-to-device transfer: on the chip, the points, the draws and a lane
count as three arguments cost ~578 us a dispatch against ~235 us for
the one packed array (PERF.md §5).  The steady state pays one buffer
fill per tick — no per-tick ``np.concatenate``/``np.repeat``
allocations, and on CPU the XLA client aliases the numpy buffer
outright (zero copy): the staging buffers ARE the device buffers.  That
aliasing is exactly why they form a RING (``STAGING_RING`` deep) per
bucket size: a submitted
bucket may still be reading its buffer while the host stages the next
tick, so consecutive submits of one shape rotate through distinct
buffers, classic double-buffering — callers may keep at most
``STAGING_RING`` handles of one bucket shape in flight (enforced per
ring slot, so collecting out of order cannot defeat the check: a
``submit`` that would restage an uncollected handle's buffer raises
instead of silently corrupting it; the pipelined grid clamps its queue
depth well under that).  Bucket shapes depend only on the block size
(and the
backend's shard-count floor), NOT on the grid's host count, so the jitted
path sees O(log k_max) distinct shapes over a whole run; ``warm()``
compiles that whole ladder up front (backends constructed with
``n_dims``/``max_bucket`` warm at construction), so a warmed backend
performs ZERO compiles mid-run — pinned by the ``compile_count`` probe in
the substrate tests.

The ladder's top is bounded by the chip.  A lane's temporaries grow with
the fitness (~800 KB a lane for a 100k-star stripe on a v5e), so a
ladder warmed up to a large fleet's ``n_hosts`` would ask for buckets no
device holds.  Where the device reports its memory limit, ``warm``
derives the widest power-of-two bucket that fits beside what the device
already holds (``ladder_top``): it estimates a lane's bytes from the
compiled bucket of ``PROBE_LANES`` and checks the compiled top bucket
whenever that top cuts the ladder.  ``submit`` of a wider bucket raises
``BucketTooWide`` and compiles nothing; callers that batch lazily (the
served client pool) stop their batches at the top.  Where no limit is
reported (the CPU) the ladder is unbounded, as before.

Results come back FINAL (DESIGN.md §7): the jitted bucket finalization
splits the staged array into points and draws, applies the sign-safe
malicious corruption ``grid.malicious_lie`` to the lanes whose draw is
finite and masks the ``PAD_MARK`` lanes to NaN on-device, so ``collect``
never patches values on the host after a blocking fetch.

Two backends ship with the repo:

  * ``InProcessEvalBackend`` — the default: one ``f_batch`` call on the
    local device inside the shared bucket finalization;
  * ``substrates/pod_mesh.py::PodMeshEvalBackend`` — ``shard_map``s each
    bucket over the ``data`` axis of the production pod mesh.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import numpy as np

from repro.core.grid import malicious_lie
from repro.obs.spans import span, spanned

#: THE bucket floor, documented once: blocks smaller than this are padded
#: up to it so tiny phases (the bootstrap probe, quorum replicas) reuse one
#: small compiled shape instead of compiling per exact size.  Backends with
#: stricter needs (the pod mesh's rows-per-shard floor) raise it; callers
#: may lower it to any power of two >= 1.
DEFAULT_MIN_BUCKET = 8

#: staging buffers per bucket shape.  XLA CPU zero-copies numpy inputs, so
#: a buffer must not be restaged while its bucket is still in flight; a
#: ring this deep supports up to STAGING_RING simultaneously in-flight
#: buckets of one shape — restaging a slot whose handle is uncollected
#: raises (per-slot flags, so out-of-order collects are handled exactly).
STAGING_RING = 8

#: the staged draw column's mark for a pad lane: neither NaN (honest) nor
#: a draw in [0.2, 0.8] (malicious), so the device tells the real lanes
#: from the pad without a lane count
PAD_MARK = np.float32(np.inf)

#: the bucket whose compiled memory a lane's bytes are estimated from: wide
#: enough that a lane's temporaries show (the TPU compiler keeps a narrow
#: bucket's per-lane work out of its temporaries altogether)
PROBE_LANES = 1024


class BucketTooWide(ValueError):
    """A bucket wider than the backend's ladder top: it would not fit in
    the device's memory, so it is refused before anything compiles."""

    def __init__(self, k: int, kp: int, top: int):
        super().__init__(
            f"{k} lanes need a bucket of {kp}, above this backend's ladder "
            f"top of {top} (the widest bucket the device's memory holds); "
            f"split the block")
        self.k, self.kp, self.top = k, kp, top


def device_headroom() -> Optional[int]:
    """Bytes the local devices can still give one program: the least of
    ``bytes_limit - bytes_in_use`` over the devices that report a limit,
    None where none does (the CPU)."""
    import jax

    free = [s["bytes_limit"] - s.get("bytes_in_use", 0)
            for s in (d.memory_stats() or {} for d in jax.local_devices())
            if "bytes_limit" in s]
    return min(free) if free else None


def ladder_top(backend, n_dims: int) -> Optional[int]:
    """The widest bucket ``backend`` dispatches for ``n_dims``-parameter
    points, as its warm-up derived it; None where it derived none.
    Wrappers that keep the backend they wrap as ``.backend`` (the eval
    cache's submitter, a recorder) are looked through."""
    while not isinstance(backend, EvalBackend):
        backend = getattr(backend, "backend", None)
        if backend is None:
            return None
    return backend._tops.get(n_dims)


def bucket_size(k: int, min_bucket: int = DEFAULT_MIN_BUCKET) -> int:
    """Smallest power of two ≥ max(k, min_bucket).  ``min_bucket`` must be
    a power of two (backends use their shard count, which is)."""
    if min_bucket & (min_bucket - 1):
        raise ValueError(f"min_bucket must be a power of two, got {min_bucket}")
    return max(min_bucket, 1 << max(k - 1, 0).bit_length())


class EvalHandle(NamedTuple):
    """An in-flight bucket evaluation returned by ``EvalBackend.submit``.

    ``ys`` is the (kp,) device array still materializing under async
    dispatch, its host copy already requested; touching it with
    ``np.asarray`` (what ``collect`` does) blocks until that copy is in
    host memory.  ``k`` is the number of real lanes,
    ``kp`` the padded bucket width, ``slot`` the staging-ring slot the
    bucket aliases until collected, and ``seq`` the submission's ownership
    token for that slot (a stale or double ``collect`` must not free a
    slot now owned by a newer submission).  ``tags`` is the per-lane
    submitter-id array shipped with a coalesced multi-search bucket
    (DESIGN.md §8) — host-side framing metadata for observability and
    debugging (which search owns each lane); never read by the device
    computation, and not the demux mechanism either (consumers slice by
    lane offsets).  ``None`` for single-submitter buckets.
    """
    ys: Any
    k: int
    kp: int
    slot: int
    seq: int
    tags: Any = None


class EvalBackend:
    """Base class: persistent-buffer bucket framing + on-device result
    finalization around a subclass evaluation.

    Subclasses implement ``_raw_eval((kp, n) f32 block) -> (kp,) fitness``
    — traced inside this class's jitted finalization, on the points'
    columns of the staged array — for ``kp`` already padded to a
    power-of-two multiple of the backend's lane count.  This
    class owns padding, the malicious-corruption lanes, and pad-lane NaN
    masking, so every backend frames and finalizes blocks identically (a
    parity requirement: the same engine seed must commit the same iterates
    on any backend, pipelined or not).
    """

    def __init__(self, min_bucket: int = DEFAULT_MIN_BUCKET):
        if min_bucket < 1 or (min_bucket & (min_bucket - 1)):
            raise ValueError(
                f"min_bucket must be a power of two >= 1, got {min_bucket}")
        self.min_bucket = min_bucket
        self._bufs: dict = {}            # kp -> ring of (kp, n + 1) bufs
        self._ring: dict = {}            # kp -> next ring slot
        self._slot_owner: dict = {}      # kp -> per-slot owning seq (or None)
        self._submit_seq = 0             # ownership tokens for ring slots
        self._warmed: set = set()        # (n_dims, kp) already compiled
        self._tops: dict = {}            # n_dims -> ladder top, or None
        #: number of bucket-shape traces performed — a warmed backend must
        #: not grow this mid-run (the zero-compile probe in the tests)
        self.compile_count = 0
        self._eval = self._make_bucket_eval()

    # -- subclass seam -------------------------------------------------------

    def _raw_eval(self, pts):
        """(kp, n) f32 bucket -> (kp,) fitness; called under jit trace."""
        raise NotImplementedError

    def _make_bucket_eval(self):
        import jax
        import jax.numpy as jnp

        def bucket_eval(staged):
            # this body runs at TRACE time only: one execution per bucket
            # shape, which is exactly what compile_count must count
            self.compile_count += 1
            pts, u = staged[:, :-1], staged[:, -1]
            ys = self._raw_eval(pts)
            # malicious corruption as mask lanes: NaN u == honest lane
            ys = jnp.where(jnp.isfinite(u), malicious_lie(ys, u), ys)
            # pad lanes (marker PAD_MARK) come back NaN from the device —
            # results are final on arrival, never patched on host
            return jnp.where(u == PAD_MARK, jnp.nan, ys)

        return jax.jit(bucket_eval)

    # -- framing -------------------------------------------------------------

    def _staging(self, kp: int, n: int):
        """Next (staged (kp, n + 1) buffer, slot) in the bucket's ring.
        The rotation is what makes restaging safe under async dispatch:
        the previous slots may still be aliased by in-flight buckets —
        and a slot whose bucket is STILL uncollected refuses to restage
        (zero-copy aliasing would silently corrupt it otherwise)."""
        ring = self._bufs.get(kp)
        if ring is None or ring[0].shape[1] != n + 1:
            ring = self._bufs[kp] = []
            for _ in range(STAGING_RING):
                buf = np.zeros((kp, n + 1), np.float32)
                buf[:, n] = np.nan
                ring.append(buf)
            self._ring[kp] = 0
            self._slot_owner[kp] = [None] * STAGING_RING
        slot = self._ring[kp]
        if self._slot_owner[kp][slot] is not None:
            raise RuntimeError(
                f"an uncollected submission still aliases staging slot "
                f"{slot} of bucket shape {kp} (ring depth {STAGING_RING}); "
                f"collect() in-flight handles before submitting more")
        self._ring[kp] = (slot + 1) % STAGING_RING
        return ring[slot], slot

    def _bucket_bytes(self, n_dims: int, kp: int) -> Optional[int]:
        """Device bytes the compiled bucket of ``kp`` lanes asks for (its
        temporaries, argument, output and code); None when the compiler
        refuses it for want of memory."""
        import jax

        try:
            compiled = self._eval.lower(jax.ShapeDtypeStruct(
                (kp, n_dims + 1), np.float32)).compile()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return None
        m = compiled.memory_analysis()
        return (m.temp_size_in_bytes + m.argument_size_in_bytes
                + m.output_size_in_bytes + m.generated_code_size_in_bytes)

    def _derive_top(self, n_dims: int, want: int) -> Optional[int]:
        """The widest power-of-two bucket that fits in the device's
        headroom, estimated from the bytes a lane takes at the probe
        bucket.  Where that estimate cuts a ladder wanted up to ``want``,
        the top bucket itself is compiled and checked, halving until one
        fits.  None where the device reports no memory limit."""
        free = device_headroom()
        if free is None:
            return None
        probe = bucket_size(PROBE_LANES, self.min_bucket)
        need = self._bucket_bytes(n_dims, probe)
        top = probe
        if need is not None:
            while need * (2 * top) // probe <= free:
                top *= 2
        if top >= want:
            return top
        while top > self.min_bucket:
            need = self._bucket_bytes(n_dims, top)
            if need is not None and need <= free:
                break
            top //= 2
        return top

    def warm(self, n_dims: int, max_k: int) -> "EvalBackend":
        """Compile AND execute the bucket ladder (min_bucket up to
        ``bucket_size(max_k)``, or to the ladder's top where the device's
        memory cuts it lower) so no compile ever lands mid-run, and
        preallocate the persistent staging buffers.  Idempotent: already
        warmed (n_dims, bucket) cells are skipped, so re-warming at the
        start of every ``BatchedVolunteerGrid.run`` costs nothing."""
        handles = []
        kp = bucket_size(1, self.min_bucket)
        top = bucket_size(max_k, self.min_bucket)
        if top > PROBE_LANES and n_dims not in self._tops:
            self._tops[n_dims] = self._derive_top(n_dims, top)
        bound = self._tops.get(n_dims)
        if bound is not None:
            top = min(top, bound)
        while True:
            if (n_dims, kp) not in self._warmed:
                buf, _ = self._staging(kp, n_dims)
                handles.append(self._eval(buf))
                self._warmed.add((n_dims, kp))
            if kp >= top:
                break
            kp *= 2
        for h in handles:
            h.block_until_ready()
        return self

    # -- the async protocol --------------------------------------------------

    @spanned("backend.submit")
    def submit(self, pts: np.ndarray,
               mal_u: Optional[np.ndarray] = None,
               lane_tags: Optional[np.ndarray] = None) -> EvalHandle:
        """Frame a (k, n) block into its bucket and dispatch the evaluation
        asynchronously.  ``mal_u``: per-lane malicious draw in [0.2, 0.8],
        NaN for honest lanes (None == all honest).  ``lane_tags``: optional
        (k,) per-lane submitter ids for coalesced multi-search buckets —
        carried on the handle so every in-flight bucket is attributable
        lane by lane (observability/debugging; demux itself is positional,
        by lane offset).  The device computation never sees them (lanes
        are row-independent, which is exactly why coalescing is safe).
        Returns immediately; pass the handle to ``collect`` for the
        values."""
        k, n = pts.shape
        kp = bucket_size(k, self.min_bucket)
        top = self._tops.get(n)
        if top is not None and kp > top:
            raise BucketTooWide(k, kp, top)
        buf, slot = self._staging(kp, n)
        self._submit_seq += 1
        self._slot_owner[kp][slot] = self._submit_seq
        buf[:k, :n] = pts
        buf[:k, n] = np.nan if mal_u is None else mal_u
        if kp != k:
            buf[k:, :n] = buf[k - 1, :n]
            buf[k:, n] = PAD_MARK
        self._warmed.add((n, kp))    # a lazy compile still warms the cell
        ys = self._eval(buf)
        # the read-back is requested now, queued behind the bucket on the
        # device, so a deferred collect finds the values already on host
        ys.copy_to_host_async()
        return EvalHandle(ys, k, kp, slot, self._submit_seq,
                          None if lane_tags is None
                          else np.asarray(lane_tags))

    @spanned("backend.collect")
    def collect(self, handle: EvalHandle) -> np.ndarray:
        """Materialize a submitted bucket (blocks until the host copy that
        ``submit`` started has landed), free its staging slot, and strip
        the pad lanes.  The slot is freed only if this handle still OWNS
        it — a double collect, or one stale across a ring reallocation,
        must not clear the flag guarding a newer in-flight submission."""
        owners = self._slot_owner.get(handle.kp)
        if owners is not None and owners[handle.slot] == handle.seq:
            owners[handle.slot] = None
        # the wait is the one blocking read-back, of a copy submit has
        # already started: a separate block_until_ready() ahead of it
        # would only add a round trip per bucket
        with span("backend.wait"):
            ys = np.asarray(handle.ys, np.float64)
        return ys[:handle.k]

    def __call__(self, pts: np.ndarray,
                 mal_u: Optional[np.ndarray] = None) -> np.ndarray:
        return self.collect(self.submit(pts, mal_u))


class InProcessEvalBackend(EvalBackend):
    """Default backend: the bucket is one ``f_batch`` call on the local
    device, inside the shared jitted finalization.

    f_batch: (kp, n) -> (kp,) fitness, jit-friendly (it is traced).
    ``min_bucket`` is validated directly as a power of two — it is NOT
    rounded through ``bucket_size``, whose job is sizing blocks, and the
    default floor lives in one place (``DEFAULT_MIN_BUCKET``).  Pass
    ``n_dims`` + ``max_bucket`` to warm the bucket ladder at construction
    (zero compiles afterwards).
    """

    def __init__(self, f_batch: Callable,
                 min_bucket: int = DEFAULT_MIN_BUCKET, *,
                 n_dims: Optional[int] = None,
                 max_bucket: Optional[int] = None):
        self.f_batch = f_batch
        super().__init__(min_bucket)
        if n_dims is not None and max_bucket is not None:
            self.warm(n_dims, max_bucket)

    def _raw_eval(self, pts):
        return self.f_batch(pts)
