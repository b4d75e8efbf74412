"""First-class data planes: how element batches reach sampler state.

The paper's composability makes the *state* of a sampler a pure pytree and
its transitions pure functions; a **data plane** is the policy for moving a
host-side stream of turnstile microbatches into that state.  Every plane
shares one host buffer discipline -- sparse signed ``(keys, values)``
microbatches accumulate as numpy arrays (zero device work) until a
``FlushPolicy`` fires -- and they differ only in the dispatch step:

  ``DensePlane``   vmapped registry-spec update (the pure-jnp reference
                   plane; ``batched_ops(spec).update`` on the concatenated
                   batch).
  ``SparsePlane``  the batched Pallas scatter path: ``ingest_sparse``
                   routes every sketch-backed sampler through ONE
                   ``countsketch_scatter_batched`` pallas_call (the
                   sampler-name registry below), falling back to the
                   vmapped update for samplers with no sketch.  Dispatch
                   happens inline at the flush boundary (synchronous: the
                   caller observes errors at the flush site).
  ``AsyncPlane``   double-buffered ingest: flush batches are handed to a
                   single worker thread which dispatches and MATERIALIZES
                   them (one batch in flight while the producer
                   accumulates the next; a bounded job queue gives
                   backpressure at depth 2).  Dispatch boundaries are
                   decided by the FlushPolicy on the producer side, so
                   they are timing-independent: under the same policy and
                   microbatch stream the async plane performs the exact
                   same dispatch sequence as ``SparsePlane`` and its
                   drained state/samples are BIT-IDENTICAL.  ``drain()``
                   waits for in-flight work and flushes the tail, so any
                   read/merge/checkpoint sees a deterministic state.

  ``PipelinePlane``  per-shard + collapse: the flushed batch is hash-
                   partitioned per KEY across S sub-planes (disjoint
                   sub-streams, identical seeds), and every state read
                   collapses the shard states through the sampler's merge
                   -- the paper's composability as a data plane.  Feeds
                   either from plain ``ingest`` (self-partitioning) or
                   pre-partitioned per-shard via ``ingest_shard`` (the
                   ``repro.data.ingest_pipeline`` producer fast path).
                   Equivalence to the single-plane path is KS-level, not
                   bitwise (fp reduction order and candidate refresh order
                   differ across the merge tree).

``FlushPolicy`` is the pluggable flush threshold: element count
(``max_elems``), byte budget (``max_bytes``), and/or wall-clock interval
(``max_interval``; note the interval trigger is inherently
timing-DEPENDENT and therefore trades away the bitwise-reproducibility of
the element/byte triggers).  On the synchronous planes the interval is
evaluated at ingest time; ``AsyncPlane`` additionally arms a timer so an
idle producer's tail publishes within the age bound on its own.

Planes are registered by name (``register_plane`` / ``make_plane`` /
``available_planes``) so the engine, the serving launcher (``serve
--plane``), the conformance harness (``repro.validate.empirics``
parametrizes its trial runners over this registry), and the benchmarks all
select planes without naming classes.  ``"ingest"`` is kept as an alias of
``"sparse"`` (the pre-plane name of the scatter path in the conformance
grid).
"""
from __future__ import annotations

import atexit
import functools
import queue
import threading
import time
import weakref
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import obs
from repro.core import countsketch, hashing, tv_sampler, worp
from repro.core import sampler as core_sampler
from repro.core import transforms
from repro.core.sampler import SamplerSpec
from repro.distributed import codecs as wire_codecs
from repro.distributed import sharding as shd
from repro.engine.engine import _refresh_candidates, batched_ops
from repro.kernels import ops, tiling
from repro.launch.mesh import make_mesh_auto


# ---------------------------------------------------------------------------
# sparse kernel paths by sampler name (mirrors the core sampler registry):
# a new sketch-backed sampler opts into the scatter-kernel ingest plane with
# ``@register_sparse_path("myname")`` (uniform signature
# ``fn(state, keys, values, p, scheme, *, interpret, use_kernel)``) instead
# of editing the engine; unregistered samplers fall back to the vmapped
# spec update in ``ingest_sparse``.  ``register_frozen_sketch`` likewise
# exposes the pass-II frozen CountSketch for the batched-priority path.
# ---------------------------------------------------------------------------

_SPARSE_PATHS: dict = {}
_SCATTER_ROWS: dict = {}
_FROZEN_SKETCH: dict = {}


def register_sparse_path(name: str, scatter_rows=lambda B, cfg: (B,)):
    """``scatter_rows(B, cfg)`` gives the stream count of each scatter
    kernel call the path makes for a (B, n) batch (``scatter_slots``)."""
    def deco(fn):
        _SPARSE_PATHS[name] = fn
        _SCATTER_ROWS[name] = scatter_rows
        return fn

    return deco


def scatter_slots(spec: SamplerSpec, B: int, n: int) -> int:
    """Slots, padding included, that the scatter kernel sweeps when
    ``spec``'s sparse path ingests one (B, n) batch: the ``slots`` count of
    a ``plane.dispatch`` span.  0 where the path has no scatter."""
    rows = _SCATTER_ROWS.get(spec.name)
    if rows is None or n == 0:
        return 0
    total = 0
    for b in rows(B, spec.cfg):
        (_, b_pad), (_, n_pad) = tiling.scatter_tiles(b, n)
        total += b_pad * n_pad
    return total


def register_frozen_sketch(name: str):
    def deco(fn):
        _FROZEN_SKETCH[name] = fn
        return fn

    return deco


register_frozen_sketch("onepass")(lambda st: st.sketch)
register_frozen_sketch("twopass")(lambda st: st.pass1.sketch)


def frozen_sketch_getter(name: str):
    """The registered frozen pass-I sketch accessor for ``name`` (None when
    the sampler registered none)."""
    return _FROZEN_SKETCH.get(name)


@register_sparse_path("onepass")
@functools.partial(jax.jit, static_argnames=("p", "scheme", "interpret",
                                             "use_kernel"))
def onepass_update_sparse(st: worp.OnePassState, keys: jnp.ndarray,
                          values: jnp.ndarray, p: float,
                          scheme: str = transforms.PPSWOR,
                          interpret: Optional[bool] = None,
                          use_kernel: Optional[bool] = None):
    """Turnstile fast path: B sparse signed batches through ONE scatter
    pallas_call (``kernels.countsketch_scatter_batched``).

    ``(keys[b, i], values[b, i])`` is an arbitrary signed update of stream b
    (negative values are deletions); ``keys == -1`` slots are padding.  The
    candidate refresh then queries (C + n) per-stream keys through the
    batched estimate chokepoint.  Semantically identical to the vmapped jnp
    ``onepass_update`` with the same batch (padding slots carry value 0
    there), up to fp reduction order.
    """
    keys = jnp.asarray(keys, jnp.int32)
    delta = ops.sketch_sparse_batch(
        keys, values.astype(jnp.float32), st.sketch.table.shape[1],
        st.sketch.table.shape[2], st.sketch.seed, p=p, scheme=scheme,
        transform_seeds=st.seed_transform, interpret=interpret)
    sk = countsketch.CountSketch(table=st.sketch.table + delta,
                                 seed=st.sketch.seed)
    cand = _refresh_candidates(sk, st.cand_keys, keys,
                               use_kernel=use_kernel, interpret=interpret)
    return worp.OnePassState(sketch=sk, cand_keys=cand,
                             seed_transform=st.seed_transform)


@jax.jit
def twopass_update_from_priorities_batched(st2, keys, values, prio):
    """vmapped ``worp.twopass_update_from_priorities``: one compiled call
    updates all B pass-II buffers from precomputed (B, n) priorities."""
    return jax.vmap(worp.twopass_update_from_priorities)(st2, keys, values,
                                                         prio)


@register_sparse_path("twopass")
@functools.partial(jax.jit, static_argnames=("p", "scheme", "interpret",
                                             "use_kernel"))
def twopass_run_update_sparse(st, keys: jnp.ndarray, values: jnp.ndarray,
                              p: float, scheme: str = transforms.PPSWOR,
                              interpret: Optional[bool] = None,
                              use_kernel: Optional[bool] = None):
    """Sparse kernel path for the streaming "twopass" sampler state
    (``core.sampler.TwoPassRunState``): pass I goes through the scatter
    kernel; the pass-II buffer gets its online priorities from the batched
    query chokepoint and updates via the vmapped from-priorities seam."""
    keys = jnp.asarray(keys, jnp.int32)
    p1 = onepass_update_sparse(st.pass1, keys, values, p, scheme,
                               interpret=interpret, use_kernel=use_kernel)
    prio = ops.estimate_batched(p1.sketch.table, keys, p1.sketch.seed,
                                use_kernel=use_kernel, interpret=interpret)
    p2 = twopass_update_from_priorities_batched(st.pass2, keys, values, prio)
    return core_sampler.TwoPassRunState(pass1=p1, pass2=p2)


@register_sparse_path(
    "tv", scatter_rows=lambda B, cfg: (B * cfg.num_samplers, B))
@functools.partial(jax.jit, static_argnames=("p", "scheme", "interpret",
                                             "use_kernel"))
def tv_update_sparse(st, keys: jnp.ndarray, values: jnp.ndarray, p: float,
                     scheme: str = transforms.PPSWOR,
                     interpret: Optional[bool] = None,
                     use_kernel: Optional[bool] = None):
    """Sparse kernel path for the batched TV cascade: the B*r cascade
    sketches (each with its own hash + transform seed) flatten into ONE
    scatter pallas_call, their candidate refresh into one batched query
    dispatch, and the rHH sketch rides the one-pass sparse path."""
    keys = jnp.asarray(keys, jnp.int32)
    values = values.astype(jnp.float32)
    B, r = st.transform_seeds.shape
    rows, width = st.sketches.table.shape[-2:]
    C = st.cand_keys.shape[-1]

    flat_seeds = st.sketches.seed.reshape(B * r)
    flat_tseeds = st.transform_seeds.reshape(B * r)
    keys_f = jnp.repeat(keys, r, axis=0)      # (B*r, n): stream b feeds all
    vals_f = jnp.repeat(values, r, axis=0)    # r of its cascade samplers
    delta = ops.sketch_sparse_batch(
        keys_f, vals_f, rows, width, flat_seeds, p=p, scheme=scheme,
        transform_seeds=flat_tseeds, interpret=interpret)
    tables = st.sketches.table.reshape(B * r, rows, width) + delta
    flat_sk = countsketch.CountSketch(table=tables, seed=flat_seeds)
    cand = _refresh_candidates(flat_sk, st.cand_keys.reshape(B * r, C),
                               keys_f, use_kernel=use_kernel,
                               interpret=interpret)
    return tv_sampler.TVSamplerState(
        sketches=countsketch.CountSketch(
            table=tables.reshape(B, r, rows, width), seed=st.sketches.seed),
        cand_keys=cand.reshape(B, r, C),
        transform_seeds=st.transform_seeds,
        rhh=onepass_update_sparse(st.rhh, keys, values, p, scheme,
                                  interpret=interpret,
                                  use_kernel=use_kernel))


def ingest_sparse(spec: SamplerSpec, state, keys, values,
                  interpret: Optional[bool] = None,
                  use_kernel: Optional[bool] = None):
    """Route one batched sparse signed update through the sampler's kernel
    path: every sketch-backed sampler (onepass, twopass pass-I/II, tv)
    dispatches the batched Pallas scatter kernel via ``_SPARSE_PATHS``;
    unregistered samplers (perfect: no sketch) fall back to the vmapped
    spec update with identical semantics."""
    path = _SPARSE_PATHS.get(spec.name)
    if path is None:
        return batched_ops(spec).update(state, keys, values)
    return path(state, keys, values, spec.cfg.p, spec.cfg.scheme,
                interpret=interpret, use_kernel=use_kernel)


# ---------------------------------------------------------------------------
# flush policy
# ---------------------------------------------------------------------------

class FlushPolicy(NamedTuple):
    """When does the host buffer dispatch?  Any trigger that is not None
    fires the flush once reached; the element and byte triggers depend only
    on the ingested data (timing-independent, hence bitwise-reproducible
    dispatch boundaries), while ``max_interval`` (seconds since the oldest
    pending microbatch) is wall-clock and trades that reproducibility for
    age-bounded batches.  On synchronous planes triggers are evaluated AT
    INGEST TIME -- an interval-aged buffer dispatches on the next
    ``ingest`` (or any read, which always drains).  ``AsyncPlane``
    additionally backs ``max_interval`` with a timer, so an idle
    producer's tail publishes within the age bound on its own."""

    max_elems: Optional[int] = 4096   # per-stream pending element count
    max_bytes: Optional[int] = None   # pending host-buffer bytes (keys+vals)
    max_interval: Optional[float] = None  # seconds since first pending batch
    # max_interval on synchronous planes is evaluated at ingest time (no
    # timer thread: an interval-aged buffer dispatches on the next ingest
    # or read); AsyncPlane arms a timer per buffered tail, so its age bound
    # holds even for a producer that goes fully idle.

    def should_flush(self, elems: int, nbytes: int, age: float) -> bool:
        if self.max_elems is not None and elems >= self.max_elems:
            return True
        if self.max_bytes is not None and nbytes >= self.max_bytes:
            return True
        if self.max_interval is not None and age >= self.max_interval:
            return True
        return False


# ---------------------------------------------------------------------------
# plane registry
# ---------------------------------------------------------------------------

_PLANES: dict = {}


def register_plane(name: str, *aliases: str):
    """Register a DataPlane subclass under ``name`` (+ optional aliases)."""

    def deco(cls):
        cls.name = name
        for key in (name, *aliases):
            _PLANES[key] = cls
        return cls

    return deco


def available_planes() -> tuple:
    """Canonical plane names (aliases excluded), registration order."""
    seen = []
    for cls in _PLANES.values():
        if cls.name not in seen:
            seen.append(cls.name)
    return tuple(seen)


def make_plane(name: str, spec: SamplerSpec, state,
               policy: Optional[FlushPolicy] = None,
               interpret: Optional[bool] = None,
               use_kernel: Optional[bool] = None,
               **plane_opts) -> "DataPlane":
    """Instantiate a registered plane over ``spec`` and its batched state.

    ``plane_opts`` are plane-specific keywords forwarded to the class
    (e.g. ``shards=`` / ``subplane=`` for the ``"pipeline"`` plane); planes
    that take none reject extras loudly."""
    cls = _PLANES.get(name)
    if cls is None:
        raise ValueError(f"unknown data plane {name!r}; registered planes: "
                         f"{sorted(set(_PLANES))}")
    return cls(spec, state, policy=policy, interpret=interpret,
               use_kernel=use_kernel, **plane_opts)


# ---------------------------------------------------------------------------
# the planes
# ---------------------------------------------------------------------------

class DataPlane:
    """Shared host-buffer discipline; subclasses define ``_dispatch``.

    The plane OWNS the batched sampler state while ingest is in progress:
    ``state`` settles any in-flight work (async) before returning but does
    NOT flush the host buffer -- ``drain()`` does both, and is what every
    read/merge/checkpoint boundary must call (``SketchEngine`` does).
    """

    name = "abstract"

    def __init__(self, spec: SamplerSpec, state,
                 policy: Optional[FlushPolicy] = None,
                 interpret: Optional[bool] = None,
                 use_kernel: Optional[bool] = None,
                 codec: str = "none"):
        self.spec = spec
        self.policy = policy if policy is not None else FlushPolicy()
        # the wire codec this plane's state crosses boundaries under.  It
        # also drives byte accounting: ``FlushPolicy.max_bytes`` budgets
        # what would actually go on the wire (encoded payload size), not
        # raw fp32 bytes -- with codec ``none`` the two are identical.
        self.codec = wire_codecs.get_codec(codec)
        self._state = state
        self._interpret = interpret
        self._use_kernel = use_kernel
        self._buf_keys: list = []
        self._buf_vals: list = []
        self._buf_elems = 0
        self._buf_bytes = 0
        self._buf_t0: Optional[float] = None

    # -- dispatch hook ------------------------------------------------------
    def _dispatch(self, state, keys, values, interpret, use_kernel):
        raise NotImplementedError

    def _scatter_slots(self, B: int, n: int) -> int:
        """Scatter-kernel slots one (B, n) ``_dispatch`` sweeps."""
        return 0

    def _stage(self, keys, vals):
        """The host batch on the device the dispatch reads it from."""
        return jnp.asarray(keys), jnp.asarray(vals)

    def _stage_and_dispatch(self, state, keys, vals, interpret, use_kernel,
                            parent=None):
        """Hand one flushed host batch to the device (``plane.stage``) and
        dispatch it (``plane.dispatch``, counting the scatter's slots);
        returns the new state, still in flight."""
        with obs.span("plane.stage", parent):
            dkeys, dvals = self._stage(keys, vals)
        with obs.span("plane.dispatch", parent,
                      slots=self._scatter_slots(*keys.shape)):
            return self._dispatch(state, dkeys, dvals, interpret, use_kernel)

    # -- host buffer --------------------------------------------------------
    def ingest(self, keys, values):
        """Buffer one sparse signed (B, n) microbatch; dispatch when the
        flush policy fires.  Shape/stream-count validation is the caller's
        (the engine's) job -- planes only require keys.shape == values.shape."""
        keys = np.asarray(keys, np.int32)
        values = np.asarray(values, np.float32)
        self._buf_keys.append(keys)
        self._buf_vals.append(values)
        self._buf_elems += keys.shape[1]
        self._buf_bytes += (self.codec.payload_nbytes(keys)
                            + self.codec.payload_nbytes(values))
        if self._buf_t0 is None:
            self._buf_t0 = time.monotonic()
        if self.policy.should_flush(self._buf_elems, self._buf_bytes,
                                    time.monotonic() - self._buf_t0):
            self._flush_buffer()
        return self

    @property
    def pending(self) -> int:
        """Per-stream element count buffered host-side (submitted/in-flight
        async batches are no longer pending -- ``drain`` settles those)."""
        return self._buf_elems

    @property
    def pending_bytes(self) -> int:
        return self._buf_bytes

    def _concat_buffer(self):
        keys = np.concatenate(self._buf_keys, axis=1)
        vals = np.concatenate(self._buf_vals, axis=1)
        return keys, vals

    def _clear_buffer(self):
        self._buf_keys, self._buf_vals = [], []
        self._buf_elems = self._buf_bytes = 0
        self._buf_t0 = None

    def _flush_buffer(self, interpret=None, use_kernel=None):
        """Synchronous submit: dispatch the whole buffer inline.  The buffer
        clears only after a successful dispatch -- a failed flush (OOM,
        trace error) leaves the microbatches intact for retry instead of
        silently dropping them."""
        keys, vals = self._concat_buffer()
        self._state = self._stage_and_dispatch(
            self._state, keys, vals,
            self._interpret if interpret is None else interpret,
            self._use_kernel if use_kernel is None else use_kernel)
        self._clear_buffer()

    # -- drain / state ------------------------------------------------------
    def drain(self, interpret=None, use_kernel=None):
        """Make every ingested element visible in ``state``: flush the host
        buffer and settle any in-flight dispatches.  Deterministic: after
        drain, the state is a pure function of the ingested stream and the
        flush-policy boundaries."""
        if self._buf_keys:
            self._flush_buffer(interpret=interpret, use_kernel=use_kernel)
        self._settle()
        return self

    def _settle(self):
        """Wait for in-flight work (no-op for synchronous planes)."""

    @property
    def state(self):
        """The settled device state (in-flight work completed; the host
        buffer is NOT flushed -- pending microbatches stay pending)."""
        self._settle()
        return self._state

    def set_state(self, st):
        """Replace the device state (checkpoint restore, merge results).
        In-flight work settles first so nothing is silently dropped; a
        pending host buffer is preserved and will apply on top."""
        self._settle()
        self._state = st

    def close(self):
        """Release plane resources (worker threads); no-op for synchronous
        planes, and optional everywhere (GC/atexit cover the async one)."""


@register_plane("dense")
class DensePlane(DataPlane):
    """Pure-jnp reference plane: the vmapped registry-spec update on the
    concatenated buffer (the conformance harness's reference dispatch)."""

    def _dispatch(self, state, keys, values, interpret, use_kernel):
        del interpret, use_kernel  # the vmapped spec update has no kernel
        # honor the ingest padding contract (keys == -1 contribute nothing):
        # the scatter kernel masks padding itself, but the plain spec update
        # would hash key -1 into a real bucket -- zeroing the value is
        # enough because every randomizer is multiplicative in the value,
        # so a 0 update is a no-op on the linear sketch, and the candidate
        # refresh already masks -1 slots
        values = jnp.where(keys == jnp.int32(-1), 0.0, values)
        return batched_ops(self.spec).update(state, keys, values)


@register_plane("sparse", "ingest")
class SparsePlane(DataPlane):
    """Synchronous scatter-kernel plane: one batched Pallas scatter
    pallas_call per flush for every sketch-backed sampler (``ingest_sparse``;
    vmapped fallback for samplers with no sketch)."""

    def _dispatch(self, state, keys, values, interpret, use_kernel):
        return ingest_sparse(self.spec, state, keys, values,
                             interpret=interpret, use_kernel=use_kernel)

    def _scatter_slots(self, B: int, n: int) -> int:
        return scatter_slots(self.spec, B, n)


# Async planes whose worker thread is running: shut them down at interpreter
# exit (a daemon thread still inside a jax computation during runtime
# teardown can abort the process), and individually when a plane is GC'd.
_LIVE_ASYNC: "weakref.WeakSet" = weakref.WeakSet()


@atexit.register
def _shutdown_live_async_planes():
    for plane in list(_LIVE_ASYNC):
        try:
            plane.close()
        except Exception:
            pass


def _shutdown_worker(jobs: queue.Queue):
    """GC finalizer for AsyncPlane: ask the worker to exit (best-effort --
    a full queue means the worker is alive and will drain it, then see the
    sentinel on a later get; daemon threads never block interpreter exit)."""
    try:
        jobs.put_nowait(None)
    except queue.Full:
        pass


@register_plane("async")
class AsyncPlane(SparsePlane):
    """Double-buffered asynchronous scatter plane.

    Flush batches are handed to ONE worker thread (FIFO) which dispatches
    and materializes them (``jax.block_until_ready``), so batch N executes
    while the producer accumulates batch N+1 -- the double buffer.  The job
    queue is bounded (one in flight + one queued): a producer that runs
    more than two batches ahead blocks, which bounds host memory and gives
    natural backpressure.

    Determinism: dispatch boundaries are computed on the PRODUCER side by
    the FlushPolicy, never by worker timing, so the dispatch sequence --
    and therefore the drained state and samples, bit for bit -- equals the
    synchronous ``SparsePlane`` under the same policy and microbatch
    stream.  Timing only moves WHERE the producer waits.

    Errors: a failed dispatch parks the failed batch and every batch
    queued behind it (order preserved); the next ``drain()``/flush
    re-raises the error with those batches re-queued at the FRONT of the
    host buffer, so a retry drain replays them in the original order.

    Interval trigger: with ``FlushPolicy.max_interval`` set, a one-shot
    timer is armed whenever the host buffer becomes non-empty, so a
    producer that goes IDLE still has its tail submitted within the age
    bound -- no drain or read required.  A timer flush submits to the same
    worker FIFO as an ingest-time flush, so ordering is preserved; the
    boundary itself is wall-clock (the documented ``max_interval``
    trade-off).  A dispatch error raised by a timer flush is parked like
    any worker error and surfaces at the next drain/flush.
    """

    _QUEUE_DEPTH = 1  # + the batch the worker holds = double buffering

    def __init__(self, spec, state, policy=None, interpret=None,
                 use_kernel=None, codec: str = "none"):
        super().__init__(spec, state, policy=policy, interpret=interpret,
                         use_kernel=use_kernel, codec=codec)
        self._jobs: queue.Queue = queue.Queue(maxsize=self._QUEUE_DEPTH)
        self._lock = threading.Lock()
        self._error: Optional[BaseException] = None
        self._parked: list = []     # batches skipped after an error, in order
        self._worker: Optional[threading.Thread] = None
        # host-buffer guard: the interval timer fires on its own thread, so
        # buffer mutation (ingest / flush / error requeue) is serialized.
        # RLock: flush paths that already hold it re-enter via
        # _raise_pending_error's requeue.
        self._buf_lock = threading.RLock()
        self._timer: Optional[threading.Timer] = None
        # close() guard for the interval timer: Timer.cancel() cannot stop a
        # callback that already started and is blocked on _buf_lock, so a
        # timer racing close() could otherwise resurrect the worker after
        # shutdown (or enqueue a batch behind the exit sentinel, silently
        # dropping it).  _timer_fire checks the flag under _buf_lock; an
        # explicit later ingest() clears it (planes stay reusable after a
        # clean close).
        self._closed = False

    def _ensure_worker(self):
        if self._worker is None:
            self._worker = threading.Thread(
                target=self._run, name="repro-async-plane", daemon=True)
            self._worker.start()
            _LIVE_ASYNC.add(self)
            weakref.finalize(self, _shutdown_worker, self._jobs)

    def _run(self):
        while True:
            job = self._jobs.get()
            if job is None:
                self._jobs.task_done()
                return
            keys, vals, interpret, use_kernel, parent = job
            try:
                with self._lock:
                    if self._error is not None:
                        # preserve order behind the failed batch: park, so a
                        # retry drain replays failed + parked in sequence
                        self._parked.append((keys, vals))
                        continue
                st = self._stage_and_dispatch(self._state, keys, vals,
                                              interpret, use_kernel, parent)
                jax.block_until_ready(st)  # materialize: bounds in-flight
                self._state = st
            except Exception as e:  # surfaced at the next drain/flush
                with self._lock:
                    self._error = e
                    self._parked.append((keys, vals))
            finally:
                self._jobs.task_done()

    # -- interval timer ------------------------------------------------------
    def ingest(self, keys, values):
        with self._buf_lock:
            self._closed = False  # explicit reuse after close() reopens
            super().ingest(keys, values)
            if (self.policy.max_interval is not None and self._buf_keys
                    and self._timer is None):
                self._arm_timer(self.policy.max_interval)
        return self

    def drain(self, interpret=None, use_kernel=None):
        with self._buf_lock:
            self._cancel_timer()
            if self._buf_keys:
                self._flush_buffer(interpret=interpret,
                                   use_kernel=use_kernel)
        self._settle()
        return self

    def _arm_timer(self, delay: float):
        t = threading.Timer(max(delay, 0.0), self._timer_fire)
        t.daemon = True
        self._timer = t
        t.start()

    def _cancel_timer(self):
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    def _timer_fire(self):
        with self._buf_lock:
            self._timer = None
            if self._closed:
                # lost the race with close(): the cancel() missed us because
                # we were already running, but dispatching now would push
                # work into a shut-down plane -- the tail stays buffered for
                # an explicit drain/reuse instead
                return
            if not self._buf_keys or self.policy.max_interval is None:
                return
            age = time.monotonic() - self._buf_t0
            if age < self.policy.max_interval:
                # an ingest restarted the age clock meanwhile: re-arm for
                # the remaining window instead of flushing early
                self._arm_timer(self.policy.max_interval - age)
                return
            try:
                # submit WITHOUT the pending-error check: a timer thread
                # cannot surface an exception to the caller, so an earlier
                # worker error stays parked until the next drain/flush
                self._submit_buffer(self._interpret, self._use_kernel)
            except Exception as e:
                with self._lock:
                    if self._error is None:
                        self._error = e

    # -- flush / settle ------------------------------------------------------
    def _submit_buffer(self, interpret, use_kernel):
        self._ensure_worker()
        keys, vals = self._concat_buffer()
        self._clear_buffer()
        self._cancel_timer()
        # the worker's spans take the span that submitted the batch as
        # parent (the ``engine.ingest`` or ``engine.flush`` that flushed it)
        self._jobs.put((keys, vals, interpret, use_kernel, obs.current()))

    def _flush_buffer(self, interpret=None, use_kernel=None):
        self._raise_pending_error()
        with self._buf_lock:
            if not self._buf_keys:
                return  # a timer flush beat this caller to the buffer
            self._submit_buffer(
                self._interpret if interpret is None else interpret,
                self._use_kernel if use_kernel is None else use_kernel)

    def _settle(self):
        if self._worker is not None:
            self._jobs.join()
        self._raise_pending_error()

    def _raise_pending_error(self):
        with self._lock:
            if self._error is None:
                return
        # settle the job queue BEFORE clearing the error: batches still
        # queued behind the failure must park (the worker skips dispatch
        # while the error is set) or they would dispatch ahead of the
        # re-queued failed batch and break the order-preserving retry
        self._jobs.join()
        with self._lock:
            err, self._error = self._error, None
            parked, self._parked = self._parked, []
        if err is None:
            return
        # re-queue the failed + parked batches ahead of anything currently
        # buffered, preserving the original dispatch order for the retry
        with self._buf_lock:
            for keys, vals in reversed(parked):
                self._buf_keys.insert(0, keys)
                self._buf_vals.insert(0, vals)
                self._buf_elems += keys.shape[1]
                self._buf_bytes += (self.codec.payload_nbytes(keys)
                                    + self.codec.payload_nbytes(vals))
            if self._buf_t0 is None and self._buf_keys:
                self._buf_t0 = time.monotonic()
            pending = self._buf_elems
        raise RuntimeError(
            f"async ingest dispatch failed; the failed microbatches were "
            f"re-queued ({pending} per-stream elements pending) -- "
            f"drain() again to retry") from err

    def close(self):
        """Stop the worker thread (tests / explicit teardown; GC and daemon
        threading make this optional).  Blocks until the worker drains its
        in-flight dispatch and exits; if it fails to stop, the plane
        refuses further use rather than risk TWO workers mutating the
        state concurrently (which would silently break bitwise parity)."""
        with self._buf_lock:
            self._cancel_timer()
            self._closed = True  # fences any timer already past cancel()
        if self._worker is None:
            return
        self._jobs.put(None)
        self._worker.join(timeout=60.0)
        if self._worker.is_alive():
            raise RuntimeError(
                "async plane worker did not stop within 60s (dispatch "
                "stuck?); the plane cannot be reused safely")
        self._worker = None


# ---------------------------------------------------------------------------
# per-shard + collapse plane
# ---------------------------------------------------------------------------

def _compact_shard_rows(keys: np.ndarray, vals: np.ndarray,
                        mask: np.ndarray) -> tuple:
    """Per-row compaction of the masked slots of a (B, n) batch: selected
    entries slide left in order, rows pad with key -1 / value 0, and the
    column count quantizes to a lane multiple so repeated flushes of
    similar sizes reuse one kernel trace.  Returns (keys', vals') of shape
    (B, m_pad)."""
    counts = mask.sum(axis=1)
    m = int(counts.max()) if counts.size else 0
    if m == 0:
        return (np.empty((keys.shape[0], 0), np.int32),
                np.empty((keys.shape[0], 0), np.float32))
    m = ops.pad_to(m, ops.LANE)
    # stable argsort of ~mask floats selected slots to the front, in order
    order = np.argsort(~mask, axis=1, kind="stable")
    take = order[:, :min(m, keys.shape[1])]
    gk = np.take_along_axis(keys, take, axis=1)
    gv = np.take_along_axis(vals, take, axis=1)
    if gk.shape[1] < m:
        gk = np.pad(gk, ((0, 0), (0, m - gk.shape[1])), constant_values=-1)
        gv = np.pad(gv, ((0, 0), (0, m - gv.shape[1])))
    live = np.arange(m)[None, :] < counts[:, None]
    return (np.where(live, gk, np.int32(-1)).astype(np.int32),
            np.where(live, gv, np.float32(0.0)).astype(np.float32))


def partition_by_key(keys: np.ndarray, vals: np.ndarray,
                     shards: int) -> list:
    """Hash-partition one (B, n) microbatch into ``shards`` compacted
    per-shard blocks ``[(keys_s, vals_s), ...]`` (``hashing.shard_of_keys``
    per key; ``keys == -1`` padding slots belong to no shard).  Sticky by
    key hash and shard-count-independent, so a key's deletions always land
    on the shard that saw its insertions.

    This is THE routing function: ``PipelinePlane`` uses it at every flush
    boundary and the multi-process fleet router
    (``repro.distributed.fleet``) uses the very same code path, which is
    what makes the fleet bitwise-reproducible against the in-process
    ``"fleet"`` plane -- identical partition, identical compacted block
    shapes, identical per-shard dispatch sequences.
    """
    return [_compact_shard_rows(keys, vals, m)
            for m in _shard_masks(keys, shards)]


def _shard_masks(keys: np.ndarray, shards: int) -> list:
    """Per shard, the (B, n) mask of the live slots routed to it."""
    shard_ids = hashing.shard_of_keys(keys, shards)
    live = keys != np.int32(-1)
    return [(shard_ids == s) & live for s in range(shards)]


def stack_by_key(keys: np.ndarray, vals: np.ndarray, shards: int) -> tuple:
    """``partition_by_key``'s blocks stacked into one (shards * B, m) block
    of one common width: rows ``s * B .. (s + 1) * B`` are shard ``s``'s
    block, padded with key -1 / value 0 to the widest shard's lane
    multiple, so one compiled program serves every shard of a flush."""
    return _compact_shard_rows(np.tile(keys, (shards, 1)),
                               np.tile(vals, (shards, 1)),
                               np.concatenate(_shard_masks(keys, shards)))


@functools.lru_cache(maxsize=None)
def _device_programs(spec: SamplerSpec, mesh, interpret, use_kernel):
    """The device path's sharding and programs over a 1-D ``shard`` mesh,
    where every leaf's leading axis stacks the shards' streams: one SPMD
    sparse update (each device ingests its own shard's rows) and the
    collapse (the collective ``butterfly_allmerge``, after which every
    device holds the merged state).  Cached, so that every engine over
    ``spec`` and ``mesh`` reuses one set of compiled programs."""
    shard = PartitionSpec("shard")
    merge = batched_ops(spec).merge

    def update(st, keys, vals):
        return ingest_sparse(spec, st, keys, vals, interpret=interpret,
                             use_kernel=use_kernel)

    def collapse(st):
        return shd.butterfly_allmerge(st, "shard", merge,
                                      axis_size=mesh.shape["shard"])

    smap = functools.partial(jax.shard_map, mesh=mesh, out_specs=shard,
                             check_vma=False)
    return (NamedSharding(mesh, shard),
            jax.jit(smap(update, in_specs=(shard, shard, shard))),
            jax.jit(smap(collapse, in_specs=(shard,))))


def _first_shard(x: jax.Array) -> jax.Array:
    """The block of a shard-stacked array that the mesh's first device
    holds, as an ordinary one-device array (no copy)."""
    return min(x.addressable_shards, key=lambda s: s.index[0].start or 0).data


@register_plane("pipeline")
class PipelinePlane(DataPlane):
    """Per-shard + collapse plane: the sharded ingestion pipeline's dispatch
    policy as a first-class data plane.

    ``shards`` sub-planes (default 2 x the synchronous scatter plane) start
    from the SAME initial state -- identical seeds, empty tables/candidates,
    so the copies are merge-neutral -- and each flushed batch is partitioned
    per KEY (``hashing.shard_of_keys``: shard-count-independent, deletions
    follow their insertions) into disjoint sub-streams.  Every state read
    COLLAPSES the shard states through the sampler's batched merge -- the
    paper's composability (Sec. 1) exercised on every read, which is
    exactly what the conformance grid pins distributionally.

    Equivalence contract: KS-level against the dense/sparse single-plane
    paths, NOT bitwise -- fp summation order and candidate-refresh order
    differ across the merge tree (same reason the scatter kernel is
    allclose-not-bitwise against the vmapped update).

    Producer fast path: ``ingest_shard(s, keys, values)`` feeds sub-plane
    ``s`` directly with a PRE-partitioned block (the prefetching feeder's
    per-shard mode; safe from S producer threads as long as each shard has
    one producer).  With ``subplane="async"`` each shard gets its own
    double-buffered worker -- N planes dispatching concurrently, collapsed
    at read time.

    Device path: ``devices = shards > 1`` (``subplane="sparse"`` only) keeps
    shard ``s`` resident on device ``s`` instead of in a sub-plane.  The
    shard states are one pytree whose leading axis stacks the shards'
    streams, sharded over a 1-D ``shard`` mesh; each flush is routed by
    ``stack_by_key`` into one block of one common width, placed with one
    ``device_put`` and ingested by one SPMD program (one compile per width,
    not per shard).  A read collapses through the collective
    ``butterfly_allmerge`` in one program and returns the first device's
    merged copy as an ordinary one-device array.  The butterfly merges
    ((0+1)+(2+3)) where the host fold merges ((0+1)+2)+3: tables differ by
    fp32 summation order, candidate sets only by near-ties.  ``devices=1``
    (the default) is the sub-plane path above, unchanged.

    ``set_state`` routes the restored state to shard 0 and resets the other
    shards to the construction-time initial state; the restored state must
    be seed-compatible with it (the merge's seed check enforces this).
    """

    def __init__(self, spec, state, policy=None, interpret=None,
                 use_kernel=None, shards: int = 2, subplane: str = "sparse",
                 codec: str = "none", devices: int = 1):
        super().__init__(spec, state, policy=policy, interpret=interpret,
                         use_kernel=use_kernel, codec=codec)
        if shards < 1:
            raise ValueError(f"pipeline plane needs shards >= 1, got {shards}")
        if subplane == "pipeline":
            raise ValueError("pipeline sub-planes cannot nest")
        self.shards = int(shards)
        self.subplane = subplane
        self.devices = int(devices)
        self._initial = state    # merge-neutral reset state for set_state
        self._ops = batched_ops(spec)
        self._state_bytes = sum(int(x.nbytes)
                                for x in jax.tree_util.tree_leaves(state))
        self._merged = None      # collapse cache, invalidated by ingest
        if self.devices != 1:
            self._check_devices()
            self._subplanes = []
            mesh = make_mesh_auto((self.devices,), ("shard",),
                                  devices=jax.devices()[:self.devices])
            self._sharding, self._update, self._collapse = _device_programs(
                spec, mesh, interpret, use_kernel)
            self.set_state(state)
            return
        # sub-planes flush every forwarded batch: dispatch granularity is
        # decided HERE (the outer FlushPolicy / the feeder's block size).
        # They run in-process under codec "none": the wire boundary this
        # plane models is the COLLAPSE (each shard state crosses once,
        # encoded, before the merge -- see ``state``).
        self._subplanes = [
            make_plane(subplane, spec, state,
                       policy=FlushPolicy(max_elems=1),
                       interpret=interpret, use_kernel=use_kernel)
            for _ in range(self.shards)]

    def _check_devices(self):
        have = len(jax.devices())
        if self.devices < 1 or self.devices > have:
            raise ValueError(f"pipeline plane: devices={self.devices}, but "
                             f"JAX sees {have} device(s)")
        if self.devices != self.shards:
            raise ValueError(f"pipeline plane: devices={self.devices} needs "
                             f"shards={self.devices} (one shard per device), "
                             f"got shards={self.shards}")
        if self.subplane != "sparse":
            raise ValueError(f"pipeline plane: devices > 1 runs the sparse "
                             f"update on each device; subplane="
                             f"{self.subplane!r} has no device path")
        if self.codec.rel_step != 0.0:
            raise ValueError(f"pipeline plane: the collective collapse cannot "
                             f"apply lossy codec {self.codec.name!r}")

    # -- partitioned dispatch ------------------------------------------------
    def _flush_buffer(self, interpret=None, use_kernel=None):
        keys, vals = self._concat_buffer()
        if self.devices > 1:
            with obs.span("plane.route"):
                keys, vals = stack_by_key(keys, vals, self.shards)
            if keys.shape[1]:
                self._state = self._stage_and_dispatch(
                    self._state, keys, vals, interpret, use_kernel)
        else:
            with obs.span("plane.route"):
                parts = partition_by_key(keys, vals, self.shards)
            for sub, (k, v) in zip(self._subplanes, parts):
                if k.shape[1]:
                    sub.ingest(k, v)
        self._clear_buffer()
        self._merged = None

    def _stage(self, keys, vals):
        return jax.device_put((keys, vals), self._sharding)

    def _dispatch(self, state, keys, values, interpret, use_kernel):
        # the device programs carry the plane's own interpret/use_kernel
        del interpret, use_kernel
        return self._update(state, keys, values)

    def _scatter_slots(self, B: int, n: int) -> int:
        return self.shards * scatter_slots(self.spec, B // self.shards, n)

    def ingest_shard(self, shard: int, keys, values):
        """Feed one PRE-partitioned block straight to sub-plane ``shard``
        (every key must hash to ``shard``; -1 padding slots exempt).  This
        bypasses the outer buffer/policy -- the caller owns the dispatch
        granularity -- and is the only plane entry point that is safe to
        call from per-shard producer threads concurrently.  The device
        path has no sub-planes and raises: feed it through ``ingest``."""
        if self.devices > 1:
            raise ValueError("pipeline plane: ingest_shard has no device "
                             "path (devices > 1); use ingest")
        self._merged = None
        self._subplanes[shard].ingest(keys, values)
        return self

    # -- collapse ------------------------------------------------------------
    def _settle(self):
        for sub in self._subplanes:
            sub.drain()

    @property
    def state(self):
        """The collapsed (merged-across-shards) settled state, on one
        device."""
        self._settle()
        if self._merged is None:
            on_device = self.devices > 1
            rounds = ((self.devices - 1).bit_length() if on_device
                      else self.shards - 1)
            with obs.span("plane.collapse", devices=self.devices,
                          rounds=rounds, state_bytes=self._state_bytes):
                if on_device:
                    merged = jax.tree_util.tree_map(
                        _first_shard, self._collapse(self._state))
                else:
                    # each shard state crosses the wire ONCE (encoded +
                    # decoded) before merging; codec "none" is a copy-free
                    # identity
                    merged = self.codec.roundtrip(self._subplanes[0].state)
                    for sub in self._subplanes[1:]:
                        merged = self._ops.merge(
                            merged, self.codec.roundtrip(sub.state))
                self._merged = merged
        return self._merged

    def collapse_hlo(self) -> str:
        """The compiled HLO text of the device path's collapse program, or
        "" on the sub-plane path.  A device profile names each operation
        by its HLO instruction, so this tells a profile's collapse
        operations apart from the other programs'.  Compiles (or fetches
        from the compile cache) when called."""
        if self.devices == 1:
            return ""
        return self._collapse.lower(self._state).compile().as_text()

    def set_state(self, st):
        self._settle()
        if self.devices > 1:
            self._state = jax.device_put(jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a] + [b] * (self.shards - 1)),
                st, self._initial), self._sharding)
        else:
            self._subplanes[0].set_state(st)
            for sub in self._subplanes[1:]:
                sub.set_state(self._initial)
        self._merged = None

    def close(self):
        for sub in self._subplanes:
            sub.close()


# The serving fleet's in-process data-path model registers itself as the
# "fleet" plane (replica-sharded ingest collapsed through the checkpoint
# merge protocol).  Imported LAST so the registry order -- and with it the
# conformance PATHS grid -- is deterministic no matter which module pulls
# the plane layer in first.  The import is cycle-safe: fleet.py only needs
# names defined above this line at its import time.
from repro.distributed import fleet as _fleet  # noqa: E402,F401
