"""Batched multi-stream sampler engine (the paper's composability, scaled out).

A *batched state* is the single-stream state pytree of ANY registered
``repro.core.sampler`` spec with a leading stream axis on every leaf: for
one-pass WORp, ``OnePassState.sketch.table`` is (B, rows, width),
``seed_transform`` is (B,), and so on.  Because specs expose uniform pure
functions over plain pytrees, ``jax.vmap`` of the spec IS the batched
engine -- the single-stream code in ``repro.core`` stays the canonical
per-stream definition and the engine never re-implements sampler math.
``SketchEngine(cfg, sampler="onepass"|"twopass"|"perfect"|"tv")`` picks the
sampler from the registry; adding a new sampler is a one-file registry
entry, not an engine change.

Two seeding regimes:
  * independent (default): every stream hashes its own sketch/transform seeds
    from the engine seed -- B statistically independent samplers (per-user,
    per-layer, per-tenant streams).
  * shared: all streams share seeds -- the B streams are SHARDS of one
    logical stream, and ``reduce_streams`` collapses them to the union state
    in O(log B) vmapped merge rounds (the paper's merge, as a tree).

Data plane (one-pass WORp): ``onepass_update_dense`` routes dense per-stream
segments through the batched Pallas update kernel
(``kernels.countsketch_update_batched``) so all B streams share one
``pallas_call``; and the query plane -- batched ``sample``, ``estimate``, and
the dense-update candidate refresh -- goes through
``kernels.ops.estimate_batched``, which dispatches ONE batched Pallas query
kernel on TPU and the bit-identical jnp oracle elsewhere.

Turnstile ingest is a first-class DATA-PLANE layer (``repro.engine.planes``):
``SketchEngine(cfg, plane="dense"|"sparse"|"async"|"pipeline",
flush=FlushPolicy(...), plane_opts={...})`` selects how host-side
microbatches reach the state -- the vmapped-jnp reference plane, the
synchronous batched Pallas scatter plane, the double-buffered asynchronous
plane (worker-thread dispatch, bit-identical drained state under the same
flush policy), or the per-shard + collapse pipeline plane (``plane_opts=
{"shards": S, "subplane": ...}``; merged through the sampler's composable
merge at every read; ``"devices": S`` keeps one shard on each device and
collapses them through the collective all-merge).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import countsketch, hashing, transforms, worp
from repro.core import sampler as core_sampler
from repro.core.perfect import Sample
from repro.core.sampler import SamplerSpec
from repro.kernels import ops

_EMPTY = jnp.int32(-1)


class EngineConfig(NamedTuple):
    num_streams: int          # B: streams batched as one pytree
    rows: int = 7
    width: int = 2048
    candidates: int = 512     # one-pass candidate buffer per stream
    capacity: int = 512       # two-pass exact-frequency buffer per stream
    p: float = 1.0
    scheme: str = transforms.PPSWOR
    seed: int = 0x5EED
    shared_seeds: bool = False  # True => streams are mergeable shards
    sampler: str = "onepass"    # registry key (see repro.core.sampler)
    domain: int = 4096          # "perfect" sampler: frequency-vector size
    num_samplers: int = 8       # "tv" sampler: cascade length r


def sampler_config(cfg: EngineConfig) -> core_sampler.SamplerConfig:
    """Project the engine config onto the registry's SamplerConfig."""
    return core_sampler.SamplerConfig(
        rows=cfg.rows, width=cfg.width, candidates=cfg.candidates,
        capacity=cfg.capacity, p=cfg.p, scheme=cfg.scheme, domain=cfg.domain,
        num_samplers=cfg.num_samplers)


def engine_spec(cfg: EngineConfig) -> SamplerSpec:
    """The (cached) SamplerSpec this engine config selects."""
    return core_sampler.make_sampler(cfg.sampler, sampler_config(cfg))


def derive_stream_seeds(cfg: EngineConfig, offset: int = 0):
    """Per-stream (sketch, transform) seed vectors, both (B,) uint32.

    ``offset`` shifts the stream indices the seeds are hashed from: block t
    of a repeated-trial experiment passes ``offset = t * num_streams`` to
    get B FRESH independent samplers per block without constructing a new
    config -- the ``repro.validate`` trial-seeding hook.  Ignored under
    ``shared_seeds`` (shards of one logical stream have one seed pair).
    """
    b = jnp.arange(cfg.num_streams, dtype=jnp.uint32) + jnp.uint32(offset)
    if cfg.shared_seeds:
        ones = jnp.ones((cfg.num_streams,), jnp.uint32)
        return (ones * jnp.uint32(cfg.seed),
                ones * jnp.uint32(cfg.seed ^ 0xA5A5A5A5))
    return (hashing.hash_u32(b, jnp.uint32(cfg.seed)),
            hashing.hash_u32(b, jnp.uint32(cfg.seed) ^ jnp.uint32(0xA5A5A5A5)))


# ---------------------------------------------------------------------------
# generic batched sampler ops: vmap + jit of any registered spec
# ---------------------------------------------------------------------------

class BatchedSamplerOps:
    """Jitted, vmapped forms of one SamplerSpec's functions.

    ``init(sk_seeds, t_seeds)`` maps (B,) seed vectors to the batched state;
    every other op maps batched states / (B, n) element batches exactly like
    a Python loop of the single-stream spec functions (the engine's
    vmap-consistency contract).  Two-phase hooks are present iff the spec
    has an exact second pass.
    """

    def __init__(self, spec: SamplerSpec):
        self.spec = spec
        self.init = jax.jit(jax.vmap(spec.init))
        self.update = jax.jit(jax.vmap(spec.update))
        self.merge = jax.jit(jax.vmap(spec.merge))
        self.sample = jax.jit(
            lambda st, k: jax.vmap(lambda s: spec.sample(s, k))(st),
            static_argnames=("k",))
        self.estimate = jax.jit(jax.vmap(spec.estimate))
        if spec.two_phase:
            self.init2 = jax.jit(jax.vmap(spec.init2))
            self.update2 = jax.jit(jax.vmap(spec.update2))
            self.merge2 = jax.jit(jax.vmap(spec.merge2))
            self.sample2 = jax.jit(
                lambda st2, k: jax.vmap(lambda s: spec.sample2(s, k))(st2),
                static_argnames=("k",))


@functools.lru_cache(maxsize=None)
def batched_ops(spec: SamplerSpec) -> BatchedSamplerOps:
    """Batched ops for a spec; cached so jit caches persist per spec."""
    return BatchedSamplerOps(spec)


def init_batched(cfg: EngineConfig):
    """Batched initial state for cfg's registered sampler."""
    return batched_ops(engine_spec(cfg)).init(*derive_stream_seeds(cfg))


# ---------------------------------------------------------------------------
# batched one-pass WORp (legacy names; the engine data plane's fast paths)
# ---------------------------------------------------------------------------

def onepass_init_batched(cfg: EngineConfig) -> worp.OnePassState:
    sk_seeds, t_seeds = derive_stream_seeds(cfg)
    B = cfg.num_streams
    return worp.OnePassState(
        sketch=countsketch.CountSketch(
            table=jnp.zeros((B, cfg.rows, cfg.width), jnp.float32),
            seed=sk_seeds),
        cand_keys=jnp.full((B, cfg.candidates), _EMPTY, jnp.int32),
        seed_transform=t_seeds,
    )


@functools.partial(jax.jit, static_argnames=("p", "scheme"))
def onepass_update_batched(st: worp.OnePassState, keys: jnp.ndarray,
                           values: jnp.ndarray, p: float,
                           scheme: str = transforms.PPSWOR):
    """vmapped ``worp.onepass_update``: keys/values are (B, n)."""
    return jax.vmap(
        lambda s, k, v: worp.onepass_update(s, k, v, p, scheme)
    )(st, keys, values)


@jax.jit
def onepass_merge_batched(a: worp.OnePassState, b: worp.OnePassState):
    """Stream-wise merge of two batched states (same seeds stream-by-stream)."""
    return jax.vmap(worp.onepass_merge)(a, b)


@functools.partial(jax.jit, static_argnames=("k", "p", "scheme", "use_kernel",
                                             "interpret"))
def onepass_sample_batched(st: worp.OnePassState, k: int, p: float,
                           scheme: str = transforms.PPSWOR,
                           use_kernel: Optional[bool] = None,
                           interpret: Optional[bool] = None) -> Sample:
    """Per-stream WOR samples; every Sample leaf grows a leading (B,) axis.

    The B-stream candidate estimates come from ONE batched query dispatch
    (``ops.estimate_batched``: Pallas kernel on TPU, bit-identical jnp
    oracle elsewhere); the per-stream top-k/invert is the vmapped
    single-stream tail (``worp.onepass_sample_from_estimates``).
    """
    est = ops.estimate_batched(st.sketch.table, st.cand_keys, st.sketch.seed,
                               use_kernel=use_kernel, interpret=interpret)
    return jax.vmap(
        lambda s, e: worp.onepass_sample_from_estimates(s, e, k, p, scheme)
    )(st, est)


@functools.partial(jax.jit, static_argnames=("p", "scheme", "interpret",
                                             "use_kernel"))
def onepass_update_dense(st: worp.OnePassState, values: jnp.ndarray,
                         p: float, base_keys=None, lengths=None,
                         scheme: str = transforms.PPSWOR,
                         interpret: Optional[bool] = None,
                         use_kernel: Optional[bool] = None):
    """Fast path: B dense segments through ONE batched pallas_call.

    ``values[b, i]`` is the frequency increment of key ``base_keys[b] + i``
    for stream b (columns past ``lengths[b]`` ignored).  Both bottom-k
    schemes fuse into the kernel (the randomizer dispatch is static).  The
    candidate refresh queries the (C + n) per-stream keys through the
    batched estimate chokepoint -- one more batched dispatch instead of B
    vmapped gathers.
    """
    B, n = values.shape
    if base_keys is None:
        base_keys = jnp.zeros((B,), jnp.uint32)
    base_keys = jnp.broadcast_to(jnp.asarray(base_keys, jnp.uint32), (B,))
    if lengths is None:
        lengths = jnp.full((B,), n, jnp.int32)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))

    delta = ops.sketch_dense_batch(
        values.astype(jnp.float32), st.sketch.table.shape[1],
        st.sketch.table.shape[2], st.sketch.seed, p=p, scheme=scheme,
        transform_seeds=st.seed_transform, base_keys=base_keys,
        lengths=lengths, interpret=interpret)
    sk = countsketch.CountSketch(table=st.sketch.table + delta,
                                 seed=st.sketch.seed)
    offs = jnp.arange(n, dtype=jnp.int32)
    keys_dense = jnp.where(offs[None, :] < lengths[:, None],
                           base_keys[:, None].astype(jnp.int32) + offs[None, :],
                           _EMPTY)
    cand = _refresh_candidates(sk, st.cand_keys, keys_dense,
                               use_kernel=use_kernel, interpret=interpret)
    return worp.OnePassState(sketch=sk, cand_keys=cand,
                             seed_transform=st.seed_transform)


def _refresh_candidates(sk: countsketch.CountSketch, cand_keys, batch_keys,
                        use_kernel=None, interpret=None):
    """Batched candidate refresh (same policy as ``worp.onepass_update``):
    estimates of (old candidates U batch keys) for all B streams through the
    single batched query chokepoint -- one dispatch, not B vmapped gathers."""
    all_keys = jnp.concatenate([cand_keys, batch_keys], axis=1)  # (B, C+n)
    est = jnp.abs(ops.estimate_batched(sk.table, all_keys, sk.seed,
                                       use_kernel=use_kernel,
                                       interpret=interpret))
    est = jnp.where(all_keys == _EMPTY, -jnp.inf, est)
    return jax.vmap(
        lambda ak, e: worp._dedup_topc(ak, jnp.zeros_like(e), e,
                                       cand_keys.shape[1])[0]
    )(all_keys, est)


# ---------------------------------------------------------------------------
# data planes: the turnstile sparse/async ingest machinery lives in
# ``repro.engine.planes`` (DataPlane protocol + registry + the sampler-name
# sparse kernel paths).  ``planes`` imports this module for ``batched_ops``
# and ``_refresh_candidates``, so the import here must stay lazy.
# ---------------------------------------------------------------------------

def _planes():
    from repro.engine import planes

    return planes


# ---------------------------------------------------------------------------
# batched two-pass WORp (legacy names)
# ---------------------------------------------------------------------------

def twopass_init_batched(cfg: EngineConfig) -> worp.TwoPassState:
    _, t_seeds = derive_stream_seeds(cfg)
    B = cfg.num_streams
    return worp.TwoPassState(
        keys=jnp.full((B, cfg.capacity), _EMPTY, jnp.int32),
        freqs=jnp.zeros((B, cfg.capacity), jnp.float32),
        priority=jnp.full((B, cfg.capacity), -jnp.inf, jnp.float32),
        seed_transform=t_seeds,
    )


@jax.jit
def twopass_update_batched(st: worp.TwoPassState,
                           frozen: countsketch.CountSketch,
                           keys: jnp.ndarray, values: jnp.ndarray):
    """vmapped pass-II step; ``frozen`` is the batched pass-I sketch."""
    return jax.vmap(worp.twopass_update)(st, frozen, keys, values)


@jax.jit
def twopass_merge_batched(a: worp.TwoPassState, b: worp.TwoPassState):
    return jax.vmap(worp.twopass_merge)(a, b)


@functools.partial(jax.jit, static_argnames=("k", "p", "scheme"))
def twopass_sample_batched(st: worp.TwoPassState, k: int, p: float,
                           scheme: str = transforms.PPSWOR) -> Sample:
    return jax.vmap(lambda s: worp.twopass_sample(s, k, p, scheme))(st)


# ---------------------------------------------------------------------------
# stream collapse: O(log B) merge tree over the leading axis
# ---------------------------------------------------------------------------

def reduce_streams(st, merge_batched):
    """Collapse a batched state's B streams to ONE state in ceil(log2 B)
    vmapped merge rounds (valid when streams share seeds, i.e. are shards).

    ``merge_batched`` is a batched merge fn -- e.g. ``onepass_merge_batched``
    or ``batched_ops(spec).merge`` for any registered sampler.  Each round
    merges the first half with the second half stream-wise, so round r
    performs B / 2^(r+1) merges as one vmapped call -- the same O(log) shape
    as the distributed tree in ``repro.distributed.sharding``.
    """
    num = jax.tree_util.tree_leaves(st)[0].shape[0]
    while num > 1:
        half = num // 2
        lo = jax.tree_util.tree_map(lambda x: x[:half], st)
        hi = jax.tree_util.tree_map(lambda x: x[half:2 * half], st)
        merged = merge_batched(lo, hi)
        if num % 2:  # odd stream carries to the next round
            carry = jax.tree_util.tree_map(lambda x: x[2 * half:], st)
            merged = jax.tree_util.tree_map(
                lambda m, c: jnp.concatenate([m, c], axis=0), merged, carry)
        st, num = merged, half + (num % 2)
    return jax.tree_util.tree_map(lambda x: x[0], st)


# ---------------------------------------------------------------------------
# stateful convenience wrapper
# ---------------------------------------------------------------------------

class SketchEngine:
    """Holds a batched state for any registered sampler (plus an optional
    exact pass-II state when the sampler has one).

    Thin object shell over the functional batched ops above -- all state is
    jax pytrees, so an engine can live inside jit/scan via its ``.state``.

    Data plane: ``plane=`` picks how turnstile microbatches reach the state
    (``repro.engine.planes`` registry).  ``ingest(keys, values)`` buffers
    sparse signed microbatches host-side (numpy, zero device work) and the
    plane's ``FlushPolicy`` (element count / byte budget / interval;
    ``flush=FlushPolicy(...)`` or the ``flush_elems`` shorthand) decides
    when they dispatch: the default ``"sparse"`` plane pushes the whole
    buffer through ONE batched Pallas scatter dispatch per sketch-backed
    sampler inline, ``"async"`` double-buffers the dispatch on a worker
    thread (bit-identical drained state under the same policy), and
    ``"dense"`` is the vmapped-jnp reference plane.  Every read or
    state-mixing operation (update/sample/estimate/merge/freeze/collapse)
    drains the plane first, so the visible state is always up to date and
    deterministic.
    """

    def __init__(self, cfg: EngineConfig, sampler: Optional[str] = None,
                 flush_elems: int = 4096, plane: str = "sparse",
                 flush=None, plane_opts: Optional[dict] = None):
        if sampler is not None and sampler != cfg.sampler:
            cfg = cfg._replace(sampler=sampler)
        self.cfg = cfg
        self.spec = engine_spec(cfg)
        self.ops = batched_ops(self.spec)
        planes = _planes()
        policy = flush if flush is not None \
            else planes.FlushPolicy(max_elems=int(flush_elems))
        self._plane = planes.make_plane(
            plane, self.spec, self.ops.init(*derive_stream_seeds(cfg)),
            policy=policy, **(plane_opts or {}))
        self.pass2 = None

    @property
    def num_streams(self) -> int:
        return self.cfg.num_streams

    @property
    def sampler(self) -> str:
        return self.cfg.sampler

    @property
    def plane(self):
        """The engine's DataPlane instance (see ``repro.engine.planes``)."""
        return self._plane

    @property
    def state(self):
        """The settled batched sampler state.  In-flight async dispatches
        complete first; microbatches still in the HOST buffer stay pending
        (``flush()`` applies them).  A pipeline plane with ``devices > 1``
        returns the shards' collapsed state on the first device, as an
        ordinary one-device array that ``sample_state`` can query."""
        return self._plane.state

    @state.setter
    def state(self, st):
        self._plane.set_state(st)

    # -- pass I -------------------------------------------------------------
    def update(self, keys, values):
        """Sparse element batches: keys/values (B, n) int32/float32.

        Any pending ingest buffer drains FIRST: interleaving ``ingest`` and
        ``update`` applies the elements in call order, so ingest -> update
        -> sample equals the aggregated-stream oracle regardless of how the
        stream was split across the two entry points."""
        self.flush()
        self.state = self.ops.update(self.state, keys, values)
        return self

    def ingest(self, keys, values):
        """Buffer a sparse signed (B, n) turnstile microbatch.

        Negative values are deletions; ``keys == -1`` slots are padding.
        Microbatches accumulate host-side and dispatch through the engine's
        data plane when its FlushPolicy fires (or on the next read/flush).
        Ingesting a batch and later its negation returns the sketch exactly
        to zero (linearity).
        """
        with obs.span("engine.ingest"):
            keys = np.asarray(keys, np.int32)
            values = np.asarray(values, np.float32)
            if keys.shape != values.shape or keys.ndim != 2 \
                    or keys.shape[0] != self.cfg.num_streams:
                raise ValueError(
                    f"ingest: keys/values must both be (num_streams={self.cfg.num_streams}, n), "
                    f"got {keys.shape} / {values.shape}")
            self._plane.ingest(keys, values)
        return self

    @property
    def pending(self) -> int:
        """Per-stream element count currently buffered (not yet flushed)."""
        return self._plane.pending

    def flush(self, interpret=None, use_kernel=None):
        """Drain the data plane: flush buffered turnstile microbatches and
        settle any in-flight async dispatches; no-op when nothing pends."""
        with obs.span("engine.flush"):
            self._plane.drain(interpret=interpret, use_kernel=use_kernel)
        return self

    def update_dense(self, values, base_keys=None, lengths=None,
                     interpret=None):
        """Dense segments through the batched Pallas kernel (one call).

        One-pass WORp only: the other samplers have no fused dense kernel."""
        if self.cfg.sampler != "onepass":
            raise ValueError(
                f"update_dense: sampler {self.cfg.sampler!r} has no Pallas "
                f"dense fast path (only 'onepass'); use update()")
        self.flush()
        self.state = onepass_update_dense(self.state, values, self.cfg.p,
                                          base_keys=base_keys,
                                          lengths=lengths,
                                          scheme=self.cfg.scheme,
                                          interpret=interpret)
        return self

    def merge_with(self, other: "SketchEngine"):
        """Stream-wise union with another engine.

        Stream b of ``self`` merges with stream b of ``other``; that is only
        the union of the two engines' data when both derive IDENTICAL
        per-stream seeds and state shapes, i.e. when the configs are equal
        (under either seeding regime -- ``shared_seeds`` additionally makes
        the B streams shards of one logical stream, which is what
        ``collapse()`` requires)."""
        ocfg = getattr(other, "cfg", None)
        if not isinstance(other, SketchEngine) or ocfg is None:
            raise TypeError(
                f"merge_with expects a SketchEngine, got {type(other).__name__}")
        self.flush()
        other.flush()
        if ocfg != self.cfg:
            diff = [f"{f}={getattr(self.cfg, f)!r} vs {getattr(ocfg, f)!r}"
                    for f in self.cfg._fields
                    if getattr(self.cfg, f) != getattr(ocfg, f)]
            raise ValueError(
                "merge_with: engines are not mergeable -- stream-wise union "
                "requires identical EngineConfig (per-stream hash seeds and "
                "state shapes must agree, or the merged sketch is garbage); "
                "mismatched fields: " + ", ".join(diff))
        self.state = self.ops.merge(self.state, other.state)
        return self

    def sample(self, k: int) -> Sample:
        self.flush()
        return self.sample_state(self.state, k)

    def sample_state(self, state, k: int) -> Sample:
        """Per-stream WOR samples of an ARBITRARY batched state of this
        engine's sampler (e.g. a cross-worker merge result) -- the same
        dispatch as ``sample`` without touching the engine's own state."""
        if self.cfg.sampler == "onepass":
            # batched query-kernel path (one dispatch for all B streams)
            return onepass_sample_batched(state, k, self.cfg.p,
                                          self.cfg.scheme)
        return self.ops.sample(state, k=k)

    def estimate(self, keys) -> jnp.ndarray:
        """Per-stream transformed-domain estimates for (B, n) keys."""
        self.flush()
        if self.cfg.sampler == "onepass":
            return ops.estimate_batched(self.state.sketch.table, keys,
                                        self.state.sketch.seed)
        return self.ops.estimate(self.state, keys)

    # -- exact pass II (samplers with a frozen-priority second pass) --------
    def freeze(self):
        """Freeze pass-I priorities and start the exact second pass."""
        if not self.spec.two_phase:
            raise ValueError(
                f"freeze: sampler {self.cfg.sampler!r} has no exact second "
                f"pass (two-phase samplers: onepass, twopass)")
        self.flush()
        self.pass2 = self.ops.init2(self.state)
        return self

    def _frozen_sketch(self):
        """The batched frozen pass-I CountSketch backing pass-II priorities
        (None for samplers that registered no ``register_frozen_sketch``
        accessor)."""
        getter = _planes().frozen_sketch_getter(self.cfg.sampler)
        return getter(self.state) if getter is not None else None

    def update_pass2(self, keys, values):
        """Exact-frequency pass-II replay; priorities against the FROZEN
        pass-I sketch come from the batched query chokepoint (one dispatch
        for all B streams) when the sampler exposes its sketch."""
        assert self.pass2 is not None, "call freeze() before pass II"
        frozen = self._frozen_sketch()
        if frozen is not None:
            prio = ops.estimate_batched(frozen.table,
                                        jnp.asarray(keys, jnp.int32),
                                        frozen.seed)
            self.pass2 = _planes().twopass_update_from_priorities_batched(
                self.pass2, jnp.asarray(keys, jnp.int32),
                jnp.asarray(values, jnp.float32), prio)
        else:
            self.pass2 = self.ops.update2(self.pass2, self.state, keys,
                                          values)
        return self

    def sample_exact(self, k: int) -> Sample:
        assert self.pass2 is not None, "call freeze() before pass II"
        return self.ops.sample2(self.pass2, k=k)

    # -- shard collapse -----------------------------------------------------
    def collapse(self):
        """Merge all B streams into one state (requires shared_seeds)."""
        if not self.cfg.shared_seeds:
            raise ValueError("collapse() requires shared_seeds=True "
                             "(independent streams are not mergeable)")
        self.flush()
        return reduce_streams(self.state, self.ops.merge)
