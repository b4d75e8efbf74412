"""Wire-volume benchmark: bytes crossing each comm boundary, per codec.

Three boundaries carry sampler payloads (``repro.distributed.codecs``):
the shard merge tree (``sharding.merge_states``), the fleet checkpoint
publish (``train.checkpoint``), and the gradient-compression all-reduce
(``optim.gradcomp``).  For each registered production codec this reports

  ``comm_volume_merge_<codec>``  microseconds per 2-shard ``merge_states``
                                 with ``bytes_per_shard=`` (the encoded
                                 wire image, ``Codec.tree_nbytes``)
  ``comm_volume_ckpt_<codec>``   microseconds per checkpoint save+restore
                                 round-trip with ``bytes=`` from the
                                 committed manifest
                                 (``checkpoint.payload_nbytes``)
  ``comm_volume_fleet_<codec>``  end-to-end multi-process fleet
                                 ``samples_per_s=`` with ``pub_bytes=``
                                 (coordinator-accounted published bytes)
  ``comm_volume_gradcomp_<codec>``  static bytes-on-wire per worker step
                                 from the compressor's ``comm_bytes`` stat

Every row sits behind a parity guard evaluated BEFORE timing: codec
``none`` must be BITWISE identical to the codec-free path, and each lossy
codec's merged/restored state must land within its derived round-trip
tolerance (``codecs.assert_trees_within_codec``); the fleet rows are held
bitwise to the single-process fleet-plane reference AT THE SAME CODEC.
The ``ratio_vs_none=`` columns are asserted in-bench: ``size_adaptive``
must cut checkpoint and gradcomp wire bytes by >= 3.5x, so a silent codec
regression fails the benchmark rather than shading a number.
"""
from __future__ import annotations

import shutil
import tempfile
import time

import jax
import numpy as np

from repro.core import transforms
from repro.core.sampler import SamplerConfig, make_sampler
from repro.data.pipeline import TurnstileZipfStream
from repro.distributed import codecs as wire_codecs
from repro.distributed import fleet as F
from repro.distributed import sharding as shd
from repro.engine import EngineConfig
from repro.engine import engine as eng
from repro.engine import planes
from repro.launch.fleet_serve import traffic
from repro.train import checkpoint

from .common import emit

CODECS = ("none", "fp16", "q8", "size_adaptive")
MIN_RATIO = 3.5  # acceptance floor: size_adaptive vs none, ckpt + gradcomp


def _trees_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def _shard_states(streams: int = 8, n: int = 4096, k: int = 8,
                  shards: int = 2, seed: int = 0x5EED):
    """Two mergeable shard states: same seed bank, disjoint key slices
    (the merge-tree workload shape: a (streams, rows, width) sketch leaf
    big enough that size_adaptive picks the 8-bit arm)."""
    spec = make_sampler("onepass", SamplerConfig(
        rows=5, width=512, candidates=4 * k, capacity=4 * k, p=1.0,
        scheme=transforms.PPSWOR, domain=n))
    sk, ts = eng.derive_stream_seeds(
        eng.EngineConfig(num_streams=streams, seed=seed))
    ops = eng.batched_ops(spec)
    init = ops.init(sk, ts)
    rng = np.random.default_rng(seed)
    keys = np.broadcast_to(np.arange(n, dtype=np.int32), (streams, n))
    vals = np.broadcast_to(
        rng.gamma(0.3, 50.0, size=n).astype(np.float32), (streams, n))
    states = []
    for s in range(shards):
        pl = planes.make_plane("sparse", spec, init,
                               policy=planes.FlushPolicy(max_elems=1))
        pl.ingest(np.ascontiguousarray(keys[:, s::shards]),
                  np.ascontiguousarray(vals[:, s::shards]))
        pl.drain()
        states.append(pl.state)
        pl.close()
    return states, ops


def _merge_rows(fast: bool) -> list:
    states, ops = _shard_states()
    ref = shd.merge_states(states, ops.merge)  # codec-free baseline
    reps = 3 if fast else 8
    rows, nbytes = [], {}
    for name in CODECS:
        cdc = wire_codecs.get_codec(name)
        merged = shd.merge_states(states, ops.merge, codec=cdc)
        if cdc.rel_step == 0.0 and cdc.clamp is None:
            if not _trees_equal(merged, ref):
                raise AssertionError(
                    f"comm_volume: codec {name!r} merge is not bitwise "
                    "identical to the codec-free merge")
            parity = "bitwise"
        else:
            # lossy merges may legitimately reselect candidates, so the
            # guard binds the wire crossing itself: every shard's decoded
            # image must land within the codec's derived round-trip bound
            for i, st in enumerate(states):
                wire_codecs.assert_trees_within_codec(
                    cdc.roundtrip(st), st, cdc, shards=1,
                    label=f"merge@{name} shard {i}")
            parity = "allclose"
        per_shard = cdc.tree_nbytes(states[0])
        nbytes[name] = per_shard
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(
                jax.tree_util.tree_leaves(
                    shd.merge_states(states, ops.merge, codec=cdc)))
        us = (time.perf_counter() - t0) * 1e6 / reps
        rows.append((f"comm_volume_merge_{name}", us,
                     f"bytes_per_shard={per_shard} "
                     f"ratio_vs_none={nbytes['none'] / per_shard:.2f} "
                     f"shards={len(states)} parity={parity}"))
    return rows, states, ops, ref


def _ckpt_rows(ref, fast: bool) -> list:
    rows, nbytes = [], {}
    scratch = tempfile.mkdtemp(prefix="repro-comm-volume-")
    try:
        for name in CODECS:
            cdc = wire_codecs.get_codec(name)
            t0 = time.perf_counter()
            path = checkpoint.save(scratch + f"/{name}", 0, ref, codec=cdc)
            restored = checkpoint.restore(scratch + f"/{name}", 0, ref)
            us = (time.perf_counter() - t0) * 1e6
            if cdc.rel_step == 0.0 and cdc.clamp is None:
                if not _trees_equal(restored, ref):
                    raise AssertionError(
                        f"comm_volume: codec {name!r} checkpoint round-trip "
                        "is not bitwise identical")
                parity = "bitwise"
            else:
                wire_codecs.assert_trees_within_codec(
                    restored, ref, cdc, shards=1, label=f"ckpt@{name}")
                parity = "allclose"
            nbytes[name] = checkpoint.payload_nbytes(path)
            rows.append((f"comm_volume_ckpt_{name}", us,
                         f"bytes={nbytes[name]} "
                         f"ratio_vs_none={nbytes['none'] / nbytes[name]:.2f} "
                         f"parity={parity}"))
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    ratio = nbytes["none"] / nbytes["size_adaptive"]
    if ratio < MIN_RATIO:
        raise AssertionError(
            f"comm_volume: size_adaptive checkpoint reduction {ratio:.2f}x "
            f"is below the {MIN_RATIO}x acceptance floor")
    return rows


def _fleet_rows(fast: bool, replicas: int = 2, requests: int = 8,
                k: int = 8) -> list:
    steps = 8 if fast else 24
    ecfg = EngineConfig(
        num_streams=requests, rows=5, width=max(256, 31 * k),
        candidates=4 * k, capacity=4 * k, p=1.0, seed=0x5EED,
        sampler="onepass", domain=4096, num_samplers=max(4, k))
    stream = TurnstileZipfStream(vocab_size=ecfg.domain, alpha=1.3, seed=0)
    batches = traffic(stream, requests, steps, 16)
    rows, pub = [], {}
    for name in ("none", "size_adaptive"):
        fcfg = F.FleetConfig(engine=ecfg, replicas=replicas,
                             publish_every=max(2, steps // 4), codec=name)
        with F.FleetCoordinator(fcfg) as co:
            for keys, vals in batches:
                co.route(keys, vals)
            sample = co.sample(k)  # warm + parity input
            ref = F.reference_sample(ecfg, batches, replicas, k, codec=name)
            if not (np.array_equal(np.asarray(sample.keys),
                                   np.asarray(ref.keys))
                    and np.array_equal(np.asarray(sample.freqs),
                                       np.asarray(ref.freqs))):
                raise AssertionError(
                    f"comm_volume: fleet sample at codec {name!r} diverged "
                    "from the single-process fleet-plane reference")
            t0 = time.perf_counter()
            for _ in range(2 if fast else 3):
                co.sample(k)
            us = (time.perf_counter() - t0) * 1e6 / (2 if fast else 3)
            stats = co.stats
        per_ckpt = stats.published_bytes / max(stats.publishes, 1)
        pub[name] = per_ckpt
        rows.append((f"comm_volume_fleet_{name}", us,
                     f"samples_per_s={requests * k / max(us * 1e-6, 1e-9):.1f} "
                     f"pub_bytes={stats.published_bytes} "
                     f"bytes_per_ckpt={per_ckpt:.0f} "
                     f"publishes={stats.publishes} "
                     f"ratio_vs_none={pub['none'] / max(per_ckpt, 1):.2f} "
                     f"parity=bitwise"))
    ratio = pub["none"] / max(pub["size_adaptive"], 1)
    if ratio < MIN_RATIO:
        raise AssertionError(
            f"comm_volume: size_adaptive fleet publish reduction "
            f"{ratio:.2f}x is below the {MIN_RATIO}x acceptance floor")
    return rows


def _gradcomp_rows(fast: bool) -> list:
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh_auto
    from repro.optim import gradcomp

    mesh = make_mesh_auto((1,), ("data",))
    n = 1 << 16
    rng = np.random.default_rng(0)
    g = (rng.standard_t(3, size=n) *
         (1 + 50 * (rng.random(n) < 0.001))).astype(np.float32)
    rows, nbytes = [], {}
    for name in CODECS:
        cc = gradcomp.CompressorConfig(k=256, rows=7, width=4096,
                                       candidates=512, p=1.0,
                                       mode="twopass", codec=name)

        def step(a):
            return gradcomp.compress_step(a, cc, ("data",))

        f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P(),
                                  out_specs=P(), check_vma=False))
        t0 = time.perf_counter()
        sparse, _, stats = f(g)
        jax.block_until_ready(sparse)
        us = (time.perf_counter() - t0) * 1e6
        comm = float(stats["comm_bytes"])
        if name == "none":
            # consistency guard: raw wire bytes must be 4B per float
            # (sketch table + pass-II exact values) + 4B per candidate id
            expect = 4.0 * (cc.rows * cc.width + cc.k) + 4.0 * cc.candidates
            if comm != expect:
                raise AssertionError(
                    "comm_volume: codec-none gradcomp byte accounting "
                    f"diverged ({comm} vs {expect})")
        nbytes[name] = comm
        cos = float(np.dot(np.asarray(sparse), g) /
                    (np.linalg.norm(np.asarray(sparse)) *
                     np.linalg.norm(g) + 1e-9))
        rows.append((f"comm_volume_gradcomp_{name}", us,
                     f"bytes_wire={comm:.0f} "
                     f"dense_bytes={float(stats['dense_bytes']):.0f} "
                     f"ratio_vs_none={nbytes['none'] / comm:.2f} "
                     f"cos_dense={cos:.3f}"))
    ratio = nbytes["none"] / nbytes["size_adaptive"]
    if ratio < MIN_RATIO:
        raise AssertionError(
            f"comm_volume: size_adaptive gradcomp reduction {ratio:.2f}x "
            f"is below the {MIN_RATIO}x acceptance floor")
    return rows


def run(verbose: bool = True, fast: bool = False) -> list:
    merge_rows, _, _, ref = _merge_rows(fast)
    rows = (merge_rows + _ckpt_rows(ref, fast) + _fleet_rows(fast)
            + _gradcomp_rows(fast))
    if verbose:
        emit(rows)
    return rows


if __name__ == "__main__":
    run()
