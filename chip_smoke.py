"""Smoke run of the sketch engine's main path on a TPU (not a benchmark).

Drives ``SketchEngine`` -> sparse data plane -> Pallas scatter and query
kernels -> ``merge_states`` at the state size a deployment holds: 256
independent tenant streams of a k=1024 one-pass WORp sampler (5 x 31,744
tables, ~160 MB on the device), fed windows of seeded signed Zipf events
through ``PackedBatcher``.  Every phase is checked against references that
share no code with the Pallas kernels: ``kernels/ref.py``, the vmapped-jnp
``dense`` plane and the host ``tree_merge``.  The seconds it prints are one
run's, with compilation counted apart; they are not a measurement.

    python chip_smoke.py             # one chip: ingest, sample, merge
    python chip_smoke.py --chips 4   # only the 4-device pipeline plane

Exits non-zero and prints no result when JAX finds no TPU.  The last line
of standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from contextlib import contextmanager

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

STREAMS = 256          # independent tenant streams (engine num_streams)
ROWS = 5
K = 1024               # sample size
WIDTH = 31 * K         # the paper's k x 31 sketch width
CANDIDATES = 4 * K
DOMAIN = 1 << 24       # key domain
ALPHA = 1.1            # Zipf exponent
INSERTS = 4096         # per tenant and window: 2^20 inserts per window
WINDOWS = 3            # each window retracts a quarter of the previous one
SHARDS = 4             # shards of one logical stream (merge phases)
SHARD_INSERTS = 1 << 18  # inserts per window of that one stream
SEED = 0
SCHEMES = ((1.0, "ppswor"), (2.0, "priority"))
RTOL = 1e-4            # fp32 summation-order tolerance, scaled as in
ATOL_OF_SCALE = 1e-5   # benchmarks/engine_throughput.py


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require(ok, msg: str) -> None:
    """A failed check; raises under ``python -O`` too."""
    if not ok:
        raise AssertionError(msg)


# -- compile-time accounting --------------------------------------------------

_COMPILE_S = [0.0]


def _count_compile(event: str, duration: float, **_) -> None:
    if event.startswith("/jax/core/compile/"):
        _COMPILE_S[0] += duration


@contextmanager
def phase(name: str):
    """Print one phase's wall and compile seconds and the device peak."""
    import jax

    c0, t0 = _COMPILE_S[0], time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    log(f"phase={name} wall_s={wall:.3f} compile_s={_COMPILE_S[0] - c0:.3f} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


# -- traffic ------------------------------------------------------------------

def tenant_blocks(stream, tenants: int, windows: int, inserts: int,
                  span_elems: int):
    """Tenant t's signed events are ``stream``'s shard t, packed per tenant
    by ``PackedBatcher`` and stacked into fixed (tenants, span) blocks; every
    tenant sees the same event count, so the batchers stay in step."""
    from repro.data.ingest_pipeline import PackedBatcher

    batchers = [PackedBatcher(span_elems) for _ in range(tenants)]

    def stack(per_tenant):
        require(len({len(b) for b in per_tenant}) == 1,
                "batchers out of step")
        return [tuple(np.concatenate([per_tenant[t][i][j]
                                      for t in range(tenants)])
                      for j in (0, 1))
                for i in range(len(per_tenant[0]))]

    blocks = []
    for w in range(windows):
        blocks += stack([b.add(*stream.sparse_batch_at(w, t, inserts))
                         for t, b in enumerate(batchers)])
    tails = [b.flush_tail() for b in batchers]
    if tails[0] is not None:
        blocks += stack([[tail] for tail in tails])
    return blocks, batchers[0].span


def shard_blocks(stream, shards: int, windows: int, inserts: int):
    """One logical stream split by key hash (``shard_of_keys``) into
    ``shards`` rows, each window padded with key -1 to one span."""
    from repro.kernels import ops

    per = [[stream.shard_batch_at(w, s, shards, inserts)
            for s in range(shards)] for w in range(windows)]
    span = ops.packed_span(max(k.size for win in per for k, _ in win))
    blocks = []
    for win in per:
        keys = np.full((shards, span), -1, np.int32)
        vals = np.zeros((shards, span), np.float32)
        for s, (k, v) in enumerate(win):
            keys[s, :k.size], vals[s, :v.size] = k, v
        blocks.append((keys, vals))
    return blocks


# -- checks -------------------------------------------------------------------

def check_close(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    require(got.shape == want.shape, f"{name}: {got.shape} != {want.shape}")
    scale = max(1.0, float(np.abs(want).max()))
    err = float(np.abs(got - want).max())
    bitwise = bool(np.array_equal(got, want))
    log(f"check={name} shape={got.shape} max_abs_err={err:.6g} "
        f"scale={scale:.6g} bitwise={bitwise}")
    np.testing.assert_allclose(got, want, rtol=RTOL,
                               atol=ATOL_OF_SCALE * scale, err_msg=name)


def check_equal(name: str, a, b) -> None:
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    same = len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))
    log(f"check={name} bitwise={same}")
    require(same, name)


def check_sample_keys(name: str, got, want, tables, seeds) -> None:
    """Per stream, the two samples' key sets agree except at near-ties: a
    key in one sample only must sit within the fp32 tolerance of the
    reference sample's threshold, by its estimate on the reference tables."""
    from repro.kernels import ref

    gk, wk = np.asarray(got.keys), np.asarray(want.keys)
    require((gk >= 0).all() and (wk >= 0).all(), f"{name}: underfull sample")
    require(np.isfinite(np.asarray(got.freqs)).all(), f"{name}: freqs")
    thr = np.abs(np.asarray(want.threshold))
    diff_streams, diff_keys = 0, 0
    for b in range(gk.shape[0]):
        only = np.setxor1d(gk[b], wk[b])
        if only.size == 0:
            continue
        diff_streams += 1
        diff_keys += only.size
        est = np.abs(np.asarray(ref.countsketch_estimate_batched_ref(
            tables[b:b + 1], only[None].astype(np.int32), seeds[b:b + 1])))[0]
        scale = max(1.0, float(np.abs(np.asarray(tables[b])).max()))
        tol = RTOL * thr[b] + ATOL_OF_SCALE * scale
        far = np.abs(est - thr[b]) > tol
        require(not far.any(),
                f"{name}: stream {b} keys {only[far]} differ beyond a "
                f"near-tie (estimates {est[far]}, threshold {thr[b]}, "
                f"tol {tol})")
    log(f"check={name} streams={gk.shape[0]} k={gk.shape[1]} "
        f"streams_with_near_ties={diff_streams} near_tie_keys={diff_keys}")


def state_nbytes(state) -> int:
    import jax

    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(state))


# -- phases -------------------------------------------------------------------

def tenant_phase(p: float, scheme: str) -> None:
    """256 tenants: sparse-plane ingest, estimate and sample, each against
    a reference that runs no Pallas code."""
    import jax

    from repro import engine as E
    from repro.data.pipeline import TurnstileZipfStream
    from repro.kernels import ref

    tag = f"{scheme}_p{p:g}"
    stream = TurnstileZipfStream(DOMAIN, ALPHA, SEED)
    with phase(f"{tag}/traffic"):
        blocks, span = tenant_blocks(stream, STREAMS, WINDOWS, INSERTS,
                                     INSERTS)
    keys = np.concatenate([k for k, _ in blocks], axis=1)
    vals = np.concatenate([v for _, v in blocks], axis=1)
    log(f"{tag}: {len(blocks)} blocks of {(STREAMS, span)}, "
        f"{int((keys >= 0).sum())} live events "
        f"({int((vals < 0).sum())} retractions)")

    cfg = E.EngineConfig(num_streams=STREAMS, rows=ROWS, width=WIDTH,
                         candidates=CANDIDATES, p=p, scheme=scheme, seed=SEED)
    eng = E.SketchEngine(cfg, plane="sparse", flush_elems=span)
    with phase(f"{tag}/ingest_sparse"):
        for k, v in blocks:
            eng.ingest(k, v)
        st = jax.block_until_ready(eng.flush().state)
    log(f"{tag}: state_bytes={state_nbytes(st)} "
        f"table_shape={tuple(st.sketch.table.shape)}")

    with phase(f"{tag}/sample"):
        sample = jax.block_until_ready(eng.sample(K))
    with phase(f"{tag}/estimate"):
        est = jax.block_until_ready(eng.estimate(sample.keys))

    with phase(f"{tag}/reference_scatter_and_estimate"):
        want_table = jax.block_until_ready(
            ref.countsketch_scatter_batched_ref(
                keys, vals, ROWS, WIDTH, st.sketch.seed, p=p,
                transform_seeds=st.seed_transform, scheme=scheme))
        want_est = jax.block_until_ready(
            ref.countsketch_estimate_batched_ref(
                st.sketch.table, sample.keys, st.sketch.seed))
    with phase(f"{tag}/reference_dense_plane"):
        dense = E.SketchEngine(cfg, plane="dense", flush_elems=span)
        for k, v in blocks:
            dense.ingest(k, v)
        dst = dense.flush().state
        want_sample = jax.block_until_ready(E.onepass_sample_batched(
            dst, K, p, scheme, use_kernel=False))
    check_close(f"{tag}/scatter_vs_ref", st.sketch.table, want_table)
    check_close(f"{tag}/estimate_vs_ref", est, want_est)
    check_sample_keys(f"{tag}/sample_vs_dense_plane", sample, want_sample,
                      dst.sketch.table, dst.sketch.seed)


def _shard_stream():
    from repro.data.pipeline import TurnstileZipfStream

    return TurnstileZipfStream(DOMAIN, ALPHA, SEED + 1)


def _shard_config(num_streams: int):
    from repro import engine as E

    return E.EngineConfig(num_streams=num_streams, rows=ROWS, width=WIDTH,
                          candidates=CANDIDATES, p=1.0, seed=SEED,
                          shared_seeds=True)


def merge_phase() -> None:
    """One stream in 4 shards of a shared-seeds engine: ``collapse()`` and
    ``merge_states`` against the host ``tree_merge``."""
    import jax

    from repro import engine as E
    from repro.distributed import sharding as shd

    blocks = shard_blocks(_shard_stream(), SHARDS, WINDOWS, SHARD_INSERTS)
    span = blocks[0][0].shape[1]
    cfg = _shard_config(SHARDS)
    eng = E.SketchEngine(cfg, plane="sparse", flush_elems=span)
    with phase("merge/ingest_sparse"):
        for k, v in blocks:
            eng.ingest(k, v)
        st = jax.block_until_ready(eng.flush().state)
    with phase("merge/collapse"):
        collapsed = jax.block_until_ready(eng.collapse())
    shards = [jax.tree_util.tree_map(lambda x, s=s: x[s], st)
              for s in range(SHARDS)]
    with phase("merge/merge_states"):
        merged = jax.block_until_ready(shd.merge_states(shards, eng.spec))
    with phase("merge/tree_merge"):
        tree = jax.block_until_ready(shd.tree_merge(shards, eng.spec))

    check_equal("merge/merge_states_vs_tree_merge", merged, tree)
    check_close("merge/collapse_vs_tree_merge", collapsed.sketch.table,
                tree.sketch.table)

    def lift(state):  # one stream -> a batch of one
        return jax.tree_util.tree_map(lambda x: x[None], state)

    tree1 = lift(tree)
    check_sample_keys("merge/collapse_sample_vs_tree_merge",
                      E.onepass_sample_batched(lift(collapsed), K, 1.0),
                      E.onepass_sample_batched(tree1, K, 1.0,
                                               use_kernel=False),
                      tree1.sketch.table, tree1.sketch.seed)


def four_chip_phase() -> None:
    """One stream through the ``pipeline`` plane with one key-hash shard
    resident on each of 4 devices (``devices=4``: one SPMD sparse update a
    flush, the collective ``butterfly_allmerge`` on read) against the same
    plane with its 4 shards on one device, folded on the host."""
    import jax

    from repro import engine as E

    stream = _shard_stream()
    blocks = [tuple(x[None] for x in stream.sparse_batch_at(w, 0, SHARD_INSERTS))
              for w in range(WINDOWS)]
    cfg = E.EngineConfig(num_streams=1, rows=ROWS, width=WIDTH,
                         candidates=CANDIDATES, p=2.0, scheme="priority",
                         seed=SEED)
    engines = {}
    for name, opts in (("devices", {"devices": SHARDS}), ("host_fold", {})):
        eng = E.SketchEngine(cfg, plane="pipeline",
                             flush_elems=max(k.size for k, _ in blocks),
                             plane_opts=dict(shards=SHARDS, subplane="sparse",
                                             **opts))
        with phase(f"4chip/ingest_and_collapse_{name}"):
            for k, v in blocks:
                eng.ingest(k, v)
            jax.block_until_ready(eng.flush().state)
        engines[name] = eng
    got, want = engines["devices"].state, engines["host_fold"].state
    require(len(got.sketch.table.devices()) == 1,
            "4chip: the collapsed state is not a one-device array")
    check_close("4chip/collective_collapse_vs_host_fold", got.sketch.table,
                want.sketch.table)
    check_sample_keys("4chip/collective_collapse_sample_vs_host_fold",
                      engines["devices"].sample(K),
                      engines["host_fold"].sample(K),
                      np.asarray(want.sketch.table),
                      np.asarray(want.sketch.seed))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the pipeline plane with one shard "
                         "on each of four chips")
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    dev = devices[0]
    log(f"smoke run, not a benchmark: device_kind={dev.device_kind!r} "
        f"devices={len(devices)} jax={jax.__version__}")
    log(f"shapes: rows={ROWS} width={WIDTH} k={K} candidates={CANDIDATES} "
        f"domain=2^24 alpha={ALPHA} windows={WINDOWS}; tenant phases: "
        f"streams={STREAMS} inserts/window={STREAMS * INSERTS}; merge "
        f"phases: shards={SHARDS} inserts/window={SHARD_INSERTS}")

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase()
    else:
        for p, scheme in SCHEMES:
            tenant_phase(p, scheme)
        merge_phase()
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
