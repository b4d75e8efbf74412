"""Tests of the chip benchmark's yardstick, on the CPU at tiny sizes.

The harness's look for a chip is turned off here only (``require_tpu``).
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import harness  # noqa: E402
import reference as ref  # noqa: E402
import tracing  # noqa: E402
import work  # noqa: E402
import ycsb  # noqa: E402

# 2.2 s of a traced stream.ingest window on one TPU v5e
RECORDED = os.path.join(HERE, "testdata", "stream_ingest.xplane.pb")


# -- the YCSB generator and the pool --------------------------------------------

def test_zipfian_rank_frequencies_follow_theta():
    """Empirical rank frequencies of 2^21 draws match the generator's pmf
    within 5 binomial standard deviations, and the pmf's log-log slope
    over ranks 10..1000 is -theta within 2%."""
    gen = ycsb.Zipfian(100_000, 0.99)
    n = 1 << 21
    r = gen.ranks(ycsb.rng_for(5, 0).random(n))
    ranks = np.arange(50)
    want = gen.pmf(ranks)
    got = np.bincount(r, minlength=50)[:50] / n
    sd = np.sqrt(want * (1 - want) / n)
    assert np.all(np.abs(got - want) <= 5 * sd), (got - want) / sd
    assert abs(gen.pmf(np.arange(gen.items)).sum() - 1.0) < 1e-9
    lo, hi = gen.pmf(np.array([10.0, 1000.0]))
    slope = np.log(hi / lo) / np.log(1000.0 / 10.0)
    assert abs(slope + 0.99) < 0.02 * 0.99, slope


def _fnvhash64_java(val: int) -> int:
    """YCSB's Utils.fnvhash64 line by line, with Java's wrapping longs."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = ((h ^ (val & 0xFF)) * 1099511628211) & (2**64 - 1)
        val >>= 8
    h = h - 2**64 if h >= 2**63 else h
    return abs(h)


def test_fnvhash64_matches_ycsb():
    vals = [0, 1, 255, 256, 2**31 - 1, 10**10, 123456789012]
    got = ycsb.fnvhash64(np.array(vals, np.int64))
    assert got.tolist() == [_fnvhash64_java(v) for v in vals]


def test_pool_is_a_pure_function_of_the_seed():
    a = ycsb.build_pool(2**40 + 3, 3, 160, 4, 1 << 24)[0]
    b = ycsb.build_pool(2**40 + 3, 3, 160, 4, 1 << 24)[0]
    c = ycsb.build_pool(2**40 + 4, 3, 160, 4, 1 << 24)[0]
    for (ka, va), (kb, vb) in zip(a, b):
        assert np.array_equal(ka, kb) and np.array_equal(va, vb)
    assert not all(np.array_equal(ka, kc) for (ka, _), (kc, _) in zip(a, c))


@pytest.mark.parametrize("start", [0, 3])
def test_every_retraction_cancels_an_insertion_across_the_wrap(start):
    """Ingesting the primer, then the pool in cycles, never takes a key's
    net frequency below zero, from whichever block the cycle starts; a
    rotated pool holds the same blocks in another order."""
    pool, primer = ycsb.build_pool(9, 2, 125, 5, 1000, start=start)
    base = ycsb.build_pool(9, 2, 125, 5, 1000)[0]
    for b, (keys, vals) in enumerate(pool):
        k0, v0 = base[(b + start) % len(base)]
        assert np.array_equal(keys, k0) and np.array_equal(vals, v0)
    assert np.array_equal(primer[0][:, :100], pool[-1][0][:, :100])
    assert (primer[0][:, 100:] == -1).all()
    ins, nret = 100, 25
    for keys, vals in pool:
        assert ((keys >= 0).sum(axis=1) == ins + nret).all()
        assert (vals[:, :ins] == 1).all() and (vals[:, ins:ins + nret] == -1).all()
    for b, (keys, _) in enumerate(pool):
        prev = pool[b - 1][0]
        assert np.array_equal(keys[:, ins:ins + nret], prev[:, :nret])
    for s in range(2):
        f = np.zeros(1000)
        for k, v in [primer] + pool * 3:
            np.add.at(f, k[s][k[s] >= 0], v[s][k[s] >= 0])
            assert f.min() >= 0


# -- the reference --------------------------------------------------------------

def test_reference_matches_the_program_hashes():
    """The reference restates the program's hash family: same buckets,
    signs and randomizers, bit for bit."""
    import jax.numpy as jnp

    from repro.core import hashing, transforms

    keys = np.arange(-1, 5000, 7, dtype=np.int32)
    seed = 0xDEADBEEF
    b, s = ref.buckets_signs(keys, seed, 5, 31744)
    for r in range(5):
        salt = hashing.row_salt(jnp.uint32(seed), jnp.uint32(r))
        assert np.array_equal(b[r], np.asarray(hashing.bucket_hash(
            keys.astype(np.uint32), salt, 31744)))
        assert np.array_equal(s[r], np.asarray(hashing.sign_hash(
            keys.astype(np.uint32), salt)))
    for scheme in ("ppswor", "priority"):
        want = np.asarray(transforms.randomizer(keys.astype(np.uint32),
                                                jnp.uint32(seed), scheme))
        got = ref.randomizer(keys, seed, scheme)
        np.testing.assert_allclose(got, want, rtol=2e-7)
    live = keys[keys >= 0]
    for shards in (1, 4):
        assert np.array_equal(ref.shard_of(live, shards),
                              hashing.shard_of_keys(live, shards))


# -- work counts and the trace reduction -------------------------------------------

def test_work_counts_depend_on_shapes_only():
    assert work.scatter_bytes(1000, 4, 5, 100) == 8 * 1000 + 8 * 2000
    assert work.scatter_bytes(10, 4, 5, 100) == 8 * 10 + 8 * 50
    assert work.query_bytes(100, 5) == 400 + 4000
    assert work.roofline_pct(819e9, 0.0, 2.0, work.peaks("TPU v5 lite")) == 50.0
    assert work.roofline_pct(1.0, 0.0, 0.0, work.peaks("TPU v5 lite")) is None
    with pytest.raises(KeyError):
        work.peaks("cpu")


def _ev(plane, line, name, start, dur):
    return tracing.Event(plane, line, name, start, dur)


def test_reduce_busy_union_idle_and_gaps():
    d, h = "/device:TPU:0", tracing.HOST_PLANE
    evs = [
        _ev(h, "t", "window", 0, 100),
        _ev(h, "t", "ingest", 0, 30),
        _ev(h, "t", "read", 60, 30),
        _ev(d, tracing.OPS_LINE, "worp_countsketch_scatter_batched", 10, 20),
        _ev(d, tracing.OPS_LINE, "fusion.1", 25, 10),     # overlaps: union
        _ev(d, tracing.OPS_LINE, "worp_countsketch_query_batched", 50, 20),
        _ev(d, tracing.OPS_LINE, "fusion.2", 95, 20),     # clipped at 100
        _ev(d, "XLA Modules", "jit_step", 0, 100),        # not an op line
    ]
    s = tracing.reduce(evs)
    assert s["window_s"] == 100e-9
    assert s["busy_s"] == pytest.approx((25 + 20 + 5) * 1e-9)
    assert tracing.kernel_s(s, "scatter_batched") == pytest.approx(20e-9)
    gaps = dict((round(g * 1e9), n) for n, g in s["idle_gaps"])
    assert gaps == {10: "ingest", 15: "none", 25: "read"}


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_reduce_recorded_trace():
    s = tracing.reduce(tracing.load(RECORDED))
    assert 0 < s["busy_s"] <= s["window_s"]
    assert tracing.kernel_s(s, "worp_countsketch_scatter_batched") > 0
    assert tracing.kernel_s(s, "worp_countsketch_query_batched") > 0
    assert s["idle_gaps"] and all(n in ("generate", "ingest", "read", "wait",
                                        "none") for n, _ in s["idle_gaps"])


# -- the harness, driven by data ------------------------------------------------------

TINY = {
    "tiny_sparse": {
        "source": "test", "engine": {"num_streams": 4, "rows": 5, "width": 512,
                                     "candidates": 64, "p": 1.0,
                                     "scheme": "ppswor", "sampler": "onepass"},
        "plane": "sparse", "plane_opts": {}, "flush_elems": 128,
        "sample_k": 16, "keys": {"theta": 0.99, "records": 4096,
                                 "retract": 0.25}},
    "tiny_pipeline": {
        "source": "test", "engine": {"num_streams": 1, "rows": 5, "width": 512,
                                     "candidates": 64, "p": 2.0,
                                     "scheme": "priority", "sampler": "onepass"},
        "plane": "pipeline", "plane_opts": {"shards": 2, "subplane": "sparse"},
        "flush_elems": 256, "sample_k": 16,
        "keys": {"theta": 0.99, "records": 4096, "retract": 0.25}},
}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A throwaway benchmark: its own BENCHMARK.json, configurations, mixes
    and limits, found through the harness's discovery; drivers and
    metrics come from the benchmark's own directory."""
    d = tmp_path_factory.mktemp("bench")
    for sub in ("configs", "mixes", "limits"):
        (d / sub).mkdir()
    for name, c in TINY.items():
        (d / "configs" / f"{name}.json").write_text(json.dumps(c))
    (d / "mixes" / "tiny_closed.json").write_text(json.dumps(
        {"driver": "closed_ingest", "pool_blocks": 3, "inflight": 2}))
    (d / "mixes" / "tiny_reads.json").write_text(json.dumps(
        {"driver": "open_read", "pool_blocks": 3, "inflight": 2,
         "events_per_s": 2000, "reads_per_s": 6, "read": "all"}))
    (d / "limits" / "t.ingest.json").write_text(json.dumps(
        {"table_err": 1e-4, "cand_err": 1e-4}))
    (d / "mixes" / "tiny_tenant_reads.json").write_text(json.dumps(
        {"driver": "open_read", "pool_blocks": 3, "inflight": 2,
         "events_per_s": 4000, "reads_per_s": 6, "read": "one_stream"}))
    for w in ("t.read", "t.tenant_read"):
        (d / "limits" / f"{w}.json").write_text(json.dumps(
            {"table_err": 1e-5, "est_err": 1e-5, "thr_err": 1e-5}))
    bench = {
        "workloads": [
            {"name": "t.ingest", "config": "tiny_sparse",
             "traffic": "tiny_closed", "chips": 1},
            {"name": "t.read", "config": "tiny_pipeline",
             "traffic": "tiny_reads", "chips": 1},
            {"name": "t.tenant_read", "config": "tiny_sparse",
             "traffic": "tiny_tenant_reads", "chips": 1}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s"},
            {"name": "ingest_events_per_s", "unit": "events/s",
             "workloads": ["t.ingest"]},
            {"name": "read_p95_ms", "unit": "ms",
             "workloads": ["t.read", "t.tenant_read"]}],
        "per_layer": [
            {"name": "ingest_host_us_per_kevent", "unit": "us",
             "moves": "ingest_events_per_s"},
            {"name": "read_merge_ms", "unit": "ms", "moves": "read_p95_ms"}],
    }
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    return d


def _run(tiny, workload, seed=2**33 + 1, seconds=0.6, trace=False):
    return harness.run(str(tiny / "BENCHMARK.json"), workload, seed, seconds,
                       trace, dirs=(str(tiny), harness.BENCH_DIR),
                       require_tpu=False, log=lambda m: None)


@pytest.mark.parametrize("workload", ["t.ingest", "t.read", "t.tenant_read"])
def test_tiny_cell_runs_correct(tiny, workload):
    r = _run(tiny, workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {
        "t.ingest": {"setup_s", "ingest_events_per_s"},
        "t.read": {"setup_s", "read_p95_ms"},
        "t.tenant_read": {"setup_s", "read_p95_ms"}}[workload]
    assert r["window_compiles"] == 0


def test_tiny_cell_per_layer_metrics(tiny):
    r = _run(tiny, "t.read", trace=False)
    assert "read_merge_ms" not in r["metrics"]
    ctx_metrics = harness.Cell(json.loads((tiny / "BENCHMARK.json").read_text()),
                               "t.read", (str(tiny), harness.BENCH_DIR))
    assert [m["name"] for m in ctx_metrics.per_layer] == ["read_merge_ms"]


def _alter_answer(orig):
    def sample_state(self, state, k):
        s = orig(self, state, k)
        return s._replace(keys=s.keys + 1)
    return sample_state


def _drop_half(orig):
    def ingest(self, keys, values):
        keys = np.array(keys, copy=True)
        keys[:, keys.shape[1] // 2:] = -1
        return orig(self, keys, values)
    return ingest


def _wrong_bucket(orig):
    def estimate_batched(tables, keys, seeds, **kw):
        return orig(tables, keys, seeds + 1, **kw)
    return estimate_batched


@pytest.mark.parametrize("fault,workload", [
    ("state_unchanged", "t.ingest"),
    ("half_the_batch", "t.ingest"),
    ("query_wrong_bucket", "t.ingest"),
    ("candidates_unrefreshed", "t.ingest"),
    ("answer_altered", "t.read"),
    ("answer_altered", "t.tenant_read"),
])
def test_faults_come_out_not_correct(tiny, monkeypatch, fault, workload):
    import jax

    from repro.engine import engine as E
    from repro.engine import planes
    from repro.kernels import ops

    if fault == "state_unchanged":
        monkeypatch.setattr(E.SketchEngine, "ingest",
                            lambda self, k, v: self)
    elif fault == "half_the_batch":
        monkeypatch.setattr(E.SketchEngine, "ingest",
                            _drop_half(E.SketchEngine.ingest))
    elif fault == "query_wrong_bucket":
        monkeypatch.setattr(ops, "estimate_batched",
                            _wrong_bucket(ops.estimate_batched))
    elif fault == "candidates_unrefreshed":
        monkeypatch.setattr(planes, "_refresh_candidates",
                            lambda sk, cand, keys, **kw: cand)
    else:
        monkeypatch.setattr(E.SketchEngine, "sample_state",
                            _alter_answer(E.SketchEngine.sample_state))
    jax.clear_caches()
    try:
        # an ingest that does nothing lets the window replay thousands of
        # blocks, each of which the reference then replays: keep it short
        r = _run(tiny, workload,
                 seconds=0.05 if fault == "state_unchanged" else 0.6)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("workload", ["t.ingest", "t.read"])
def test_control_comes_out_not_correct(tiny, workload):
    import control

    control.use_control(True)
    try:
        r = _run(tiny, workload)
    finally:
        control.use_control(False)
    assert not r["correct"], r["checks"]
    assert r["checks"]["table_err"]["value"] > r["checks"]["table_err"]["limit"]
