"""The program's span recorder (``repro.obs``), the spans the engine takes on
its ingest path, and the benchmark's per-layer metrics that read them."""
from __future__ import annotations

import os
import sys
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.engine import EngineConfig, SketchEngine
from repro.engine import planes
from repro.kernels import tiling

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "chip")
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import tracing  # noqa: E402

CFG = EngineConfig(num_streams=2, rows=3, width=256, candidates=16, p=1.0,
                   scheme="priority", seed=11)


def _batch(n, seed=0, streams=CFG.num_streams):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 5000, size=(streams, n)).astype(np.int32)
    keys[:, -3:] = -1                       # padding slots
    vals = rng.choice([-1.0, 1.0, 2.0], size=(streams, n)).astype(np.float32)
    return keys, vals


def _slots(B, n):
    (_, b_pad), (_, n_pad) = tiling.scatter_tiles(B, n)
    return b_pad * n_pad


# -- the recorder ----------------------------------------------------------------

def test_nesting_and_parent_ids():
    rec = obs.Recorder()
    with rec.span("a") as a:
        assert rec.current() == a._id
        with rec.span("b", slots=7):
            with rec.span("c"):
                pass
        with rec.span("d"):
            pass
    with rec.span("e", parent=123):
        pass
    assert rec.current() == 0
    got = {r.name: r for r in rec.records()}
    assert [r.name for r in rec.records()] == ["c", "b", "d", "a", "e"]
    assert got["a"].parent == 0
    assert got["b"].parent == got["d"].parent == got["a"].id
    assert got["c"].parent == got["b"].id
    assert got["e"].parent == 123
    assert got["b"].counts == {"slots": 7} and got["a"].counts == {}
    assert got["a"].start_s <= got["b"].start_s <= got["c"].start_s \
        <= got["c"].end_s <= got["b"].end_s <= got["d"].start_s \
        <= got["d"].end_s <= got["a"].end_s
    assert rec.dropped == 0 and rec.dropped_until == float("-inf")


def test_ring_keeps_the_newest_and_counts_the_dropped():
    rec = obs.Recorder(capacity=4)
    for i in range(10):
        with rec.span(f"s{i}"):
            pass
    kept = rec.records()
    assert [r.name for r in kept] == ["s6", "s7", "s8", "s9"]
    assert rec.dropped == 6
    assert kept[0].start_s >= rec.dropped_until > float("-inf")
    rec.reset()
    assert rec.records() == [] and rec.dropped == 0


def test_spans_from_many_threads_keep_their_own_parents():
    rec = obs.Recorder()

    def work(i):
        for _ in range(50):
            with rec.span(f"outer{i}"):
                with rec.span(f"inner{i}"):
                    pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    by_id = {r.id: r for r in rec.records()}
    assert len(by_id) == 800
    for r in by_id.values():
        if r.name.startswith("inner"):
            assert by_id[r.parent].name == "outer" + r.name[5:]


def test_disabled_recorder_is_a_shared_noop():
    obs.reset()
    obs.disable()
    try:
        a, b = obs.span("x"), obs.span("y", slots=3)
        assert a is b
        with a:
            assert obs.current() == 0
        eng = SketchEngine(CFG, plane="sparse", flush_elems=8)
        eng.ingest(*_batch(8))
        eng.flush()
        assert obs.records() == []
    finally:
        obs.enable()
    with obs.span("z"):
        pass
    assert [r.name for r in obs.records()] == ["z"]


# -- the engine's spans -------------------------------------------------------------

def test_span_tree_of_one_ingest_on_the_sparse_plane():
    eng = SketchEngine(CFG, plane="sparse", flush_elems=200)
    keys, vals = _batch(200)
    obs.reset()
    eng.ingest(keys, vals)
    recs = obs.records()
    assert sorted(r.name for r in recs) == [
        "engine.ingest", "plane.dispatch", "plane.stage"]
    by = {r.name: r for r in recs}
    root = by["engine.ingest"]
    assert root.parent == 0
    assert by["plane.stage"].parent == by["plane.dispatch"].parent == root.id
    assert by["plane.stage"].end_s <= by["plane.dispatch"].start_s
    assert by["plane.dispatch"].counts == {"slots": _slots(2, 200)}
    assert _slots(2, 200) == 2 * 256        # B < 8: no padded streams


def test_span_tree_of_one_ingest_on_the_pipeline_plane():
    eng = SketchEngine(CFG._replace(shared_seeds=True), plane="pipeline",
                       flush_elems=300,
                       plane_opts={"shards": 4, "subplane": "sparse"})
    keys, vals = _batch(300, seed=1)
    parts = [k.shape for k, _ in planes.partition_by_key(keys, vals, 4)
             if k.shape[1]]
    obs.reset()
    eng.ingest(keys, vals)
    recs = obs.records()
    roots = [r for r in recs if r.name == "engine.ingest"]
    assert len(roots) == 1 and roots[0].parent == 0
    root = roots[0]
    assert all(r.parent == root.id for r in recs if r is not root)
    assert [r.name for r in recs if r.name == "plane.route"] == ["plane.route"]
    stages = [r for r in recs if r.name == "plane.stage"]
    dispatches = [r for r in recs if r.name == "plane.dispatch"]
    assert len(stages) == len(dispatches) == len(parts) == 4
    assert [d.counts["slots"] for d in dispatches] == [
        _slots(b, n) for b, n in parts]
    assert all(r.start_s >= root.start_s and r.end_s <= root.end_s
               for r in recs)


def test_async_worker_spans_take_the_submitting_flush_as_parent():
    eng = SketchEngine(CFG, plane="async", flush_elems=10_000)
    try:
        eng.ingest(*_batch(64, seed=2))
        obs.reset()
        eng.flush()
        recs = obs.records()
        flush = [r for r in recs if r.name == "engine.flush"]
        assert len(flush) == 1
        worker = [r for r in recs if r.name in ("plane.stage",
                                                "plane.dispatch")]
        assert sorted(r.name for r in worker) == ["plane.dispatch",
                                                  "plane.stage"]
        assert all(r.parent == flush[0].id for r in worker)
        d = next(r for r in worker if r.name == "plane.dispatch")
        assert d.counts == {"slots": _slots(2, 64)}
    finally:
        eng.plane.close()


def test_ingest_spans_never_wait_for_the_device(monkeypatch):
    eng = SketchEngine(CFG, plane="sparse", flush_elems=100)

    def refuse(*a, **k):
        raise AssertionError("the ingest path waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    obs.reset()
    for i in range(3):
        eng.ingest(*_batch(100, seed=10 + i))
    names = [r.name for r in obs.records()]
    assert names.count("plane.dispatch") == 3
    assert names.count("engine.ingest") == 3


def test_spans_land_in_a_profiler_trace_inside_the_callers_span(tmp_path):
    """Each span is also a TraceAnnotation: on the trace's host plane the
    engine's spans lie inside the benchmark's own ``ingest`` span."""
    eng = SketchEngine(CFG._replace(shared_seeds=True), plane="pipeline",
                       flush_elems=128, plane_opts={"shards": 2})
    eng.ingest(*_batch(128, seed=3))          # compile outside the trace
    spans = tracing.Spans(annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("ingest"):
            eng.ingest(*_batch(128, seed=4))
    finally:
        jax.profiler.stop_trace()
    pb = next(tmp_path.rglob("*.xplane.pb"))
    host = [e for e in tracing.load(str(pb)) if e.plane == tracing.HOST_PLANE]
    outer = [e for e in host if e.name == "ingest"]
    mine = [e for e in host if e.name in ("engine.ingest", "plane.route",
                                          "plane.stage", "plane.dispatch")]
    assert len(outer) == 1
    assert sorted({e.name for e in mine}) == [
        "engine.ingest", "plane.dispatch", "plane.route", "plane.stage"]
    o = outer[0]
    assert all(e.line == o.line and o.start_ns <= e.start_ns
               and e.start_ns + e.dur_ns <= o.start_ns + o.dur_ns
               for e in mine)


def test_scatter_slots_count_every_scatter_call():
    onepass = SketchEngine(CFG).spec
    tv = SketchEngine(CFG, sampler="tv").spec
    perfect = SketchEngine(CFG, sampler="perfect", plane="dense").spec
    assert planes.scatter_slots(onepass, 2, 600) == 2 * 1024
    assert planes.scatter_slots(tv, 2, 600) == (
        _slots(2 * CFG.num_samplers, 600) + _slots(2, 600))
    assert planes.scatter_slots(perfect, 2, 600) == 0
    assert planes.scatter_slots(onepass, 2, 0) == 0


# -- the benchmark's readers -----------------------------------------------------------

READERS = ("ingest_host_work_us_per_kevent", "ingest_enqueue_us_per_kevent",
           "scatter_pad_share.ingest")


def _reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def _synthetic_run(monkeypatch, dropped_until=float("-inf")):
    R = obs.Record
    recs = [
        # set-up's primer ingest, before the window: never counted
        R(1, 0, "engine.ingest", 2.0, 5.0, {}),
        R(2, 1, "plane.dispatch", 3.0, 4.0, {"slots": 1000}),
        # a sparse-plane ingest: 0.1 s staging, 0.5 s dispatching
        R(11, 10, "plane.stage", 11.1, 11.2, {}),
        R(12, 10, "plane.dispatch", 11.2, 11.7, {"slots": 2048}),
        R(10, 0, "engine.ingest", 11.0, 12.0, {}),
        # a pipeline-plane ingest: routing is host work
        R(21, 20, "plane.route", 13.0, 13.3, {}),
        R(22, 20, "plane.stage", 13.3, 13.4, {}),
        R(23, 20, "plane.dispatch", 13.4, 13.6, {"slots": 2048}),
        R(20, 0, "engine.ingest", 13.0, 14.0, {}),
        # straddles the window's end: not counted
        R(31, 30, "plane.dispatch", 19.6, 20.2, {"slots": 9999}),
        R(30, 0, "engine.ingest", 19.5, 20.5, {}),
    ]
    monkeypatch.setattr(obs, "records", lambda: list(recs))
    monkeypatch.setattr(obs, "dropped_until", lambda: dropped_until)
    spans = tracing.Spans()
    spans.records = [("generate", 0.0, 1.0), ("ingest", 2.0, 5.0),
                     ("window", 10.0, 20.0), ("ingest", 11.0, 12.0),
                     ("ingest", 13.0, 14.0)]
    return {"spans": spans, "events": 2000, "trace": None}


@pytest.mark.parametrize("name,want", [
    ("ingest_host_work_us_per_kevent", 1e6 * (0.4 + 0.7) / 2),
    ("ingest_enqueue_us_per_kevent", 1e6 * (0.6 + 0.3) / 2),
    ("scatter_pad_share.ingest", 100.0 * (1 - 2000 / 4096)),
])
def test_readers_count_only_the_window(monkeypatch, name, want):
    """Set-up's spans and a span that straddles the window's end count for
    nothing, which is what ``ingest_host_us_per_kevent`` gets wrong."""
    assert _reader(name).read(_synthetic_run(monkeypatch)) == \
        pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_on_a_dropped_record(monkeypatch, name):
    reader = _reader(name)
    assert reader.read(_synthetic_run(monkeypatch, 5.0)) is not None
    assert reader.read(_synthetic_run(monkeypatch, 10.5)) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_without_program_spans(monkeypatch, name):
    run = _synthetic_run(monkeypatch)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(sys.modules["repro"], "obs")
    assert _reader(name).read(run) is None
