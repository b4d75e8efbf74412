"""Tests of the ``stream4.read`` cell's pieces, on the CPU at tiny sizes.

The cell's plane keeps one shard on each of 4 devices.  This process has
one CPU device and its device count locks at JAX's first use, so the end
to end run goes through a subprocess with 4 forced host devices: this
file, run as a script on a throwaway benchmark directory.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import collective_work as cw  # noqa: E402
import harness  # noqa: E402
import tracing  # noqa: E402

TINY = {
    "source": "test", "engine": {"num_streams": 1, "rows": 5, "width": 512,
                                 "candidates": 64, "p": 2.0,
                                 "scheme": "priority", "sampler": "onepass"},
    "plane": "pipeline",
    "plane_opts": {"shards": 4, "subplane": "sparse", "devices": 4},
    "flush_elems": 256, "sample_k": 16,
    "keys": {"theta": 0.99, "records": 4096, "retract": 0.25}}


def tiny_bench(d: str) -> str:
    """A throwaway benchmark with one tiny cell of the new kind: its own
    configuration, mix and limits; driver and readers are the benchmark's."""
    for sub in ("configs", "mixes", "limits"):
        os.makedirs(os.path.join(d, sub), exist_ok=True)

    def put(path, obj):
        with open(os.path.join(d, path), "w") as f:
            json.dump(obj, f)

    put("configs/tiny_d4.json", TINY)
    put("mixes/tiny_reads.json", {
        "driver": "closed_ingest_reads", "pool_blocks": 3, "inflight": 2,
        "read_every_blocks": 2, "reads_per_s": 1, "read": "all"})
    put("limits/t4.read.json", {"table_err": 1e-5, "cand_err": 1e-5,
                                "est_err": 1e-5, "thr_err": 1e-5})
    put("BENCHMARK.json", {
        "workloads": [{"name": "t4.read", "config": "tiny_d4",
                       "traffic": "tiny_reads", "chips": 4}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "ingest_events_per_s", "unit": "events/s"}],
        "per_layer": [{"name": "collapse_host_us.read", "unit": "us",
                       "moves": "ingest_events_per_s"}]})
    return d


def run_tiny(d: str) -> None:
    """The tiny cell as the program runs it, then under the bf16 control;
    one JSON line each.  The program's run also reports where its window's
    enqueue spans lie and what the host-span readers read of it."""
    import control
    import program_spans as ps
    from repro import obs

    kept = []

    class KeptSpans(tracing.Spans):
        def __init__(self, annotate=False):
            super().__init__(annotate)
            kept.append(self)

    harness.tracing.Spans = KeptSpans
    for on in (False, True):
        control.use_control(on)
        logs = []
        r = harness.run(os.path.join(d, "BENCHMARK.json"), "t4.read",
                        2**33 + 5, 0.6, False, dirs=(d, HERE),
                        require_tpu=False, log=logs.append)
        out = {"control": on, "result": r, "logs": logs}
        if not on:
            # a nominal event count: the test reads only the signs
            run = {"spans": kept[-1], "events": 1000}
            recs = ps.window_records(run)
            by_id = {x.id: x for x in recs}
            out["enqueue_under"] = sorted({
                getattr(ps.ancestor(x, by_id, ps.INGEST), "name", "none")
                for x in recs if x.name in ps.ENQUEUE})
            out["flushes"] = sum(x.name == "engine.flush" for x in recs)
            out["host_metrics"] = {
                name: _reader(name).read(run) for name in (
                    "ingest_host_work_us_per_kevent",
                    "ingest_enqueue_us_per_kevent")}
        obs.reset()
        print("RESULT " + json.dumps(out), flush=True)


@pytest.fixture(scope="module")
def tiny_runs(tmp_path_factory):
    d = tiny_bench(str(tmp_path_factory.mktemp("bench4")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.abspath(__file__), d],
                       env=env, capture_output=True, text=True, timeout=900)
    out = [json.loads(ln[len("RESULT "):]) for ln in p.stdout.splitlines()
           if ln.startswith("RESULT ")]
    assert p.returncode == 0 and len(out) == 2, p.stderr[-3000:]
    return {o["control"]: o for o in out}


def test_tiny_cell_runs_correct_on_4_devices(tiny_runs):
    o = tiny_runs[False]
    r = o["result"]
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4 and r["device"]["platform"] == "cpu"
    assert set(r["metrics"]) == {"setup_s", "ingest_events_per_s"}
    assert r["window_compiles"] == 0
    assert r["info"]["reads_checked"] > 0
    window = next(ln for ln in o["logs"] if ln.startswith("window"))
    blocks, reads = (int(re.search(rf"(\d+) {w}", window).group(1))
                     for w in ("blocks ingested", "reads"))
    assert reads > 0 and r["attempted"] == (blocks - 1) + reads  # - primer


def test_reads_flush_nothing_so_host_spans_read_ingest_only(tiny_runs):
    """A block is one flush, so every flush runs inside ``engine.ingest``
    and a read's drain (``engine.flush``) finds the buffer empty: the
    host-span readers, which count ``engine.ingest`` and the enqueue spans,
    read ingest only in this cell."""
    o = tiny_runs[False]
    assert o["flushes"] > 0
    assert o["enqueue_under"] == ["engine.ingest"]
    assert all(v > 0 for v in o["host_metrics"].values()), o["host_metrics"]


def test_tiny_cell_under_the_control_is_not_correct(tiny_runs):
    r = tiny_runs[True]["result"]
    assert not r["correct"], r["checks"]
    assert r["checks"]["table_err"]["value"] > r["checks"]["table_err"]["limit"]


def test_the_cell_is_found_with_its_files():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.Cell(bench, "stream4.read", (HERE,))
    assert cell.chips == 4
    assert cell.config["plane_opts"]["devices"] == 4
    assert cell.mix["read_every_blocks"] == 16
    assert sorted(cell.limits) == ["cand_err", "est_err", "table_err",
                                   "thr_err"]
    assert [m["name"] for m in cell.per_layer] == [
        "device_idle_pct.ingest", "ingest_host_work_us_per_kevent",
        "ingest_enqueue_us_per_kevent", "scatter_pad_share.ingest",
        "collapse_ici_roofline.read", "collapse_host_us.read"]


# -- the readers and the work count ------------------------------------------

def test_least_ici_bytes_by_hand():
    # one shard state of the stream cell: a 5 x 31,744 f32 table, 4,096
    # int32 candidates and two uint32 seeds
    state = 5 * 31744 * 4 + 4096 * 4 + 4 + 4
    assert state == 651_272
    assert cw.least_ici_bytes(4, state) == 3 * 651_272 / 4 == 488_454
    assert cw.least_ici_bytes(2, 1000) == 500
    assert cw.least_ici_bytes(1, 1000) == 0
    assert cw.ici_peak("TPU v5 lite") == 1600e9 / 8
    with pytest.raises(KeyError):
        cw.ici_peak("cpu")


# a trace's operations (operand shapes printed) and the compiled collapse
# program's HLO text (operand shapes left out), in the forms a v5e gives
TRACE_OPS = {
    "%collective-permute-start = (f32[1,5,31744]{2,0,1:T(1,128)}, "
    "f32[1,5,31744]{2,0,1:T(1,128)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) "
    "collective-permute-start(f32[1,5,31744]{2,0,1:T(1,128)} %param.10), "
    "channel_id=1, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}": 1e-6,
    "%collective-permute-done = f32[1,5,31744]{2,0,1:T(1,128)S(1)} "
    "collective-permute-done((f32[1,5,31744]{2,0,1:T(1,128)}, "
    "f32[1,5,31744]{2,0,1:T(1,128)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) "
    "%collective-permute-start)": 3e-6,
    "%add.125 = f32[1,5,31744]{2,0,1:T(1,128)S(1)} add(f32[1,5,31744]"
    "{2,0,1:T(1,128)S(1)} %copy-done.1, f32[1,5,31744]{2,0,1:T(1,128)S(1)} "
    "%collective-permute-done)": 4e-4,
    "%fusion.5 = f32[40960]{0:T(1024)S(1)} fusion(f32[1,5,31744]"
    "{2,0,1:T(1,128)S(1)} %add.125, s32[40960]{0:T(1024)S(1)} %reshape.139), "
    "kind=kCustom, calls=%fused_computation.5": 6e-4,
    # another program's: the same instruction name, another shape
    "%fusion.5 = f32[23040]{0:T(1024)S(1)} fusion(f32[1,23040]{1,0:T(1,128)"
    "S(1)} %compare_select_fusion.1, s32[23040]{0:T(1024)S(1)} %fusion.7), "
    "kind=kCustom, calls=%fused_computation.5": 2e-3,
    "%worp_countsketch_scatter_batched.1 = f32[1,5,31744]{2,1,0:T(8,128)S(1)} "
    "custom-call(s32[1,128]{1,0:T(1,128)S(1)} %scatter.28)": 5e-3,
}
COLLAPSE_HLO = """HloModule jit_collapse, is_scheduled=true

%fused_computation.5 (param_0.1: f32[1,5,31744], param_1.1: s32[40960]) -> f32[40960] {
  %param_0.1 = f32[1,5,31744]{2,0,1:T(1,128)} parameter(0)
  ROOT %gather.1 = f32[40960]{0:T(1024)} gather(%param_0.1, %param_1.1), slice_sizes={1,1,1}
}

ENTRY %main.1 (param.10: f32[1,5,31744]) -> f32[40960] {
  %param.10 = f32[1,5,31744]{2,0,1:T(1,128)} parameter(0), metadata={op_name="x"}
  %collective-permute-start = (f32[1,5,31744]{2,0,1:T(1,128)}, f32[1,5,31744]{2,0,1:T(1,128)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%param.10), channel_id=1, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}, metadata={op_name="jit(collapse)/shard_map/ppermute"}, backend_config={"barrier_config":{"id":"0"}}
  %collective-permute-done = f32[1,5,31744]{2,0,1:T(1,128)S(1)} collective-permute-done(%collective-permute-start), metadata={op_name="jit(collapse)/shard_map/ppermute"}
  %add.125 = f32[1,5,31744]{2,0,1:T(1,128)S(1)} add(%copy-done.1, %collective-permute-done), metadata={op_name="add"}
  ROOT %fusion.5 = f32[40960]{0:T(1024)S(1)} fusion(%add.125, %reshape.139), kind=kCustom, calls=%fused_computation.5, backend_config={"integer_config":{"integer":"64"}}
}
"""
COLLAPSE_S = 1e-6 + 3e-6 + 4e-4 + 6e-4


def test_op_key_reads_a_trace_name_and_an_hlo_line_alike():
    trace = next(n for n in TRACE_OPS if n.startswith("%fusion.5 = f32[40960]"))
    line = next(ln for ln in COLLAPSE_HLO.splitlines()
                if "ROOT %fusion.5" in ln)
    assert cw.op_key(trace) == cw.op_key(line) == (
        "%fusion.5", "f32[40960]{0:T(1024)S(1)}", "fusion",
        ("%add.125", "%reshape.139"))
    tup = cw.op_key(next(iter(TRACE_OPS)))
    assert tup[2] == "collective-permute-start" and tup[3] == ("%param.10",)
    assert cw.op_key("HloModule jit_collapse") is None
    assert cw.op_key("}") is None


def test_program_s_counts_the_entry_instructions_only():
    keys = cw.program_keys(COLLAPSE_HLO)
    assert {k[0] for k in keys} == {
        "%param.10", "%collective-permute-start", "%collective-permute-done",
        "%add.125", "%fusion.5"}              # not the fusion's own body
    assert cw.program_s(TRACE_OPS, COLLAPSE_HLO) == pytest.approx(COLLAPSE_S)
    assert cw.program_s(TRACE_OPS, "") == 0


def _synthetic_run(collapses: int, op_s: dict | None, hlo=COLLAPSE_HLO):
    """A run record: the benchmark's window span around ``collapses``
    program ``plane.collapse`` spans of 4 devices and 1,000 bytes; the
    driver's record holds the collapse program's text where ``hlo`` is
    not None."""
    from repro import obs

    obs.reset()
    spans = tracing.Spans()
    with spans.span(tracing.WINDOW):
        for _ in range(collapses):
            with obs.span("plane.collapse", devices=4, rounds=2,
                          state_bytes=1000):
                time.sleep(0.001)
    trace = None if op_s is None else {"op_s": op_s}
    record = {} if hlo is None else {"collapse_hlo": lambda: hlo}
    return {"spans": spans, "trace": trace, "events": 1000,
            "peak": {"hbm_bytes_per_s": 819e9}, "record": record}


def _reader(name):
    return harness.load_module(harness.find("metrics", name, (HERE,), ".py"))


def test_collapse_readers_on_a_synthetic_run(monkeypatch):
    from repro import obs

    run = _synthetic_run(2, TRACE_OPS)
    spent = [r.end_s - r.start_s for r in obs.records()
             if r.name == "plane.collapse"]
    host = _reader("collapse_host_us.read").read(run)
    assert host == pytest.approx(1e6 * sum(spent) / 2)
    monkeypatch.setattr(cw, "ici_peak", lambda kind: 200e9)
    share = _reader("collapse_ici_roofline.read").read(run)
    # two collapses of 3/4 of 1,000 bytes at 200 GB/s over the collapse
    # program's operations, the other programs' left out
    assert share == pytest.approx(100.0 * 1500 / 200e9 / COLLAPSE_S)


def test_collapse_readers_read_nothing_without_spans_or_trace(monkeypatch):
    monkeypatch.setattr(cw, "ici_peak", lambda kind: 200e9)
    none_yet = _synthetic_run(0, TRACE_OPS)     # a program without the span
    assert _reader("collapse_host_us.read").read(none_yet) is None
    assert _reader("collapse_ici_roofline.read").read(none_yet) is None
    untraced = _synthetic_run(2, None)
    assert _reader("collapse_ici_roofline.read").read(untraced) is None
    no_program = _synthetic_run(2, TRACE_OPS, hlo=None)   # an older driver
    assert _reader("collapse_ici_roofline.read").read(no_program) is None
    sub_plane = _synthetic_run(2, TRACE_OPS, hlo="")      # no device path
    assert _reader("collapse_ici_roofline.read").read(sub_plane) is None


if __name__ == "__main__":
    run_tiny(sys.argv[1])
