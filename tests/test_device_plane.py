"""The pipeline plane's device path: one shard resident on each of 4
devices, one SPMD update per flush, the collective all-merge on read.

The device path needs 4 devices, and the host device count locks at JAX's
first use, so ``_device_plane_run.py`` runs it once in a subprocess with 4
forced CPU devices and prints its readings; the tests below assert on
them.  ``devices=1`` (today's sub-plane path) is tested in-process.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import engine as E
from repro.engine import planes as P

jax.config.update("jax_platform_name", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
D = 4


@pytest.fixture(scope="module")
def run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(HERE, "_device_plane_run.py")],
                       env=env, capture_output=True, text=True, timeout=600)
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert r.returncode == 0 and lines, r.stderr[-3000:]
    out = json.loads(lines[-1][len("RESULT "):])
    assert out["devices_seen"] == D
    return out


def test_collapsed_table_equals_the_one_device_plane(run):
    """Same events, same shards: the collective collapse ((0+1)+(2+3)) and
    the host fold (((0+1)+2)+3) differ by fp32 summation order only, and
    the read is an ordinary array on the first device."""
    for r in run["vs_one_device"]:
        assert r["max_diff"] <= 1e-6 * r["scale"], r
        assert r["sharding"].startswith("SingleDeviceSharding")
        assert "id=0" in r["device"] and r["shape"] == [1, 5, 512]


def test_top_candidate_estimates_equal_the_float64_policy(run):
    """The first C/4 ranks of the candidates' estimates, read from the
    collapsed table, against the float64 one-pass policy's candidates
    collapsed in its own order: equal to rounding, as the table is."""
    for r in run["vs_policy"]:
        assert r["gap"] <= 2e-6 * r["scale"], r
        assert r["gap"] <= r["table_err"] * (1 + 1e-6), r


def test_sample_keys_equal_the_one_device_plane_but_near_ties(run):
    for r in run["sample"]:
        for est in r["odd_est"]:
            assert abs(est - r["threshold"]) <= 1e-4 * r["threshold"], r


def test_each_device_holds_its_key_hash_shard(run):
    """Device s holds exactly the one-device plane's shard s (routing by
    key hash), so a retraction lands on its insertion's device: retracting
    every event leaves every device's table exactly zero."""
    for s, r in enumerate(run["per_device"]):
        assert r["device"] == f"TFRT_CPU_{s}"
        assert r["index"].startswith(f"(slice({s}, {s + 1}, None)")
        assert r["max_diff"] <= 1e-6 and r["nonzero"], r
    assert run["after_retracting_all"] == 0.0


def test_collapse_span_and_counts(run):
    """One ``plane.collapse`` per read after an ingest (a second read hits
    the cache): 2 collective rounds on the device path, 3 host merges on
    the sub-plane path, one shard state's bytes each."""
    b = run["state_bytes"]
    assert b == 5 * 512 * 4 + 128 * 4 + 4 + 4
    assert run["spans"] == [
        ["plane.collapse", {"devices": D, "rounds": 2, "state_bytes": b}],
        ["plane.collapse", {"devices": 1, "rounds": D - 1, "state_bytes": b}]]


def test_collapse_hlo_is_the_collective_program(run):
    """The device path hands out its collapse program's compiled text: the
    collective butterfly's rounds (a state's leaves permuted per round);
    the sub-plane path has no such program."""
    h = run["collapse_hlo"]
    assert h["module"] == "HloModule jit_collapse"
    assert h["permutes"] >= 2 and h["permutes"] % 2 == 0
    assert h["sub_plane"] == ""


def test_dispatch_slots_count_every_device_row(run):
    assert run["dispatch_slots"][0] == D * run["stacked_width"]


def test_one_program_per_compacted_width(run):
    c = run["compiles"]
    assert len(c["widths"]) == 3 and c["programs"] == len(c["widths"])


def test_set_state_restores_the_collapsed_state(run):
    assert run["set_state_diff"] == 0.0


def test_ingest_shard_raises_on_the_device_path(run):
    assert "no device path" in run["ingest_shard"]


@pytest.mark.parametrize("case,words", [
    ("too_many", "JAX sees 4 device"),
    ("not_shards", "needs shards=4"),
    ("subplane", "has no device path"),
    ("codec", "lossy codec"),
])
def test_device_path_errors(run, case, words):
    assert run["errors"][case] is not None and words in run["errors"][case]


def test_too_few_devices_in_process():
    """This process has one CPU device: asking for 4 raises, no fallback."""
    cfg = E.EngineConfig(num_streams=1, rows=3, width=128, candidates=32)
    with pytest.raises(ValueError, match="JAX sees 1 device"):
        E.SketchEngine(cfg, plane="pipeline",
                       plane_opts={"shards": 4, "devices": 4})


def test_devices_1_is_the_sub_plane_path():
    """``devices=1`` is today's path: bitwise the default's state."""
    cfg = E.EngineConfig(num_streams=2, rows=3, width=256, candidates=32,
                         p=2.0, scheme="priority", seed=5)
    rng = np.random.default_rng(3)
    batches = [(rng.integers(0, 900, (2, 100)).astype(np.int32),
                rng.normal(size=(2, 100)).astype(np.float32))
               for _ in range(3)]
    states = []
    for opts in ({"shards": 4}, {"shards": 4, "devices": 1}):
        eng = E.SketchEngine(cfg, plane="pipeline", flush_elems=100,
                             plane_opts=opts)
        assert len(eng.plane._subplanes) == 4
        for k, v in batches:
            eng.ingest(k, v)
        states.append(eng.state)
    for a, b in zip(jax.tree_util.tree_leaves(states[0]),
                    jax.tree_util.tree_leaves(states[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stack_by_key_stacks_partition_by_key():
    """Row block s of ``stack_by_key`` is ``partition_by_key``'s shard s,
    padded to the widest shard's lane multiple."""
    rng = np.random.default_rng(4)
    keys = rng.integers(0, 5000, (2, 700)).astype(np.int32)
    keys[:, ::9] = -1
    vals = rng.normal(size=(2, 700)).astype(np.float32)
    sk, sv = P.stack_by_key(keys, vals, D)
    parts = P.partition_by_key(keys, vals, D)
    m = max(k.shape[1] for k, _ in parts)
    assert sk.shape == (2 * D, m) and m % 128 == 0
    for s, (k, v) in enumerate(parts):
        w = k.shape[1]
        np.testing.assert_array_equal(sk[2 * s:2 * s + 2, :w], k)
        np.testing.assert_array_equal(sv[2 * s:2 * s + 2, :w], v)
        assert (sk[2 * s:2 * s + 2, w:] == -1).all()
        assert (sv[2 * s:2 * s + 2, w:] == 0).all()
