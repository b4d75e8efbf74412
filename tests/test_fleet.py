"""Fault-injected serving fleet (ISSUE 9): merge protocol, router
properties, and chaos recovery.

Four layers of guarantees:
  * merge protocol (in-process, tier-1): the ``fleet`` data plane's
    checkpoint round-trip + ``sharding.merge_states`` collapse is BITWISE
    equal to the plain pipeline collapse at R=2; the butterfly and tree
    reductions agree bitwise at power-of-two R; corrupted checkpoints fail
    CRC (IOError) and mismatched-seed shards fail the merge guard
    (ValueError) -- rejection, never silent merging.
  * router properties (hypothesis via tests/_hypothesis_compat): the host
    hash ``hash_u32_np`` is bit-compatible with the device ``hash_u32``,
    and ``shard_of_keys`` / ``partition_by_key`` are pure, in-range, and
    exactly partition every live event -- including the edge keys 0, the
    -1 padding sentinel, int32 extremes, and duplicates.
  * process fleet (tier-1): a replica killed mid-stream (applied, not
    acked, not committed) is respawned from its last published checkpoint
    and replayed; the aggregated sample stays bitwise equal to the
    single-process ``fleet`` plane reference.  Corrupt / wrong-seed
    publishes raise at the merge boundary and the fleet recovers once the
    fault clears.
  * chaos grid (@pytest.mark.chaos, seed-matrixed in CI via
    FLEET_CHAOS_SEED): hang detection via probe, delay + bounded-queue
    backpressure, non-power-of-two replica counts under windowed
    turnstile retractions, and double kills -- every scenario closes with
    the same bitwise-parity assertion.
"""
import collections
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as E
from repro.core import hashing
from repro.data.pipeline import TurnstileZipfStream
from repro.distributed import fleet as F
from repro.distributed import sharding as shd
from repro.engine import planes as P
from repro.launch.fleet_serve import traffic
from repro.train import checkpoint
from tests._hypothesis_compat import given, settings, st

jax.config.update("jax_platform_name", "cpu")

# CI matrixes the chaos suite over seeds; everything stream- or
# fault-placement-shaped derives from this one knob
FLEET_CHAOS_SEED = int(os.environ.get("FLEET_CHAOS_SEED", "0"))


def _cfg(seed=7, **kw):
    base = dict(num_streams=3, rows=3, width=128, candidates=16,
                capacity=16, p=1.0, seed=seed, sampler="onepass",
                domain=40, num_samplers=8)
    base.update(kw)
    return E.EngineConfig(**base)


def _batches(nb, seed, B=3, n=8, domain=40):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, domain, (B, n)).astype(np.int32),
             rng.integers(1, 4, (B, n)).astype(np.float32))
            for _ in range(nb)]


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def _assert_samples_equal(sample, ref):
    assert np.array_equal(np.asarray(sample.keys), np.asarray(ref.keys))
    assert np.array_equal(np.asarray(sample.freqs), np.asarray(ref.freqs))


# ---------------------------------------------------------------------------
# merge protocol (in-process)
# ---------------------------------------------------------------------------

class TestMergeProtocol:
    def test_fleet_plane_bitwise_equals_pipeline_at_r2(self):
        """The checkpoint publish round-trip is an identity and the R=2
        butterfly equals the pipeline's pairwise fold, so the ``fleet``
        plane's collapse must match the plain pipeline BIT for bit --
        this is the keystone of the multi-process parity contract."""
        cfg = _cfg()
        fleet = E.SketchEngine(cfg, flush_elems=1, plane="fleet",
                               plane_opts={"replicas": 2})
        pipe = E.SketchEngine(cfg, flush_elems=1, plane="pipeline",
                              plane_opts={"shards": 2})
        try:
            for k, v in _batches(6, seed=3):
                fleet.ingest(k, v)
                pipe.ingest(k, v)
            _assert_trees_equal(fleet.state, pipe.state)
            _assert_samples_equal(fleet.sample(4), pipe.sample(4))
        finally:
            fleet.plane.close()
            pipe.plane.close()

    @pytest.mark.parametrize("shards", [2, 3, 4, 5])
    def test_merge_states_equals_tree_merge_bitwise(self, shards):
        """``merge_states`` picks butterfly (power of two) or tree; both
        reduce through the same pairing, so the result is bitwise
        independent of which branch ran."""
        cfg = _cfg()
        engines = [E.SketchEngine(cfg, flush_elems=1)
                   for _ in range(shards)]
        for k, v in _batches(5, seed=11):
            for eng, (bk, bv) in zip(engines,
                                     P.partition_by_key(k, v, shards)):
                if bk.shape[1]:
                    eng.ingest(bk, bv)
        states = [eng.state for eng in engines]
        merged = shd.merge_states(states, engines[0].ops.merge)
        ref = shd.tree_merge(states, engines[0].ops.merge)
        _assert_trees_equal(merged, ref)

    def test_merge_states_empty_raises(self):
        with pytest.raises(ValueError, match="no states"):
            shd.merge_states([], lambda a, b: a)

    def test_merge_states_single_state_is_identity(self):
        eng = E.SketchEngine(_cfg(), flush_elems=1)
        k, v = _batches(1, seed=5)[0]
        eng.ingest(k, v)
        _assert_trees_equal(shd.merge_states([eng.state], eng.ops.merge),
                            eng.state)

    @pytest.mark.parametrize("shards", [2, 3])
    def test_merge_states_seed_mismatch_rejected(self, shards):
        """A shard hashed under a different seed is not a shard of the
        same logical stream: both reduction branches must raise, never
        silently merge."""
        good = E.SketchEngine(_cfg(seed=7), flush_elems=1)
        rogue = E.SketchEngine(_cfg(seed=8), flush_elems=1)
        states = [good.state] * (shards - 1) + [rogue.state]
        with pytest.raises(ValueError, match="seeds"):
            shd.merge_states(states, good.ops.merge)

    def test_corrupt_checkpoint_fails_crc(self, tmp_path):
        """The fault injector's byte flip leaves the manifest CRC stale;
        ``checkpoint.restore`` must refuse the shard (this is exactly how
        a corrupted replica publish is rejected at the merge boundary)."""
        eng = E.SketchEngine(_cfg(), flush_elems=1)
        k, v = _batches(1, seed=9)[0]
        eng.ingest(k, v)
        root = str(tmp_path / "shard")
        path = checkpoint.save(root, 3, eng.state)
        F._flip_committed_byte(path)
        with pytest.raises(IOError, match="CRC"):
            checkpoint.restore(root, 3, eng.state)

    def test_nesting_and_bounds_guards(self):
        cfg = _cfg()
        with pytest.raises(ValueError, match="nest"):
            E.SketchEngine(cfg, plane="fleet",
                           plane_opts={"subplane": "fleet"})
        with pytest.raises(ValueError, match="nest"):
            F.FleetCoordinator(F.FleetConfig(engine=cfg, plane="fleet"))
        with pytest.raises(ValueError, match="replicas"):
            F.FleetCoordinator(F.FleetConfig(engine=cfg, replicas=0))

    def test_fleet_is_a_registered_plane_and_conformance_path(self):
        assert "fleet" in P.available_planes()
        from repro.validate import empirics
        assert "fleet" in empirics.PATHS


# ---------------------------------------------------------------------------
# router properties (hypothesis)
# ---------------------------------------------------------------------------

class TestRouterProperties:
    # no deadline: the first example pays the jax compile of hash_u32
    @settings(max_examples=24, deadline=None)
    @given(st.integers(min_value=0, max_value=2**32 - 1),
           st.integers(min_value=0, max_value=2**32 - 1))
    def test_hash_u32_np_bit_compatible_with_device(self, key, salt):
        """Routing decisions are made host-side with ``hash_u32_np``; any
        device-side replay of the same hash must agree on every bit, for
        every key including 0 and uint32 max."""
        ks = np.asarray([key, 0, 2**32 - 1, key ^ salt], np.uint32)
        host = hashing.hash_u32_np(ks, np.uint32(salt))
        dev = np.asarray(hashing.hash_u32(jnp.asarray(ks),
                                          jnp.uint32(salt)))
        assert host.dtype == np.uint32
        assert np.array_equal(host, dev)

    @settings(max_examples=24)
    @given(st.integers(min_value=1, max_value=64),
           st.integers(min_value=-2**31, max_value=2**31 - 1))
    def test_shard_of_keys_pure_in_range_and_duplicate_stable(
            self, shards, key):
        """Shard assignment is a pure per-key function: in range, batch-
        independent, and identical for duplicates -- the stickiness that
        makes deletions land where the insertions did.  Edge keys ride
        along on every draw: 0, the -1 padding sentinel (uint32 max after
        the int32 reinterpret), and both int32 extremes."""
        edge = np.asarray([key, 0, -1, 2**31 - 1, -2**31, key], np.int32)
        sh = hashing.shard_of_keys(edge, shards)
        assert sh.shape == edge.shape
        assert ((sh >= 0) & (sh < shards)).all()
        solo = hashing.shard_of_keys(np.asarray([key], np.int32), shards)
        assert sh[0] == solo[0]        # batch-independent
        assert sh[0] == sh[-1]         # duplicate keys agree
        # shard-COUNT invariance: the assignment derives from one
        # count-independent hash (only the final modulo sees ``shards``),
        # so resizing the fleet re-partitions the same hash stream
        # instead of rehashing the keys
        h = hashing.hash_u32_np(edge, hashing._SHARD_SALT)
        assert np.array_equal(sh, (h % np.uint32(shards)).astype(sh.dtype))

    @settings(max_examples=10)
    @given(st.integers(min_value=2, max_value=5),
           st.integers(min_value=0, max_value=2**31 - 1))
    def test_partition_by_key_is_an_exact_partition(self, shards, seed):
        """Every live (key, value) event lands in exactly one shard block
        (multiset equality per stream row), every routed key hashes to
        its block's shard, and padding slots are inert (-1 keys, 0
        values) -- with sentinel/extreme/duplicate keys in the batch."""
        rng = np.random.default_rng(seed)
        B, n = 3, 16
        keys = rng.integers(0, 40, (B, n)).astype(np.int32)
        keys[0, :3] = (0, -1, 2**31 - 1)   # edges + a padding sentinel
        keys[1, 0] = keys[1, 1] = keys[1, 2]  # forced duplicates
        vals = rng.standard_normal((B, n)).astype(np.float32)
        parts = P.partition_by_key(keys, vals, shards)
        assert len(parts) == shards
        for s, (k, v) in enumerate(parts):
            live = k != np.int32(-1)
            assert (hashing.shard_of_keys(k, shards)[live] == s).all()
            assert (v[~live] == 0.0).all()
        for b in range(B):
            want = collections.Counter(
                (int(k), float(v)) for k, v in zip(keys[b], vals[b])
                if k != -1)
            got = collections.Counter(
                (int(k), float(v))
                for pk, pv in parts
                for k, v in zip(pk[b], pv[b]) if k != -1)
            assert got == want


# ---------------------------------------------------------------------------
# multi-process fleet (tier-1: one kill + one rejection flow)
# ---------------------------------------------------------------------------

def _fcfg(cfg, **kw):
    base = dict(engine=cfg, replicas=2, publish_every=2,
                ack_timeout=3.0, ping_timeout=1.5)
    base.update(kw)
    return F.FleetConfig(**base)


class TestFleetProcess:
    def test_kill_midstream_restart_restores_bitwise_parity(self):
        """Replica 1 dies abruptly AFTER applying its 3rd block but before
        acking or committing it (the worst-case window: the in-memory
        state is lost wholesale).  The router must detect the death,
        respawn from the last published checkpoint, replay the journal
        suffix, and the aggregated sample must equal the single-process
        fleet-plane reference bit for bit."""
        cfg = _cfg()
        batches = _batches(10, seed=1)
        with F.FleetCoordinator(
                _fcfg(cfg), faults={1: F.FaultPlan(kill_after=3)}) as co:
            for k, v in batches:
                co.route(k, v)
            sample = co.sample(4)
            stats = co.stats
        assert stats.restarts == 1
        _assert_samples_equal(sample,
                              F.reference_sample(cfg, batches, 2, 4))

    def test_bad_shards_rejected_then_fleet_recovers(self):
        """Corrupted publish -> CRC IOError; wrong-seed publish -> merge
        ValueError; neither is ever silently merged.  Once the fault
        clears, the next publish overwrites the poisoned artifact and the
        fleet returns a bitwise-correct aggregate -- rejection does not
        strand the replica."""
        cfg = _cfg()
        batches = _batches(3, seed=1)
        with F.FleetCoordinator(_fcfg(cfg)) as co:
            for k, v in batches:
                co.route(k, v)
            co.inject_fault(0, F.FaultPlan(corrupt_publish=True))
            with pytest.raises(IOError, match="CRC"):
                co.merged_state()
            co.inject_fault(0, F.FaultPlan(publish_wrong_seed=True))
            with pytest.raises(ValueError, match="seeds"):
                co.merged_state()
            co.inject_fault(0, F.FaultPlan())  # clear: self-heals
            sample = co.sample(4)
        _assert_samples_equal(sample,
                              F.reference_sample(cfg, batches, 2, 4))


# ---------------------------------------------------------------------------
# chaos grid (seed-matrixed in CI: FLEET_CHAOS_SEED)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestChaosFleet:
    """Scripted kill/hang/delay chaos; every scenario's exit criterion is
    the same bitwise parity against ``reference_sample``.  The stream
    content, engine seed, and fault placement all derive from
    FLEET_CHAOS_SEED so the CI matrix explores distinct trajectories."""

    def _seeded_cfg(self, **kw):
        return _cfg(seed=7 ^ FLEET_CHAOS_SEED, **kw)

    def test_hang_detected_by_probe_and_recovered(self):
        """A hung replica (alive but unresponsive) cannot be caught by
        is_alive(); the silence budget must trigger a probe, the failed
        probe a restart, and the replay must restore bitwise parity."""
        cfg = self._seeded_cfg()
        batches = _batches(8, seed=FLEET_CHAOS_SEED)
        fcfg = _fcfg(cfg, ack_timeout=2.0, ping_timeout=1.0)
        with F.FleetCoordinator(
                fcfg, faults={0: F.FaultPlan(hang_after=2)}) as co:
            for k, v in batches:
                co.route(k, v)
            sample = co.sample(4)
            stats = co.stats
        assert stats.restarts >= 1
        assert stats.probes >= 1
        _assert_samples_equal(sample,
                              F.reference_sample(cfg, batches, 2, 4))

    def test_slow_replica_backpressure_not_death(self):
        """Injected per-ingest latency against a depth-1 command queue:
        the router must absorb it as bounded backpressure (backoff
        retries), NOT misdiagnose the slow replica as dead -- and parity
        must hold exactly as in the healthy run."""
        cfg = self._seeded_cfg()
        batches = _batches(8, seed=FLEET_CHAOS_SEED + 1)
        fcfg = _fcfg(cfg, queue_depth=1, publish_every=3,
                     ack_timeout=20.0, ping_timeout=5.0)
        with F.FleetCoordinator(
                fcfg, faults={0: F.FaultPlan(delay_s=0.05)}) as co:
            for k, v in batches:
                co.route(k, v)
            sample = co.sample(4)
            stats = co.stats
        assert stats.restarts == 0, "slow replica misdiagnosed as dead"
        _assert_samples_equal(sample,
                              F.reference_sample(cfg, batches, 2, 4))

    def test_three_replicas_windowed_turnstile_kill(self):
        """Non-power-of-two fleet (tree-merge branch) under the paper's
        turnstile workload: every step retracts a slice of the previous
        step's insertions, so recovery correctness depends on sticky
        routing (a key's deletions replay to the replica that saw its
        insertions).  One replica -- seed-chosen -- dies mid-window."""
        replicas = 3
        requests = 3
        cfg = self._seeded_cfg(domain=64)
        stream = TurnstileZipfStream(vocab_size=64, alpha=1.2,
                                     seed=FLEET_CHAOS_SEED)
        batches = traffic(stream, requests, steps=10, batch=6)
        victim = FLEET_CHAOS_SEED % replicas
        fcfg = _fcfg(cfg, replicas=replicas)
        with F.FleetCoordinator(
                fcfg, faults={victim: F.FaultPlan(kill_after=4)}) as co:
            for k, v in batches:
                co.route(k, v)
            sample = co.sample(4)
            stats = co.stats
        assert stats.restarts == 1
        _assert_samples_equal(
            sample, F.reference_sample(cfg, batches, replicas, 4))

    def test_double_kill_both_replicas_recover(self):
        """Both replicas die at different stream points; both must be
        respawned and replayed independently, and the union must still
        equal the reference bit for bit."""
        cfg = self._seeded_cfg()
        batches = _batches(10, seed=FLEET_CHAOS_SEED + 2)
        faults = {0: F.FaultPlan(kill_after=2),
                  1: F.FaultPlan(kill_after=5)}
        with F.FleetCoordinator(_fcfg(cfg), faults=faults) as co:
            for k, v in batches:
                co.route(k, v)
            sample = co.sample(4)
            stats = co.stats
        assert stats.restarts == 2
        _assert_samples_equal(sample,
                              F.reference_sample(cfg, batches, 2, 4))
