"""Fault-injected multi-process serving fleet for composable sketches.

The paper's mergeability (Sec. 1-2: merge(a, b) is the state of the union
of the shards' streams) is what lets WOR ell_p sampling run as a FLEET of
independent replicas.  This module makes that operational, and -- because
correctness under failure is the whole point -- ships the fault-injection
machinery as a first-class part of the design:

``FleetPlane`` (registered data plane ``"fleet"``)
    the single-process model of the fleet's data path: the router's
    sticky per-key-hash partition (``planes.partition_by_key``) across R
    replica sub-planes, collapsed at every read through the CHECKPOINT
    merge protocol -- each replica state round-trips through
    ``train.checkpoint`` (atomic commit + per-leaf CRC32) and the results
    reduce via ``sharding.merge_states`` (host-form butterfly for
    power-of-two R, pairwise tree otherwise) under the seed-agreement
    guards.  Registering it as a plane puts a ``fleet`` path in the
    conformance PATHS grid for free, and it is the bitwise REFERENCE the
    multi-process fleet is held equal to.

``FleetCoordinator`` + ``_replica_main``
    the real thing: R spawn-context OS processes, each owning a
    ``SketchEngine`` shard that dispatches every routed block immediately
    (``flush_elems=1``: reproducible dispatch boundaries).  State crosses
    the process boundary ONLY as committed checkpoint files; the
    coordinator restores and collapses them through the same
    ``merge_states`` reduction, so a corrupted shard fails its CRC
    (IOError) and a wrong-seed shard fails the merge guard (ValueError)
    instead of silently poisoning the union.

    The router is health-aware: bounded command queues give backpressure,
    a full queue or ack timeout triggers exponential-backoff retries and
    a ping probe, and a replica declared dead is killed, respawned, and
    REPLAYED -- the coordinator journals every routed block until its
    replica confirms a publish, and a restarted replica restores its last
    committed checkpoint and receives exactly the journal suffix past it.
    Replay is exactly-once by construction: a dying replica loses its
    un-published in-memory state wholesale, so the restored-checkpoint +
    journal-suffix composition applies every block exactly once, and the
    aggregated samples stay BITWISE equal to the single-process
    ``FleetPlane`` reference (``tests/test_fleet.py`` proves this under
    scripted kill/hang/delay faults).

``FaultPlan``
    scripted fault injection, interpreted inside the replica process:
    kill (``os._exit``, no ack, no commit) or hang (stop servicing) after
    N ingests, per-ingest latency, and publish-time corruption (flip a
    byte in a committed leaf) or seed-swapping (publish a state hashed
    under a different seed).  Faults are one-shot: a recovered replica
    restarts with a clean plan.
"""
from __future__ import annotations

import collections
import contextlib
import multiprocessing
import os
import queue
import shutil
import tempfile
import time
import weakref
from typing import NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.distributed import sharding as shd
from repro.engine import planes
from repro.engine.engine import EngineConfig, SketchEngine
from repro.train import checkpoint

_KILL_EXIT = 17      # replica suicide exit code (distinguishes fault kills)
_HANG_S = 3600.0     # a "hung" replica sleeps this long (probe kills it)


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------

class FaultPlan(NamedTuple):
    """Scripted faults, interpreted inside the replica process.  Ingest
    counts are measured from the moment the plan is installed (spawn or
    ``inject_fault``), so tests can script faults at exact stream points."""

    kill_after: Optional[int] = None   # os._exit after applying N ingests
                                       # (applied but NOT acked/committed)
    hang_after: Optional[int] = None   # stop servicing after N ingests
                                       # (alive but unresponsive)
    delay_s: float = 0.0               # injected latency per ingest
    corrupt_publish: bool = False      # flip a byte in the committed shard
    publish_wrong_seed: bool = False   # publish a state hashed under a
                                       # different seed (merge must reject)


def _flip_committed_byte(ckpt_path: str) -> None:
    """Corrupt a committed checkpoint in place: flip the last byte of the
    first leaf file (raw data region), leaving the manifest CRC stale --
    the restore side must refuse the shard."""
    leaf = sorted(f for f in os.listdir(ckpt_path) if f.endswith(".npy"))[0]
    with open(os.path.join(ckpt_path, leaf), "r+b") as f:
        f.seek(-1, os.SEEK_END)
        byte = f.read(1)[0]
        f.seek(-1, os.SEEK_END)
        f.write(bytes([byte ^ 0xFF]))


# ---------------------------------------------------------------------------
# fleet configuration
# ---------------------------------------------------------------------------

class FleetConfig(NamedTuple):
    """The fleet's operating point.  ``engine`` is shared verbatim by every
    replica (identical seeds => mergeable shards; the merge guards enforce
    it).  Timeouts are generous by default -- chaos tests shrink them."""

    engine: EngineConfig
    replicas: int = 2
    plane: str = "sparse"        # each replica's engine data plane
    publish_every: int = 8       # replica batches between checkpoint publishes
    queue_depth: int = 8         # bounded command queue / outstanding acks
    ack_timeout: float = 30.0    # silence budget before a health probe
    ping_timeout: float = 5.0    # probe budget before declaring death
    backoff: float = 0.02        # initial retry backoff (doubles per retry)
    max_backoff: float = 0.5
    max_restarts: int = 5        # per-replica restart budget per run
    start_timeout: float = 180.0  # spawn + jax import + restore budget
    # env forced into replica processes (spawn inherits os.environ):
    # analytics replicas are host/CPU tier by default
    child_env: Tuple[Tuple[str, str], ...] = (("JAX_PLATFORMS", "cpu"),)
    # wire codec for published checkpoints (repro.distributed.codecs):
    # replicas commit ENCODED leaves (CRC over encoded bytes), the
    # coordinator restores+decodes before the merge.  Seed/key leaves stay
    # lossless under every codec, so the corrupt-shard and seed-guard
    # rejection contracts are codec-independent.
    codec: str = "none"


class FleetStats:
    """Coordinator-side counters + per-route latencies (seconds)."""

    def __init__(self):
        self.restarts = 0       # replica respawns (kill/hang recoveries)
        self.retries = 0        # backpressure/backoff retries on full queues
        self.probes = 0         # health pings issued
        self.routed_batches = 0  # non-empty per-replica blocks dispatched
        self.routed_events = 0   # per-stream elements routed (sum of n)
        self.route_s: list = []  # wall-clock per route() call
        self.publishes = 0       # confirmed checkpoint publishes
        self.published_bytes = 0  # wire bytes across all publishes (encoded)

    def latency_percentile(self, q: float) -> float:
        if not self.route_s:
            return 0.0
        return float(np.percentile(np.asarray(self.route_s, np.float64), q))


# ---------------------------------------------------------------------------
# replica process
# ---------------------------------------------------------------------------

def _replica_main(rid: int, ecfg: EngineConfig, plane: str, ckpt_dir: str,
                  cmd_q, out_q, fault: FaultPlan,
                  codec: str = "none") -> None:
    """One replica: a SketchEngine shard behind a command queue.

    ``flush_elems=1`` dispatches every routed block at its own boundary --
    the same granularity as the in-process ``FleetPlane`` sub-planes, which
    is half of the bitwise-parity contract (the other half is the checkpoint
    round-trip being exact).  On start the replica restores its newest
    COMMITTED checkpoint (crash recovery) and reports the restored step so
    the coordinator can replay exactly the journal suffix past it.
    """
    eng = SketchEngine(ecfg, plane=plane, flush_elems=1)
    applied = 0  # seq of the last applied ingest (0 = nothing yet)
    checkpoint.gc_tmp(ckpt_dir)
    restored, step = checkpoint.restore_latest(ckpt_dir, eng.state)
    if restored is not None:
        eng.state = restored
        applied = int(step)
    out_q.put(("ready", applied))
    n_since_plan = 0
    while True:
        cmd = cmd_q.get()
        op = cmd[0]
        if op == "stop":
            out_q.put(("stopped",))
            return
        if op == "ping":
            out_q.put(("pong", cmd[1]))
        elif op == "fault":
            fault = cmd[1]
            n_since_plan = 0
            out_q.put(("fault_set",))
        elif op == "ingest":
            _, seq, keys, vals = cmd
            n_since_plan += 1
            if fault.delay_s:
                time.sleep(fault.delay_s)
            if (fault.hang_after is not None
                    and n_since_plan > fault.hang_after):
                time.sleep(_HANG_S)  # unresponsive: the probe must kill us
                continue
            eng.ingest(keys, vals)
            applied = seq
            if (fault.kill_after is not None
                    and n_since_plan >= fault.kill_after):
                # abrupt death AFTER applying, BEFORE acking/committing:
                # the in-memory state is lost wholesale, so recovery =
                # restored checkpoint + journal replay applies this block
                # exactly once
                os._exit(_KILL_EXIT)
            out_q.put(("ack", seq))
        elif op == "publish":
            eng.flush()
            st = eng.state
            if fault.publish_wrong_seed:
                rogue = SketchEngine(
                    ecfg._replace(seed=int(ecfg.seed) ^ 0x0BAD5EED))
                st = rogue.state
            path = checkpoint.save(ckpt_dir, applied, st, codec=codec)
            if fault.corrupt_publish:
                _flip_committed_byte(path)
            # the confirmation carries the wire size of the committed
            # (encoded) payload so the coordinator can account comm volume
            out_q.put(("published", applied, checkpoint.payload_nbytes(path)))
        else:
            out_q.put(("error", f"unknown command {op!r}"))


# ---------------------------------------------------------------------------
# coordinator (router + merge protocol)
# ---------------------------------------------------------------------------

class _Replica:
    """Coordinator-side handle: process, queues, journal, protocol state."""

    def __init__(self, rid: int, ckpt_dir: str):
        self.rid = rid
        self.ckpt_dir = ckpt_dir
        self.proc = None
        self.cmd_q = None
        self.out_q = None
        self.journal: list = []       # [(seq, keys, vals)] not yet published
        self.outstanding = collections.deque()  # expected responses, FIFO
        self.applied = 0              # highest seq the replica confirmed
        self.published = 0            # step of the last confirmed publish
        self.since_publish = 0
        self.restarts = 0
        self.pong = None              # token of the last pong received


@contextlib.contextmanager
def _forced_env(pairs: Sequence[Tuple[str, str]]):
    """Temporarily force env vars around a child spawn (the child inherits
    os.environ at Process.start); pre-existing values win."""
    added = []
    for key, val in pairs:
        if key not in os.environ:
            os.environ[key] = val
            added.append(key)
    try:
        yield
    finally:
        for key in added:
            os.environ.pop(key, None)


def _discard_queue(q) -> None:
    """Drop a dead replica's queue without letting its feeder thread block
    interpreter/coordinator teardown on an orphaned pipe."""
    if q is None:
        return
    try:
        q.cancel_join_thread()
        q.close()
    except Exception:
        pass


class FleetCoordinator:
    """Owns R replica processes: routes, probes, recovers, merges.

    Lifecycle: ``start()`` (or use as a context manager), ``route()`` per
    microbatch, ``sample(k)`` / ``merged_state()`` at read points,
    ``stop()``.  ``faults`` maps replica id -> FaultPlan installed at spawn;
    ``inject_fault`` scripts faults mid-stream.  All recovery is internal --
    callers only see ``stats.restarts`` move -- except an unmergeable
    published shard, which raises at the merge boundary by design.
    """

    def __init__(self, cfg: FleetConfig, root: Optional[str] = None,
                 faults: Optional[dict] = None):
        if cfg.replicas < 1:
            raise ValueError(f"fleet needs replicas >= 1, got {cfg.replicas}")
        if cfg.plane in ("fleet",):
            raise ValueError("fleet replicas cannot nest the fleet plane")
        self.cfg = cfg
        self._own_root = root is None
        self.root = root or tempfile.mkdtemp(prefix="repro-fleet-")
        self._faults = dict(faults or {})
        self._ctx = multiprocessing.get_context("spawn")
        self._seq = 0
        self.stats = FleetStats()
        # local reference engine: like-trees for restore, merge/sample ops;
        # it never ingests, so it is NOT a hidden (R+1)-th shard
        self._ref = SketchEngine(cfg.engine)
        self._replicas = [
            _Replica(r, os.path.join(self.root, f"replica_{r:02d}"))
            for r in range(cfg.replicas)]
        self._started = False

    # -- lifecycle ----------------------------------------------------------
    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def start(self):
        if self._started:
            return self
        # launch all replicas before waiting on any: startup cost is one
        # process spawn + jax import, paid once in parallel, not R times
        for r in self._replicas:
            self._launch(r, self._faults.get(r.rid, FaultPlan()))
        for r in self._replicas:
            self._wait_ready(r)
        self._started = True
        return self

    def stop(self):
        for r in self._replicas:
            if r.proc is None:
                continue
            if r.proc.is_alive():
                try:
                    r.cmd_q.put(("stop",), timeout=1.0)
                except queue.Full:
                    pass
            r.proc.join(timeout=10.0)
            if r.proc.is_alive():
                r.proc.terminate()
                r.proc.join(timeout=10.0)
            _discard_queue(r.cmd_q)
            _discard_queue(r.out_q)
            r.proc = None
        if self._own_root:
            shutil.rmtree(self.root, ignore_errors=True)

    def _launch(self, r: _Replica, fault: FaultPlan) -> None:
        r.cmd_q = self._ctx.Queue(maxsize=self.cfg.queue_depth)
        r.out_q = self._ctx.Queue()
        r.proc = self._ctx.Process(
            target=_replica_main,
            args=(r.rid, self.cfg.engine, self.cfg.plane, r.ckpt_dir,
                  r.cmd_q, r.out_q, fault, self.cfg.codec),
            name=f"repro-fleet-replica-{r.rid}", daemon=True)
        with _forced_env(self.cfg.child_env):
            r.proc.start()

    def _wait_ready(self, r: _Replica) -> None:
        deadline = time.monotonic() + self.cfg.start_timeout
        while True:
            try:
                msg = r.out_q.get(timeout=1.0)
            except queue.Empty:
                if not r.proc.is_alive() or time.monotonic() > deadline:
                    raise RuntimeError(
                        f"fleet replica {r.rid} failed to start "
                        f"(alive={r.proc.is_alive()})")
                continue
            if msg[0] == "ready":
                break
        # the replica restored its newest committed checkpoint: protocol
        # state resets to that point; everything past it must be replayed
        r.applied = r.published = int(msg[1])
        r.outstanding = collections.deque()
        r.since_publish = 0

    def _spawn(self, r: _Replica, fault: FaultPlan) -> None:
        self._launch(r, fault)
        self._wait_ready(r)

    # -- routing ------------------------------------------------------------
    def route(self, keys, values):
        """Route one (B, n) turnstile microbatch: partition sticky by key
        hash (deletions land on the replica that saw the insertions),
        journal each non-empty block, dispatch with bounded backpressure."""
        if not self._started:
            raise RuntimeError("fleet not started (use start() or `with`)")
        t0 = time.perf_counter()
        keys = np.asarray(keys, np.int32)
        values = np.asarray(values, np.float32)
        parts = planes.partition_by_key(keys, values, self.cfg.replicas)
        for r, (k, v) in zip(self._replicas, parts):
            if not k.shape[1]:
                continue  # no seq consumed: replicas see only their blocks
            self._seq += 1
            r.journal.append((self._seq, k, v))
            self.stats.routed_batches += 1
            self.stats.routed_events += int(k.shape[1])
            if self._send(r, ("ingest", self._seq, k, v),
                          expect=("ack", self._seq)):
                r.since_publish += 1
                # bounded pipeline: never run more than queue_depth acks
                # ahead of the replica
                self._await_outstanding(r, limit=self.cfg.queue_depth)
            if r.since_publish >= self.cfg.publish_every:
                self._publish(r)
        self.stats.route_s.append(time.perf_counter() - t0)
        return self

    def inject_fault(self, rid: int, fault: FaultPlan) -> None:
        """Install a FaultPlan in a RUNNING replica (scripted chaos); the
        plan's ingest counters restart from this point in the stream."""
        r = self._replicas[rid]
        if self._send(r, ("fault", fault), expect=("fault_set",)):
            self._await_outstanding(r, limit=0)

    def _publish(self, r: _Replica) -> None:
        """Fire-and-track publish: the 'published' confirmation drains with
        the other outstanding responses (journal trimming happens there)."""
        if self._send(r, ("publish",), expect=("publish",)):
            r.since_publish = 0

    # -- merge protocol -----------------------------------------------------
    def publish_all(self):
        """Drive every replica to a committed checkpoint covering its whole
        routed stream (recovering and retrying as needed)."""
        for r in self._replicas:
            for _ in range(self.cfg.max_restarts + 2):
                if not self._await_outstanding(r, limit=0):
                    continue  # recovered mid-wait: journal was replayed
                # always re-publish (even when nothing new was applied): a
                # fresh commit at the same step overwrites any unreadable
                # artifact a since-cleared fault left behind
                if not self._send(r, ("publish",), expect=("publish",)):
                    continue
                if not self._await_outstanding(r, limit=0):
                    continue
                break
            else:
                raise RuntimeError(
                    f"replica {r.rid} failed to publish within the restart "
                    f"budget ({self.cfg.max_restarts})")
        return self

    def merged_state(self):
        """Publish, restore, and collapse every replica shard.

        Rejection is the contract here: a corrupted shard fails its CRC32
        (IOError from ``checkpoint.restore``) and a shard published under
        different seeds fails the merge-tree seed guard (ValueError from
        ``sharding.merge_states``) -- neither is ever silently merged.
        """
        self.publish_all()
        states = []
        for r in self._replicas:
            step = checkpoint.latest_step(r.ckpt_dir)
            if step is None:
                raise RuntimeError(
                    f"replica {r.rid} has no committed checkpoint")
            states.append(checkpoint.restore(r.ckpt_dir, step,
                                             self._ref.state))
        return shd.merge_states(states, self._ref.ops.merge)

    def sample(self, k: int):
        """Aggregated per-stream WOR sample over the union of all routed
        traffic (the quantity held bitwise-equal to the single-process
        reference by the chaos tests)."""
        return self._ref.sample_state(self.merged_state(), k)

    # -- health / transport -------------------------------------------------
    def _send(self, r: _Replica, msg, expect=None) -> bool:
        """Enqueue with bounded backpressure: retry with exponential
        backoff while the command queue is full, probe after the silence
        budget, recover on a failed probe.  Returns False when the replica
        was recovered instead (journaled work was replayed; non-journaled
        commands are the caller's to retry)."""
        backoff = self.cfg.backoff
        deadline = time.monotonic() + self.cfg.ack_timeout
        while True:
            if not r.proc.is_alive():
                self._recover(r)
                return False
            try:
                r.cmd_q.put(msg, timeout=backoff)
            except queue.Full:
                self.stats.retries += 1
                self._pump(r)
                backoff = min(backoff * 2.0, self.cfg.max_backoff)
                if time.monotonic() > deadline:
                    if self._probe(r):
                        deadline = time.monotonic() + self.cfg.ack_timeout
                    else:
                        self._recover(r)
                        return False
                continue
            if expect is not None:
                r.outstanding.append(expect)
            return True

    def _pump(self, r: _Replica) -> None:
        while True:
            try:
                msg = r.out_q.get_nowait()
            except queue.Empty:
                return
            self._apply_msg(r, msg)

    def _apply_msg(self, r: _Replica, msg) -> None:
        kind = msg[0]
        if kind == "ack":
            r.applied = max(r.applied, int(msg[1]))
            if r.outstanding and r.outstanding[0] == ("ack", msg[1]):
                r.outstanding.popleft()
        elif kind == "published":
            r.published = max(r.published, int(msg[1]))
            if len(msg) > 2:  # wire bytes of the committed encoded payload
                self.stats.publishes += 1
                self.stats.published_bytes += int(msg[2])
            # the journal only needs to cover un-committed suffix
            r.journal = [e for e in r.journal if e[0] > r.published]
            if r.outstanding and r.outstanding[0][0] == "publish":
                r.outstanding.popleft()
        elif kind == "pong":
            r.pong = msg[1]
            if r.outstanding and r.outstanding[0] == ("pong", msg[1]):
                r.outstanding.popleft()
        elif kind == "fault_set":
            if r.outstanding and r.outstanding[0][0] == "fault_set":
                r.outstanding.popleft()
        elif kind == "error":
            raise RuntimeError(f"replica {r.rid}: {msg[1]}")
        # "ready"/"stopped" are handled at spawn/stop boundaries

    def _await_outstanding(self, r: _Replica, limit: int = 0) -> bool:
        """Pump responses until at most ``limit`` remain outstanding.
        Health-aware: silence past ack_timeout triggers a probe; a failed
        probe (or a dead process) triggers recovery.  Returns False when
        the replica was recovered (outstanding reset by the respawn)."""
        deadline = time.monotonic() + self.cfg.ack_timeout
        while len(r.outstanding) > limit:
            try:
                msg = r.out_q.get(timeout=0.05)
            except queue.Empty:
                if not r.proc.is_alive():
                    self._recover(r)
                    return False
                if time.monotonic() > deadline:
                    if self._probe(r):
                        deadline = time.monotonic() + self.cfg.ack_timeout
                    else:
                        self._recover(r)
                        return False
                continue
            self._apply_msg(r, msg)
            deadline = time.monotonic() + self.cfg.ack_timeout
        return True

    def _probe(self, r: _Replica) -> bool:
        """Ping through the command FIFO and wait for the matching pong
        (FIFO ordering means the pong also certifies every command ahead
        of it was serviced).  Any arriving message extends the probe --
        a backlogged-but-alive replica is making progress, not dead."""
        self.stats.probes += 1
        if not r.proc.is_alive():
            return False
        token = f"probe-{self.stats.probes}"
        try:
            r.cmd_q.put_nowait(("ping", token))
        except queue.Full:
            return False  # wedged: queue full AND the silence budget spent
        r.outstanding.append(("pong", token))
        deadline = time.monotonic() + self.cfg.ping_timeout
        while time.monotonic() < deadline:
            try:
                msg = r.out_q.get(timeout=0.05)
            except queue.Empty:
                if not r.proc.is_alive():
                    return False
                continue
            self._apply_msg(r, msg)
            if r.pong == token:
                return True
            deadline = time.monotonic() + self.cfg.ping_timeout
        return False

    def _recover(self, r: _Replica) -> None:
        """Kill (if needed), respawn clean, restore, replay.

        The respawned replica restores its last COMMITTED checkpoint and
        reports that step as ``ready``; the coordinator then replays
        exactly the journal suffix past it.  One-shot faults: the fresh
        process gets an empty FaultPlan."""
        if r.restarts >= self.cfg.max_restarts:
            raise RuntimeError(
                f"replica {r.rid} exceeded the restart budget "
                f"({self.cfg.max_restarts}); giving up")
        r.restarts += 1
        self.stats.restarts += 1
        if r.proc is not None and r.proc.is_alive():
            r.proc.terminate()
            r.proc.join(timeout=10.0)
            if r.proc.is_alive():
                r.proc.kill()
                r.proc.join(timeout=10.0)
        _discard_queue(r.cmd_q)
        _discard_queue(r.out_q)
        self._spawn(r, FaultPlan())
        replay = [e for e in r.journal if e[0] > r.applied]
        for seq, k, v in replay:
            if self._send(r, ("ingest", seq, k, v), expect=("ack", seq)):
                self._await_outstanding(r, limit=self.cfg.queue_depth)
        r.since_publish = len(replay)


# ---------------------------------------------------------------------------
# the in-process reference: the "fleet" data plane
# ---------------------------------------------------------------------------

@planes.register_plane("fleet")
class FleetPlane(planes.PipelinePlane):
    """Single-process model of the fleet's data path, and the conformance
    grid's ``fleet`` path.

    Same router (``partition_by_key`` across ``replicas`` sub-planes, each
    dispatching per forwarded block), but every collapse runs the REAL
    merge protocol: each replica state is published through a
    ``train.checkpoint`` save/restore round-trip (atomic commit, per-leaf
    CRC32 -- bit-exact by the checkpoint tests) into a scratch directory,
    then reduced via ``sharding.merge_states`` under the seed guards.  The
    multi-process ``FleetCoordinator`` is held BITWISE equal to this plane
    by the chaos tests, which is what makes kill-and-restart recovery
    provable rather than plausible.
    """

    def __init__(self, spec, state, policy=None, interpret=None,
                 use_kernel=None, replicas: int = 2,
                 subplane: str = "sparse", codec: str = "none"):
        if subplane == "fleet":
            raise ValueError("fleet sub-planes cannot nest")
        super().__init__(spec, state, policy=policy, interpret=interpret,
                         use_kernel=use_kernel, shards=replicas,
                         subplane=subplane, codec=codec)
        self.replicas = self.shards
        self._scratch: Optional[str] = None

    def _scratch_dir(self) -> str:
        if self._scratch is None:
            self._scratch = tempfile.mkdtemp(prefix="repro-fleet-plane-")
            weakref.finalize(self, shutil.rmtree, self._scratch,
                             ignore_errors=True)
        return self._scratch

    def _publish_roundtrip(self, shard: int, st):
        """One replica publish: commit + CRC-verified restore (step 0 is
        overwritten per collapse, so scratch usage stays bounded).  With a
        lossy codec the commit stores the ENCODED leaves -- exactly what
        the multi-process replicas publish -- so this plane stays the
        bitwise reference at every codec."""
        d = os.path.join(self._scratch_dir(), f"replica_{shard:02d}")
        checkpoint.save(d, 0, st, codec=self.codec)
        return checkpoint.restore(d, 0, st)

    @property
    def state(self):
        """The collapsed state via the checkpoint merge protocol."""
        self._settle()
        if self._merged is None:
            published = [self._publish_roundtrip(i, sub.state)
                         for i, sub in enumerate(self._subplanes)]
            # no codec here: the publish round-trip above IS the wire
            # crossing; a second application would quantize twice
            self._merged = shd.merge_states(published, self._ops.merge)
        return self._merged

    def close(self):
        super().close()
        if self._scratch is not None:
            shutil.rmtree(self._scratch, ignore_errors=True)
            self._scratch = None


def reference_sample(ecfg: EngineConfig, batches, replicas: int, k: int,
                     subplane: str = "sparse", codec: str = "none"):
    """Single-process bitwise reference for a fleet run: feed the same
    microbatch stream through the ``fleet`` plane (identical routing,
    dispatch granularity, and merge protocol -- including the wire codec)
    and sample once."""
    eng = SketchEngine(ecfg, flush_elems=1, plane="fleet",
                       plane_opts={"replicas": replicas,
                                   "subplane": subplane,
                                   "codec": codec})
    try:
        for keys, vals in batches:
            eng.ingest(keys, vals)
        return eng.sample(k)
    finally:
        eng.plane.close()


__all__ = [
    "FaultPlan",
    "FleetConfig",
    "FleetCoordinator",
    "FleetPlane",
    "FleetStats",
    "reference_sample",
]
