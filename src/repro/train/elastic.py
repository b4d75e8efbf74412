"""Elastic scaling + straggler mitigation (design + host-side machinery).

What runs for real in this container:
  * ``StragglerWatchdog`` -- per-step wall-clock monitor with EWMA baseline;
    flags steps slower than ``threshold`` x the baseline and invokes a
    callback (in production: trigger checkpoint + reschedule of the slow
    host; here: recorded + tested with synthetic delays).
  * ``plan_remesh`` -- given a checkpointed (N-host) run and a new device
    count, produce the new mesh + shardings; ``checkpoint.restore`` then
    re-shards every leaf (elastic restart).  Works across pod counts because
    checkpoints are stored UNSHARDED (gathered numpy) with content hashes.

At 1000+ node scale the control plane (failure detection, re-scheduling) is
external (Borg/K8s); the contract this library provides is: any committed
checkpoint restores onto any mesh whose axis sizes divide the model dims --
verified by tests/test_checkpoint.py::test_elastic_remesh.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh_auto


class StragglerWatchdog:
    """EWMA step-time monitor; flags outlier steps (straggler suspects)."""

    def __init__(self, threshold: float = 2.0, alpha: float = 0.1,
                 warmup_steps: int = 3,
                 on_straggler: Optional[Callable[[int, float, float], None]]
                 = None):
        self.threshold = threshold
        self.alpha = alpha
        self.warmup = warmup_steps
        self.on_straggler = on_straggler
        self.ewma: Optional[float] = None
        self.seen = 0
        self.flagged: list[tuple[int, float, float]] = []
        self._t0: Optional[float] = None

    def step_begin(self):
        self._t0 = time.monotonic()

    def step_end(self, step: int):
        dt = time.monotonic() - self._t0
        self.seen += 1
        if self.ewma is None:
            self.ewma = dt
            return dt
        if self.seen > self.warmup and dt > self.threshold * self.ewma:
            self.flagged.append((step, dt, self.ewma))
            if self.on_straggler:
                self.on_straggler(step, dt, self.ewma)
            # do NOT poison the baseline with the outlier
            return dt
        self.ewma = (1 - self.alpha) * self.ewma + self.alpha * dt
        return dt


def plan_remesh(num_devices: int, model_parallel: int, pods: int = 1):
    """Mesh for a (possibly different) device count at restart time."""
    per_pod = num_devices // pods
    data = per_pod // model_parallel
    if pods > 1:
        return make_mesh_auto((pods, data, model_parallel),
                              ("pod", "data", "model"))
    return make_mesh_auto((data, model_parallel), ("data", "model"))


def reshard_tree(tree, mesh, pspecs):
    """device_put every leaf onto the new mesh (elastic restart step 2)."""
    from jax.sharding import NamedSharding

    def put(x, spec):
        return jax.device_put(np.asarray(x), NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, tree, pspecs)
