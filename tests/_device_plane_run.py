"""The pipeline plane's device path on 4 host devices, for
``tests/test_device_plane.py``.

Run as a script: the host device count locks at JAX's first use, so the
test runs this in a subprocess with 4 forced CPU devices.  Prints one JSON
object of readings; the tests assert on it.
"""
import json
import os
import re
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import reference as ref  # noqa: E402
from repro import engine as E  # noqa: E402
from repro import obs  # noqa: E402
from repro.engine import planes  # noqa: E402

D = 4
ROWS, WIDTH, C, K = 5, 512, 128, 32
FLUSH = 512
CFG = E.EngineConfig(num_streams=1, rows=ROWS, width=WIDTH, candidates=C,
                     p=2.0, scheme="priority", seed=2024)


def opts(**kw):
    return dict({"shards": D, "subplane": "sparse", "devices": D}, **kw)


def blocks(seed, n_blocks=6):
    """Zipf-skewed +1 insertions; each block retracts a quarter of the
    previous block's insertions."""
    rng = np.random.default_rng(seed)
    out, prev = [], None
    for _ in range(n_blocks):
        ins = (rng.zipf(1.3, FLUSH * 3 // 4) % 4000).astype(np.int32)
        keys, vals = ins, np.ones(ins.size, np.float32)
        if prev is not None:
            ret = prev[:FLUSH // 4]
            keys = np.concatenate([keys, ret])
            vals = np.concatenate([vals, -np.ones(ret.size, np.float32)])
        prev = ins
        out.append((keys[None], vals[None]))
    return out


def engine(plane_opts, batches):
    eng = E.SketchEngine(CFG, plane="pipeline", flush_elems=FLUSH,
                         plane_opts=plane_opts)
    for k, v in batches:
        eng.ingest(k, v)
    eng.flush()
    return eng


def ranked(tab, keys, seed, n):
    keys = np.asarray(keys).reshape(-1)
    keys = keys[keys >= 0]
    est = np.sort(np.abs(ref.estimate(tab, keys, seed)))[::-1]
    return np.pad(est, (0, max(n - est.size, 0)))[:n]


def error_of(**kw):
    try:
        E.SketchEngine(CFG, plane="pipeline", plane_opts=opts(**kw))
    except ValueError as e:
        return str(e)
    return None


def main():
    out = {"devices_seen": len(jax.devices())}

    # the collapsed state against the one-device 4-shard plane, and the
    # top C/4 candidate estimates against the float64 one-pass policy
    out["vs_one_device"], out["vs_policy"], out["sample"] = [], [], []
    for seed in (1, 2, 3):
        batches = blocks(seed)
        dev = engine(opts(), batches)
        one = engine({"shards": D, "subplane": "sparse"}, batches)
        a, b = dev.state, one.state
        ta, tb = np.asarray(a.sketch.table[0]), np.asarray(b.sketch.table[0])
        out["vs_one_device"].append({
            "max_diff": float(np.abs(ta - tb).max()),
            "scale": float(np.abs(tb).max()),
            "sharding": str(a.sketch.table.sharding),
            "device": str(a.sketch.table.devices()),
            "shape": list(a.sketch.table.shape)})
        seed_s = int(np.asarray(a.sketch.seed)[0])
        seed_t = int(np.asarray(a.seed_transform)[0])
        pol = ref.OnePass(seed_s, seed_t, ROWS, WIDTH, 2.0, "priority", C, D)
        for k, v in batches:
            pol.flush(k[0], v[0])
        keys = np.concatenate([k[0] for k, _ in batches])
        vals = np.concatenate([v[0] for _, v in batches])
        uniq, inv = np.unique(keys, return_inverse=True)
        tab = ref.table(uniq, np.bincount(inv, weights=vals), seed_s, seed_t,
                        ROWS, WIDTH, 2.0, "priority")
        mine = ranked(ta, a.cand_keys, seed_s, C // 4)
        want = ranked(tab, pol.collapse(), seed_s, C // 4)
        out["vs_policy"].append({
            "gap": float(np.abs(mine - want).max()),
            "scale": float(np.abs(tab).max()),
            "table_err": float(np.abs(ta - tab).max())})
        sa, sb = dev.sample(K), one.sample(K)
        ka, kb = set(np.asarray(sa.keys)[0]), set(np.asarray(sb.keys)[0])
        thr = float(np.asarray(sb.threshold)[0])
        odd = sorted(int(x) for x in ka ^ kb)
        est = np.abs(ref.estimate(tb.astype(np.float64), np.asarray(odd),
                                  seed_s)) if odd else np.zeros(0)
        out["sample"].append({"differ": odd, "threshold": thr,
                              "odd_est": est.tolist()})

    # routing: each device's shard equals the one-device plane's sub-plane
    # of the same index; retracting every event empties every device
    batches = blocks(7, 2)
    dev = engine(opts(), batches)
    one = engine({"shards": D, "subplane": "sparse"}, batches)
    local = np.asarray(dev.plane._state.sketch.table)
    out["per_device"] = [{
        "device": str(dev.plane._state.sketch.table.addressable_shards[s]
                      .device),
        "index": str(dev.plane._state.sketch.table.addressable_shards[s]
                     .index),
        "max_diff": float(np.abs(
            local[s] - np.asarray(one.plane._subplanes[s].state.sketch.table[0])
        ).max()),
        "nonzero": bool(np.abs(local[s]).max() > 0)} for s in range(D)]
    for k, v in batches:
        dev.ingest(k, -v)
    dev.flush()
    out["after_retracting_all"] = float(np.abs(
        np.asarray(dev.plane._state.sketch.table)).max())

    # the collapse span: one per read after an ingest, none from the cache
    obs.reset()
    dev.ingest(*batches[0])
    dev.flush()
    _ = dev.state
    _ = dev.state
    one.ingest(*batches[0])
    one.flush()
    _ = one.state
    out["spans"] = [[r.name, r.counts] for r in obs.records()
                    if r.name == "plane.collapse"]
    out["dispatch_slots"] = [r.counts.get("slots") for r in obs.records()
                             if r.name == "plane.dispatch"]
    out["state_bytes"] = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(
        E.init_batched(CFG)))
    out["stacked_width"] = int(planes.stack_by_key(*batches[0], D)[0].shape[1])

    # the collapse program's compiled text, for a profile's reader
    hlo = dev.plane.collapse_hlo()
    # (a synchronous collective-permute on the CPU, start and done on a TPU)
    out["collapse_hlo"] = {"permutes": len(re.findall(
                               r" collective-permute(?:-start)?\(", hlo)),
                           "module": hlo.split(",", 1)[0],
                           "sub_plane": one.plane.collapse_hlo()}

    # one compiled update per compacted width, whatever the shard count
    update = dev.plane._update
    jax.clear_caches()
    eng = E.SketchEngine(CFG, plane="pipeline", flush_elems=1,
                         plane_opts=opts())
    rng = np.random.default_rng(11)
    sized = [(rng.integers(0, 4000, (1, n)).astype(np.int32),
              np.ones((1, n), np.float32)) for n in (200, 600, 1200, 590)]
    for k, v in sized:
        eng.ingest(k, v)
    widths = {planes.stack_by_key(k, v, D)[0].shape[1] for k, v in sized}
    out["compiles"] = {"programs": update._cache_size(),
                       "widths": sorted(widths)}

    # set_state: the restored state goes to shard 0, the others reset
    st = dev.state
    dev.state = st
    out["set_state_diff"] = float(np.abs(
        np.asarray(dev.state.sketch.table) - np.asarray(st.sketch.table)).max())

    try:
        dev.plane.ingest_shard(0, *batches[0])
        out["ingest_shard"] = None
    except ValueError as e:
        out["ingest_shard"] = str(e)

    out["errors"] = {"too_many": error_of(devices=8, shards=8),
                     "not_shards": error_of(shards=2),
                     "subplane": error_of(subplane="async"),
                     "codec": error_of(codec="q8")}
    print("RESULT " + json.dumps(out))


if __name__ == "__main__":
    main()
