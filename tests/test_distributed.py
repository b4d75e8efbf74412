"""Sharding resolver, checkpoint/restart, elastic remesh, gradient
compression, straggler watchdog."""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh_auto
from repro.train import checkpoint
from repro.train.elastic import StragglerWatchdog

jax.config.update("jax_platform_name", "cpu")


class _FakeMesh:
    """Duck-typed mesh exposing only .shape (what resolve_pspec reads)."""

    def __init__(self, **axes):
        self.shape = dict(axes)


class TestResolvePspec:
    def test_basic_tp(self):
        m = _FakeMesh(data=16, model=16)
        spec = shd.resolve_pspec((8192, 22016), ("embed", "mlp"), m)
        assert spec == P(("data",), ("model",))

    def test_multi_axis_fsdp(self):
        m = _FakeMesh(pod=2, data=16, model=16)
        spec = shd.resolve_pspec((8192, 22016), ("embed", "mlp"), m)
        assert spec == P(("pod", "data"), ("model",))

    def test_divisibility_fallback(self):
        """gemma2: 8 heads on a 16-way model axis -> replicated."""
        m = _FakeMesh(data=16, model=16)
        spec = shd.resolve_pspec((2304, 8, 256), ("embed", "heads", None), m)
        assert spec == P(("data",), None, None)

    def test_axis_reuse_blocked(self):
        """olmoe experts claim 'model'; expert_mlp must NOT double-claim."""
        m = _FakeMesh(data=16, model=16)
        spec = shd.resolve_pspec((64, 2048, 1024),
                                 ("experts", "embed", "expert_mlp"), m)
        assert spec == P(("model",), ("data",), None)

    def test_grok_expert_fallback(self):
        """grok: E=8 skips model; expert_mlp then claims it."""
        m = _FakeMesh(data=16, model=16)
        spec = shd.resolve_pspec((8, 6144, 32768),
                                 ("experts", "embed", "expert_mlp"), m)
        assert spec == P(None, ("data",), ("model",))

    def test_partial_multi_axis(self):
        """d_model divisible by data(16) but not pod*data(32): keep pod only
        if divisible by progressive product -- 2304 % 32 = 0 so both."""
        m = _FakeMesh(pod=2, data=16, model=16)
        spec = shd.resolve_pspec((2304,), ("embed",), m)
        assert spec == P(("pod", "data"))

    def test_missing_axis_ignored(self):
        m = _FakeMesh(data=4)
        spec = shd.resolve_pspec((128, 64), ("embed", "mlp"), m)
        assert spec == P(("data",), None)


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(12.0).reshape(3, 4),
                "b": {"c": jnp.ones((5,), jnp.int32)}}
        checkpoint.save(str(tmp_path), 7, tree)
        out, step = checkpoint.restore_latest(str(tmp_path), tree)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(tree["a"]))
        np.testing.assert_array_equal(np.asarray(out["b"]["c"]),
                                      np.asarray(tree["b"]["c"]))

    def test_latest_wins_and_tmp_ignored(self, tmp_path):
        tree = {"x": jnp.zeros(3)}
        checkpoint.save(str(tmp_path), 1, tree)
        checkpoint.save(str(tmp_path), 5, {"x": jnp.ones(3)})
        os.makedirs(tmp_path / "step_000000009.tmp")  # crash residue
        out, step = checkpoint.restore_latest(str(tmp_path), tree)
        assert step == 5
        assert float(out["x"][0]) == 1.0
        checkpoint.gc_tmp(str(tmp_path))
        assert not (tmp_path / "step_000000009.tmp").exists()

    def test_crc_detects_corruption(self, tmp_path):
        tree = {"w": jnp.arange(100.0)}
        path = checkpoint.save(str(tmp_path), 3, tree)
        fn = os.path.join(path, "w.npy")
        arr = np.load(fn)  # raw uint8 byte stream
        arr[0] ^= 0xFF     # flip a byte (torn-write simulation)
        np.save(fn, arr)
        with pytest.raises(IOError):
            checkpoint.restore(str(tmp_path), 3, tree)

    def test_elastic_remesh_subprocess(self, tmp_path):
        """Save on an 8-device mesh, restore re-sharded on a 4-device mesh.

        Runs in a subprocess because host device count locks at first use.
        """
        script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import checkpoint
from repro.train.elastic import plan_remesh
mesh8 = plan_remesh(8, model_parallel=2)
tree = {{"w": jax.device_put(np.arange(64.0).reshape(8, 8),
        NamedSharding(mesh8, P("data", "model")))}}
checkpoint.save(r"{tmp_path}", 1, tree)
# pretend a restart with fewer devices: 4-device submesh
mesh4 = plan_remesh(4, model_parallel=2)
shardings = {{"w": NamedSharding(mesh4, P("data", "model"))}}
out = checkpoint.restore(r"{tmp_path}", 1, tree, shardings)
assert np.allclose(np.asarray(out["w"]), np.arange(64.0).reshape(8, 8))
assert len(out["w"].sharding.device_set) == 4
print("ELASTIC_OK")
"""
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                           "src"))
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=300)
        assert "ELASTIC_OK" in r.stdout, r.stderr[-2000:]


class TestEngineCheckpoint:
    """SketchEngine batched states survive a checkpoint round-trip for EVERY
    registered sampler: same treedef, same leaf dtypes (uint32 seeds
    included), and bit-identical subsequent sample/estimate outputs when
    restored into a freshly constructed engine (the restart scenario)."""

    def _cfg(self, name):
        from repro import engine as E

        return E.EngineConfig(num_streams=3, rows=3, width=128,
                              candidates=16, capacity=16, p=1.0, seed=11,
                              sampler=name, domain=600, num_samplers=3)

    def _data(self):
        rng = np.random.default_rng(0)
        return (jnp.asarray(rng.integers(0, 500, (3, 40)), jnp.int32),
                jnp.asarray(rng.normal(size=(3, 40)).astype(np.float32)))

    @pytest.mark.parametrize("name", ["onepass", "twopass", "perfect", "tv"])
    def test_state_roundtrip_every_sampler(self, tmp_path, name):
        from repro import engine as E

        cfg = self._cfg(name)
        keys, vals = self._data()
        eng = E.SketchEngine(cfg)
        eng.ingest(keys, vals)
        eng.flush()  # checkpoint the device state, not the host buffer
        checkpoint.save(str(tmp_path), 5, eng.state,
                        extra={"sampler": name})

        fresh = E.SketchEngine(cfg)  # restart: like-tree from a fresh init
        restored, step = checkpoint.restore_latest(str(tmp_path),
                                                   fresh.state)
        assert step == 5
        assert (jax.tree_util.tree_structure(restored)
                == jax.tree_util.tree_structure(eng.state))
        for a, b in zip(jax.tree_util.tree_leaves(eng.state),
                        jax.tree_util.tree_leaves(restored)):
            assert a.dtype == b.dtype
            assert np.array_equal(np.asarray(a), np.asarray(b))
        fresh.state = restored

        s_old, s_new = eng.sample(4), fresh.sample(4)
        assert np.array_equal(np.asarray(s_old.keys), np.asarray(s_new.keys))
        assert np.array_equal(np.asarray(s_old.freqs),
                              np.asarray(s_new.freqs))
        assert np.array_equal(np.asarray(s_old.threshold),
                              np.asarray(s_new.threshold), equal_nan=True)
        e_old, e_new = eng.estimate(keys[:, :8]), fresh.estimate(keys[:, :8])
        assert np.array_equal(np.asarray(e_old), np.asarray(e_new))
        # restored engines keep working: further updates agree bitwise
        eng.update(keys[:, :8], vals[:, :8])
        fresh.update(keys[:, :8], vals[:, :8])
        for a, b in zip(jax.tree_util.tree_leaves(eng.state),
                        jax.tree_util.tree_leaves(fresh.state)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_pass2_state_roundtrip(self, tmp_path):
        from repro import engine as E

        cfg = self._cfg("onepass")
        keys, vals = self._data()
        eng = E.SketchEngine(cfg)
        eng.update(keys, jnp.abs(vals))
        eng.freeze()
        eng.update_pass2(keys, jnp.abs(vals))
        checkpoint.save(str(tmp_path), 2,
                        {"state": eng.state, "pass2": eng.pass2})

        fresh = E.SketchEngine(cfg)
        fresh.freeze()
        restored, _ = checkpoint.restore_latest(
            str(tmp_path), {"state": fresh.state, "pass2": fresh.pass2})
        fresh.state, fresh.pass2 = restored["state"], restored["pass2"]
        a, b = eng.sample_exact(4), fresh.sample_exact(4)
        assert np.array_equal(np.asarray(a.keys), np.asarray(b.keys))
        assert np.array_equal(np.asarray(a.freqs), np.asarray(b.freqs))


class TestStragglerWatchdog:
    def test_flags_outlier(self):
        w = StragglerWatchdog(threshold=2.0, warmup_steps=1)
        for step in range(6):
            w.step_begin()
            time.sleep(0.01 if step != 4 else 0.08)
            w.step_end(step)
        assert [f[0] for f in w.flagged] == [4]

    def test_baseline_not_poisoned(self):
        w = StragglerWatchdog(threshold=2.0, warmup_steps=1)
        w.step_begin(); time.sleep(0.01); w.step_end(0)
        w.step_begin(); time.sleep(0.01); w.step_end(1)
        base = w.ewma
        w.step_begin(); time.sleep(0.1); w.step_end(2)  # straggler
        assert w.ewma == base  # outlier did not move the EWMA


class TestGradComp:
    def test_compression_invariants_single_worker(self):
        """With one worker + twopass: sampled ids carry exact values and
        error feedback holds exactly the untransmitted residual."""
        from repro.optim import gradcomp

        mesh = make_mesh_auto((1,), ("data",))
        cc = gradcomp.CompressorConfig(k=32, rows=5, width=512,
                                       candidates=64, p=1.0, mode="twopass")
        a = jnp.asarray(
            np.random.default_rng(0).normal(size=4096).astype(np.float32))
        a = a.at[:8].set(jnp.arange(8, dtype=jnp.float32) * 50 + 100)

        def f(x):
            return gradcomp.compress_step(x, cc, ("data",))

        sparse, err, stats = jax.shard_map(
            f, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)(a)
        nz = np.nonzero(np.asarray(sparse))[0]
        assert len(nz) == cc.k
        # twopass: exact values at the sampled coordinates
        np.testing.assert_allclose(np.asarray(sparse)[nz],
                                   np.asarray(a)[nz], rtol=1e-5)
        # error feedback = residual
        np.testing.assert_allclose(np.asarray(sparse + err), np.asarray(a),
                                   rtol=1e-5, atol=1e-5)

    def test_sample_is_wor_ppswor(self):
        """decode_sample picks exactly the perfect p-ppswor top-k when the
        candidates cover them (same transform seed)."""
        from repro.core import countsketch, perfect, transforms
        from repro.optim import gradcomp

        cc = gradcomp.CompressorConfig(k=16, rows=7, width=2048,
                                       candidates=256, p=1.0)
        rng = np.random.default_rng(1)
        a = rng.normal(size=2000).astype(np.float32) * \
            (rng.random(2000) < 0.05)  # sparse-ish gradient
        table, cand = gradcomp.compress_locally(jnp.asarray(a), cc)
        ids, vals, tau = gradcomp.decode_sample(table, cand, cc)
        oracle = perfect.ppswor_sample(jnp.asarray(a), cc.k, cc.p,
                                       jnp.uint32(cc.seed))
        assert set(np.asarray(ids).tolist()) == set(
            np.asarray(oracle.keys).tolist())
