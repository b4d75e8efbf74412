"""End-to-end behaviour tests: training loop + restart, data pipeline
determinism, TV sampler, WORp-weighted data selection."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import perfect, tv_sampler
from repro.data.pipeline import FrequencySketcher, ZipfStream
from repro.train import loop

jax.config.update("jax_platform_name", "cpu")


class TestTrainingLoop:
    def test_loss_decreases(self, tmp_path):
        cfg = get_config("phi4_mini_38b").reduced()
        out = loop.run_training(cfg, num_steps=12, batch=4, seq=64,
                                lr=1e-3, log_every=100,
                                print_fn=lambda s: None)
        losses = out["losses"]
        assert np.isfinite(losses).all()
        assert np.mean(losses[-4:]) < np.mean(losses[:4])

    def test_token_analytics_plane_parity(self):
        """Training-stream token analytics through the async data plane
        equal the sync sparse plane bit for bit (the engine drains the
        double buffer deterministically at the final sample)."""
        cfg = get_config("phi4_mini_38b").reduced()
        kw = dict(num_steps=4, batch=2, seq=32, lr=1e-3, log_every=100,
                  print_fn=lambda s: None, analytics_sampler="onepass",
                  analytics_topk=8)
        a = loop.run_training(cfg, analytics_plane="async", **kw)
        b = loop.run_training(cfg, analytics_plane="sparse", **kw)
        assert a["top_tokens"] == b["top_tokens"]

    def test_checkpoint_restart_exact(self, tmp_path):
        """Crash/restart: resumed run produces the same final loss as an
        uninterrupted run (deterministic data + saved optimizer state)."""
        cfg = get_config("mamba2_13b").reduced()
        kw = dict(batch=2, seq=32, lr=1e-3, log_every=100,
                  print_fn=lambda s: None)
        full = loop.run_training(cfg, num_steps=8, **kw)
        d = str(tmp_path / "ck")
        loop.run_training(cfg, num_steps=4, ckpt_dir=d, ckpt_every=100, **kw)
        resumed = loop.run_training(cfg, num_steps=8, ckpt_dir=d,
                                    ckpt_every=100, **kw)
        assert resumed["final_loss"] == pytest.approx(full["final_loss"],
                                                      rel=1e-4)


class TestDataPipeline:
    def test_determinism(self):
        s = ZipfStream(vocab_size=1000, alpha=1.5, seed=3)
        a = s.batch_at(step=5, shard=2, batch=4, seq=16)
        b = s.batch_at(step=5, shard=2, batch=4, seq=16)
        c = s.batch_at(step=6, shard=2, batch=4, seq=16)
        assert np.array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_shards_disjoint_randomness(self):
        s = ZipfStream(vocab_size=1000, alpha=1.5, seed=3)
        a = s.batch_at(step=5, shard=0, batch=4, seq=16)
        b = s.batch_at(step=5, shard=1, batch=4, seq=16)
        assert not np.array_equal(a, b)

    def test_frequency_sketcher_weights(self):
        sk = FrequencySketcher(k=32, p=0.5, seed=5)
        stream = ZipfStream(vocab_size=500, alpha=2.0, seed=7)
        for step in range(6):
            sk.observe(jnp.asarray(stream.batch_at(step, 0, 8, 64)))
        toks = jnp.asarray(stream.batch_at(99, 0, 4, 32))
        w = np.asarray(sk.selection_weights(toks))
        assert w.shape == toks.shape
        assert np.isfinite(w).all() and (w > 0).all()
        # frequent token 0 must be down-weighted vs the tail
        flat_t, flat_w = np.asarray(toks).ravel(), w.ravel()
        if (flat_t == 0).any() and (flat_t > 100).any():
            assert flat_w[flat_t == 0].mean() <= flat_w[flat_t > 100].mean()

    def test_sketcher_merge(self):
        a = FrequencySketcher(k=16, p=1.0, seed=9)
        b = FrequencySketcher(k=16, p=1.0, seed=9)
        s = ZipfStream(vocab_size=300, alpha=2.0, seed=11)
        for step in range(4):
            a.observe(jnp.asarray(s.batch_at(step, 0, 4, 64)))
            b.observe(jnp.asarray(s.batch_at(step, 1, 4, 64)))
        a.merge_from(b)
        smp = a.sample()
        assert bool(jnp.all(smp.keys >= 0))


class TestTVSampler:
    def test_returns_k_distinct_heavy_keys(self):
        n, k = 400, 8
        freqs = np.ones(n, np.float32)
        heavy = [3, 77, 150, 222]
        for h in heavy:
            freqs[h] = 300.0
        st = tv_sampler.init(num_samplers=24, rows=5, width=256,
                             candidates=16, rhh_rows=5, rhh_width=512,
                             rhh_candidates=64, seed=13)
        keys = jnp.arange(n)
        for lo in range(0, n, 100):
            st = tv_sampler.update(st, keys[lo:lo + 100],
                                   jnp.asarray(freqs[lo:lo + 100]), p=1.0)
        sel = np.asarray(tv_sampler.produce_sample(st, k, p=1.0))
        got = [s for s in sel.tolist() if s >= 0]
        assert len(set(got)) == len(got)  # without replacement
        assert len(got) >= k // 2
        # heavy keys should dominate the sample
        assert len(set(got) & set(heavy)) >= 3

    def test_inclusion_tracks_ppswor(self):
        """Marginal inclusion of the heaviest key ~ perfect p-ppswor."""
        n, k, p = 100, 4, 1.0
        freqs = np.ones(n, np.float32)
        freqs[0] = 30.0
        hits_tv = 0
        trials = 12
        for t in range(trials):
            st = tv_sampler.init(num_samplers=16, rows=5, width=128,
                                 candidates=8, rhh_rows=5, rhh_width=256,
                                 rhh_candidates=32, seed=100 + t)
            st = tv_sampler.update(st, jnp.arange(n), jnp.asarray(freqs),
                                   p=p)
            sel = np.asarray(tv_sampler.produce_sample(st, k, p=p))
            hits_tv += int(0 in sel.tolist())
        # perfect inclusion prob of key 0 is high (~0.7+); allow slack
        assert hits_tv >= trials // 2


class TestCompileCache:
    def test_env_directory_is_used(self, tmp_path):
        """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there
        and the helper sets no other directory."""
        import os
        import subprocess
        import sys

        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   PYTHONPATH=os.pathsep.join(
                       ["src", os.environ.get("PYTHONPATH", "")]))
        script = (
            "import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()\n")
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=120,
                             cwd=os.path.dirname(os.path.dirname(__file__)))
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == [str(tmp_path)] * 2
        assert any(tmp_path.iterdir())

    def test_default_is_repo_root(self, monkeypatch):
        """Unset, the cache is .jax_cache/ at the repository root."""
        import pathlib

        from repro.launch.compile_cache import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        was = jax.config.jax_compilation_cache_dir
        try:
            got = enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert pathlib.Path(got) == root / ".jax_cache"
