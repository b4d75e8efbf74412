"""Unit + property tests for the WORp core: hashing, CountSketch, counters.

The hypothesis properties pin the invariants everything else relies on:
  * CountSketch is LINEAR (signed updates cancel; merge == concat)
  * processing order / sharding never changes the sketch
  * counter estimates are underestimates within the MG error bound
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.core import counters, countsketch, hashing, transforms

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# hashing
# ---------------------------------------------------------------------------

class TestHashing:
    def test_deterministic(self):
        k = jnp.arange(1000)
        assert jnp.array_equal(hashing.hash_u32(k, 7), hashing.hash_u32(k, 7))

    def test_salt_changes_everything(self):
        k = jnp.arange(1000)
        a, b = hashing.hash_u32(k, 1), hashing.hash_u32(k, 2)
        assert float(jnp.mean(a == b)) < 0.01

    def test_uniform01_range_and_mean(self):
        u = np.asarray(hashing.uniform01(jnp.arange(100_000), 3))
        assert u.min() > 0.0 and u.max() <= 1.0
        assert abs(u.mean() - 0.5) < 0.01

    def test_exp1_moments(self):
        e = np.asarray(hashing.exp1(jnp.arange(200_000), 5))
        assert abs(e.mean() - 1.0) < 0.02
        assert abs(e.var() - 1.0) < 0.05

    def test_exp1_top_bin_is_positive(self):
        """Key 1487 under salt 2308002755 lands in uniform01's top bin,
        which rounds to 1.0; its variate must stay positive so the ppswor
        transform stays finite."""
        key, salt = jnp.uint32(1487), jnp.uint32(2308002755)
        assert float(hashing.uniform01(key, salt)) == 1.0
        assert float(hashing.exp1(key, salt)) == 2.0**-25
        assert np.isfinite(float(transforms.transform_values(
            key, jnp.float32(1.0), 1.0, salt)))

    def test_sign_hash_balanced(self):
        s = np.asarray(hashing.sign_hash(jnp.arange(100_000), 11))
        assert set(np.unique(s)) == {-1.0, 1.0}
        assert abs(s.mean()) < 0.02

    def test_bucket_hash_uniform(self):
        b = np.asarray(hashing.bucket_hash(jnp.arange(100_000), 13, 64))
        counts = np.bincount(b, minlength=64)
        assert counts.min() > 0.8 * 100_000 / 64
        assert counts.max() < 1.2 * 100_000 / 64

    def test_pairwise_sign_independence(self):
        """Products of sign pairs should be ~balanced (2-wise property)."""
        s = np.asarray(hashing.sign_hash(jnp.arange(50_000), 17))
        prod = s[:-1] * s[1:]
        assert abs(prod.mean()) < 0.02


# ---------------------------------------------------------------------------
# CountSketch
# ---------------------------------------------------------------------------

class TestCountSketch:
    def test_single_key_exact(self):
        sk = countsketch.init(5, 64, 3)
        sk = countsketch.update(sk, jnp.array([42]), jnp.array([7.5]))
        est = countsketch.estimate(sk, jnp.array([42]))
        assert est[0] == pytest.approx(7.5)

    def test_signed_updates_cancel(self):
        sk = countsketch.init(5, 128, 3)
        keys = jnp.arange(50)
        vals = jnp.linspace(1, 5, 50)
        sk = countsketch.update(sk, keys, vals)
        sk = countsketch.update(sk, keys, -vals)
        # linear in exact arithmetic; fp32 rounding leaves ~ulp residue
        assert float(jnp.abs(sk.table).max()) < 1e-5 * 5.0

    def test_merge_equals_single_pass(self):
        rng = np.random.default_rng(0)
        keys = jnp.asarray(rng.integers(0, 500, 400), jnp.int32)
        vals = jnp.asarray(rng.normal(size=400).astype(np.float32))
        whole = countsketch.update(countsketch.init(5, 256, 9), keys, vals)
        a = countsketch.update(countsketch.init(5, 256, 9), keys[:137],
                               vals[:137])
        b = countsketch.update(countsketch.init(5, 256, 9), keys[137:],
                               vals[137:])
        merged = countsketch.merge(a, b)
        np.testing.assert_allclose(np.asarray(merged.table),
                                   np.asarray(whole.table), rtol=1e-5,
                                   atol=1e-5)

    def test_error_bound_l2(self):
        """|est - nu| <= ||tail_k(nu)||_2 * sqrt(c / width) whp (Table 1)."""
        from tests.conftest import zipf_freqs
        n, k = 4000, 50
        freqs = zipf_freqs(n, 1.5, seed=1)
        sk = countsketch.sketch_vector(jnp.asarray(freqs), 7, 1024, 5)
        est = np.asarray(countsketch.estimate(sk, jnp.arange(n)))
        err = np.abs(est - freqs)
        tail = np.sort(np.abs(freqs))[::-1][k:]
        bound = np.linalg.norm(tail) * np.sqrt(8.0 / 1024)
        # median-of-7 estimate: the bound should hold for ~all keys
        assert np.mean(err <= bound * 4) > 0.999

    def test_unbiased_per_row(self):
        """Single-row estimates are unbiased over seeds."""
        freqs = jnp.asarray([100.0] + [1.0] * 200)
        ests = []
        for seed in range(200):
            sk = countsketch.sketch_vector(freqs, 1, 32, seed)
            ests.append(float(countsketch.estimate_single_row(
                sk, jnp.array([0]), 0)[0]))
        assert np.mean(ests) == pytest.approx(100.0, abs=3.0)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 200))
    def test_prop_permutation_invariance(self, seed, nkeys):
        rng = np.random.default_rng(seed)
        keys = jnp.asarray(rng.integers(0, 10_000, nkeys), jnp.int32)
        vals = jnp.asarray(rng.normal(size=nkeys).astype(np.float32))
        perm = rng.permutation(nkeys)
        a = countsketch.update(countsketch.init(3, 64, seed), keys, vals)
        b = countsketch.update(countsketch.init(3, 64, seed), keys[perm],
                               vals[perm])
        np.testing.assert_allclose(np.asarray(a.table), np.asarray(b.table),
                                   rtol=1e-4, atol=1e-4)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(2, 150),
           st.integers(1, 149))
    def test_prop_split_merge(self, seed, nkeys, cut):
        cut = min(cut, nkeys - 1)
        rng = np.random.default_rng(seed)
        keys = jnp.asarray(rng.integers(0, 1000, nkeys), jnp.int32)
        vals = jnp.asarray(rng.normal(size=nkeys).astype(np.float32))
        whole = countsketch.update(countsketch.init(3, 64, 5), keys, vals)
        m = countsketch.merge(
            countsketch.update(countsketch.init(3, 64, 5), keys[:cut],
                               vals[:cut]),
            countsketch.update(countsketch.init(3, 64, 5), keys[cut:],
                               vals[cut:]))
        np.testing.assert_allclose(np.asarray(whole.table),
                                   np.asarray(m.table), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# counters (ell_1, positive)
# ---------------------------------------------------------------------------

class TestCounters:
    def test_underestimate_within_bound(self):
        from tests.conftest import zipf_freqs
        n, m = 2000, 128
        freqs = zipf_freqs(n, 2.0, seed=2)
        cs = counters.init(m)
        # stream in chunks
        for lo in range(0, n, 250):
            cs = counters.update(cs, jnp.arange(lo, min(lo + 250, n)),
                                 jnp.asarray(freqs[lo:lo + 250]))
        est = np.asarray(counters.estimate(cs, jnp.arange(n)))
        total = freqs.sum()
        # MG invariant: underestimate, off by at most total/ (m+1) ... we use
        # the weaker classical bound total/m
        assert np.all(est <= freqs + 1e-3)
        assert np.all(freqs - est <= total / m * 2 + 1e-3)

    def test_top_keys_present(self):
        from tests.conftest import zipf_freqs
        freqs = zipf_freqs(1000, 2.0, seed=3)
        cs = counters.update(counters.init(64), jnp.arange(1000),
                             jnp.asarray(freqs))
        keys, _ = counters.stored(cs)
        top5 = set(np.argsort(-freqs)[:5].tolist())
        assert top5 <= set(np.asarray(keys).tolist())

    def test_merge_preserves_bound(self):
        from tests.conftest import zipf_freqs
        freqs = zipf_freqs(1000, 1.5, seed=4)
        a = counters.update(counters.init(96), jnp.arange(500),
                            jnp.asarray(freqs[:500]))
        b = counters.update(counters.init(96), jnp.arange(500, 1000),
                            jnp.asarray(freqs[500:]))
        m = counters.merge(a, b)
        est = np.asarray(counters.estimate(m, jnp.arange(1000)))
        assert np.all(est <= freqs + 1e-3)
        assert np.all(freqs - est <= freqs.sum() / 96 * 2 + 1e-3)
