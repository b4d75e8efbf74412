"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

All kernels run in interpret mode (CPU container); on a real TPU the same
wrappers compile via Mosaic.  assert_allclose per the kernel contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

jax.config.update("jax_platform_name", "cpu")


SHAPES_N = [1, 127, 128, 1000, 4096, 5001]
WIDTHS = [64, 256, 777, 2048]
ROWS = [1, 3, 7]
DTYPES = [jnp.float32, jnp.bfloat16]


def _vals(n, dtype, seed=0):
    v = np.random.default_rng(seed).normal(size=n).astype(np.float32)
    return jnp.asarray(v).astype(dtype)


class TestCountSketchUpdateKernel:
    @pytest.mark.parametrize("n", SHAPES_N)
    @pytest.mark.parametrize("width", [256, 777])
    def test_shape_sweep(self, n, width):
        vals = _vals(n, jnp.float32)
        out = ops.sketch_dense_vector(vals, 5, width, seed=9)
        want = ref.countsketch_update_ref(vals, 0, 5, width, seed=9)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("rows", ROWS)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_rows_dtypes(self, rows, dtype):
        vals = _vals(1000, dtype)
        out = ops.sketch_dense_vector(vals, rows, 512, seed=3)
        want = ref.countsketch_update_ref(vals, 0, rows, 512, seed=3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-2 if dtype == jnp.bfloat16
                                   else 2e-5, atol=1e-2)

    @pytest.mark.parametrize("p", [0.5, 1.0, 2.0])
    def test_fused_transform(self, p):
        vals = _vals(3000, jnp.float32, seed=4)
        out = ops.sketch_dense_vector(vals, 5, 999, seed=9, p=p,
                                      transform_seed=11)
        want = ref.countsketch_update_ref(vals, 0, 5, 999, seed=9, p=p,
                                          transform_seed=11)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=1e-3)

    def test_base_key_offset(self):
        """Segmenting a vector with base keys == one-shot whole sketch."""
        vals = _vals(2048, jnp.float32, seed=5)
        whole = ref.countsketch_update_ref(vals, 0, 3, 256, seed=7)
        a = ops.sketch_dense_vector(vals[:1024], 3, 256, seed=7, base_key=0)
        b = ops.sketch_dense_vector(vals[1024:], 3, 256, seed=7,
                                    base_key=1024)
        np.testing.assert_allclose(np.asarray(a + b), np.asarray(whole),
                                   rtol=2e-5, atol=2e-5)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 3000), st.integers(33, 1024),
           st.integers(0, 2**31 - 1))
    def test_prop_matches_oracle(self, n, width, seed):
        vals = _vals(n, jnp.float32, seed=seed % 100)
        out = ops.sketch_dense_vector(vals, 3, width, seed=seed)
        want = ref.countsketch_update_ref(vals, 0, 3, width, seed=seed)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)


class TestCountSketchQueryKernel:
    @pytest.mark.parametrize("nkeys", [1, 37, 128, 400])
    @pytest.mark.parametrize("width", WIDTHS)
    def test_query_sweep(self, nkeys, width):
        table = jnp.asarray(
            np.random.default_rng(1).normal(size=(5, width)).astype(
                np.float32))
        keys = jnp.asarray(
            np.random.default_rng(2).integers(0, 10_000, nkeys), jnp.int32)
        out = ops.query_rows(table, keys, seed=9)
        want = ref.countsketch_query_ref(table, keys, seed=9)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    def test_estimate_median(self):
        vals = _vals(2000, jnp.float32, seed=6)
        table = ref.countsketch_update_ref(vals, 0, 7, 512, seed=3)
        keys = jnp.arange(50)
        out = ops.estimate(table, keys, seed=3)
        want = ref.countsketch_estimate_ref(table, keys, seed=3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


class TestTransformKernel:
    @pytest.mark.parametrize("n", [1, 100, 4096, 9999])
    @pytest.mark.parametrize("p", [0.5, 1.0, 1.5, 2.0])
    def test_sweep(self, n, p):
        keys = jnp.asarray(
            np.random.default_rng(3).integers(0, 2**31 - 1, n), jnp.int32)
        vals = _vals(n, jnp.float32, seed=7)
        out = ops.transform(keys, vals, p, 12)
        want = ref.ppswor_transform_ref(keys.astype(jnp.uint32), vals, p, 12)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_dtypes(self, dtype):
        keys = jnp.arange(512)
        vals = _vals(512, dtype)
        out = ops.transform(keys, vals, 1.0, 5)
        want = ref.ppswor_transform_ref(keys.astype(jnp.uint32), vals, 1.0,
                                        5)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-2)


class TestKernelCoreEquivalence:
    def test_kernel_table_equals_core_library(self):
        """The Pallas path and repro.core.countsketch agree bit-for-bit up to
        reduction order, so the sampler stack can swap them freely."""
        from repro.core import countsketch as cs
        vals = _vals(5000, jnp.float32, seed=8)
        t_kernel = ops.sketch_dense_vector(vals, 5, 777, seed=9)
        sk = cs.sketch_vector(vals, 5, 777, seed=9)
        np.testing.assert_allclose(np.asarray(t_kernel),
                                   np.asarray(sk.table), rtol=2e-5,
                                   atol=2e-5)


def _query_keys(rng, B, k, seeds, width):
    """(B, k) query keys: random, a few ``_EMPTY`` (-1) padding keys, and
    first in stream 0 a key whose row-0 bucket is ``width - 1``, so the last
    addressed sublane row of the query's (H, 128) table view is read."""
    from repro.core import hashing

    keys = rng.integers(0, 99_999, (B, k)).astype(np.int32)
    keys[:, 1:4] = -1
    cand = np.arange(100_000, 100_000 + 64 * width, dtype=np.int32)
    salt = hashing.row_salt(jnp.uint32(seeds[0]), jnp.uint32(0))
    bucket = np.asarray(hashing.bucket_hash(jnp.asarray(cand), salt, width))
    assert (bucket == width - 1).any()
    keys[0, 0] = cand[np.argmax(bucket == width - 1)]
    return jnp.asarray(keys)


class TestBatchedKernelEdgeCases:
    """Grid/padding edge cases for the BATCHED query + scatter kernels:
    widths and batch sizes that do NOT divide the block sizes, all-padding
    streams, and k == 1 key batches -- all bit-exact vs the ref.py oracles
    (fp32 reduction-order tolerance on accumulated scatter tables)."""

    # (B, width) pairs chosen so b_pad/w_pad require real padding and the
    # grid has multiple blocks per axis under the small block sizes below.
    RAGGED = [(1, 130), (5, 200), (10, 333), (13, 1025)]

    def _streams(self, B, n, seed=0, hi=50_000):
        rng = np.random.default_rng(seed)
        keys = jnp.asarray(rng.integers(0, hi, (B, n)), jnp.int32)
        vals = jnp.asarray(rng.normal(size=(B, n)).astype(np.float32))
        seeds = jnp.asarray(rng.integers(0, 2**31 - 1, B), jnp.uint32)
        tseeds = jnp.asarray(rng.integers(0, 2**31 - 1, B), jnp.uint32)
        return keys, vals, seeds, tseeds

    # the query's own cases beside RAGGED: B of 3, 8 and 9 streams at
    # widths that are no multiple of 128, of 1024, or the deployment's
    QUERY_RAGGED = RAGGED + [(3, 777), (8, 1000), (9, 31 * 1024)]

    @pytest.mark.parametrize("B,width", QUERY_RAGGED)
    def test_query_nonmultiple_blocks(self, B, width):
        from repro.kernels.countsketch_query import countsketch_query_batched

        rng = np.random.default_rng(B)
        tables = jnp.asarray(
            rng.normal(size=(B, 3, width)).astype(np.float32))
        seeds = jnp.asarray(rng.integers(0, 2**31 - 1, B), jnp.uint32)
        keys = _query_keys(rng, B, 37, seeds, width)
        out = countsketch_query_batched(tables, keys, seeds, block_b=8,
                                        interpret=True)
        want = ref.countsketch_query_batched_ref(tables, keys, seeds)
        assert np.array_equal(np.asarray(out), np.asarray(want))

    @pytest.mark.parametrize("B,width", RAGGED)
    def test_scatter_nonmultiple_blocks(self, B, width):
        from repro.kernels.countsketch_scatter import (
            countsketch_scatter_batched)

        keys, vals, seeds, tseeds = self._streams(B, 300, seed=B)
        out = countsketch_scatter_batched(
            keys, vals, 3, width, seeds, p=1.0, transform_seeds=tseeds,
            block_n=128, block_w=128, block_b=8, interpret=True)
        want = ref.countsketch_scatter_batched_ref(
            keys, vals, 3, width, seeds, p=1.0, transform_seeds=tseeds)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_scatter_all_padding_stream(self):
        """A stream whose keys are ALL -1 contributes an all-zero table; its
        neighbors are unaffected."""
        from repro.kernels.countsketch_scatter import (
            countsketch_scatter_batched)

        keys, vals, seeds, tseeds = self._streams(3, 200, seed=42)
        keys = keys.at[1].set(-1)
        out = countsketch_scatter_batched(
            keys, vals, 3, 200, seeds, p=1.0, transform_seeds=tseeds,
            block_n=128, block_w=128, interpret=True)
        want = ref.countsketch_scatter_batched_ref(
            keys, vals, 3, 200, seeds, p=1.0, transform_seeds=tseeds)
        assert not np.asarray(out[1]).any()
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_scatter_zero_lengths_stream(self):
        """lengths[b] == 0 masks the whole stream even with live keys."""
        from repro.kernels.countsketch_scatter import (
            countsketch_scatter_batched)

        keys, vals, seeds, tseeds = self._streams(3, 150, seed=7)
        lengths = jnp.asarray([150, 0, 37], jnp.int32)
        out = countsketch_scatter_batched(
            keys, vals, 3, 256, seeds, p=1.0, transform_seeds=tseeds,
            lengths=lengths, block_n=128, interpret=True)
        want = ref.countsketch_scatter_batched_ref(
            keys, vals, 3, 256, seeds, p=1.0, transform_seeds=tseeds,
            lengths=lengths)
        assert not np.asarray(out[1]).any()
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_query_multiple_key_blocks(self):
        """More keys than one key tile: the batched query's key grid axis,
        for a one-block batch and for two blocks of 8 streams."""
        from repro.kernels import tiling
        from repro.kernels.countsketch_query import countsketch_query_batched

        rng = np.random.default_rng(4)
        for B in (3, 9):
            block_b, _ = tiling.batch_block(tiling.BLOCK_B, B)
            k = 2 * tiling.query_block_k(block_b, 8) + 77
            tables = jnp.asarray(
                rng.normal(size=(B, 2, 300)).astype(np.float32))
            seeds = jnp.asarray(rng.integers(0, 2**31 - 1, B), jnp.uint32)
            keys = _query_keys(rng, B, k, seeds, 300)
            out = countsketch_query_batched(tables, keys, seeds,
                                            interpret=True)
            want = ref.countsketch_query_batched_ref(tables, keys, seeds)
            assert np.array_equal(np.asarray(out), np.asarray(want))

    def test_query_single_key(self):
        """k == 1 sample queries (the smallest possible key batch)."""
        from repro.kernels.countsketch_query import countsketch_query_batched

        rng = np.random.default_rng(3)
        tables = jnp.asarray(rng.normal(size=(5, 3, 777)).astype(np.float32))
        seeds = jnp.asarray(rng.integers(0, 2**31 - 1, 5), jnp.uint32)
        keys = _query_keys(rng, 5, 1, seeds, 777)
        out = countsketch_query_batched(tables, keys, seeds, interpret=True)
        want = ref.countsketch_query_batched_ref(tables, keys, seeds)
        assert out.shape == (5, 3, 1)
        assert np.array_equal(np.asarray(out), np.asarray(want))

    def test_scatter_single_element(self):
        """n == 1 scatter batches (one signed update per stream)."""
        from repro.kernels.countsketch_scatter import (
            countsketch_scatter_batched)

        keys, vals, seeds, tseeds = self._streams(4, 1, seed=11)
        out = countsketch_scatter_batched(
            keys, vals, 5, 333, seeds, p=2.0, transform_seeds=tseeds,
            interpret=True)
        want = ref.countsketch_scatter_batched_ref(
            keys, vals, 5, 333, seeds, p=2.0, transform_seeds=tseeds)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_onepass_sample_k1_through_engine(self):
        """k == 1 WOR samples flow through the batched query chokepoint."""
        from repro import engine as E

        cfg = E.EngineConfig(num_streams=3, rows=3, width=130,
                             candidates=8, p=1.0, seed=5)
        rng = np.random.default_rng(5)
        keys = jnp.asarray(rng.integers(0, 500, (3, 40)), jnp.int32)
        vals = jnp.asarray(rng.normal(size=(3, 40)).astype(np.float32))
        st = E.onepass_update_batched(E.onepass_init_batched(cfg), keys,
                                      vals, cfg.p)
        s = E.onepass_sample_batched(st, 1, cfg.p)
        assert s.keys.shape == (3, 1)
        for b in range(3):
            want = worp_onepass_sample_single(st, b, 1, cfg.p)
            assert int(s.keys[b, 0]) == int(want.keys[0])


class TestBatchBlock:
    """The batch tiling of the batched kernels (``tiling.batch_block``):
    fewer than SUBLANE streams run as one block of exactly B rows, more pad
    to a multiple of SUBLANE; either way each kernel equals its oracle."""

    BATCHES = [1, 2, 3, 7, 8, 9, 17]

    @pytest.mark.parametrize("B", BATCHES)
    def test_batch_block(self, B):
        from repro.kernels import tiling

        block_b, b_pad = tiling.batch_block(tiling.BLOCK_B, B)
        assert b_pad % block_b == 0 and b_pad >= B
        if B < tiling.SUBLANE:
            assert (block_b, b_pad) == (B, B)
        else:
            assert block_b == tiling.BLOCK_B and b_pad % tiling.SUBLANE == 0
            assert b_pad - B < tiling.SUBLANE
        assert tiling.scatter_tiles(B, 300)[0] == (block_b, b_pad)

    # (block_b, h, key tile): 8 or 1 streams at the deployment's h = 248,
    # and at k = 4096's h = 992, where the one-hot cap binds
    @pytest.mark.parametrize("block_b,h,want", [
        (8, 248, 512), (1, 248, 1024), (3, 8, 1024), (8, 992, 128),
        (1, 992, 1024)])
    def test_query_block_k(self, block_b, h, want):
        from repro.kernels import tiling

        got = tiling.query_block_k(block_b, h)
        assert got == want and got % tiling.LANE == 0
        assert got <= tiling.BLOCK_K
        assert block_b * h * got <= max(tiling.QUERY_ONEHOT,
                                        block_b * h * tiling.LANE)

    @staticmethod
    def _streams(B, n, seed):
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, 50_000, (B, n)).astype(np.int32)
        keys[:, -5:] = -1
        vals = rng.normal(size=(B, n)).astype(np.float32)
        seeds = jnp.asarray(rng.integers(0, 2**31 - 1, B), jnp.uint32)
        tseeds = jnp.asarray(rng.integers(0, 2**31 - 1, B), jnp.uint32)
        lengths = jnp.asarray(rng.integers(n // 2, n + 1, B), jnp.int32)
        return jnp.asarray(keys), jnp.asarray(vals), seeds, tseeds, lengths

    @pytest.mark.parametrize("B", BATCHES)
    def test_scatter_matches_oracle(self, B):
        from repro.kernels.countsketch_scatter import (
            countsketch_scatter_batched)

        keys, vals, seeds, tseeds, lengths = self._streams(B, 200, B)
        out = countsketch_scatter_batched(
            keys, vals, 3, 300, seeds, p=2.0, scheme="priority",
            transform_seeds=tseeds, lengths=lengths, block_n=128,
            block_w=128, interpret=True)
        want = ref.countsketch_scatter_batched_ref(
            keys, vals, 3, 300, seeds, p=2.0, transform_seeds=tseeds,
            lengths=lengths, scheme="priority")
        assert out.shape == (B, 3, 300)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("B", BATCHES)
    def test_query_matches_oracle_bitwise(self, B):
        from repro.kernels.countsketch_query import countsketch_query_batched

        rng = np.random.default_rng(100 + B)
        tables = jnp.asarray(rng.normal(size=(B, 3, 300)).astype(np.float32))
        seeds = jnp.asarray(rng.integers(0, 2**31 - 1, B), jnp.uint32)
        keys = _query_keys(rng, B, 150, seeds, 300)
        out = countsketch_query_batched(tables, keys, seeds, interpret=True)
        want = ref.countsketch_query_batched_ref(tables, keys, seeds)
        assert out.shape == (B, 3, 150)
        assert np.array_equal(np.asarray(out), np.asarray(want))

    @pytest.mark.parametrize("B", BATCHES)
    def test_update_matches_oracle(self, B):
        from repro.kernels.countsketch_update import (
            countsketch_update_batched)

        _, vals, seeds, tseeds, lengths = self._streams(B, 200, 200 + B)
        base_keys = jnp.asarray(np.arange(B) * 1000, jnp.uint32)
        out = countsketch_update_batched(
            vals, 3, 300, seeds, p=1.0, transform_seeds=tseeds,
            base_keys=base_keys, lengths=lengths, block_n=128, block_w=128,
            interpret=True)
        live = jnp.where(jnp.arange(200)[None, :] < lengths[:, None], vals,
                         0.0)
        want = jax.vmap(lambda v, b, s, t: ref.countsketch_update_ref(
            v, b, 3, 300, s, p=1.0, transform_seed=t))(
                live, base_keys, seeds, tseeds)
        assert out.shape == (B, 3, 300)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def worp_onepass_sample_single(st, b, k, p):
    import jax as _jax
    from repro.core import worp

    one = _jax.tree_util.tree_map(lambda x: x[b], st)
    return worp.onepass_sample(one, k, p)
