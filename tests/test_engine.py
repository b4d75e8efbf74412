"""SketchEngine contracts: the batched pytree engine must be a bit-exact
vectorization of the single-stream WORp functions (the vmap-consistency
contract), the Pallas fast path must agree with the jnp path, and the merge
trees (host, stream-collapse, butterfly) must equal sequential merging.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as E
from repro.core import countsketch, transforms, worp
from repro.core import sampler as core_sampler
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh_auto

jax.config.update("jax_platform_name", "cpu")

B, ROWS, WIDTH, CAND, CAP = 4, 5, 256, 64, 64

# per-sampler overrides for the registry contract (small enough to keep the
# parametrized sweep fast; "perfect" needs a domain covering the test keys)
SAMPLER_TEST_CFG = {
    "onepass": {},
    "twopass": {},
    "perfect": dict(domain=2000),
    "tv": dict(num_samplers=3, rows=3, width=128, candidates=16),
}


def _registry_cfg(name, scheme=transforms.PPSWOR):
    base = dict(num_streams=B, rows=3, width=128, candidates=24, capacity=24,
                p=1.0, scheme=scheme, seed=11, sampler=name)
    base.update(SAMPLER_TEST_CFG[name])
    return E.EngineConfig(**base)


def _cfg(**kw):
    base = dict(num_streams=B, rows=ROWS, width=WIDTH, candidates=CAND,
                capacity=CAP, p=1.0, seed=7)
    base.update(kw)
    return E.EngineConfig(**base)


def _batches(seed=0, n=100):
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(0, 2000, (B, n)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(B, n)).astype(np.float32))
    return keys, vals


def _stream_states(cfg, keys, vals, nbatches=2):
    """Python-loop reference: single-stream onepass per stream."""
    sks, tss = E.derive_stream_seeds(cfg)
    out = []
    for b in range(cfg.num_streams):
        st = worp.onepass_init(cfg.rows, cfg.width, cfg.candidates,
                               sks[b], tss[b])
        n = keys.shape[1]
        step = n // nbatches
        for lo in range(0, n, step):
            st = worp.onepass_update(st, keys[b, lo:lo + step],
                                     vals[b, lo:lo + step], cfg.p)
        out.append(st)
    return out


class TestVmapConsistency:
    """Batched engine == Python loop over single-stream ops, BITWISE."""

    def test_onepass_single_update_bitwise(self):
        """One update from init: tables AND candidates bitwise equal."""
        cfg = _cfg()
        keys, vals = _batches()
        st = E.onepass_update_batched(E.onepass_init_batched(cfg), keys,
                                      vals, cfg.p)
        for b, ref in enumerate(_stream_states(cfg, keys, vals, nbatches=1)):
            assert np.array_equal(np.asarray(st.sketch.table[b]),
                                  np.asarray(ref.sketch.table))
            assert np.array_equal(np.asarray(st.cand_keys[b]),
                                  np.asarray(ref.cand_keys))
            assert int(st.seed_transform[b]) == int(ref.seed_transform)

    def test_onepass_multi_update_consistency(self):
        """Across repeated updates the discrete outputs (candidate buffers)
        stay bitwise equal; accumulated fp tables are allowed 1-ulp scatter
        reduction-order drift (XLA batches the scatter-add differently under
        vmap), bounded here at 2e-6."""
        cfg = _cfg()
        keys, vals = _batches()
        st = E.onepass_init_batched(cfg)
        n, step = keys.shape[1], keys.shape[1] // 2
        for lo in range(0, n, step):
            st = E.onepass_update_batched(st, keys[:, lo:lo + step],
                                          vals[:, lo:lo + step], cfg.p)
        refs = _stream_states(cfg, keys, vals)
        for b, ref in enumerate(refs):
            np.testing.assert_allclose(np.asarray(st.sketch.table[b]),
                                       np.asarray(ref.sketch.table),
                                       rtol=0, atol=2e-6)
            assert np.array_equal(np.asarray(st.cand_keys[b]),
                                  np.asarray(ref.cand_keys))

    def test_onepass_sample_bitwise(self):
        cfg = _cfg()
        keys, vals = _batches(seed=1)
        st = E.onepass_update_batched(E.onepass_init_batched(cfg), keys,
                                      vals, cfg.p)
        sample = E.onepass_sample_batched(st, 8, cfg.p)
        for b, ref in enumerate(_stream_states(cfg, keys, vals, nbatches=1)):
            want = worp.onepass_sample(ref, 8, cfg.p)
            assert np.array_equal(np.asarray(sample.keys[b]),
                                  np.asarray(want.keys))
            assert np.array_equal(np.asarray(sample.freqs[b]),
                                  np.asarray(want.freqs))
            assert float(sample.threshold[b]) == float(want.threshold)

    def test_twopass_update_bitwise(self):
        cfg = _cfg()
        keys, vals = _batches(seed=2)
        st1 = E.onepass_update_batched(E.onepass_init_batched(cfg), keys,
                                       vals, cfg.p)
        st2 = E.twopass_init_batched(cfg)
        st2 = E.twopass_update_batched(st2, st1.sketch, keys, vals)
        sample = E.twopass_sample_batched(st2, 8, cfg.p)

        _, tss = E.derive_stream_seeds(cfg)
        for b, ref1 in enumerate(_stream_states(cfg, keys, vals, nbatches=1)):
            r2 = worp.twopass_init(cfg.capacity, tss[b])
            r2 = worp.twopass_update(r2, ref1.sketch, keys[b], vals[b])
            assert np.array_equal(np.asarray(st2.keys[b]), np.asarray(r2.keys))
            assert np.array_equal(np.asarray(st2.freqs[b]),
                                  np.asarray(r2.freqs))
            want = worp.twopass_sample(r2, 8, cfg.p)
            assert np.array_equal(np.asarray(sample.keys[b]),
                                  np.asarray(want.keys))

    def test_merge_batched_bitwise(self):
        cfg = _cfg()
        ka, va = _batches(seed=3)
        kb, vb = _batches(seed=4)
        a = E.onepass_update_batched(E.onepass_init_batched(cfg), ka, va,
                                     cfg.p)
        b_ = E.onepass_update_batched(E.onepass_init_batched(cfg), kb, vb,
                                      cfg.p)
        m = E.onepass_merge_batched(a, b_)
        for b in range(B):
            sa = jax.tree_util.tree_map(lambda x: x[b], a)
            sb = jax.tree_util.tree_map(lambda x: x[b], b_)
            want = worp.onepass_merge(sa, sb)
            assert np.array_equal(np.asarray(m.sketch.table[b]),
                                  np.asarray(want.sketch.table))
            assert np.array_equal(np.asarray(m.cand_keys[b]),
                                  np.asarray(want.cand_keys))


class TestKernelFastPath:
    def test_dense_update_matches_jnp_path(self):
        """Batched pallas_call path == vmapped jnp path (reduction-order tol);
        candidate buffers must agree exactly."""
        cfg = _cfg(num_streams=3, rows=3, width=512, candidates=32)
        rng = np.random.default_rng(5)
        dense = jnp.asarray(rng.normal(size=(3, 700)).astype(np.float32))
        fast = E.onepass_update_dense(E.onepass_init_batched(cfg), dense,
                                      cfg.p)
        dkeys = jnp.broadcast_to(jnp.arange(700, dtype=jnp.int32), (3, 700))
        slow = E.onepass_update_batched(E.onepass_init_batched(cfg), dkeys,
                                        dense, cfg.p)
        np.testing.assert_allclose(np.asarray(fast.sketch.table),
                                   np.asarray(slow.sketch.table),
                                   rtol=1e-4, atol=1e-4)
        assert np.array_equal(np.asarray(fast.cand_keys),
                              np.asarray(slow.cand_keys))

    def test_dense_update_ragged_lengths(self):
        """Streams of different true lengths batch into one kernel call."""
        cfg = _cfg(num_streams=3, rows=3, width=512, candidates=32)
        rng = np.random.default_rng(6)
        dense = jnp.asarray(rng.normal(size=(3, 600)).astype(np.float32))
        lengths = jnp.asarray([600, 123, 400], jnp.int32)
        fast = E.onepass_update_dense(E.onepass_init_batched(cfg), dense,
                                      cfg.p, lengths=lengths)
        sks, tss = E.derive_stream_seeds(cfg)
        for b, ln in enumerate([600, 123, 400]):
            ref = worp.onepass_init(cfg.rows, cfg.width, cfg.candidates,
                                    sks[b], tss[b])
            ref = worp.onepass_update(ref, jnp.arange(ln, dtype=jnp.int32),
                                      dense[b, :ln], cfg.p)
            np.testing.assert_allclose(np.asarray(fast.sketch.table[b]),
                                       np.asarray(ref.sketch.table),
                                       rtol=1e-4, atol=1e-4)


class TestMergeTrees:
    def test_reduce_streams_equals_sequential(self):
        for nstreams in (4, 5):  # power of two + odd carry
            cfg = _cfg(num_streams=nstreams, shared_seeds=True)
            rng = np.random.default_rng(7)
            keys = jnp.asarray(rng.integers(0, 2000, (nstreams, 80)),
                               jnp.int32)
            vals = jnp.asarray(
                rng.normal(size=(nstreams, 80)).astype(np.float32))
            st = E.onepass_update_batched(E.onepass_init_batched(cfg), keys,
                                          vals, cfg.p)
            got = E.reduce_streams(st, E.onepass_merge_batched)
            shards = [jax.tree_util.tree_map(lambda x: x[b], st)
                      for b in range(nstreams)]
            want = shards[0]
            for s in shards[1:]:
                want = worp.onepass_merge(want, s)
            # tables are linear: tree order == sequential order (fp tol)
            np.testing.assert_allclose(np.asarray(got.sketch.table),
                                       np.asarray(want.sketch.table),
                                       rtol=1e-5, atol=1e-5)
            # candidate buffers truncate top-C per ROUND, so tree and
            # sequential merges may retain different (equally valid) tails;
            # the actual WOR sample must nevertheless agree.
            sg = worp.onepass_sample(got, 8, cfg.p)
            sw = worp.onepass_sample(want, 8, cfg.p)
            assert (set(np.asarray(sg.keys).tolist())
                    == set(np.asarray(sw.keys).tolist()))

    def test_host_tree_merge(self):
        sks = [countsketch.update(countsketch.init(3, 64, 9),
                                  jnp.arange(10) + 10 * i,
                                  jnp.ones(10) * (i + 1))
               for i in range(5)]
        got = shd.tree_merge(sks, countsketch.merge)
        want = sks[0]
        for s in sks[1:]:
            want = countsketch.merge(want, s)
        np.testing.assert_allclose(np.asarray(got.table),
                                   np.asarray(want.table), rtol=1e-6)

    def test_butterfly_allmerge_subprocess(self):
        """4 host devices: every device ends with the global merged state.

        Subprocess because the host device count locks at first jax use.
        """
        script = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import worp
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh_auto

mesh = make_mesh_auto((4,), ("data",))
rng = np.random.default_rng(0)
keys = jnp.asarray(rng.integers(0, 3000, (4, 200)), jnp.int32)
vals = jnp.asarray(rng.normal(size=(4, 200)).astype(np.float32))

def worker(k, v):
    st = worp.onepass_init(5, 256, 64, 3, 77)
    st = worp.onepass_update(st, k[0], v[0], 1.0)
    g = shd.butterfly_allmerge(st, "data", worp.onepass_merge, axis_size=4)
    return jax.tree_util.tree_map(lambda x: x[None], g)

out = jax.shard_map(worker, mesh=mesh, in_specs=(P("data"), P("data")),
                    out_specs=P("data"), check_vma=False)(keys, vals)
sts = []
for b in range(4):
    st = worp.onepass_init(5, 256, 64, 3, 77)
    sts.append(worp.onepass_update(st, keys[b], vals[b], 1.0))
ref = shd.tree_merge(sts, worp.onepass_merge)
for b in range(4):
    np.testing.assert_allclose(np.asarray(out.sketch.table[b]),
                               np.asarray(ref.sketch.table),
                               rtol=1e-5, atol=1e-5)
print("BUTTERFLY_OK")
"""
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                           "src"))
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=300)
        assert "BUTTERFLY_OK" in r.stdout, r.stderr[-2000:]

    def _shard_states(self, seeds=(77, 77, 77, 77)):
        rng = np.random.default_rng(2)
        out = []
        for i, ts in enumerate(seeds):
            st = worp.onepass_init(3, 128, 32, 9, ts)
            out.append(worp.onepass_update(
                st, jnp.asarray(rng.integers(0, 900, 60), jnp.int32),
                jnp.asarray(rng.normal(size=60).astype(np.float32)), 1.0))
        return out

    def test_butterfly_host_form_equals_tree_merge(self):
        """The eager list form of butterfly_allmerge merges to the same
        global state as the host tree (linear tables: exact up to fp)."""
        sts = self._shard_states()
        got = shd.butterfly_allmerge(sts, None, worp.onepass_merge)
        want = shd.tree_merge(sts, worp.onepass_merge)
        np.testing.assert_allclose(np.asarray(got.sketch.table),
                                   np.asarray(want.sketch.table),
                                   rtol=1e-5, atol=1e-5)
        sg = worp.onepass_sample(got, 8, 1.0)
        sw = worp.onepass_sample(want, 8, 1.0)
        assert (set(np.asarray(sg.keys).tolist())
                == set(np.asarray(sw.keys).tolist()))

    def test_butterfly_rejects_seed_mismatch(self):
        """Seed-mismatch rejection, matching the tree_merge guard: shards
        hashed under different transform seeds are not shards of one
        logical stream -- the butterfly must fail loudly, not merge
        garbage."""
        sts = self._shard_states(seeds=(77, 77, 78, 77))
        with pytest.raises(ValueError, match="butterfly_allmerge.*seeds"):
            shd.butterfly_allmerge(sts, None, worp.onepass_merge)
        # same states through tree_merge: identical contract
        with pytest.raises(ValueError, match="seeds"):
            shd.tree_merge(sts, worp.onepass_merge)

    def test_butterfly_host_form_rejects_ragged(self):
        sts = self._shard_states(seeds=(77, 77, 77))
        with pytest.raises(ValueError, match="power-of-two"):
            shd.butterfly_allmerge(sts, None, worp.onepass_merge)

    def test_psum_sketch_single_device(self):
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh_auto((1,), ("data",))
        sk = countsketch.update(countsketch.init(3, 64, 9), jnp.arange(10),
                                jnp.ones(10))

        def f(table):
            merged = shd.psum_sketch(
                countsketch.CountSketch(table=table, seed=jnp.uint32(9)),
                ("data",))
            return merged.table

        out = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                            check_vma=False)(sk.table)
        np.testing.assert_allclose(np.asarray(out), np.asarray(sk.table))


class TestEngineGradComp:
    def test_per_layer_invariants_single_worker(self):
        """Engine path: each layer gets its own exact-valued WOR sample and
        error feedback holds exactly the untransmitted residual."""
        from jax.sharding import PartitionSpec as P
        from repro.optim import gradcomp

        mesh = make_mesh_auto((1,), ("data",))
        cc = gradcomp.CompressorConfig(k=32, rows=5, width=512, p=1.0,
                                       mode="twopass")
        rng = np.random.default_rng(0)
        grads = {"wq": jnp.asarray(
                     rng.normal(size=(64, 32)).astype(np.float32)),
                 "wk": jnp.asarray(rng.normal(size=1500).astype(np.float32)),
                 "b": jnp.asarray(rng.normal(size=130).astype(np.float32))}
        err = gradcomp.init_error(grads)

        def f(g, e):
            return gradcomp.tree_compress_step_engine(g, e, cc, ("data",),
                                                      k_per_leaf=16)

        sparse, new_err, stats = jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False)(grads, err)
        for name in grads:
            s = np.asarray(sparse[name]).ravel()
            a = np.asarray(grads[name]).ravel()
            nz = np.nonzero(s)[0]
            assert 1 <= len(nz) <= 16  # every layer represented
            np.testing.assert_allclose(s[nz], a[nz], rtol=1e-5)
            np.testing.assert_allclose(
                s + np.asarray(new_err[name]).ravel(), a, rtol=1e-5,
                atol=1e-5)
        assert float(stats["comm_floats"]) < float(stats["dense_floats"]) * 10

    def test_small_leaf_regression(self):
        """A leaf smaller than k_per_leaf (bias/LayerNorm scale) must not
        crash the per-layer path or corrupt other leaves."""
        from jax.sharding import PartitionSpec as P
        from repro.optim import gradcomp

        mesh = make_mesh_auto((1,), ("data",))
        cc = gradcomp.CompressorConfig(k=32, rows=3, width=256, p=1.0,
                                       mode="twopass")
        rng = np.random.default_rng(1)
        grads = {"w": jnp.asarray(
                     rng.normal(size=(64, 32)).astype(np.float32)),
                 "scale": jnp.asarray(
                     rng.normal(size=8).astype(np.float32))}
        err = gradcomp.init_error(grads)

        def f(g, e):
            return gradcomp.tree_compress_step_engine(g, e, cc, ("data",),
                                                      k_per_leaf=32,
                                                      cand_per_leaf=64)

        sparse, new_err, _ = jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False)(grads, err)
        for name in grads:
            s = np.asarray(sparse[name]).ravel()
            a = np.asarray(grads[name]).ravel()
            nz = np.nonzero(s)[0]
            assert len(nz) >= 1
            np.testing.assert_allclose(s[nz], a[nz], rtol=1e-5)
            np.testing.assert_allclose(
                s + np.asarray(new_err[name]).ravel(), a, rtol=1e-5,
                atol=1e-5)


class TestRegistryContract:
    """EVERY registered sampler satisfies the engine's batched==single-stream
    consistency contract: the vmapped/jitted batched ops equal a Python loop
    of the spec's single-stream functions.  Discrete outputs (keys) must be
    bitwise equal; accumulated fp leaves get 1-ulp reduction-order slack."""

    @pytest.mark.parametrize("scheme", [transforms.PPSWOR,
                                        transforms.PRIORITY])
    @pytest.mark.parametrize("name", core_sampler.available())
    def test_batched_equals_single(self, name, scheme):
        cfg = _registry_cfg(name, scheme)
        spec = E.engine_spec(cfg)
        bops = E.batched_ops(spec)
        keys, vals = _batches(seed=5, n=60)
        sks, tss = E.derive_stream_seeds(cfg)

        st = bops.init(sks, tss)
        st = bops.update(st, keys[:, :30], vals[:, :30])
        st = bops.update(st, keys[:, 30:], vals[:, 30:])
        m = bops.merge(st, st)
        s = bops.sample(m, k=4)
        est = bops.estimate(m, keys[:, :10])

        for b in range(cfg.num_streams):
            ref = spec.init(sks[b], tss[b])
            ref = spec.update(ref, keys[b, :30], vals[b, :30])
            ref = spec.update(ref, keys[b, 30:], vals[b, 30:])
            refm = spec.merge(ref, ref)
            sref = spec.sample(refm, 4)
            assert np.array_equal(np.asarray(s.keys[b]),
                                  np.asarray(sref.keys)), name
            np.testing.assert_allclose(np.asarray(s.freqs[b]),
                                       np.asarray(sref.freqs),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(float(s.threshold[b]),
                                       float(sref.threshold),
                                       rtol=1e-5, equal_nan=True)
            np.testing.assert_allclose(np.asarray(est[b]),
                                       np.asarray(spec.estimate(
                                           refm, keys[b, :10])),
                                       rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("name", ["onepass", "twopass"])
    def test_two_phase_batched_equals_single(self, name):
        """Exact pass-II hooks obey the same vmap-consistency contract."""
        cfg = _registry_cfg(name)
        spec = E.engine_spec(cfg)
        assert spec.two_phase
        bops = E.batched_ops(spec)
        keys, vals = _batches(seed=6, n=50)
        sks, tss = E.derive_stream_seeds(cfg)

        st = bops.update(bops.init(sks, tss), keys, vals)
        st2 = bops.update2(bops.init2(st), st, keys, vals)
        s = bops.sample2(st2, k=4)

        for b in range(cfg.num_streams):
            ref = spec.update(spec.init(sks[b], tss[b]), keys[b], vals[b])
            ref2 = spec.update2(spec.init2(ref), ref, keys[b], vals[b])
            sref = spec.sample2(ref2, 4)
            assert np.array_equal(np.asarray(s.keys[b]),
                                  np.asarray(sref.keys)), name
            np.testing.assert_allclose(np.asarray(s.freqs[b]),
                                       np.asarray(sref.freqs), rtol=1e-5)

    @pytest.mark.parametrize("name", core_sampler.available())
    def test_engine_class_roundtrip(self, name):
        """SketchEngine(cfg, sampler=name) works end to end for every
        registered sampler (update/merge_with/sample shapes)."""
        cfg = _registry_cfg(name)
        keys, vals = _batches(seed=12, n=40)
        a = E.SketchEngine(cfg)
        b_ = E.SketchEngine(cfg, sampler=name)
        a.update(keys, vals)
        b_.update(keys, vals * 2.0)
        a.merge_with(b_)
        s = a.sample(4)
        assert s.keys.shape == (B, 4)
        assert a.estimate(keys[:, :8]).shape == (B, 8)

    def test_spec_merge_in_distributed_trees(self):
        """tree_merge accepts a SamplerSpec directly (spec-aware merge)."""
        cfg = _registry_cfg("onepass")
        spec = E.engine_spec(cfg)
        rng = np.random.default_rng(13)
        sts = []
        for i in range(3):
            st = spec.init(jnp.uint32(3), jnp.uint32(77))
            sts.append(spec.update(
                st, jnp.asarray(rng.integers(0, 500, 40), jnp.int32),
                jnp.asarray(rng.normal(size=40).astype(np.float32))))
        got = shd.tree_merge(sts, spec)
        want = spec.merge(spec.merge(sts[0], sts[1]), sts[2])
        np.testing.assert_allclose(np.asarray(got.sketch.table),
                                   np.asarray(want.sketch.table),
                                   rtol=1e-5, atol=1e-5)


class TestSketchEngineClass:
    def test_update_sample_merge_roundtrip(self):
        cfg = _cfg(shared_seeds=True)
        keys, vals = _batches(seed=8)
        a, b = E.SketchEngine(cfg), E.SketchEngine(cfg)
        a.update(keys, vals)
        b.update(keys, vals * 2.0)
        a.merge_with(b)
        s = a.sample(8)
        assert s.keys.shape == (B, 8)
        collapsed = a.collapse()
        assert collapsed.sketch.table.shape == (ROWS, WIDTH)

    def test_collapse_requires_shared_seeds(self):
        eng = E.SketchEngine(_cfg(shared_seeds=False))
        with pytest.raises(ValueError):
            eng.collapse()

    def test_merge_with_rejects_mismatched_cfg(self):
        """Engines with different seeds/shapes hash differently stream-by-
        stream: merging them must fail loudly, naming the bad fields."""
        a = E.SketchEngine(_cfg())
        with pytest.raises(ValueError, match="seed"):
            a.merge_with(E.SketchEngine(_cfg(seed=8)))
        with pytest.raises(ValueError, match="width"):
            a.merge_with(E.SketchEngine(_cfg(width=2 * WIDTH)))
        with pytest.raises(ValueError, match="shared_seeds"):
            a.merge_with(E.SketchEngine(_cfg(shared_seeds=True)))
        with pytest.raises(ValueError, match="sampler"):
            a.merge_with(E.SketchEngine(_cfg(), sampler="twopass"))
        with pytest.raises(TypeError):
            a.merge_with("not an engine")
        # matching cfg still merges
        a.merge_with(E.SketchEngine(_cfg()))

    def test_update_dense_requires_onepass(self):
        eng = E.SketchEngine(_registry_cfg("perfect"))
        with pytest.raises(ValueError, match="onepass"):
            eng.update_dense(jnp.ones((B, 32), jnp.float32))

    def test_freeze_requires_two_phase(self):
        eng = E.SketchEngine(_registry_cfg("perfect"))
        with pytest.raises(ValueError, match="second pass"):
            eng.freeze()

    def test_pass2_exact_frequencies(self):
        cfg = _cfg()
        keys, vals = _batches(seed=9)
        vals = jnp.abs(vals)
        eng = E.SketchEngine(cfg)
        eng.update(keys, vals)
        eng.freeze()
        eng.update_pass2(keys, vals)
        s = eng.sample_exact(4)
        # exact per-stream frequencies: compare against numpy aggregation
        for b in range(B):
            agg = {}
            for k, v in zip(np.asarray(keys[b]), np.asarray(vals[b])):
                agg[int(k)] = agg.get(int(k), 0.0) + float(v)
            for k, f in zip(np.asarray(s.keys[b]), np.asarray(s.freqs[b])):
                assert f == pytest.approx(agg[int(k)], rel=1e-5)
