"""Wire-codec subsystem contracts (``repro.distributed.codecs``).

Five layers of guarantees:
  * registry + resolution mirror the sampler/plane registries;
  * dtype guard: uint32 hash/transform seeds, int32 key slots and any other
    non-float leaf travel RAW under every codec -- the seed-agreement and
    exact-key-identity contracts survive any wire;
  * roundtrip errors sit inside each codec's derived per-slice bound
    (``roundtrip_atol``), per-leading-axis scales isolate streams, and the
    in-jit ``fake_quant`` grid matches the host byte codec exactly;
  * checkpoints round-trip for EVERY registered sampler x codec (lossless
    bit-exact, lossy within the codec bound; CRC over the ENCODED bytes
    still rejects torn writes) and the merge trees keep their seed guards;
  * the derived quantization allowances in ``validate.bounds`` admit the
    production codecs and deterministically reject the 2-bit control.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import engine as E
from repro.distributed import codecs as C
from repro.distributed import sharding as shd
from repro.train import checkpoint
from repro.validate import bounds

jax.config.update("jax_platform_name", "cpu")

LOSSY = ("fp16", "q8", "size_adaptive", "q2")
SAMPLERS = ("onepass", "twopass", "perfect", "tv")


class TestRegistry:
    def test_registered_names(self):
        names = C.available_codecs()
        for n in ("none",) + LOSSY:
            assert n in names

    def test_resolution(self):
        assert C.get_codec(None).name == "none"
        assert C.get_codec("q8") is C.get_codec("q8")
        inst = C.FP16Codec()
        assert C.get_codec(inst) is inst
        with pytest.raises(ValueError, match="unknown codec"):
            C.get_codec("zstd")

    def test_none_has_zero_step(self):
        cdc = C.get_codec("none")
        assert cdc.rel_step == 0.0 and cdc.clamp is None


class TestDtypeGuard:
    @pytest.mark.parametrize("codec", C.available_codecs())
    @pytest.mark.parametrize("dtype", [np.uint32, np.int32, np.bool_])
    def test_non_float_leaves_travel_raw(self, codec, dtype):
        cdc = C.get_codec(codec)
        arr = (np.arange(32) % 3 == 0).reshape(4, 8) if dtype == np.bool_ \
            else np.arange(32, dtype=dtype).reshape(4, 8)
        enc = cdc.encode_leaf(arr)
        assert enc.kind == "raw"
        np.testing.assert_array_equal(C.decode_leaf(enc), arr)
        assert cdc.payload_nbytes(arr) == arr.nbytes


class TestRoundtrip:
    @pytest.mark.parametrize("codec", LOSSY)
    def test_error_within_derived_bound(self, codec):
        cdc = C.get_codec(codec)
        rng = np.random.default_rng(3)
        # heavy-tailed, spans both size_adaptive branches across the slices
        arr = (rng.standard_t(3, size=(4, 5000)) * 100).astype(np.float32)
        dec = np.asarray(C.decode_leaf(cdc.encode_leaf(arr)))
        atol = cdc.roundtrip_atol(arr) + 1e-7
        diff = np.abs(dec.astype(np.float64) - arr.astype(np.float64))
        assert np.all(diff.reshape(4, -1) <= atol)

    def test_none_roundtrip_is_identity_object(self):
        tree = {"a": jnp.arange(4.0), "s": jnp.zeros(2, jnp.uint32)}
        assert C.get_codec("none").roundtrip(tree) is tree

    def test_per_slice_scales_isolate_streams(self):
        # one stream's huge magnitudes must not degrade another's precision
        arr = np.stack([np.linspace(-1e6, 1e6, 1 << 13),
                        np.linspace(-1.0, 1.0, 1 << 13)]).astype(np.float32)
        dec = np.asarray(C.decode_leaf(C.get_codec("q8").encode_leaf(arr)))
        assert np.max(np.abs(dec[1] - arr[1])) <= 0.5 / 127 + 1e-7

    def test_size_adaptive_switches_at_threshold(self):
        cdc = C.get_codec("size_adaptive")
        small = np.ones(C.SIZE_ADAPTIVE_THRESHOLD - 1, np.float32)
        big = np.ones((2, C.SIZE_ADAPTIVE_THRESHOLD // 2), np.float32)
        assert cdc.encode_leaf(small).kind == "fp16"
        assert cdc.encode_leaf(big).kind == "q8"

    def test_fp16_clamps_instead_of_overflowing(self):
        arr = np.asarray([1e9, -1e9, 3.0], np.float32)
        dec = np.asarray(C.decode_leaf(C.get_codec("fp16").encode_leaf(arr)))
        assert np.all(np.isfinite(dec))
        assert dec[0] == C.FP16_MAX and dec[1] == -C.FP16_MAX

    @pytest.mark.parametrize("codec", ("fp16", "q8", "size_adaptive"))
    def test_fake_quant_matches_host_grid(self, codec):
        cdc = C.get_codec(codec)
        rng = np.random.default_rng(5)
        arr = (rng.normal(size=(3, 1 << 12)) * 50).astype(np.float32)
        host = np.asarray(C.decode_leaf(cdc.encode_leaf(arr)))
        dev = np.asarray(jax.jit(cdc.fake_quant)(jnp.asarray(arr)))
        np.testing.assert_array_equal(dev, host)

    @pytest.mark.parametrize("codec", C.available_codecs())
    def test_payload_nbytes_matches_encoding(self, codec):
        cdc = C.get_codec(codec)
        for arr in (np.zeros((4, 1 << 12), np.float32),
                    np.zeros(64, np.float32),
                    np.arange(10, dtype=np.int32)):
            assert cdc.payload_nbytes(arr) == cdc.encode_leaf(arr).nbytes


def _engine_cfg(name):
    return E.EngineConfig(num_streams=3, rows=3, width=128, candidates=16,
                          capacity=16, p=1.0, seed=11, sampler=name,
                          domain=600, num_samplers=3)


def _ingested_engine(name, seed=11):
    cfg = _engine_cfg(name)._replace(seed=seed)
    eng = E.SketchEngine(cfg)
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, 500, (3, 40)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(3, 40)).astype(np.float32))
    eng.ingest(keys, vals)
    eng.flush()
    return eng


class TestCheckpointCodecs:
    """Every registered sampler's batched state survives a checkpoint
    round-trip under every registered codec: bit-exact for lossless wires,
    within the codec's derived per-slice bound for lossy ones (seed/key
    leaves bit-exact regardless -- the dtype guard)."""

    @pytest.mark.parametrize("name", SAMPLERS)
    @pytest.mark.parametrize("codec", C.available_codecs())
    def test_state_roundtrip(self, tmp_path, name, codec):
        eng = _ingested_engine(name)
        checkpoint.save(str(tmp_path), 1, eng.state, codec=codec)
        fresh = E.SketchEngine(eng.cfg)
        restored, step = checkpoint.restore_latest(str(tmp_path), fresh.state)
        assert step == 1
        assert (jax.tree_util.tree_structure(restored)
                == jax.tree_util.tree_structure(eng.state))
        for a, b in zip(jax.tree_util.tree_leaves(eng.state),
                        jax.tree_util.tree_leaves(restored)):
            assert np.asarray(a).dtype == np.asarray(b).dtype
        if codec == "none":
            for a, b in zip(jax.tree_util.tree_leaves(eng.state),
                            jax.tree_util.tree_leaves(restored)):
                assert np.array_equal(np.asarray(a), np.asarray(b))
        else:
            C.assert_trees_within_codec(restored, eng.state, codec,
                                        shards=1, label=f"{name}@{codec}")

    def test_codec_none_writes_precodec_format(self, tmp_path):
        """codec=none manifests carry no codec entries, so old readers (and
        the pre-codec restore path) see byte-identical checkpoints."""
        import json

        tree = {"w": jnp.arange(12.0).reshape(3, 4),
                "s": jnp.zeros(2, jnp.uint32)}
        path = checkpoint.save(str(tmp_path), 1, tree, codec="none")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        assert all("codec" not in m for m in manifest["leaves"].values())

    def test_crc_rejects_corrupt_encoded_shard(self, tmp_path):
        tree = {"w": jnp.arange(100.0) * 7.5}
        path = checkpoint.save(str(tmp_path), 3, tree, codec="q8")
        fn = os.path.join(path, "w.npy")
        arr = np.load(fn)  # the ENCODED uint8 wire image
        arr[0] ^= 0xFF
        np.save(fn, arr)
        with pytest.raises(IOError):
            checkpoint.restore(str(tmp_path), 3, tree)

    def test_payload_nbytes_from_manifest(self, tmp_path):
        tree = {"w": jnp.zeros((4, 1 << 12), jnp.float32),
                "s": jnp.zeros(3, jnp.uint32)}
        n = 4 * (1 << 12)
        p_none = checkpoint.save(str(tmp_path / "a"), 1, tree, codec="none")
        p_sa = checkpoint.save(str(tmp_path / "b"), 1, tree,
                               codec="size_adaptive")
        assert checkpoint.payload_nbytes(p_none) == 4 * n + 12
        # q8 branch: int8 payload + one fp32 scale per leading-axis slice
        assert checkpoint.payload_nbytes(p_sa) == (n + 4 * 4) + 12
        assert (checkpoint.payload_nbytes(p_none)
                / checkpoint.payload_nbytes(p_sa)) > 3.5


class TestMergeCodecs:
    def _shard_engines(self, codec_seed=11):
        engs = [_ingested_engine("onepass", seed=codec_seed)
                for _ in range(2)]
        return engs, [e.state for e in engs]

    def test_codec_none_merge_is_bitwise_identical(self):
        engs, states = self._shard_engines()
        merged_default = shd.merge_states(states, engs[0].ops.merge)
        merged_none = shd.merge_states(states, engs[0].ops.merge,
                                       codec="none")
        for a, b in zip(jax.tree_util.tree_leaves(merged_default),
                        jax.tree_util.tree_leaves(merged_none)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_lossy_merge_within_codec_bound(self):
        engs, states = self._shard_engines()
        cdc = C.get_codec("fp16")
        merged = shd.merge_states(states, engs[0].ops.merge, codec=cdc)
        ref = shd.merge_states([cdc.roundtrip(s) for s in states],
                               engs[0].ops.merge)
        for a, b in zip(jax.tree_util.tree_leaves(merged),
                        jax.tree_util.tree_leaves(ref)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_seed_guard_survives_codec(self):
        """Shards built from different seeds must still refuse to merge when
        a codec is on the wire -- the uint32 seed leaves travel raw."""
        eng_a = _ingested_engine("onepass", seed=11)
        eng_b = _ingested_engine("onepass", seed=12)
        with pytest.raises(ValueError, match="seeds"):
            shd.merge_states([eng_a.state, eng_b.state], eng_a.ops.merge,
                             codec="q8")

    def test_collective_butterfly_rejects_lossy(self):
        eng = _ingested_engine("onepass")
        with pytest.raises(ValueError, match="lossy codec"):
            shd.butterfly_allmerge(eng.state, "data", eng.ops.merge,
                                   codec="q8")


class TestQuantizationBounds:
    def _ensemble(self, trials=64, n=32, seed=7):
        rng = np.random.default_rng(seed)
        tstar = (rng.pareto(1.2, size=(trials, n)) + 0.1).astype(np.float64)
        thresholds = np.quantile(np.abs(tstar), 0.7, axis=1)
        return tstar, thresholds

    def test_flip_allowance_bounded_and_monotone(self):
        tstar, thr = self._ensemble()
        q8 = bounds.quantization_flip_allowance(tstar, thr, 0.5 / 127)
        q2 = bounds.quantization_flip_allowance(tstar, thr, 0.5)
        assert np.all((0.0 <= q8) & (q8 <= 1.0))
        assert np.all(q8 <= q2 + 1e-12)  # coarser grid, larger allowance

    def test_q2_saturates_the_gate_deterministically(self):
        """pert = 2 * m_t >= 2 * every gap, so each uniform tail exceeds
        1/2 and the mean flip allowance crosses the admissibility gate on
        ANY ensemble -- the negative control cannot sneak through."""
        for seed in range(5):
            tstar, thr = self._ensemble(seed=seed)
            flip = bounds.quantization_flip_allowance(tstar, thr, 0.5)
            assert float(flip.mean()) > 0.5
            assert not bounds.codec_admissible(float(flip.mean()), 0.0)

    def test_fine_codecs_admissible_on_separated_ensemble(self):
        tstar, thr = self._ensemble()
        for rel_step, clamp in ((2.0 ** -11, C.FP16_MAX), (0.5 / 127, None)):
            flip = bounds.quantization_flip_allowance(tstar, thr, rel_step,
                                                      clamp=clamp)
            assert bounds.codec_admissible(float(flip.mean()), 0.0)

    def test_clamp_contributes_saturation_bias(self):
        tstar, thr = self._ensemble()
        freqs = np.abs(np.random.default_rng(0).normal(size=tstar.shape[1]))
        free = bounds.quantization_ht_allowance(freqs, tstar, thr, 2.0 ** -11)
        # clamp below the magnitude range: saturation bias must appear
        clamped = bounds.quantization_ht_allowance(
            freqs, tstar, thr, 2.0 ** -11,
            clamp=float(np.median(np.abs(tstar))))
        assert free >= 0.0
        assert clamped > free

    def test_nrmse_allowance_scale(self):
        got = bounds.quantization_nrmse_allowance(0.5 / 127, k=16, shards=2)
        assert got == pytest.approx(4.0 * 2 * 0.5 / 127)


class TestTable3CodecFloor:
    def test_quant_allowance_composes_into_golden_check(self):
        """The Table-3 golden-value check runs on a composable plane whose
        collapse crosses a lossy codec: the acceptance floor composes the
        derived quantization NRMSE allowance with the fp32 floor, and the
        widened check still passes."""
        from benchmarks.table3_nrmse import ROWS
        from repro.validate import conformance as conf

        res = conf.check_table3_nrmse(trials=8, rows=[ROWS[0]],
                                      methods=("one",), path="pipeline",
                                      codec="q8")
        assert [r.status for r in res] == [conf.PASS]
        assert res[0].path == "pipeline@q8"
        base = conf.check_table3_nrmse(trials=8, rows=[ROWS[0]],
                                       methods=("one",))
        assert (res[0].details["fp32_floor"]
                > base[0].details["fp32_floor"])


class TestGradcompCodecs:
    def _run(self, codec):
        from repro.launch.mesh import make_mesh_auto
        from repro.optim import gradcomp

        mesh = make_mesh_auto((1,), ("data",))
        cc = gradcomp.CompressorConfig(k=32, rows=5, width=2048,
                                       candidates=64, p=1.0,
                                       mode="twopass", codec=codec)
        a = jnp.asarray(np.random.default_rng(0)
                        .normal(size=4096).astype(np.float32))
        # planted heavy hitters: selection is then stable across codecs
        a = a.at[:16].set(jnp.arange(16, dtype=jnp.float32) * 50 + 100)
        f = jax.jit(jax.shard_map(
            lambda x: gradcomp.compress_step(x, cc, ("data",)),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
        sparse, err, stats = f(a)
        return np.asarray(sparse), np.asarray(err), stats, cc

    def test_codec_none_bytes_are_raw_fp32(self):
        sparse, err, stats, cc = self._run("none")
        assert len(np.nonzero(sparse)[0]) == cc.k
        expect = 4.0 * (cc.rows * cc.width + cc.k) + 4.0 * cc.candidates
        assert float(stats["comm_bytes"]) == expect

    def test_size_adaptive_shrinks_the_wire(self):
        s_none, _, st_none, _ = self._run("none")
        s_sa, _, st_sa, cc = self._run("size_adaptive")
        ratio = float(st_none["comm_bytes"]) / float(st_sa["comm_bytes"])
        assert ratio > 3.5  # rows*width table lands in the q8 branch
        # the compressed update still points the same way
        num = float(np.dot(s_none, s_sa))
        den = (np.linalg.norm(s_none) * np.linalg.norm(s_sa)) + 1e-30
        assert num / den > 0.9
