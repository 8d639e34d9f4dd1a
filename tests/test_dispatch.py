"""Kernel-dispatch subsystem tests: policy resolution (env var, context,
config, explicit arg), pallas(interpret) vs reference parity across
1D/2D/3D blocks, odd (padded) shapes and both block tables, and full
compressor roundtrips under a forced-pallas policy (bit-exact with the
reference pipeline on CPU)."""
import dataclasses
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest
import pytest

from repro.core import compressor as C, dualquant as dq, gradient as G, \
    huffman as hf, kvcache as KV, weights as W
from repro.io import checkpoint as CK
from repro.kernels import dispatch
from repro.kernels.deflate import ops as deflate_ops
from repro.kernels.encode import ops as encode_ops
from repro.kernels.histogram import ops as hist_ops
from repro.kernels.inflate import ops as inflate_ops
from repro.kernels.lorenzo import ops as lorenzo_ops


# ---------------------------------------------------------------------------
# Policy resolution
# ---------------------------------------------------------------------------

class TestPolicyResolution:
    def test_registry_covers_pipeline(self):
        reg = dispatch.registered()
        for stage in dispatch.PIPELINE_STAGES:
            assert stage in reg, stage
        # gap-array two-phase decode gave inflate a real pallas impl
        assert reg["inflate"] == ("jax", "pallas")

    def test_auto_is_reference_on_cpu(self):
        assert jax.default_backend() == "cpu"
        assert dispatch.resolve("lorenzo.dualquant") == \
            dispatch.Resolved("jax", False)

    def test_forced_pallas_interprets_on_cpu(self):
        r = dispatch.resolve("histogram", impl="pallas")
        assert r == dispatch.Resolved("pallas", True)

    def test_pallas_interpret_choice(self):
        r = dispatch.resolve("deflate", impl="pallas-interpret")
        assert r == dispatch.Resolved("pallas", True)

    def test_explicit_pallas_on_jax_only_raises(self):
        # the jax-only protocol outlived inflate's graduation to a real
        # pallas impl; exercise it on a synthetic registration
        dispatch.register("testonly.seq", impls=("jax",),
                          jax_only_reason="synthetic: protocol test")
        try:
            # an explicit per-call request must not silently measure the
            # reference path; the error carries the declared reason
            with pytest.raises(NotImplementedError, match="synthetic"):
                dispatch.resolve("testonly.seq", impl="pallas")
        finally:
            dispatch._REGISTRY.pop("testonly.seq", None)
            dispatch._JAX_ONLY_REASON.pop("testonly.seq", None)

    def test_ambient_pallas_on_jax_only_falls_back(self):
        # forwarded policy/config impls keep the documented fallback so a
        # forced pipeline never crashes on a jax-only stage
        dispatch.register("testonly.seq", impls=("jax",),
                          jax_only_reason="synthetic: protocol test")
        try:
            with dispatch.kernel_policy("pallas"):
                assert dispatch.resolve("testonly.seq") == \
                    dispatch.Resolved("jax", False)
            assert dispatch.resolve("testonly.seq", "pallas",
                                    explicit=False) == \
                dispatch.Resolved("jax", False)
        finally:
            dispatch._REGISTRY.pop("testonly.seq", None)
            dispatch._JAX_ONLY_REASON.pop("testonly.seq", None)

    def test_jax_only_reason_recorded(self):
        dispatch.register("testonly.seq", impls=("jax",),
                          jax_only_reason="synthetic: protocol test")
        try:
            assert "synthetic" in dispatch.jax_only_reason("testonly.seq")
        finally:
            dispatch._REGISTRY.pop("testonly.seq", None)
            dispatch._JAX_ONLY_REASON.pop("testonly.seq", None)
        assert dispatch.jax_only_reason("histogram") is None
        assert dispatch.jax_only_reason("inflate") is None   # graduated

    def test_explicit_pallas_inflate_without_gaps_raises(self):
        # the pallas inflate IS the gap decoder: explicitly requesting it
        # on a gap-less (format v1) stream must raise, not silently
        # measure the sequential reference
        words = jnp.zeros((1, 64), jnp.uint32)
        table = hf.decode_table(
            hf.codeword_lengths(jnp.asarray([5, 5], jnp.int32)), 8)
        with pytest.raises(NotImplementedError, match="gap"):
            inflate_ops.inflate(words, jnp.zeros((1,), jnp.int32),
                                jnp.zeros((1,), jnp.int32), table, 8,
                                impl="pallas-interpret")

    def test_env_var_policy(self, monkeypatch):
        monkeypatch.setenv(dispatch.ENV_VAR, "pallas-interpret")
        assert dispatch.resolve("encode") == dispatch.Resolved("pallas", True)

    def test_context_overrides_env(self, monkeypatch):
        monkeypatch.setenv(dispatch.ENV_VAR, "pallas-interpret")
        with dispatch.kernel_policy("jax"):
            assert dispatch.resolve("encode") == \
                dispatch.Resolved("jax", False)
        assert dispatch.resolve("encode") == dispatch.Resolved("pallas", True)

    def test_explicit_arg_wins_over_context(self):
        with dispatch.kernel_policy("pallas-interpret"):
            assert dispatch.resolve("histogram", impl="jax") == \
                dispatch.Resolved("jax", False)

    def test_per_kernel_override_and_prefix(self):
        with dispatch.kernel_policy(
                "jax", overrides={"histogram": "pallas-interpret",
                                  "lorenzo": "pallas-interpret"}):
            assert dispatch.resolve("histogram").impl == "pallas"
            assert dispatch.resolve("lorenzo.dualquant").impl == "pallas"
            assert dispatch.resolve("lorenzo.reverse").impl == "pallas"
            assert dispatch.resolve("deflate").impl == "jax"

    def test_pipeline_policy_from_config_default(self):
        pp = dispatch.pipeline_policy("pallas-interpret")
        for stage in ("dualquant", "reverse", "histogram", "encode",
                      "deflate", "inflate"):
            assert getattr(pp, stage) == dispatch.Resolved("pallas", True)

    def test_ambient_beats_config_default(self):
        with dispatch.kernel_policy("jax"):
            pp = dispatch.pipeline_policy("pallas-interpret")
        assert pp.dualquant == dispatch.Resolved("jax", False)

    def test_invalid_impl_rejected(self):
        with pytest.raises(ValueError):
            dispatch.resolve("histogram", impl="cuda")
        with pytest.raises(KeyError):
            dispatch.resolve("not-a-kernel")
        with pytest.raises(ValueError):
            dispatch.KernelPolicy.make("jax", {"histogram": "wat"})


# ---------------------------------------------------------------------------
# Parity: pallas(interpret) == reference, odd shapes, both block tables
# ---------------------------------------------------------------------------

ODD_CASES = [
    # (shape, use_tpu_blocks) — shapes chosen NOT to divide the blocks so
    # the edge-replicate padding path is exercised
    ((1000,), False),
    ((5000,), True),
    ((37, 53), False),
    ((65, 130), True),
    ((11, 13, 17), False),
    ((9, 17, 130), True),
]


def _delta(out, nbins):
    """The blocked deltas, rebuilt from the fused op's codes and outlier
    store as the decoder does."""
    codes, idx, val = out[:3]
    d = dq.scatter_outliers(dq.codes_to_delta(codes.reshape(-1), nbins),
                            idx, val)
    return d.reshape(codes.shape)


def _field(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.cumsum(rng.standard_normal(shape), axis=-1)
                       .astype(np.float32))


class TestParity:
    @pytest.mark.parametrize("shape,tpu", ODD_CASES)
    def test_dualquant_and_reverse(self, shape, tpu):
        table = dq.TPU_BLOCKS if tpu else dq.DEFAULT_BLOCKS
        block = table[len(shape)]
        xb = dq.block_split(dq.pad_to_blocks(_field(shape), block), block)
        outk = lorenzo_ops.dualquant_blocks(xb, 1e-3, 1024, xb.size,
                                            impl="pallas-interpret")
        outr = lorenzo_ops.dualquant_blocks(xb, 1e-3, 1024, xb.size,
                                            impl="jax")
        for k, r in zip(outk[:4], outr[:4]):
            np.testing.assert_array_equal(np.asarray(k), np.asarray(r))
        dk, dr = _delta(outk, 1024), _delta(outr, 1024)
        rk = lorenzo_ops.reverse_blocks(dk, 1e-3, impl="pallas-interpret")
        rr = lorenzo_ops.reverse_blocks(dr, 1e-3, impl="jax")
        np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))

    @pytest.mark.parametrize("n", [100, 4096, 10001])
    def test_histogram(self, n):
        rng = np.random.default_rng(n)
        codes = jnp.asarray(rng.integers(0, 512, n).astype(np.int32))
        hk = hist_ops.histogram(codes, 512, impl="pallas-interpret")
        hr = hist_ops.histogram(codes, 512, impl="jax")
        np.testing.assert_array_equal(np.asarray(hk), np.asarray(hr))

    @pytest.mark.parametrize("n,k", [(777, 64), (3000, 1024)])
    def test_encode_and_deflate(self, n, k):
        rng = np.random.default_rng(n + k)
        codes = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
        cb = hf.canonical_codebook(hf.codeword_lengths(
            hf.histogram(codes, k)))
        ck, bk = encode_ops.encode(codes, cb, impl="pallas-interpret")
        cr, br = encode_ops.encode(codes, cb, impl="jax")
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
        np.testing.assert_array_equal(np.asarray(bk), np.asarray(br))
        wk, ik, gbk, gsk = deflate_ops.deflate(ck, bk, 512,
                                               impl="pallas-interpret")
        wr, ir, gbr, gsr = deflate_ops.deflate(cr, br, 512, impl="jax")
        np.testing.assert_array_equal(np.asarray(wk), np.asarray(wr))
        np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
        np.testing.assert_array_equal(np.asarray(gbk), np.asarray(gbr))
        np.testing.assert_array_equal(np.asarray(gsk), np.asarray(gsr))

    def test_fused_matches_unfused_reference(self):
        """The fused kernels-op output == the two-dispatch reference form
        the compressor used before the dispatch refactor."""
        x = _field((37, 53), seed=9)
        block = dq.DEFAULT_BLOCKS[2]
        xb = dq.block_split(dq.pad_to_blocks(x, block), block)
        out = lorenzo_ops.dualquant_blocks(xb, 1e-3, 1024, xb.size,
                                           impl="jax")
        cf, df = out[0], _delta(out, 1024)
        du = dq.blocked_delta(x, 1e-3, block)
        cu, _ = dq.postquant_codes(du, 1024)
        np.testing.assert_array_equal(np.asarray(df), np.asarray(du))
        np.testing.assert_array_equal(np.asarray(cf), np.asarray(cu))


# ---------------------------------------------------------------------------
# Full-pipeline roundtrips under forced policy
# ---------------------------------------------------------------------------

ROUNDTRIP_SHAPES = [(2000,), (49, 61), (9, 13, 21)]


class TestForcedPallasRoundtrip:
    @pytest.mark.parametrize("shape", ROUNDTRIP_SHAPES)
    def test_bitexact_vs_reference(self, shape):
        f = _field(shape, seed=len(shape))
        base = C.CompressorConfig(eb=1e-3, eb_mode="valrel", chunk_size=512,
                                  kernel_impl="jax")
        forced = dataclasses.replace(base, kernel_impl="pallas-interpret")
        blob_r, eb_r = C.compress(f, base)
        blob_p, eb_p = C.compress(f, forced)
        assert eb_r == eb_p
        # the Pallas kernel also reports the outlier tiles it walked (a
        # counter pack_blob records, not part of the result)
        assert blob_r.outlier_tiles is None
        assert blob_p.outlier_tiles is not None
        for a, b in zip(blob_r, blob_p._replace(outlier_tiles=None)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        rec_r = C.decompress(blob_r, base, eb_r, shape)
        rec_p = C.decompress(blob_p, forced, eb_p, shape)
        np.testing.assert_array_equal(np.asarray(rec_r), np.asarray(rec_p))

    def test_context_policy_roundtrip_bound_held(self):
        from repro.core import metrics as M
        f = _field((63, 70), seed=7)
        cfg = C.CompressorConfig(eb=1e-3, eb_mode="valrel", chunk_size=512)
        with dispatch.kernel_policy("pallas-interpret"):
            recon, blob, eb, ratio = C.roundtrip(f, cfg)
        assert M.verify_error_bound(f, recon, eb)
        recon_ref, *_ = C.roundtrip(f, dataclasses.replace(
            cfg, kernel_impl="jax"))
        np.testing.assert_array_equal(np.asarray(recon),
                                      np.asarray(recon_ref))


def test_a_fresh_and_an_unpacked_blob_share_one_decompress_program():
    f = _field((40, 40), seed=3)
    cfg = C.CompressorConfig(eb=1e-3, eb_mode="valrel", chunk_size=512)
    with dispatch.kernel_policy(
            overrides={"lorenzo.dualquant": "pallas-interpret"}):
        blob, eb = C.compress(f, cfg)
    assert blob.outlier_tiles is not None       # the kernel's counter
    fresh = C.decompress(blob, cfg, eb, f.shape)
    n = C._decompress_impl._cache_size()
    back = C.decompress(C.unpack_blob(C.pack_blob(blob)), cfg, eb, f.shape)
    assert C._decompress_impl._cache_size() == n
    np.testing.assert_array_equal(np.asarray(fresh), np.asarray(back))


# ---------------------------------------------------------------------------
# Vectorized pack/unpack
# ---------------------------------------------------------------------------

class TestPackUnpack:
    def test_many_chunk_roundtrip(self):
        f = _field((40000,), seed=3)
        cfg = C.CompressorConfig(eb=1e-3, eb_mode="valrel", chunk_size=512)
        blob, eb = C.compress(f, cfg)
        assert blob.words.shape[0] > 10        # many chunks: vectorized path
        d = C.pack_blob(blob)
        blob2 = C.unpack_blob(d)
        # unused outlier slots use different (equally out-of-range, both
        # scatter-dropped) fill values on the two sides; compare the
        # meaningful prefix + every dense field exactly
        n_out = int(blob.n_outliers)
        for fld in ("words", "bits_used", "n_valid", "lengths",
                    "n_outliers", "max_len", "gap_bits", "gap_syms"):
            np.testing.assert_array_equal(
                np.asarray(getattr(blob, fld)),
                np.asarray(getattr(blob2, fld)), err_msg=fld)
        np.testing.assert_array_equal(np.asarray(blob.out_idx[:n_out]),
                                      np.asarray(blob2.out_idx[:n_out]))
        np.testing.assert_array_equal(np.asarray(blob.out_val[:n_out]),
                                      np.asarray(blob2.out_val[:n_out]))
        rec = C.decompress(blob2, cfg, eb, tuple(f.shape))
        rec0 = C.decompress(blob, cfg, eb, tuple(f.shape))
        np.testing.assert_array_equal(np.asarray(rec), np.asarray(rec0))

    def test_packed_words_match_used_words(self):
        f = _field((3000,), seed=4)
        cfg = C.CompressorConfig(eb=1e-3, eb_mode="valrel", chunk_size=256)
        blob, _ = C.compress(f, cfg)
        d = C.pack_blob(blob)
        bits = np.asarray(blob.bits_used, np.int64)
        words = np.asarray(blob.words)
        manual = np.concatenate([words[c, : (bits[c] + 31) // 32]
                                 for c in range(words.shape[0])])
        np.testing.assert_array_equal(d["words_packed"], manual)


# ---------------------------------------------------------------------------
# resolve_eb: one fused reduction, one transfer
# ---------------------------------------------------------------------------

class TestResolveEb:
    def test_values_unchanged(self):
        f = _field((500,), seed=5)
        cfg = C.CompressorConfig(eb=1e-3, eb_mode="valrel")
        eb = C.resolve_eb(cfg, f)
        rng = float(np.max(np.asarray(f)) - np.min(np.asarray(f)))
        assert eb == pytest.approx(1e-3 * rng, rel=1e-6)
        assert C.resolve_eb(C.CompressorConfig(eb=0.5, eb_mode="abs"), f) \
            == 0.5

    def test_domain_guard_still_raises(self):
        f = jnp.asarray(np.array([0.0, 3.0e7], np.float32))
        with pytest.raises(ValueError):
            C.resolve_eb(C.CompressorConfig(eb=1e-3, eb_mode="abs"), f)


# ---------------------------------------------------------------------------
# Consumers thread the policy through CompressorConfig
# ---------------------------------------------------------------------------

class TestConsumers:
    def test_gradient_blob_roundtrip_forced_policy(self):
        g = _field((40, 130), seed=11) * 1e-3
        cfg = C.CompressorConfig(eb=1e-5, eb_mode="valrel", chunk_size=512,
                                 outlier_frac=1.0,
                                 kernel_impl="pallas-interpret")
        packed, eb = G.cusz_compress_gradient(g, cfg)
        out = G.cusz_decompress_gradient(packed, eb, g.shape, cfg)
        from repro.core import metrics as M
        assert M.verify_error_bound(g, out, eb)

    def test_kv_offload_roundtrip(self):
        x = _field((4, 256, 8), seed=12).astype(jnp.float32)
        cfg = C.CompressorConfig(eb=1e-4, eb_mode="valrel", chunk_size=512,
                                 outlier_frac=1.0, kernel_impl="jax")
        packed, eb = KV.kv_offload_pack(x, cfg)
        out = KV.kv_offload_restore(packed, eb, x.shape, cfg,
                                    dtype=jnp.float32)
        assert float(jnp.max(jnp.abs(out - x))) <= eb * (1 + 1e-4) + 1e-9

    def test_checkpoint_kernel_impl_roundtrip(self):
        rng = np.random.default_rng(13)
        tree = {"w": np.cumsum(rng.standard_normal((64, 128)), axis=-1)
                .astype(np.float32),
                "b": rng.standard_normal((8,)).astype(np.float32)}
        with tempfile.TemporaryDirectory() as d:
            CK.save_checkpoint(d, 1, tree, mode="cusz", eb_valrel=1e-4,
                               kernel_impl="pallas-interpret")
            out, step = CK.load_checkpoint(
                d, jax.tree.map(jnp.asarray, tree),
                kernel_impl="pallas-interpret")
        assert step == 1
        np.testing.assert_allclose(np.asarray(out["b"]), tree["b"],
                                   rtol=0, atol=0)
        mx = float(np.max(tree["w"]) - np.min(tree["w"]))
        np.testing.assert_allclose(np.asarray(out["w"]), tree["w"],
                                   atol=1.1e-4 * mx)

    def test_weights_codec_config_carries_policy(self):
        cfg = W.checkpoint_codec_config(1e-5, kernel_impl="jax")
        assert cfg.kernel_impl == "jax"
        assert cfg.eb_mode == "valrel" and cfg.use_tpu_blocks
