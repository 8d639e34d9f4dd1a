"""Staged-pipeline regression suite (core.stages + core.compressor).

Four pillars:

  * golden bit-identity — re-encoding the committed cusz v2 fixture
    through the staged pipeline reproduces the stored container header
    and every payload array bit-for-bit (the refactor is format-neutral),
    and the stored fixture still decodes within its bound;
  * registry contract — stage ids resolve to singletons, unknown ids
    fail loudly, predictor/encoder payload key sets stay disjoint;
  * kernel parity — interp and bitshuffle jax references and Pallas
    (interpret) kernels agree bit-exactly, and both stage pipelines are
    impl-invariant end to end;
  * 8-fake-device elasticity — checkpoint save/restore over the two new
    codec ids ("cusz-i", "fz") across a mesh reshape, bitwise-stable
    between shardings (subprocess so the device-count flag stays local).
"""
from __future__ import annotations

import itertools
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro import codecs
from repro.codecs.container import Container, Header
from repro.core import compressor as CZ
from repro.core import stages
from repro.kernels.bitshuffle import ops as bitshuffle_ops
from repro.kernels.interp import ops as interp_ops

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")

# the exact config the committed fixture was produced with
GOLDEN_CFG = CZ.CompressorConfig(eb=1e-3, eb_mode="abs", chunk_size=256,
                                 sub_size=64, outlier_frac=1.0)


def _golden():
    z = np.load(os.path.join(DATA, "cusz_v2_golden.npz"))
    hdr = json.load(open(os.path.join(DATA, "cusz_v2_golden_header.json")))
    return z, hdr


def _smooth(shape, seed=0):
    rng = np.random.default_rng(seed)
    x = np.cumsum(rng.standard_normal(shape, dtype=np.float64),
                  axis=-1).astype(np.float32)
    return jnp.asarray(x)


# ---------------------------------------------------------------------------
# Golden-fixture bit-identity
# ---------------------------------------------------------------------------

class TestGoldenFixture:
    def test_reencode_is_bit_identical(self):
        """The staged lorenzo+huffman pipeline must reproduce the
        pre-refactor container byte-for-byte: same header JSON (checksum
        included), same packed payload arrays, same dtypes."""
        z, hdr = _golden()
        codec = codecs.get("cusz", cfg=GOLDEN_CFG)
        c = codec.pack(codec.encode(jnp.asarray(z["field"])))
        assert c.header.to_json() == hdr
        payload_keys = sorted(k for k in z.files if k != "field")
        assert sorted(c.payload) == payload_keys
        for k in payload_keys:
            got = np.asarray(c.payload[k])
            np.testing.assert_array_equal(got, z[k], err_msg=k)
            assert got.dtype == z[k].dtype, (k, got.dtype, z[k].dtype)

    def test_stored_fixture_decodes_within_bound(self):
        """Backward decode: the container as committed (not re-encoded)
        must decode via the registry within its recorded abs bound."""
        z, hdr = _golden()
        cont = Container(Header.from_json(hdr),
                         {k: z[k] for k in z.files if k != "field"})
        rec = np.asarray(codecs.decode(cont))
        eb = float(hdr["params"]["eb"])
        assert np.abs(rec - z["field"]).max() <= eb * 1.0001


# ---------------------------------------------------------------------------
# Registry contract
# ---------------------------------------------------------------------------

class TestStageRegistry:
    def test_registered_ids(self):
        assert {"lorenzo", "interp"} <= set(stages.predictor_names())
        assert {"huffman", "bitshuffle"} <= set(stages.encoder_names())

    def test_lookup_returns_singletons(self):
        for name in stages.predictor_names():
            p = stages.get_predictor(name)
            assert p is stages.get_predictor(name)   # jit-static identity
            assert p.name == name
        for name in stages.encoder_names():
            e = stages.get_encoder(name)
            assert e is stages.get_encoder(name)
            assert e.name == name

    def test_unknown_ids_fail_loudly(self):
        with pytest.raises(KeyError, match="unknown predictor"):
            stages.get_predictor("nope")
        with pytest.raises(KeyError, match="unknown encoder"):
            stages.get_encoder("nope")

    def test_payload_keys_disjoint_across_all_compositions(self):
        """The composed payload is a dict union, so every predictor's
        key set must be disjoint from every encoder's."""
        for pn, en in itertools.product(stages.predictor_names(),
                                        stages.encoder_names()):
            pk = set(stages.get_predictor(pn).payload_keys)
            ek = set(stages.get_encoder(en).payload_keys)
            assert not (pk & ek), (pn, en, pk & ek)


# ---------------------------------------------------------------------------
# Every predictor x encoder composition round-trips within bound
# ---------------------------------------------------------------------------

COMBOS = tuple(itertools.product(("lorenzo", "interp"),
                                 ("huffman", "bitshuffle")))


@pytest.mark.parametrize("predictor,encoder", COMBOS)
def test_composition_roundtrip_within_bound(predictor, encoder):
    cfg = CZ.CompressorConfig(eb=1e-3, eb_mode="abs", chunk_size=256,
                              sub_size=64, outlier_frac=1.0,
                              predictor=predictor, encoder=encoder)
    x = _smooth((24, 48), seed=3)
    pipe = CZ.StagedPipeline.from_cfg(cfg)
    payload, eb = pipe.compress(x, cfg)
    assert pipe.valid(payload)
    y = np.asarray(pipe.decompress(payload, cfg, eb, x.shape))
    assert np.abs(np.asarray(x) - y).max() <= eb * 1.0001
    # the storage boundary is an inverse: decode of unpack(pack) is
    # bit-identical to decode of the device payload
    restored = pipe.unpack(pipe.pack(payload), cfg, x.shape)
    y2 = np.asarray(pipe.decompress(restored, cfg, eb, x.shape))
    np.testing.assert_array_equal(y, y2)
    assert pipe.stored_nbytes(pipe.pack(payload)) > 0


@pytest.mark.parametrize("predictor,encoder",
                         (("interp", "huffman"), ("lorenzo", "bitshuffle")))
def test_composition_is_kernel_impl_invariant(predictor, encoder):
    """jax vs pallas-interpret produce bit-identical packed payloads."""
    x = _smooth((16, 48), seed=7)
    packs = []
    for impl in ("jax", "pallas-interpret"):
        cfg = CZ.CompressorConfig(eb=1e-3, eb_mode="abs", chunk_size=256,
                                  sub_size=64, outlier_frac=1.0,
                                  predictor=predictor, encoder=encoder,
                                  kernel_impl=impl)
        pipe = CZ.StagedPipeline.from_cfg(cfg)
        payload, _ = pipe.compress(x, cfg)
        packs.append(pipe.pack(payload))
    a, b = packs
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


# ---------------------------------------------------------------------------
# Kernel-level parity (jax reference vs Pallas interpret)
# ---------------------------------------------------------------------------

class TestKernelParity:
    def test_interp_rows_parity_and_exact_inverse(self):
        rng = np.random.default_rng(11)
        pe = jnp.asarray(rng.integers(-(2 ** 20), 2 ** 20, (5, 19)), jnp.int32)
        odd = jnp.asarray(rng.integers(-(2 ** 20), 2 ** 20, (5, 16)),
                          jnp.int32)
        r_jax = interp_ops.residual_rows(pe, odd, impl="jax")
        r_pl = interp_ops.residual_rows(pe, odd, impl="pallas",
                                        interpret=True)
        np.testing.assert_array_equal(np.asarray(r_jax), np.asarray(r_pl))
        for impl, interp in (("jax", None), ("pallas", True)):
            back = interp_ops.odd_rows(pe, r_jax, impl=impl,
                                       interpret=interp)
            np.testing.assert_array_equal(np.asarray(back), np.asarray(odd))

    # (300, 512) spans two grid steps of the kernel and pads the second
    @pytest.mark.parametrize("nc,chunk", [(3, 256), (300, 512)])
    def test_bitshuffle_planes_parity_and_exact_inverse(self, nc, chunk):
        nbins = 1024
        rng = np.random.default_rng(13)
        codes2 = jnp.asarray(rng.integers(0, nbins, (nc, chunk)), jnp.int32)
        p_jax = bitshuffle_ops.encode_planes(codes2, nbins, impl="jax")
        p_pl = bitshuffle_ops.encode_planes(codes2, nbins, impl="pallas",
                                            interpret=True)
        np.testing.assert_array_equal(np.asarray(p_jax), np.asarray(p_pl))
        for impl, interp in (("jax", None), ("pallas", True)):
            back = bitshuffle_ops.decode_planes(p_jax, nbins, impl=impl,
                                                interpret=interp)
            np.testing.assert_array_equal(np.asarray(back)[:, :chunk],
                                          np.asarray(codes2))


# ---------------------------------------------------------------------------
# 8-fake-device checkpoint elasticity over the new codec ids
# ---------------------------------------------------------------------------

STAGED_CKPT_SCRIPT = r"""
import json, os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.dist import sharding as SH
from repro.dist.context import use_mesh
from repro.io import checkpoint as CK
from repro.models import model as M

cfg = configs.reduced("qwen2.5-3b", n_periods=1)
params = M.init_params(jax.random.PRNGKey(0), cfg)
# smooth the leaves so the lossy policies genuinely code instead of
# falling back to lossless on random init
params = jax.tree_util.tree_map(
    lambda x: jnp.cumsum(x, axis=-1) / 8
    if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

# save from a (4, 2) mesh; restore onto a differently-shaped (2, 4) mesh
mesh_a = jax.make_mesh((4, 2), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
mesh_b = jax.make_mesh((2, 4), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
params = jax.device_put(params, SH.param_shardings(params, mesh_a,
                                                   fsdp=True))
shard_b = SH.param_shardings(params, mesh_b, fsdp=True)

def bits(x):
    x = np.asarray(x)
    return x.view(np.uint16) if x.dtype == jnp.bfloat16 else x

for name in ("cusz-i", "fz"):
    # 1e-3: tight enough to code, loose enough that the interpolation
    # predictor's residuals stay in-bin on the small smoothed leaves
    pol = CK.CheckpointPolicy(codec=name, eb_valrel=1e-3)
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        CK.save_checkpoint(d1, 0, params, policy=pol, nshards=1)
        CK.save_checkpoint(d2, 0, params, policy=pol, nshards=2)
        with use_mesh(mesh_b):
            a, _ = CK.load_checkpoint(d1, params, shardings=shard_b)
            b, _ = CK.load_checkpoint(d2, params, shardings=shard_b)
        stats = dict(CK.LAST_RESTORE_STATS)
        assert stats["saved_nshards"] == 2
        assert stats["wire_leaves"] > 0, stats
        assert stats["wire_bytes"] < stats["raw_bytes"], stats
        man = json.load(open(os.path.join(d2, "step_00000000",
                                          "manifest.json")))
        coded = [e["codec"] for e in man["tensors"].values()]
        assert name in coded, (name, sorted(set(coded)))
        for (pa, la), (pb, lb) in zip(
                jax.tree_util.tree_flatten_with_path(a)[0],
                jax.tree_util.tree_flatten_with_path(b)[0]):
            np.testing.assert_array_equal(bits(la), bits(lb),
                                          err_msg=str(pa))
        # restored leaves actually live on the new mesh's placement
        leaf = jax.tree_util.tree_leaves(b)[0]
        assert leaf.sharding.mesh.shape == mesh_b.shape
    print("policy", name, "elastic bitwise OK")
print("STAGED_CKPT_OK")
"""


def _run_subprocess(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(HERE))


def test_eight_device_checkpoint_roundtrip_over_staged_codecs():
    r = _run_subprocess(STAGED_CKPT_SCRIPT)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    assert "STAGED_CKPT_OK" in r.stdout
