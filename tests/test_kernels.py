"""Per-kernel allclose tests: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes/dtypes/configs."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import dualquant as dq
from repro.kernels.lorenzo import ops as lorenzo_ops
from repro.kernels.histogram import ops as hist_ops
from repro.kernels.deflate import ops as deflate_ops
from repro.kernels.encode import ops as encode_ops
from repro.core import huffman as hf


BLOCK_CASES = [
    # (data shape, block)
    ((1024,), (256,)),
    ((8192,), (4096,)),
    ((64, 64), (16, 16)),
    ((128, 256), (64, 128)),
    ((16, 16, 16), (8, 8, 8)),
    ((8, 32, 128), (8, 16, 128)),
]


def _blocked(shape, block, seed, scale=1.0):
    rng = np.random.default_rng(seed)
    x = (np.cumsum(rng.standard_normal(shape), axis=-1) * scale).astype(np.float32)
    return dq.block_split(dq.pad_to_blocks(jnp.asarray(x), block), block)


class TestLorenzoKernel:
    @pytest.mark.parametrize("shape,block", BLOCK_CASES)
    @pytest.mark.parametrize("eb", [1e-2, 1e-3])
    def test_dualquant_matches_ref(self, shape, block, eb):
        xb = _blocked(shape, block, seed=hash((shape, block)) % 2**31)
        cap = max(16, xb.size // 10)
        outk = lorenzo_ops.dualquant_blocks(xb, eb, 1024, cap, impl="pallas")
        outr = lorenzo_ops.dualquant_blocks(xb, eb, 1024, cap, impl="jax")
        for k, r in zip(outk[:4], outr[:4]):
            np.testing.assert_array_equal(np.asarray(k), np.asarray(r))
        assert outr[4] is None
        np.testing.assert_array_equal(np.asarray(outk[4]), _tiles(outr[0]))

    @pytest.mark.parametrize("shape,block", BLOCK_CASES)
    def test_reverse_matches_ref(self, shape, block):
        rng = np.random.default_rng(0)
        nb = tuple(-(-s // b) for s, b in zip(shape, block))
        delta = jnp.asarray(rng.integers(-500, 500, nb + block).astype(np.int32))
        rk = lorenzo_ops.reverse_blocks(delta, 1e-3, impl="pallas")
        rr = lorenzo_ops.reverse_blocks(delta, 1e-3, impl="jax")
        np.testing.assert_allclose(np.asarray(rk), np.asarray(rr), rtol=0, atol=0)

    @pytest.mark.parametrize("nbins", [256, 1024])
    def test_fused_roundtrip_error_bound(self, nbins):
        """Kernel forward + kernel reverse obeys the paper's bound."""
        eb = 1e-3
        xb = _blocked((64, 128), (16, 16), seed=3, scale=0.1)
        codes, idx, val, n_out, _ = lorenzo_ops.dualquant_blocks(
            xb, eb, nbins, xb.size, impl="pallas")
        delta = dq.scatter_outliers(
            dq.codes_to_delta(codes.reshape(-1), nbins), idx, val)
        recon = lorenzo_ops.reverse_blocks(delta.reshape(xb.shape), eb,
                                           impl="pallas")
        err = np.abs(np.asarray(recon) - np.asarray(xb))
        assert err.max() <= eb * (1 + 1e-4) + 1e-7


def _tiles(codes):
    """[tiles holding an outlier, tiles] over the runs of 4096 values of
    a blocked field at a lane-aligned block width, in flat order (an
    outlier has code 0)."""
    out = np.asarray(codes).reshape(-1) == 0
    t = -(-out.size // 4096)
    out = np.pad(out, (0, t * 4096 - out.size))
    return [int(out.reshape(t, 4096).any(axis=1).sum()), t]


def _spiky(n, spikes, seed=0):
    """A smooth 1-D walk (no outlier at eb 0.5) with a jump of 5000 at
    each index in `spikes` (an outlier there and one just after)."""
    rng = np.random.default_rng(seed)
    x = np.cumsum(rng.integers(-3, 4, n)).astype(np.float32)
    x[np.asarray(spikes, np.int64)] += 5000.0
    return x


def _alternating(n):
    """Every Lorenzo delta an outlier at eb 0.5."""
    return np.where(np.arange(n) % 2, 1000.0, -1000.0).astype(np.float32)


OUTLIER_CASES = {
    # name: (field, block, capacity or None for 10% of the blocked n)
    "none": (lambda: _spiky(5000, []), (256,), None),
    "all_within_capacity": (lambda: _alternating(4096), (256,), 4096),
    "overflow": (lambda: _alternating(4096), (256,), 409),
    "n_not_a_multiple_of_the_tile": (lambda: _spiky(3000, [5, 700, 2999]),
                                     (256,), None),
    "hundreds_in_one_tile": (lambda: _spiky(6000, range(100, 1900, 6)),
                             (256,), None),
    "shorter_than_one_tile": (lambda: _spiky(40, [3, 17]), (256,), None),
    "steps_and_a_partial_last_step": (
        lambda: _spiky(300_001, list(range(0, 300_001, 997)) + [131_071,
                                                                 131_072]),
        (256,), None),
    "overflow_inside_a_later_step": (
        lambda: _spiky(300_001, range(0, 300_001, 1500)), (256,), 250),
    "blocks_8x8x8_fixture_size": (
        lambda: np.cumsum(np.random.default_rng(5).standard_normal(
            (16, 64, 64)), -1).astype(np.float32) * 40, (8, 8, 8), None),
    # a tile of 8 outliers is picked one by one, one of 9 is compacted
    "eight_then_nine_in_a_tile": (
        lambda: _spiky(12288, [100, 200, 300, 400,
                               4200, 4300, 4400, 4500, 8191]),
        (256,), None),
    # the lane-aligned TPU blocks: a block row spans many 4096-value tiles
    "tpu_block_4096_hundreds_in_one_tile": (
        lambda: _spiky(20000, range(4100, 5000, 6)), (4096,), None),
    "tpu_blocks_8x16x128_two_steps": (
        lambda: np.cumsum(np.random.default_rng(6).standard_normal(
            (24, 32, 256)), -1).astype(np.float32) * 40, (8, 16, 128), None),
}


class TestOutlierStore:
    """The fused kernel's outlier store against `extract_outliers` (the
    reference), bit for bit: indices, deltas, the fill past the count
    and the true count; and the kernel's tile counts against NumPy."""

    @pytest.mark.parametrize("case", sorted(OUTLIER_CASES))
    def test_matches_ref(self, case):
        make, block, cap = OUTLIER_CASES[case]
        x = jnp.asarray(make())
        xb = dq.block_split(dq.pad_to_blocks(x, block), block)
        cap = cap or max(16, xb.size // 10)
        outk = lorenzo_ops.dualquant_blocks(xb, 0.5, 1024, cap,
                                            impl="pallas-interpret")
        outr = lorenzo_ops.dualquant_blocks(xb, 0.5, 1024, cap, impl="jax")
        for name, k, r in zip(("codes", "idx", "val", "n"), outk, outr):
            np.testing.assert_array_equal(np.asarray(k), np.asarray(r),
                                          err_msg=name)
        np.testing.assert_array_equal(np.asarray(outk[4]), _tiles(outr[0]))
        idx, val, n_out = (np.asarray(v) for v in outr[1:4])
        used = min(int(n_out), cap)
        assert (idx[used:] == xb.size).all() and (val[used:] == 0).all()
        assert (np.diff(idx[:used]) > 0).all()

    @pytest.mark.parametrize("block", [(256,), (4096,)])
    def test_counts_of_a_known_field(self, block):
        # spikes at 10 and 5000 (tiles 0 and 1 of 3 at any lane-aligned
        # block width), each an outlier and its successor
        xb = dq.block_split(jnp.asarray(_spiky(12288, [10, 5000])), block)
        codes, idx, val, n_out, tiles = lorenzo_ops.dualquant_blocks(
            xb, 0.5, 1024, 1228, impl="pallas-interpret")
        assert int(n_out) == 4
        np.testing.assert_array_equal(np.asarray(tiles), [2, 3])
        np.testing.assert_array_equal(np.asarray(idx[:5]),
                                      [10, 11, 5000, 5001, 12288])
        assert lorenzo_ops.dualquant_blocks(xb, 0.5, 1024, 1228,
                                            impl="jax")[4] is None

    def test_a_block_off_the_lanes_runs_the_reference(self):
        # 8x8 blocks (64 values) cannot be laid out in 128-lane rows
        xb = _blocked((40, 40), (8, 8), seed=4)
        outk = lorenzo_ops.dualquant_blocks(xb, 1e-3, 1024, 160,
                                            impl="pallas-interpret")
        outr = lorenzo_ops.dualquant_blocks(xb, 1e-3, 1024, 160, impl="jax")
        for k, r in zip(outk[:4], outr[:4]):
            np.testing.assert_array_equal(np.asarray(k), np.asarray(r))
        assert outk[4] is None


class TestHistogramKernel:
    @pytest.mark.parametrize("n,nbins", [(1000, 256), (4096, 1024),
                                         (10000, 1024), (333, 128)])
    def test_matches_ref(self, n, nbins):
        rng = np.random.default_rng(n)
        codes = jnp.asarray(rng.integers(0, nbins, n).astype(np.int32))
        hk = hist_ops.histogram(codes, nbins, impl="pallas")
        hr = hist_ops.histogram(codes, nbins, impl="jax")
        np.testing.assert_array_equal(np.asarray(hk), np.asarray(hr))
        assert int(np.asarray(hk).sum()) == n

    def test_skewed_distribution(self):
        rng = np.random.default_rng(1)
        codes = jnp.asarray(np.clip(rng.normal(512, 3, 8192), 0, 1023).astype(np.int32))
        hk = hist_ops.histogram(codes, 1024, impl="pallas")
        hr = hist_ops.histogram(codes, 1024, impl="jax")
        np.testing.assert_array_equal(np.asarray(hk), np.asarray(hr))


class TestEncodeKernel:
    @pytest.mark.parametrize("n,k", [(100, 64), (4096, 1024), (513, 256)])
    def test_matches_ref(self, n, k):
        """One-hot-MXU codebook gather == reference gather, bit-exact
        (incl. full-width uint32 codewords through the int32 bitcast)."""
        rng = np.random.default_rng(n * 7 + k)
        p = 1.0 / np.arange(1, k + 1) ** 1.2
        codes = jnp.asarray(rng.choice(k, n, p=p / p.sum()).astype(np.int32))
        cb = hf.canonical_codebook(hf.codeword_lengths(hf.histogram(codes, k)))
        ck, bk = encode_ops.encode(codes, cb, impl="pallas")
        cr, br = encode_ops.encode(codes, cb, impl="jax")
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
        np.testing.assert_array_equal(np.asarray(bk), np.asarray(br))
        assert ck.dtype == jnp.uint32 and bk.dtype == jnp.int32


class TestDeflateKernel:
    @pytest.mark.parametrize("n,k,chunk", [(1000, 64, 512), (4096, 256, 512),
                                           (700, 1024, 512)])
    def test_matches_ref_bitstream(self, n, k, chunk):
        rng = np.random.default_rng(n + k)
        p = 1.0 / np.arange(1, k + 1) ** 1.5
        codes = jnp.asarray(rng.choice(k, n, p=p / p.sum()).astype(np.int32))
        cb = hf.canonical_codebook(hf.codeword_lengths(hf.histogram(codes, k)))
        cw, bw = hf.encode(codes, cb)
        wk, bk, gbk, gsk = deflate_ops.deflate(cw, bw, chunk, impl="pallas")
        wr, br, gbr, gsr = deflate_ops.deflate(cw, bw, chunk, impl="jax")
        np.testing.assert_array_equal(np.asarray(wk), np.asarray(wr))
        np.testing.assert_array_equal(np.asarray(bk), np.asarray(br))
        np.testing.assert_array_equal(np.asarray(gbk), np.asarray(gbr))
        np.testing.assert_array_equal(np.asarray(gsk), np.asarray(gsr))

    @pytest.mark.parametrize("n,chunk,sub", [(5000, 384, 192),
                                             (3000, 384, 96),
                                             (5000, 1024, 256)])
    def test_gap_arrays_match_ref_at_any_sub(self, n, chunk, sub):
        """Gap samples at every `sub`-th symbol, also where a subchunk
        starts mid-row (a sub_size that neither divides 128 nor is a
        multiple of it)."""
        rng = np.random.default_rng(n + sub)
        codes = jnp.asarray(rng.integers(0, 64, n).astype(np.int32))
        cb = hf.canonical_codebook(hf.codeword_lengths(
            hf.histogram(codes, 64)))
        cw, bw = hf.encode(codes, cb)
        got = deflate_ops.deflate(cw, bw, chunk, sub, impl="pallas")
        want = deflate_ops.deflate(cw, bw, chunk, sub, impl="jax")
        assert got[2].shape == (-(-n // chunk), chunk // sub)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_kernel_stream_decodes(self):
        """Kernel-produced bitstream must inflate back to the input."""
        rng = np.random.default_rng(5)
        n, k, chunk = 2000, 128, 512
        codes = rng.integers(0, k, n).astype(np.int32)
        cb = hf.canonical_codebook(hf.codeword_lengths(
            hf.histogram(jnp.asarray(codes), k)))
        cw, bw = hf.encode(jnp.asarray(codes), cb)
        words, bits, gap_bits, _ = deflate_ops.deflate(cw, bw, chunk,
                                                       impl="pallas")
        nc = words.shape[0]
        n_valid = np.minimum(chunk, np.maximum(n - np.arange(nc) * chunk, 0)
                             ).astype(np.int32)
        out = np.asarray(hf.inflate(words, bits, jnp.asarray(n_valid), cb,
                                    int(cb.max_len)))
        np.testing.assert_array_equal(out.reshape(-1)[:n], codes)


def _fibonacci_book(k):
    """A codebook from Fibonacci frequencies: lengths 1 .. k-1, so with
    k = 33 its longest codewords are MAXLEN (32) bits."""
    fib = [1, 1]
    while len(fib) < k:
        fib.append(fib[-1] + fib[-2])
    return hf.canonical_codebook(hf.codeword_lengths(
        jnp.asarray(fib[::-1], jnp.int32)))


class TestInflateKernel:
    # (symbols, alphabet, chunk, sub, book, trailing chunks of count 0).
    # The kernel walks up to 8 x 128 subchunk cursors a grid step
    # (`kernel.tile`): 32 chunks of 4096, 256 of 512.  "flat": uniform
    # symbols, max_len <= LUT_BITS; "deep": the deepest eight codewords
    # (25-32 bits) of a Fibonacci book, so a subchunk spans ~115 words
    # and its walk crosses into the second row of its window.
    @pytest.mark.parametrize("n,k,chunk,sub,book,pad", [
        pytest.param(2000, 128, 512, 64, "flat", 0, id="2000-128-512-64"),
        pytest.param(700, 1024, 256, 32, "flat", 0, id="700-1024-256-32"),
        pytest.param(4096, 64, 512, 128, "flat", 0, id="4096-64-512-128"),
        pytest.param(5 * 4096 - 1000, 1024, 4096, 128, "flat", 0,
                     id="chunk4096-fewer-chunks-than-a-tile"),
        pytest.param(37 * 4096 - 300, 64, 4096, 128, "flat", 0,
                     id="chunk4096-two-tiles-the-last-partial"),
        pytest.param(300 * 512 - 77, 1024, 512, 128, "flat", 0,
                     id="chunk512-two-tiles-the-last-partial"),
        pytest.param(1000, 128, 4096, 128, "flat", 6,
                     id="chunk4096-padded-chunks-count-0"),
        pytest.param(3 * 4096 + 5, 33, 4096, 128, "deep", 0,
                     id="chunk4096-deep-book-short-last-chunk"),
        pytest.param(2500, 33, 512, 128, "deep", 3,
                     id="chunk512-deep-book-padded-chunks"),
        pytest.param(3000, 33, 512, 64, "deep", 0,
                     id="chunk512-sub64-deep-book"),
        pytest.param(5000, 64, 1024, 256, "flat", 0,
                     id="sub256-two-windows-a-walk"),
        pytest.param(3000, 33, 512, 512, "deep", 2,
                     id="sub512-deep-book-padded-chunks"),
        pytest.param(5000, 64, 384, 192, "flat", 1,
                     id="chunk384-sub192-a-last-phase-of-64"),
        pytest.param(3000, 33, 640, 320, "deep", 0,
                     id="chunk640-sub320-deep-book-three-phases"),
    ])
    def test_gap_kernel_matches_sequential(self, n, k, chunk, sub, book,
                                           pad):
        """Pallas gap-array inflate == sequential reference, bit-exact;
        the decoded stream equals the original codes."""
        from repro.kernels.inflate import ops as inflate_ops
        rng = np.random.default_rng(n + k)
        if book == "deep":
            cb = _fibonacci_book(k)
            deepest = np.argsort(np.asarray(cb.lengths))[-8:]
            codes = rng.choice(deepest, n).astype(np.int32)
        else:
            codes = rng.integers(0, k, n).astype(np.int32)
            cb = hf.canonical_codebook(hf.codeword_lengths(
                hf.histogram(jnp.asarray(codes), k)))
        cw, bw = hf.encode(jnp.asarray(codes), cb)
        words, bits, gap_bits, _ = deflate_ops.deflate(
            cw, bw, chunk, sub, impl="pallas")
        if pad:
            # chunks past the stream (a fixed-capacity store's tail):
            # words and gaps are noise, the count 0 says decode nothing
            words = jnp.concatenate([words, jnp.asarray(rng.integers(
                0, 2 ** 32, (pad, chunk), dtype=np.uint32))])
            bits = jnp.concatenate([bits, jnp.zeros((pad,), bits.dtype)])
            gap_bits = jnp.concatenate([gap_bits, jnp.asarray(
                rng.integers(0, 32 * chunk, (pad, chunk // sub)),
                gap_bits.dtype)])
        nv = jnp.asarray(np.minimum(
            chunk, np.maximum(n - np.arange(words.shape[0]) * chunk, 0)
        ).astype(np.int32))
        ml = hf.bucket_max_len(max(1, int(cb.max_len)))
        assert int(cb.max_len) == ml == hf.MAXLEN \
            if book == "deep" else ml <= hf.LUT_BITS
        table = hf.decode_table(cb.lengths, ml)
        seq = np.asarray(hf.inflate(words, bits, nv, cb, ml))
        out = np.asarray(inflate_ops.inflate(
            words, bits, nv, table, ml, gaps=gap_bits,
            impl="pallas-interpret"))
        np.testing.assert_array_equal(out, seq)
        np.testing.assert_array_equal(out.reshape(-1)[:n], codes)
        np.testing.assert_array_equal(out.reshape(-1)[n:], 0)

    def test_a_subchunk_too_long_for_a_grid_step_decodes_by_reference(self):
        """A subchunk of 4096 symbols: one grid step of 128 cursors would
        hold 8 MiB of words and codes, over the kernel's VMEM budget, so
        the tile refuses it and the dispatch decodes it with the jax gap
        reference, bit-exact; half of it fits."""
        from repro.kernels.inflate import kernel as inflate_kernel
        from repro.kernels.inflate import ops as inflate_ops
        assert inflate_kernel.fits(4096, 2048)
        assert not inflate_kernel.fits(4096, 4096)
        with pytest.raises(ValueError, match="VMEM"):
            inflate_kernel.tile(3, 4096, 4096)
        n, k = 3 * 4096 - 500, 256
        codes = np.random.default_rng(n).integers(0, k, n).astype(np.int32)
        cb = hf.canonical_codebook(hf.codeword_lengths(
            hf.histogram(jnp.asarray(codes), k)))
        cw, bw = hf.encode(jnp.asarray(codes), cb)
        words, bits, gap_bits, _ = deflate_ops.deflate(
            cw, bw, 4096, 4096, impl="pallas")
        nv = jnp.asarray([4096, 4096, 4096 - 500], jnp.int32)
        ml = hf.bucket_max_len(int(cb.max_len))
        out = np.asarray(inflate_ops.inflate(
            words, bits, nv, hf.decode_table(cb.lengths, ml), ml,
            gaps=gap_bits, impl="pallas-interpret"))
        np.testing.assert_array_equal(out.reshape(-1)[:n], codes)
