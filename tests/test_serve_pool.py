"""Paged compressed-KV pool: allocator properties, page math, eviction.

The tentpole invariants:

* allocator — under random admit/grow/evict/restore/release traces, no
  device page id is ever live twice, the free list is conserved
  (``free + used == n_pages``), and occupancy accounting is exact.
* page math — `kv_page_slice`/`kv_page_concat` are inverse payload-space
  ops, and a slot assembled from pages is BIT-identical to the
  whole-tensor int8-block path (the PR-5 zero-requantize trick at page
  granularity).
* eviction — evict->restore through "int8-block" is bit-exact; through
  "cusz"/"lossless" it holds the stacked error bound (codec bound +
  requantize scale/2).
"""
from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import kvcache as KVC
from repro.serve.pool import PagedKVPool, PoolExhausted

SEQ_AXIS = 2


def _quantkv(key, n_blocks: int, heads: int = 2, dim: int = 4):
    x = jax.random.normal(key, (1, 1, n_blocks * KVC.SEQ_BLOCK, heads, dim),
                          jnp.float32)
    return KVC.kv_quantize(x, SEQ_AXIS)


@pytest.fixture(scope="module")
def page_slab():
    """One reusable page slab (content is irrelevant to the allocator)."""
    return KVC.kv_page_slice(_quantkv(jax.random.PRNGKey(0), 1),
                             SEQ_AXIS, 0)


# ---------------------------------------------------------------------------
# allocator property test: random traces keep the accounting exact
# ---------------------------------------------------------------------------

def _check_invariants(pool: PagedKVPool):
    pids = [p.pid for t in pool._tables.values() for p in t if p.resident]
    assert len(pids) == len(set(pids)), f"double-allocated page: {pids}"
    assert pool.free_pages + pool.used_pages == pool.n_pages
    assert len(pids) == pool.used_pages
    assert not (set(pids) & set(pool._free)), "page both free and live"
    assert set(pids) | set(pool._free) <= set(range(pool.n_pages))
    assert pool.occupancy == pool.used_pages / pool.n_pages
    st_ = pool.stats()
    assert st_["used"] == pool.used_pages and st_["free"] == pool.free_pages
    assert (st_["host_bytes"] > 0) == (st_["host_pages"] > 0)


@settings(max_examples=12)
@given(st.integers(min_value=0, max_value=2**31 - 1),
       st.integers(min_value=1, max_value=9))
def test_allocator_random_trace_invariants(page_slab, seed, n_pages):
    rng = random.Random(seed)
    pool = PagedKVPool(n_pages, evict_codec="int8-block",
                       source_dtype=jnp.float32)
    next_sid = 0
    for _ in range(60):
        op = rng.choice(["admit", "grow", "evict", "restore", "release"])
        sids = pool.sequences()
        try:
            if op == "admit":
                sid = next_sid
                next_sid += 1
                pool.register(sid)
                for _ in range(rng.randint(1, 3)):
                    pool.append_page(sid, (page_slab,))
            elif op == "grow" and sids:
                pool.append_page(rng.choice(sids), (page_slab,))
            elif op == "evict" and sids:
                sid = rng.choice(sids)
                if pool.n_pages_of(sid):
                    pool.evict_page(sid,
                                    rng.randrange(pool.n_pages_of(sid)))
            elif op == "restore" and sids:
                sid = rng.choice(sids)
                if pool.n_pages_of(sid):
                    pool.restore_page(sid,
                                      rng.randrange(pool.n_pages_of(sid)))
            elif op == "release" and sids:
                pool.release(rng.choice(sids))
        except PoolExhausted:
            # a partially admitted sequence stays registered; its pages
            # so far must still satisfy every invariant
            pass
        _check_invariants(pool)
    # drain: releasing everything returns the pool to fully free
    for sid in pool.sequences():
        pool.release(sid)
    assert pool.used_pages == 0
    assert sorted(pool._free) == list(range(pool.n_pages))
    assert pool.stats()["host_bytes"] == 0


def test_exhaustion_raises_and_recovers(page_slab):
    pool = PagedKVPool(2, evict_codec="int8-block",
                       source_dtype=jnp.float32)
    pool.register("a")
    pool.append_page("a", (page_slab,))
    pool.append_page("a", (page_slab,))
    pool.register("b")
    with pytest.raises(PoolExhausted):
        pool.append_page("b", (page_slab,))
    # eviction frees a device page; the retry succeeds
    assert pool.evict_page("a", 0)
    pool.append_page("b", (page_slab,))
    assert pool.used_pages == 2 and pool.stats()["host_pages"] == 1


def test_evict_cold_prefers_least_recently_touched(page_slab):
    pool = PagedKVPool(4, evict_codec="int8-block",
                       source_dtype=jnp.float32)
    for sid in ("old", "hot"):
        pool.register(sid)
        pool.append_page(sid, (page_slab,))
        pool.append_page(sid, (page_slab,))
    pool.touch("hot")
    freed = pool.evict_cold(2, exclude=())
    assert freed == 2
    assert pool.n_resident("old") == 0       # cold sequence went first
    assert pool.n_resident("hot") == 2


# ---------------------------------------------------------------------------
# page math: slice/concat inverse + bit-identity of page-wise transport
# ---------------------------------------------------------------------------

def test_page_slice_concat_roundtrip_bitwise():
    qkv = _quantkv(jax.random.PRNGKey(1), 4)
    n = KVC.kv_page_count(qkv.q.shape[SEQ_AXIS])
    assert n == 4
    pages = [KVC.kv_page_slice(qkv, SEQ_AXIS, i) for i in range(n)]
    for p in pages:
        assert p.q.shape[SEQ_AXIS] == KVC.SEQ_BLOCK
        assert p.scale.shape[SEQ_AXIS] == 1
    back = KVC.kv_page_concat(pages, SEQ_AXIS)
    assert np.array_equal(np.asarray(back.q), np.asarray(qkv.q))
    assert np.array_equal(np.asarray(back.scale), np.asarray(qkv.scale))


def test_page_count():
    assert KVC.kv_page_count(0) == 0
    assert KVC.kv_page_count(1) == 1
    assert KVC.kv_page_count(KVC.SEQ_BLOCK) == 1
    assert KVC.kv_page_count(KVC.SEQ_BLOCK + 1) == 2


def test_adopted_slot_bit_identical_to_whole_tensor_path():
    """Pages written into a batched decode slot must reproduce the
    whole-tensor quantize path bit for bit — including the
    zero/SCALE_FLOOR extension past the written pages (what `prefill`
    puts there), so decode from an adopted slot is the PR-5 path."""
    from repro.serve.scheduler import _adopt_slot

    n_blocks, s_blocks = 2, 4            # 2 written pages in a 4-page slot
    qkv = _quantkv(jax.random.PRNGKey(2), n_blocks)
    pages = [KVC.kv_page_slice(qkv, SEQ_AXIS, i) for i in range(n_blocks)]

    # reference: whole padded buffer through kv_quantize (prefill's path)
    full = KVC.kv_dequantize(qkv, SEQ_AXIS, jnp.float32)
    pad = jnp.zeros(full.shape[:2]
                    + ((s_blocks - n_blocks) * KVC.SEQ_BLOCK,)
                    + full.shape[3:], full.dtype)
    ref = KVC.kv_quantize(jnp.concatenate([full, pad], axis=SEQ_AXIS),
                          SEQ_AXIS)

    buf = KVC.QuantKV(
        jnp.ones((1, 3, s_blocks * KVC.SEQ_BLOCK) + qkv.q.shape[3:],
                 jnp.int8),              # poisoned: adoption must reset
        jnp.full((1, 3, s_blocks) + qkv.scale.shape[3:], 7.0, jnp.float32))
    slot = 1
    out = _adopt_slot(buf, pages, slot, SEQ_AXIS)
    assert np.array_equal(np.asarray(out.q[:, slot]),
                          np.asarray(ref.q[:, 0]))
    assert np.array_equal(np.asarray(out.scale[:, slot]),
                          np.asarray(ref.scale[:, 0]))
    # other slots untouched
    assert np.all(np.asarray(out.q[:, 0]) == 1)
    assert np.all(np.asarray(out.scale[:, 2]) == 7.0)


# ---------------------------------------------------------------------------
# evict -> restore error bounds per codec
# ---------------------------------------------------------------------------

def _evict_restore(codec: str):
    qkv = _quantkv(jax.random.PRNGKey(3), 2)
    pages = [KVC.kv_page_slice(qkv, SEQ_AXIS, i) for i in range(2)]
    pool = PagedKVPool(2, evict_codec=codec, source_dtype=jnp.float32)
    pool.register("s")
    for p in pages:
        pool.append_page("s", (p,))
    assert pool.evict_sequence("s") == 2
    assert pool.used_pages == 0 and pool.stats()["host_bytes"] > 0
    assert pool.ensure_resident("s") == 2
    return pages, [c[0] for c in pool.read_pages("s")]


def test_evict_restore_int8_block_bit_exact():
    pages, restored = _evict_restore("int8-block")
    for orig, back in zip(pages, restored):
        assert np.array_equal(np.asarray(back.q), np.asarray(orig.q))
        assert np.array_equal(np.asarray(back.scale),
                              np.asarray(orig.scale))


@pytest.mark.parametrize("codec", ["cusz", "lossless"])
def test_evict_restore_lossy_holds_error_bound(codec):
    pages, restored = _evict_restore(codec)
    for orig, back in zip(pages, restored):
        a = np.asarray(KVC.kv_dequantize(orig, SEQ_AXIS, jnp.float32))
        b = np.asarray(KVC.kv_dequantize(back, SEQ_AXIS, jnp.float32))
        # restore re-quantizes: its own bound is scale_new/2 per element
        requant = np.broadcast_to(
            np.asarray(back.scale).repeat(KVC.SEQ_BLOCK, SEQ_AXIS) / 2,
            a.shape)
        if codec == "cusz":
            # default wire cfg: valrel eb on the dequantized slab
            eb = KVC.CUSZ_WIRE_CFG["eb"] * (a.max() - a.min())
        else:
            eb = 0.0
        assert np.all(np.abs(a - b) <= requant + eb + 1e-6), codec


def test_bad_evict_codec_rejected_at_construction():
    with pytest.raises(Exception):
        PagedKVPool(2, evict_codec="no-such-codec")


def test_evict_codec_resolves_from_context_hook():
    from repro.dist import context as dist_ctx

    with dist_ctx.use_kv_evict_codec("lossless"):
        assert PagedKVPool(2).evict_codec == "lossless"
        # explicit arg still wins over the armed hook
        assert PagedKVPool(2, evict_codec="int8-block"
                           ).evict_codec == "int8-block"
    assert PagedKVPool(2).evict_codec == "cusz"   # default past the scope


@pytest.mark.parametrize("outlier_frac, fallbacks", [(1.0, 0), (0.0, 2)])
def test_lossless_fallbacks_counted(outlier_frac, fallbacks):
    """A cusz-evicted slab whose outliers overflow the codec's store
    ships raw; the pool counts every such slab."""
    qkv = _quantkv(jax.random.PRNGKey(4), 2)
    pool = PagedKVPool(2, evict_codec="cusz", source_dtype=jnp.float32,
                       evict_cfg={"eb": 1e-6, "eb_mode": "valrel",
                                  "outlier_frac": outlier_frac})
    pool.register("s")
    for i in range(2):
        pool.append_page("s", (KVC.kv_page_slice(qkv, SEQ_AXIS, i),))
    pool.evict_sequence("s")
    assert pool.stats()["lossless_fallbacks"] == fallbacks
