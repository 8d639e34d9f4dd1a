"""Serving engine: prefill + batched synchronized decode with optional
cuSZ-compressed KV cache, split into disaggregation-ready phases:

  1. **prefill** — run the prompt through the parallel forward under the
     *prefill* mesh/shardings and build the decode caches (optionally
     already in the in-memory QuantKV compressed format).
  2. **handoff** — ``encode_handoff`` turns every cache tensor into
     per-SEQ_BLOCK-slab registry Containers (`int8-block` wire by
     default, `cusz` for the host-offload leg); the Containers — never
     decoded f32 — are what crosses the prefill->decode mesh boundary.
  3. **reshard** — ``reshard_caches`` adopts the containers under the
     *decode* mesh: int8-block payloads become the in-memory QuantKV
     cache directly (zero re-quantization round trip), other wires
     decode/quantize jitted with the decode mesh's shardings.
  4. **decode** — ``decode_tokens`` runs the jitted one-token step (one
     compiled executable per ``(cfg, scfg)``, cached across calls).

``generate`` composes 1+4 for the single-mesh path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import codecs
from repro.dist import context as dist_ctx
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.core import kvcache as KVC


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    s_max: int = 2048
    compressed_kv: bool = False
    kv_codec: str = "int8-block"     # registry id of the in-memory KV codec
    temperature: float = 0.0         # 0 = greedy
    compute_dtype: object = jnp.bfloat16


#: seq axis of every prefill cache entry ([n_periods, B, S, ...])
HANDOFF_SEQ_AXIS = 2


def load_params(key, cfg: ModelConfig, scfg: ServeConfig):
    """Serving weights: seeded random parameters stored once at the
    compute dtype.  Made and cast in one jitted program, so the float32
    tree never lives whole on the device (float32 qwen2.5-3b is 12.4 GB
    of a v5e's 16 GB; bfloat16 halves it and leaves room for the pool).
    Decode reads every weight each step, so half the bytes is also half
    the weight traffic."""
    def make(k):
        return jax.tree.map(lambda p: p.astype(scfg.compute_dtype),
                            M.init_params(k, cfg))
    # repro-lint: allow[jit-cache] built once per load; nothing to cache
    return jax.jit(make)(key)


# ---------------------------------------------------------------------------
# Phase 1: prefill
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, tokens: jax.Array,
            scfg: ServeConfig, extra=None):
    """Run the prompt through the parallel forward, build decode caches.
    Returns (last_logits [B,V], DecodeCaches, prompt_len)."""
    logits, caches = M.forward(params, cfg, tokens, extra,
                               compute_dtype=scfg.compute_dtype,
                               collect_caches=True)
    B, S = tokens.shape
    S_total = S + cfg.n_prepend_embeds
    kv_codec = (codecs.get_block_codec(scfg.kv_codec,
                                       axis=HANDOFF_SEQ_AXIS,
                                       block=KVC.SEQ_BLOCK)
                if scfg.compressed_kv else None)

    def extend(x):
        """Pad the seq axis to s_max; under compressed_kv the full buffer
        becomes the registry codec's payload, kept as the in-memory
        QuantKV format the decode-step hot path indexes directly."""
        ext = jnp.zeros(x.shape[:2] + (scfg.s_max - S_total,)
                        + x.shape[3:], x.dtype)
        full = jnp.concatenate([x, ext], axis=HANDOFF_SEQ_AXIS)
        if kv_codec is not None:
            cont = kv_codec.encode(full)
            return KVC.QuantKV(cont.payload["q"], cont.payload["scale"])
        return full

    entries = []
    for kind, c in zip(cfg.pattern, caches):
        if kind.startswith("attn"):
            if cfg.mla:
                # the MLA latent cache goes through the same block codec
                # as GQA K/V — compressed_kv is honored, not ignored
                entries.append(extend(c))
            else:
                k, v = c
                entries.append((extend(k), extend(v)))
        else:
            entries.append(c)        # MambaState carries over directly
    return logits[:, -1, :], M.DecodeCaches(tuple(entries)), S_total


# ---------------------------------------------------------------------------
# Phase 4: decode (jitted step, cached per config)
# ---------------------------------------------------------------------------

#: traces per (cfg, scfg) key — regression guard that `generate` reuses
#: the compiled step across calls instead of re-jitting every invocation
STEP_TRACES: Dict[Any, int] = {}


def make_serve_step(cfg: ModelConfig, scfg: ServeConfig):
    """Jittable one-token decode for a synchronized batch."""

    def step(params, token, caches, cache_len):
        # body runs only while tracing, so this counts (re)traces
        STEP_TRACES[(cfg, scfg)] = STEP_TRACES.get((cfg, scfg), 0) + 1
        return M.decode_step(params, cfg, token, caches, cache_len,
                             compute_dtype=scfg.compute_dtype,
                             compressed_kv=scfg.compressed_kv)

    return step


@functools.lru_cache(maxsize=None)
def get_serve_step(cfg: ModelConfig, scfg: ServeConfig):
    """The jitted serve step for `(cfg, scfg)`.  Cached: repeated
    `generate` calls reuse one compiled executable instead of discarding
    it per invocation (configs are frozen dataclasses, so the key is a
    stable hash)."""
    return jax.jit(make_serve_step(cfg, scfg))


def pick_token(logits, k, scfg: ServeConfig):
    """Greedy / temperature sampling from [B, V] logits -> [B] int32.
    Shared by `decode_tokens` and the continuous-batching scheduler's
    per-slot step."""
    if scfg.temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(k, logits / scfg.temperature
                                  ).astype(jnp.int32)


_pick = pick_token


def decode_tokens(params, cfg: ModelConfig, scfg: ServeConfig,
                  last_logits: jax.Array, caches: M.DecodeCaches,
                  plen: int, n_new: int, key=None):
    """Synchronized-batch decode loop from prefilled (or resharded)
    caches.  Returns [B, n_new] int32."""
    step_fn = get_serve_step(cfg, scfg)
    key = key if key is not None else jax.random.PRNGKey(0)
    key, k0 = jax.random.split(key)
    tok = _pick(last_logits, k0, scfg)[:, None]
    outs = []
    for i in range(n_new):
        outs.append(tok[:, 0])
        logits, caches = step_fn(params, tok, caches, jnp.int32(plen + i))
        key, ki = jax.random.split(key)
        tok = _pick(logits[:, 0, :], ki, scfg)[:, None]
    return jnp.stack(outs, axis=1)


def generate(params, cfg: ModelConfig, prompt: jax.Array, n_new: int,
             scfg: ServeConfig, extra=None, key=None):
    """Greedy/temperature generation for a batch of equal-length prompts
    (single-mesh path: prefill and decode share placement).
    Returns [B, n_new] int32."""
    last_logits, caches, plen = prefill(params, cfg, prompt, scfg, extra)
    return decode_tokens(params, cfg, scfg, last_logits, caches, plen,
                         n_new, key=key)


# ---------------------------------------------------------------------------
# Phases 2+3: compressed prefill->decode handoff across the serve reshard
# ---------------------------------------------------------------------------

class KVHandoff(NamedTuple):
    """Everything that crosses the prefill->decode mesh boundary: per
    pattern entry, a tuple of per-tensor Container tuples (attn K/V and
    MLA latents as per-seq-slab wire containers; Mamba/SSD state as
    lossless containers).  No decoded f32 rides here."""
    kinds: Tuple[str, ...]           # per entry: "kv" | "mla" | "state"
    entries: Tuple[Any, ...]
    plen: int
    wire: str


#: telemetry of the most recent encode_handoff / reshard_caches call
LAST_HANDOFF_STATS: Dict[str, Any] = {}
LAST_RESHARD_STATS: Dict[str, Any] = {}


def encode_handoff(caches: M.DecodeCaches, cfg: ModelConfig,
                   scfg: ServeConfig, *, plen: int,
                   wire: Optional[str] = None,
                   nslabs: Optional[int] = None,
                   wire_cfg: Optional[dict] = None) -> KVHandoff:
    """Phase 2: encode the prefill caches into wire Containers.

    `plen` (the prefill length, as returned by ``prefill``) rides in the
    handoff so the decode side resumes from the right position without
    out-of-band metadata.  `wire` resolution: explicit arg > the armed
    ``dist.context.use_kv_reshard_compress`` hook (an explicit disarm
    resolves to "lossless") > "int8-block".  Cache tensors are sliced
    into per-SEQ_BLOCK seq slabs (`nslabs` overrides the slab count) and
    each slab is packed to its host storage form — the container
    payloads are the bytes that move.  Updates ``LAST_HANDOFF_STATS``
    with the wire accounting."""
    wire = wire or dist_ctx.kv_reshard_codec() or "int8-block"
    item = np.dtype(jnp.bfloat16).itemsize
    # reset at call START, not return: back-to-back sessions must never
    # read the previous call's wire accounting, and a failed handoff
    # leaves partial (not stale-successful) stats behind
    LAST_HANDOFF_STATS.clear()
    LAST_HANDOFF_STATS.update(
        {"wire": wire, "tensors": 0, "containers": 0,
         "wire_bytes": 0, "raw_bf16_bytes": 0, "lossless_fallback": 0})
    stats = LAST_HANDOFF_STATS

    def account(parts, raw_bytes):
        stats["tensors"] += 1
        stats["containers"] += len(parts)
        stats["wire_bytes"] += KVC.kv_wire_nbytes(parts)
        stats["raw_bf16_bytes"] += raw_bytes
        return parts

    def ship(x):
        n = x.q.size if isinstance(x, KVC.QuantKV) else x.size
        parts = KVC.kv_wire_encode(
            x, HANDOFF_SEQ_AXIS, wire=wire, nslabs=nslabs,
            source_dtype=scfg.compute_dtype, wire_cfg=wire_cfg)
        if wire != "lossless":
            # slabs the wire codec could not represent faithfully were
            # re-encoded raw by kv_wire_encode (graceful degradation)
            stats["lossless_fallback"] += sum(
                1 for p in parts if p.header.codec == "lossless")
        return account(parts, int(n) * item)

    lossless = codecs.get("lossless")

    def ship_state(x):
        # recurrent state has no seq axis and stays lossless; its raw
        # baseline is its actual bytes, not the bf16 K/V equivalent
        return account((lossless.pack(lossless.encode(x)),),
                       int(x.size) * np.dtype(x.dtype).itemsize)

    kinds, entries = [], []
    for kind, c in zip(cfg.pattern, caches.entries):
        if kind.startswith("attn"):
            if cfg.mla:
                kinds.append("mla")
                entries.append((ship(c),))
            else:
                kinds.append("kv")
                entries.append((ship(c[0]), ship(c[1])))
        else:
            kinds.append("state")
            entries.append(tuple(ship_state(x) for x in c))
    return KVHandoff(tuple(kinds), tuple(entries), int(plen), wire)


# jitted decode/quantize caches: one compile per (codec/placement)
# signature, not one per cache tensor per reshard.  Bounded LRU: an
# elastic fleet resharding onto fresh decode meshes must not accumulate
# executables (and pinned Mesh objects) for every retired placement.

@functools.lru_cache(maxsize=64)
def _jitted_wire_decode(codec, shape, dtype_name, shd):
    like = jax.ShapeDtypeStruct(shape, np.dtype(dtype_name))
    fn = lambda c: codec.decode(c, like=like)              # noqa: E731
    return jax.jit(fn, out_shardings=shd) if shd is not None else jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _jitted_kv_quantize(shape, dtype_name, out_shd):
    fn = lambda x: KVC.kv_quantize(x, HANDOFF_SEQ_AXIS)    # noqa: E731
    return (jax.jit(fn, out_shardings=out_shd)
            if out_shd is not None else jax.jit(fn))


def reshard_caches(handoff: KVHandoff, cfg: ModelConfig, scfg: ServeConfig,
                   *, mesh=None) -> M.DecodeCaches:
    """Phase 3: adopt the handoff Containers as decode caches under the
    *decode* mesh (default: the ambient ``dist.context`` mesh; None =
    single-device).

    int8-block wire + compressed decode target: the payload (q + block
    scales) IS the in-memory QuantKV format — it is concatenated in
    payload space and placed directly, with **no f32 round trip and no
    re-quantization**.  Any other combination decodes (and, for a
    compressed target, re-quantizes) jitted with the decode mesh's
    shardings as out_shardings.  Updates ``LAST_RESHARD_STATS``."""
    mesh = mesh if mesh is not None else dist_ctx.current_mesh()
    # reset at call start (same contract as LAST_HANDOFF_STATS)
    LAST_RESHARD_STATS.clear()
    LAST_RESHARD_STATS.update({"tensors": 0, "adopted_quantkv": 0,
                               "decoded": 0})
    stats = LAST_RESHARD_STATS

    def put(x, *spec):
        if mesh is None:
            return jnp.asarray(x)
        return jax.device_put(
            x, dist_ctx.resolve_sharding(mesh, x.shape, *spec))

    def shd(shape, *spec):
        return (dist_ctx.resolve_sharding(mesh, shape, *spec)
                if mesh is not None else None)

    def arrive(parts):
        """One cache tensor's wire containers -> its decode-side form."""
        stats["tensors"] += 1
        # a slab that failed wire-codec validation arrives as "lossless";
        # adoption/payload-concat need a homogeneous wire, so any mix
        # routes through the per-part decode path (kv_wire_restore reads
        # each part's own header)
        part_codecs = {p.header.codec for p in parts}
        wire_name = (parts[0].header.codec if len(part_codecs) == 1
                     else "mixed")
        full_shape = list(KVC.kv_slab_shape(parts[0]))
        full_shape[HANDOFF_SEQ_AXIS] = sum(
            int(KVC.kv_slab_shape(p)[HANDOFF_SEQ_AXIS]) for p in parts)
        full_shape = tuple(full_shape)
        if scfg.compressed_kv:
            if wire_name == "int8-block":
                # zero-round-trip adoption: q/scale payloads become the
                # QuantKV cache as-is
                qkv = KVC.kv_wire_adopt(parts, HANDOFF_SEQ_AXIS)
                stats["adopted_quantkv"] += 1
                return KVC.QuantKV(put(qkv.q, None, "data", "model"),
                                   put(qkv.scale, None, "data", "model"))
            # lossy/raw wire: restore (host/any-device) then quantize
            # jitted under the decode mesh's shardings
            full = KVC.kv_wire_restore(parts, HANDOFF_SEQ_AXIS,
                                       dtype=scfg.compute_dtype)
            stats["decoded"] += 1
            out_shd = None
            if mesh is not None:
                sc_shape = list(full_shape)
                sc_shape[HANDOFF_SEQ_AXIS] //= KVC.SEQ_BLOCK
                out_shd = KVC.QuantKV(
                    shd(full_shape, None, "data", "model"),
                    shd(tuple(sc_shape), None, "data", "model"))
            full = put(full, None, "data", "model")
            return _jitted_kv_quantize(full_shape, full.dtype.name,
                                       out_shd)(full)
        # dense decode target
        stats["decoded"] += 1
        if wire_name == "int8-block":
            codec = codecs.get_block_codec("int8-block",
                                           axis=HANDOFF_SEQ_AXIS,
                                           block=KVC.SEQ_BLOCK)
            unpacked = [codec.unpack(p) for p in parts]
            merged = (unpacked[0] if len(unpacked) == 1 else
                      codecs.concat_containers(
                          unpacked, HANDOFF_SEQ_AXIS,
                          codec.payload_axes(HANDOFF_SEQ_AXIS)))
            return _jitted_wire_decode(
                codec, full_shape, np.dtype(scfg.compute_dtype).name,
                shd(full_shape, None, "data", "model"))(merged)
        full = KVC.kv_wire_restore(parts, HANDOFF_SEQ_AXIS,
                                   dtype=scfg.compute_dtype)
        return put(full, None, "data", "model")

    entries = []
    for kind, entry in zip(handoff.kinds, handoff.entries):
        if kind == "kv":
            entries.append((arrive(entry[0]), arrive(entry[1])))
        elif kind == "mla":
            entries.append(arrive(entry[0]))
        else:                        # "state": lossless whole tensors
            from repro.models import ssm as ssm_mod
            vals = []
            for parts in entry:
                stats["tensors"] += 1
                stats["decoded"] += 1
                vals.append(put(codecs.decode(parts[0]), None, "data"))
            entries.append(ssm_mod.MambaState(*vals))
    return M.DecodeCaches(tuple(entries))
