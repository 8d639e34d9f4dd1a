"""Chip smoke test: the compressor and the qwen2.5-3b server, once, on a TPU.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --four-chips      # four chips of one host

One process, no PYTHONPATH needed (``src`` is added here).  It fails when
JAX's first device is not a TPU.  Phases (one chip):

1. dispatch: every pipeline stage must resolve to compiled Pallas.
2. compressor, through ``repro.codecs``: seeded fields at the paper's
   Table 2 sizes (Hurricane 100x500x500 under cusz / cusz-i / fz, HACC
   280,953,867 under cusz) at eb 1e-4 valrel.  Prints ratio, max error
   against eb, warm compress / decompress wall time and peak device bytes
   per pair; decodes the Pallas-made container with the jax-reference
   decoder and the reverse, and fails if any decode breaks the bound.
3. server, through ``serve.scheduler.run_continuous``: full-width
   qwen2.5-3b with seeded random weights answers 8 requests on a paged
   compressed-KV pool small enough to evict and restore pages.

``--four-chips`` runs only the compressed cross-pod gradient all-reduce
of ``launch/train.py`` (qwen2.5-3b, published widths, depth cut) on a
(pod=2, data=2, model=1) mesh, against the uncompressed all-reduce.

The last line of standard output is one JSON object naming the device.
Wall times are informative only; the benchmark owns timing claims.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import codecs, configs  # noqa: E402
from repro.core import metrics  # noqa: E402
from repro.data import scidata  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch import env as launch_env  # noqa: E402
from repro.launch import train as launch_train  # noqa: E402
from repro.serve import engine as E  # noqa: E402
from repro.serve import scheduler as S  # noqa: E402

EB = 1e-4                                  # valrel, the paper's headline
FIELDS = (("hurricane", ("cusz", "cusz-i", "fz")), ("hacc", ("cusz",)))
ARCH = "qwen2.5-3b"
# the server phase: every prompt fits one 128-token page, so prefill
# compiles once; generation crosses into a second page and the pool
# holds fewer pages than the four slots grow into.  These lengths make
# the scheduler preempt exactly one sequence (one page out through the
# eviction codec and back): each evicted slab compiles its own codec
# program, so a thrashing pool would spend the run compiling.
SERVE = dict(prompt_len=100, max_new=(26, 30, 29, 22, 46, 23, 34, 23),
             max_batch=4, pool_pages=5, s_max=256)
# the four-chip phase: replicated float32 params + AdamW moments +
# per-pod gradients must fit one 16 GB chip, which forces the depth cut
TRAIN = dict(layers=6, steps=4, batch=8, seq=256, loss_band=0.02)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:8.1f}s] {msg}", flush=True)


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats().get("peak_bytes_in_use", -1))


def timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# -- phase 1 ------------------------------------------------------------------

def check_dispatch() -> None:
    pp = dispatch.pipeline_policy()
    got = dict(pp.entries)
    want = dispatch.Resolved("pallas", interpret=False)
    for stage in dispatch.PIPELINE_STAGES:
        check(got.get(stage) == want,
              f"stage {stage} resolved to {got.get(stage)}, not {want}")
        log(f"dispatch {stage:20s} -> pallas interpret=False")


# -- phase 2 ------------------------------------------------------------------

def make_field(name: str, seed: int) -> np.ndarray:
    if name == "hurricane":
        return scidata.hurricane_like((100, 500, 500), seed=seed)
    return scidata.hacc_like(280_953_867, seed=seed)


def payload_mismatch(a, b) -> float:
    """Fraction of payload elements (every array of the container) that
    differ between two encodes of one field."""
    diff = total = 0
    for k in sorted(set(a.payload) | set(b.payload)):
        x, y = (np.asarray(jax.device_get(c.payload[k])) for c in (a, b))
        check(x.shape == y.shape, f"payload {k}: {x.shape} vs {y.shape}")
        diff += int(np.count_nonzero(x != y))
        total += x.size
    return diff / max(total, 1)


def bound_held(x, y, eb: float, what: str) -> float:
    err = float(metrics.max_abs_err(x, y))
    check(bool(np.isfinite(err)) and metrics.verify_error_bound(x, y, eb),
          f"{what}: max error {err!r} breaks eb {eb!r}")
    return err


def compress_pair(field: str, name: str, x: jax.Array) -> None:
    codec = codecs.get(name, eb=EB, eb_mode="valrel")
    c = jax.block_until_ready(codec.encode(x))           # compiles
    c, t_enc = timed(codec.encode, x)
    check(codec.valid(c), f"{field}/{name}: outlier store overflowed")
    eb = float(c.header.param("eb"))
    y = jax.block_until_ready(codecs.decode(c))          # compiles
    y, t_dec = timed(codecs.decode, c)
    err = bound_held(x, y, eb, f"{field}/{name} pallas->pallas")
    ratio = x.nbytes / codec.stored_nbytes(c)
    gbs = x.nbytes / 1e9
    log(f"compress {field:9s} {name:6s} shape={tuple(x.shape)} "
        f"ratio={ratio:.4f} max_err={err:.6e} eb={eb:.6e} "
        f"err/eb={err / eb:.6f} compress_s={t_enc:.6f} "
        f"({gbs / t_enc:.4f} GB/s) decompress_s={t_dec:.6f} "
        f"({gbs / t_dec:.4f} GB/s) peak_bytes_in_use={peak_bytes()}")
    del y
    # the two impls must read each other's containers within the bound
    with dispatch.kernel_policy("jax"):
        y_ref = codecs.decode(c)
        c_ref = codec.encode(x)
    err_a = bound_held(x, y_ref, eb, f"{field}/{name} pallas->jax")
    del y_ref
    y_cross = codecs.decode(c_ref)
    err_b = bound_held(x, y_cross, float(c_ref.header.param("eb")),
                       f"{field}/{name} jax->pallas")
    del y_cross
    log(f"crossdecode {field:9s} {name:6s} pallas->jax max_err={err_a:.6e} "
        f"jax->pallas max_err={err_b:.6e} "
        f"payload_mismatch={payload_mismatch(c, c_ref):.6e}")


def check_compressor(seed: int) -> None:
    for field, names in FIELDS:
        t0 = time.perf_counter()
        x = jnp.asarray(make_field(field, seed))
        log(f"field {field} shape={tuple(x.shape)} bytes={x.nbytes} "
            f"made_s={time.perf_counter() - t0:.3f}")
        for name in names:
            compress_pair(field, name, x)
        del x


# -- phase 3 ------------------------------------------------------------------

def check_server(seed: int) -> None:
    cfg = configs.get(ARCH)
    scfg = E.ServeConfig(s_max=SERVE["s_max"], compressed_kv=True)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        E.load_params(jax.random.PRNGKey(seed), cfg, scfg))
    nparams = sum(p.size for p in jax.tree.leaves(params))
    log(f"serve model={cfg.name} layers={cfg.n_layers} "
        f"d_model={cfg.d_model} vocab={cfg.vocab} params={nparams} "
        f"dtype={scfg.compute_dtype.__name__} "
        f"load_s={time.perf_counter() - t0:.3f}")
    rng = np.random.default_rng(seed)
    reqs = [S.Request(rid=i,
                      prompt=rng.integers(1, cfg.vocab, SERVE["prompt_len"])
                      .astype(np.int32),
                      max_new=n, arrival=0)
            for i, n in enumerate(SERVE["max_new"])]
    schedcfg = S.SchedulerConfig(max_batch=SERVE["max_batch"],
                                 pool_pages=SERVE["pool_pages"])
    t0 = time.perf_counter()
    fin, sched = S.run_continuous(params, cfg, scfg, schedcfg, reqs)
    dt = time.perf_counter() - t0
    st = sched.pool.stats()
    for r in reqs:
        got = len(fin[r.rid]["tokens"]) if r.rid in fin else 0
        log(f"request {r.rid} prompt={len(r.prompt)} tokens={got} "
            f"of {r.max_new}")
        check(got == r.max_new, f"request {r.rid} unfinished")
    log(f"serve decode_steps={sched.n_steps} "
        f"preemptions={sched.preemptions} "
        f"evicted_pages={st['evicted_pages']} "
        f"restored_pages={st['restored_pages']} "
        f"evict_codec={st['evict_codec']} "
        f"lossless_fallbacks={st['lossless_fallbacks']} "
        f"nonfinite_logits={sched.nonfinite_logits} wall_s={dt:.3f} "
        f"peak_bytes_in_use={peak_bytes()}")
    check(sched.nonfinite_logits == 0, "non-finite logits while serving")
    check(st["evicted_pages"] >= 1 and st["restored_pages"] >= 1,
          "the pool never evicted and restored a page")


# -- four chips ---------------------------------------------------------------

def check_grad_allreduce() -> None:
    check(jax.device_count() == 4,
          f"--four-chips needs 4 devices, found {jax.device_count()}")
    full = configs.get(ARCH).n_layers
    log(f"train depth cut: {TRAIN['layers']} of {full} layers (float32 "
        f"params, AdamW moments and per-pod gradients are replicated "
        f"on each 16 GB chip); widths as published")
    common = ["--arch", ARCH, "--mesh", "local",
              "--layers", str(TRAIN["layers"]),
              "--steps", str(TRAIN["steps"]), "--batch", str(TRAIN["batch"]),
              "--seq", str(TRAIN["seq"])]
    runs = {}
    for gc in ("int8", "none"):
        t0 = time.perf_counter()
        runs[gc] = launch_train.main(common + ["--grad-compress", gc])
        log(f"train grad_compress={gc} losses={runs[gc]} "
            f"wall_s={time.perf_counter() - t0:.3f}")
    a, b = np.asarray(runs["int8"]), np.asarray(runs["none"])
    check(a.shape == b.shape == (TRAIN["steps"],), "missing steps")
    check(bool(np.all(np.isfinite(a)) and np.all(np.isfinite(b))),
          "non-finite loss")
    rel = float(np.max(np.abs(a - b) / np.abs(b)))
    log(f"train int8 vs none max relative loss gap={rel:.6e} "
        f"band={TRAIN['loss_band']}")
    check(rel <= TRAIN["loss_band"], "int8 all-reduce left the loss band")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r})", file=sys.stderr)
        return 1
    log(f"compile cache: {launch_env.enable_compile_cache()}")
    if args.four_chips:
        check_grad_allreduce()
    else:
        check_dispatch()
        check_compressor(args.seed)
        check_server(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
