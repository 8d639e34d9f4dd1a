"""Continuous-batching scheduler: compile-once guarantee, token
identity against the engine, preemption-by-eviction, and the
continuous-vs-static decode-step win.

All runs use compute_dtype=float32 so greedy token streams are exactly
reproducible across the engine path (whole-batch decode), the vmapped
per-slot batch step, and preempt/resume cycles.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as M
from repro.serve import engine as E
from repro.serve import scheduler as S

ARCH = "qwen2.5-3b"


@pytest.fixture(scope="module")
def setup():
    cfg = configs.reduced(ARCH, n_periods=1)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    scfg = E.ServeConfig(s_max=256, compressed_kv=True,
                         compute_dtype=jnp.float32)
    return cfg, params, scfg


def _requests(n, rng, plen_lo=5, plen_hi=14, new_lo=3, new_hi=7,
              arrivals=None):
    return [S.Request(
        rid=i,
        prompt=rng.integers(1, 100,
                            size=int(rng.integers(plen_lo, plen_hi))
                            ).astype(np.int32),
        max_new=int(rng.integers(new_lo, new_hi)),
        arrival=0 if arrivals is None else arrivals[i]) for i in range(n)]


class TestCompileOnce:
    def test_batch_step_compiles_exactly_once_across_churn(
            self, setup, recompile_guard):
        """Admission, retirement and ragged per-slot positions churn the
        batch composition every few steps; the vmapped step must stay
        one executable (buffer writes, never shape changes)."""
        cfg, params, _ = setup
        # distinct s_max: a fresh (cfg, scfg, max_batch) jit-cache key
        scfg = E.ServeConfig(s_max=384, compressed_kv=True,
                             compute_dtype=jnp.float32)
        key = (cfg, scfg, 2)
        S.BATCH_STEP_TRACES.pop(key, None)
        S.get_batch_step.cache_clear()
        rng = np.random.default_rng(0)
        reqs = _requests(5, rng, arrivals=[0, 0, 1, 3, 4])
        schedcfg = S.SchedulerConfig(max_batch=2, pool_pages=12)
        with recompile_guard(max_compiles=1,
                             match=r"^batch_step$") as log:
            fin, sched = S.run_continuous(params, cfg, scfg, schedcfg,
                                          reqs)
        assert log.compiles == ["batch_step"]
        assert S.BATCH_STEP_TRACES[key] == 1
        assert len(fin) == 5
        # second run at the same config: zero additional compiles
        with recompile_guard(max_compiles=0,
                             match=r"^batch_step$"):
            S.run_continuous(params, cfg, scfg, schedcfg, reqs)
        assert S.BATCH_STEP_TRACES[key] == 1


class TestTokenIdentity:
    def test_single_request_matches_engine_generate(self, setup):
        cfg, params, scfg = setup
        rng = np.random.default_rng(1)
        prompt = rng.integers(1, 100, size=9).astype(np.int32)
        n_new = 5
        ref = np.asarray(E.generate(
            params, cfg, jnp.asarray(prompt)[None, :], n_new,
            scfg))[0].tolist()
        fin, _ = S.run_continuous(
            params, cfg, scfg,
            S.SchedulerConfig(max_batch=2, pool_pages=8),
            [S.Request(rid=0, prompt=prompt, max_new=n_new)])
        assert fin[0]["tokens"] == ref

    def test_continuous_equals_static_tokens(self, setup):
        cfg, params, scfg = setup
        rng = np.random.default_rng(2)
        reqs = _requests(4, rng, arrivals=[0, 0, 2, 3])
        schedcfg = S.SchedulerConfig(max_batch=2, pool_pages=12)
        fin_c, _ = S.run_continuous(params, cfg, scfg, schedcfg, reqs)
        fin_s, _ = S.run_static(params, cfg, scfg, schedcfg, reqs)
        assert fin_c.keys() == fin_s.keys()
        for rid in fin_c:
            assert fin_c[rid]["tokens"] == fin_s[rid]["tokens"], rid

    def test_hybrid_arch_state_sidecar(self, setup):
        """Jamba-style hybrid: the Mamba recurrent state (no seq axis)
        rides the per-sequence sidecar, not the pool; tokens must still
        match the engine exactly through admit -> decode -> retire."""
        cfg = configs.reduced("jamba-1.5-large-398b", n_periods=1)
        params = M.init_params(jax.random.PRNGKey(1), cfg)
        scfg = E.ServeConfig(s_max=256, compressed_kv=True,
                             compute_dtype=jnp.float32)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 100, size=n).astype(np.int32)
                   for n in (6, 9)]
        refs = [np.asarray(E.generate(
            params, cfg, jnp.asarray(p)[None, :], 3, scfg))[0].tolist()
            for p in prompts]
        fin, _ = S.run_continuous(
            params, cfg, scfg,
            S.SchedulerConfig(max_batch=2, pool_pages=8),
            [S.Request(rid=i, prompt=p, max_new=3)
             for i, p in enumerate(prompts)])
        for i, ref in enumerate(refs):
            assert fin[i]["tokens"] == ref, i


class TestPreemption:
    def test_tiny_pool_preempts_and_stays_token_identical(self, setup):
        """3 live sequences on a 2-page pool: someone must be preempted
        (flush -> evict -> requeue -> restore) and, with the bit-exact
        int8-block eviction codec, every token stream must equal the
        unconstrained-pool run."""
        cfg, params, scfg = setup
        rng = np.random.default_rng(4)
        reqs = _requests(3, rng, plen_lo=6, plen_hi=12, new_lo=5,
                         new_hi=8)
        tiny = S.SchedulerConfig(max_batch=3, pool_pages=2,
                                 evict_codec="int8-block")
        fin, sched = S.run_continuous(params, cfg, scfg, tiny, reqs)
        assert sched.preemptions > 0
        assert sched.pool.stats()["evicted_pages"] > 0
        assert sched.pool.stats()["restored_pages"] > 0
        big = S.SchedulerConfig(max_batch=3, pool_pages=16,
                                evict_codec="int8-block")
        fin_big, sched_big = S.run_continuous(params, cfg, scfg, big,
                                              reqs)
        assert sched_big.preemptions == 0
        for rid in fin:
            assert fin[rid]["tokens"] == fin_big[rid]["tokens"], rid

    def test_pool_too_small_raises(self, setup):
        cfg, params, scfg = setup
        rng = np.random.default_rng(5)
        # prompt needs 2 pages (>SEQ_BLOCK tokens) but the pool has 1
        prompt = rng.integers(1, 100, size=150).astype(np.int32)
        with pytest.raises(RuntimeError, match="pool too small"):
            S.run_continuous(
                params, cfg, scfg,
                S.SchedulerConfig(max_batch=1, pool_pages=1,
                                  preempt=False),
                [S.Request(rid=0, prompt=prompt, max_new=2)])


class TestContinuousBeatsStatic:
    def test_fewer_decode_steps_than_wave_admission(self, setup):
        """Mixed generation lengths: wave admission holds finished slots
        hostage until the slowest member retires; continuous refills
        them.  Same tokens out, strictly fewer decode steps."""
        cfg, params, scfg = setup
        rng = np.random.default_rng(6)
        reqs = [S.Request(rid=0, prompt=rng.integers(1, 100, size=8)
                          .astype(np.int32), max_new=8),
                S.Request(rid=1, prompt=rng.integers(1, 100, size=6)
                          .astype(np.int32), max_new=2),
                S.Request(rid=2, prompt=rng.integers(1, 100, size=7)
                          .astype(np.int32), max_new=2),
                S.Request(rid=3, prompt=rng.integers(1, 100, size=9)
                          .astype(np.int32), max_new=2)]
        schedcfg = S.SchedulerConfig(max_batch=2, pool_pages=12)
        fin_c, sc = S.run_continuous(params, cfg, scfg, schedcfg, reqs)
        fin_s, ss = S.run_static(params, cfg, scfg, schedcfg, reqs)
        assert sum(len(f["tokens"]) for f in fin_c.values()) == \
            sum(len(f["tokens"]) for f in fin_s.values())
        assert sc.n_steps < ss.n_steps, (sc.n_steps, ss.n_steps)


class TestLogitHealth:
    def test_finite_run_counts_nothing(self, setup):
        cfg, params, scfg = setup
        fin, sched = S.run_continuous(
            params, cfg, scfg, S.SchedulerConfig(max_batch=2, pool_pages=8),
            _requests(3, np.random.default_rng(7)))
        assert len(fin) == 3 and sched.nonfinite_logits == 0

    def test_nan_weights_are_counted_per_prefill_and_slot_step(self, setup):
        cfg, params, scfg = setup
        bad = dict(params, out_norm=jnp.full_like(params["out_norm"],
                                                  jnp.nan))
        req = S.Request(rid=0, prompt=np.arange(1, 6, dtype=np.int32),
                        max_new=3)
        _, sched = S.run_continuous(
            bad, cfg, scfg, S.SchedulerConfig(max_batch=2, pool_pages=8),
            [req])
        # one prefill + one decode step per generated token after it
        assert sched.nonfinite_logits == 1 + sched.n_steps


class TestLifecycleAccounting:
    def test_pool_drains_and_eos_retires(self, setup):
        cfg, params, scfg = setup
        rng = np.random.default_rng(7)
        reqs = _requests(3, rng)
        fin, sched = S.run_continuous(
            params, cfg, scfg,
            S.SchedulerConfig(max_batch=2, pool_pages=8), reqs)
        assert sched.pool.used_pages == 0          # everything released
        assert sched.pool.stats()["sequences"] == 0
        assert not sched.states and not sched._suspended
        for r in reqs:
            assert len(fin[r.rid]["tokens"]) == r.max_new

    def test_eos_cuts_generation_short(self, setup):
        cfg, params, scfg = setup
        rng = np.random.default_rng(8)
        prompt = rng.integers(1, 100, size=8).astype(np.int32)
        ref = np.asarray(E.generate(
            params, cfg, jnp.asarray(prompt)[None, :], 6,
            scfg))[0].tolist()
        eos = ref[2]                    # force EOS at the 3rd token
        fin, _ = S.run_continuous(
            params, cfg, scfg,
            S.SchedulerConfig(max_batch=1, pool_pages=8, eos_id=eos),
            [S.Request(rid=0, prompt=prompt, max_new=6)])
        assert fin[0]["tokens"] == ref[:3]

    def test_requires_compressed_kv(self, setup):
        cfg, params, _ = setup
        with pytest.raises(ValueError, match="compressed_kv"):
            S.ContinuousScheduler(
                params, cfg,
                E.ServeConfig(s_max=256, compressed_kv=False),
                S.SchedulerConfig())
