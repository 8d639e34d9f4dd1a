"""Chaos-tested resilience layer: fault injection (`dist.chaos`),
straggler mitigation (`dist.fault.MitigationPolicy`), container
checksums, checkpoint quarantine/rollback, and async-writer retry.

Every injected failure here is deterministic (seeded schedule), so these
are reproducible tests of the recovery paths, not flaky chaos runs."""
import glob
import json
import os
import tempfile
import time
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import codecs
from repro.dist import chaos, fault
from repro.io import checkpoint as CK
from repro.io.async_writer import AsyncWriter


# ---------------------------------------------------------------------------
# chaos config + monkey
# ---------------------------------------------------------------------------

class TestChaosSpec:
    def test_from_spec_full_grammar(self):
        cfg = chaos.from_spec(
            "straggler:host=3,delay=0.25,start=2,stop=9;"
            "writer:failures=2,kind=partial;nan:steps=7+8;corrupt:shards=1",
            seed=5, nhosts=8)
        assert cfg.straggler_host == 3 and cfg.straggler_delay_s == 0.25
        assert (cfg.straggler_start, cfg.straggler_stop) == (2, 9)
        assert cfg.writer_failures == 2 and cfg.writer_fault == "partial"
        assert cfg.nan_steps == (7, 8)
        assert cfg.corrupt_shards == 1
        assert cfg.seed == 5 and cfg.nhosts == 8

    def test_from_spec_defaults_and_unknown_group(self):
        cfg = chaos.from_spec("writer:", nhosts=2)
        assert cfg.writer_failures == 1 and cfg.writer_fault == "raise"
        with pytest.raises(ValueError, match="unknown chaos group"):
            chaos.from_spec("gremlin:count=3")

    def test_use_chaos_none_is_noop(self):
        with chaos.use_chaos(None) as monkey:
            assert monkey is None
            assert chaos.current() is None

    def test_current_tracks_context(self):
        cfg = chaos.ChaosConfig(nhosts=4)
        assert chaos.current() is None
        with chaos.use_chaos(cfg) as monkey:
            assert chaos.current() is monkey
        assert chaos.current() is None


class TestChaosMonkey:
    def test_straggler_simulation_contract(self):
        """dur[h] = compute*share*n + delay*share*n on the straggler:
        shrinking the straggler's share genuinely shrinks its duration."""
        cfg = chaos.ChaosConfig(nhosts=4, straggler_host=1,
                                straggler_delay_s=0.4)
        m = chaos.ChaosMonkey(cfg)
        durs = m.host_step_times(0, 0.1)
        np.testing.assert_allclose(durs, [0.1, 0.5, 0.1, 0.1])
        half = np.array([1.25, 0.25, 1.25, 1.25]) / 4.0
        durs2 = m.host_step_times(0, 0.1, shares=half)
        assert durs2[1] == pytest.approx((0.1 + 0.4) * 0.25 / 4 * 4)
        assert durs2[1] < durs[1]

    def test_straggler_window(self):
        cfg = chaos.ChaosConfig(nhosts=2, straggler_host=0,
                                straggler_delay_s=1.0,
                                straggler_start=3, straggler_stop=5)
        m = chaos.ChaosMonkey(cfg)
        assert [m.straggler_active(s) for s in range(6)] == \
            [False, False, False, True, True, False]

    def test_inject_step_sleeps_the_modeled_extra(self):
        cfg = chaos.ChaosConfig(nhosts=2, straggler_host=0,
                                straggler_delay_s=0.05)
        m = chaos.ChaosMonkey(cfg)
        t0 = time.perf_counter()
        total, durs = m.inject_step(0, 0.0)
        wall = time.perf_counter() - t0
        assert total == pytest.approx(float(durs.max()))
        assert wall >= 0.04                      # the sleep is real
        assert m.events and m.events[0]["kind"] == "straggler-delay"

    def test_nan_burst_schedule(self):
        m = chaos.ChaosMonkey(chaos.ChaosConfig(nan_steps=(2, 5)))
        assert [m.nan_burst(s) for s in range(6)] == \
            [False, False, True, False, False, True]
        assert sum(e["kind"] == "nan-burst" for e in m.events) == 2

    def test_pre_write_raises_exactly_n_transient_errors(self):
        m = chaos.ChaosMonkey(chaos.ChaosConfig(writer_failures=2))
        for _ in range(2):
            with pytest.raises(chaos.TransientWriteError):
                m.pre_write("/tmp/x")
        m.pre_write("/tmp/x")                    # budget exhausted
        assert isinstance(chaos.TransientWriteError("x"), OSError)

    def test_post_write_partial_truncates(self, tmp_path):
        p = str(tmp_path / "f.bin")
        with open(p, "wb") as f:
            f.write(bytes(1000))
        m = chaos.ChaosMonkey(chaos.ChaosConfig(writer_failures=1,
                                                writer_fault="partial"))
        m.post_write(p)
        assert os.path.getsize(p) == 600
        m.post_write(p)                          # budget exhausted
        assert os.path.getsize(p) == 600

    def test_post_write_corrupt_flips_one_byte(self, tmp_path):
        p = str(tmp_path / "f.bin")
        payload = bytes(range(256)) * 8
        with open(p, "wb") as f:
            f.write(payload)
        m = chaos.ChaosMonkey(chaos.ChaosConfig(corrupt_shards=1, seed=3))
        m.post_write(p)
        got = open(p, "rb").read()
        assert len(got) == len(payload)
        diff = [i for i in range(len(payload)) if got[i] != payload[i]]
        assert len(diff) == 1 and diff[0] >= len(payload) // 2
        m.post_write(p)                          # budget exhausted
        assert open(p, "rb").read() == got


# ---------------------------------------------------------------------------
# container checksums + corruption helpers
# ---------------------------------------------------------------------------

class TestContainerChecksums:
    def _packed(self, name="lossless"):
        codec = codecs.get(name)
        x = jnp.asarray(np.linspace(-2, 7, 96, dtype=np.float32)
                        .reshape(3, 32))
        return codec, codec.pack(codec.encode(x))

    def test_pack_stamps_and_verifies(self):
        _, c = self._packed()
        assert c.header.param("checksum") is not None
        assert codecs.verify_container(c)
        codecs.check_container(c)                # no raise

    def test_corrupt_container_always_detected(self):
        codec, c = self._packed()
        bad = chaos.corrupt_container(c, seed=11)
        assert not codecs.verify_container(bad)
        with pytest.raises(codecs.ChecksumError, match="checksum"):
            codecs.check_container(bad)
        with pytest.raises(codecs.ChecksumError):
            codecs.decode(bad, verify=True)

    def test_unpack_drops_checksum_from_device_header(self):
        """The checksum covers stored bytes only: the unpacked (device)
        header — a jit cache key — must not vary with it."""
        codec, c = self._packed("cusz")
        u = codec.unpack(c)
        assert u.header.param("checksum", None) is None

    def test_unchecksummed_container_passes(self):
        codec = codecs.get("lossless")
        c = codec.encode(jnp.ones((4, 4)))       # device form: no checksum
        assert codecs.verify_container(c)
        codecs.check_container(c)


# ---------------------------------------------------------------------------
# straggler detection fixes (satellite: reset/decay)
# ---------------------------------------------------------------------------

class TestStragglerDetectorPerHost:
    def test_penalty_decays_on_clean_steps(self):
        d = fault.StragglerDetector(warmup=2, penalty_decay=0.5)
        for s in range(2):
            d.observe(s, 0.1)
        assert d.observe(2, 1.0)                 # flagged
        assert d.penalty == 1.0
        d.observe(3, 0.1)
        d.observe(4, 0.1)
        assert d.penalty == pytest.approx(0.25)  # decayed, not cumulative
        assert d.n_flagged == 1                  # telemetry stays monotone

    def test_reset_host_clears_only_that_host(self):
        d = fault.StragglerDetector(warmup=1)
        d.observe(0, 0.1, host=0)
        d.observe(0, 0.1, host=1)
        d.observe(1, 1.0, host=0)
        assert d.host(0).n_flagged == 1
        d.reset(host=0)
        assert d.host(0).n_observed == 0         # fresh child
        assert d.host(1).n_observed == 1         # untouched

    def test_reset_all_clears_children(self):
        d = fault.StragglerDetector(warmup=1)
        d.observe(0, 0.1, host=3)
        d.reset()
        assert d._hosts == {} and d.n_observed == 0


# ---------------------------------------------------------------------------
# mitigation policy
# ---------------------------------------------------------------------------

def _drive(policy, monkey, steps, compute=0.1, start=0):
    """Feed modeled per-host durations (no real sleeping) and return the
    per-step cluster step time ratio vs the fault-free compute."""
    ratios = []
    for s in range(start, start + steps):
        durs = monkey.host_step_times(s, compute, policy.shares)
        policy.observe(s, durs)
        ratios.append(float(np.max(durs)) / compute)
    return ratios


class TestMitigationPolicy:
    def test_rebalance_recovers_step_time(self):
        """Acceptance: a 5x straggler is rebalanced to within ~1.2x of
        the fault-free step time, and stays there (no limit cycle)."""
        monkey = chaos.ChaosMonkey(chaos.ChaosConfig(
            nhosts=8, straggler_host=3, straggler_delay_s=0.4))
        policy = fault.MitigationPolicy(8)
        ratios = _drive(policy, monkey, 12)
        assert ratios[0] == pytest.approx(5.0)   # fault is real pre-mitigation
        assert max(ratios[-4:]) <= 1.25, ratios
        assert any(e["kind"] == "rebalance" for e in policy.events)
        assert not policy.excluded
        # shares stay a simplex and the straggler genuinely lost work
        assert policy.shares.sum() == pytest.approx(1.0)
        assert policy.shares[3] < 1.0 / 8

    def test_slow_since_step0_is_caught(self):
        """The relative (cross-host median) flag: a host slow from its
        very first step has a poisoned self-baseline and can only be
        caught by comparison against its peers."""
        monkey = chaos.ChaosMonkey(chaos.ChaosConfig(
            nhosts=4, straggler_host=0, straggler_delay_s=0.5))
        policy = fault.MitigationPolicy(4)
        ratios = _drive(policy, monkey, 10)
        # capacity floor: 3 healthy hosts carry ~4/3 of uniform work, so
        # ~1.38x is the best possible here — assert we converge onto it
        # from the 6x fault, not the 1.2x an 8-host cluster can reach
        assert ratios[0] == pytest.approx(6.0)
        assert ratios[-1] <= 1.45, ratios

    def test_persistent_straggler_excluded(self):
        """A host so slow that rebalancing bottoms out at min_share gets
        excluded outright (share 0), and the cluster recovers fully."""
        monkey = chaos.ChaosMonkey(chaos.ChaosConfig(
            nhosts=4, straggler_host=2, straggler_delay_s=50.0))
        policy = fault.MitigationPolicy(4)
        ratios = _drive(policy, monkey, 20)
        assert 2 in policy.excluded
        assert policy.shares[2] == 0.0
        assert any(e["kind"] == "exclude-host" for e in policy.events)
        # remaining hosts take over: modeled time back to ~uniform work
        assert ratios[-1] <= 1.4, ratios

    def test_shares_restore_after_straggler_heals(self):
        monkey = chaos.ChaosMonkey(chaos.ChaosConfig(
            nhosts=8, straggler_host=3, straggler_delay_s=0.4,
            straggler_stop=12))
        policy = fault.MitigationPolicy(8)
        _drive(policy, monkey, 12)
        assert policy.shares[3] < 1.0 / 8        # mitigated while faulty
        _drive(policy, monkey, 25, start=12)     # healed: delay off
        np.testing.assert_allclose(policy.shares, 1.0 / 8)  # exact uniform
        assert any(e["kind"] == "host-recovered" for e in policy.events)

    def test_on_bad_loss_skips_and_logs(self):
        policy = fault.MitigationPolicy(2)
        assert not policy.on_bad_loss(0, 1.25)
        assert policy.on_bad_loss(1, float("nan"))
        assert policy.on_bad_loss(2, float("inf"))
        assert policy.n_skipped == 2
        skips = [e for e in policy.events if e["kind"] == "skip-step"]
        assert [e["step"] for e in skips] == [1, 2]

    def test_operator_reset_readmits_excluded_host(self):
        monkey = chaos.ChaosMonkey(chaos.ChaosConfig(
            nhosts=4, straggler_host=1, straggler_delay_s=50.0))
        policy = fault.MitigationPolicy(4)
        _drive(policy, monkey, 20)
        assert 1 in policy.excluded
        policy.reset(1)
        assert 1 not in policy.excluded
        assert policy.shares[1] > 0
        assert policy.shares.sum() == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# checkpoint quarantine + rollback
# ---------------------------------------------------------------------------

def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(np.cumsum(rng.standard_normal((32, 64)),
                                       axis=-1).astype(np.float32)),
            "step": jnp.asarray(np.int32(seed))}


def _save_steps(d, steps, nshards=2):
    for s in steps:
        CK.save_checkpoint(d, s, _tree(seed=s),
                           policy=CK.CheckpointPolicy(codec="lossless"),
                           nshards=nshards)


def _shards(d, step):
    return sorted(glob.glob(os.path.join(
        d, f"step_{step:08d}", "shard_*.npz")))


class TestCheckpointQuarantine:
    def test_corrupted_latest_falls_back_to_last_good(self):
        with tempfile.TemporaryDirectory() as d:
            _save_steps(d, [10, 20, 30])
            chaos.corrupt_file(_shards(d, 30)[0])
            restored, step = CK.load_checkpoint(d, _tree())
            assert step == 20
            np.testing.assert_array_equal(np.asarray(restored["step"]), 20)
            # structured quarantine report rides in the restore stats
            reports = CK.LAST_RESTORE_STATS["quarantine"]
            assert len(reports) == 1 and reports[0]["step"] == 30
            assert reports[0]["error_type"]
            # the bad step is marked on disk and skipped from now on
            assert os.path.exists(os.path.join(
                d, "step_00000030", CK._QUARANTINE_MARK))
            assert CK.available_steps(d) == [10, 20]
            assert CK.latest_step(d) == 20

    def test_two_corrupt_steps_fall_back_twice(self):
        with tempfile.TemporaryDirectory() as d:
            _save_steps(d, [10, 20, 30])
            chaos.corrupt_file(_shards(d, 30)[0])
            chaos.corrupt_file(_shards(d, 20)[1], seed=1)
            _, step = CK.load_checkpoint(d, _tree())
            assert step == 10
            assert [r["step"] for r in
                    CK.LAST_RESTORE_STATS["quarantine"]] == [30, 20]

    def test_quarantine_false_raises_immediately(self):
        with tempfile.TemporaryDirectory() as d:
            _save_steps(d, [10, 20])
            chaos.corrupt_file(_shards(d, 20)[0])
            with pytest.raises(CK.CheckpointCorruptionError) as ei:
                CK.load_checkpoint(d, _tree(), quarantine=False)
            assert ei.value.reports[0]["step"] == 20
            # nothing was marked: the operator opted out of fallback
            assert CK.available_steps(d) == [10, 20]

    def test_all_steps_corrupt_raises_with_full_report(self):
        with tempfile.TemporaryDirectory() as d:
            _save_steps(d, [10, 20])
            chaos.corrupt_file(_shards(d, 10)[0])
            chaos.corrupt_file(_shards(d, 20)[0], seed=1)
            with pytest.raises(CK.CheckpointCorruptionError) as ei:
                CK.load_checkpoint(d, _tree())
            assert sorted(r["step"] for r in ei.value.reports) == [10, 20]

    def test_explicit_step_falls_back_below_it(self):
        with tempfile.TemporaryDirectory() as d:
            _save_steps(d, [10, 20, 30])
            chaos.corrupt_file(_shards(d, 20)[0])
            _, step = CK.load_checkpoint(d, _tree(), step=20)
            assert step == 10                    # never forward to 30

    def test_format_gate_errors_still_propagate(self):
        """A wrong-format manifest is an operator error, not corruption:
        it must raise the actionable ValueError, not quarantine."""
        with tempfile.TemporaryDirectory() as d:
            sd = os.path.join(d, "step_00000000")
            os.makedirs(sd)
            with open(os.path.join(sd, "manifest.json"), "w") as f:
                json.dump({"step": 0, "format": 1, "tensors": {}}, f)
            with pytest.raises(ValueError, match="predates"):
                CK.load_checkpoint(d, {})


class TestWriterChaos:
    def test_transient_write_fault_retried_to_success(self):
        """chaos 'raise' faults are OSError-classed, so the AsyncWriter
        retry loop absorbs them and the checkpoint still lands."""
        cfg = chaos.ChaosConfig(writer_failures=1)
        with tempfile.TemporaryDirectory() as d, chaos.use_chaos(cfg):
            with AsyncWriter(max_pending=1, retries=2,
                             backoff_s=0.001) as w:
                CK.save_checkpoint(d, 0, _tree(), writer=w)
                w.wait()
                assert w.n_retries == 1
            restored, step = CK.load_checkpoint(d, _tree())
            assert step == 0

    def test_transient_fault_without_retries_surfaces(self):
        cfg = chaos.ChaosConfig(writer_failures=1)
        with tempfile.TemporaryDirectory() as d, chaos.use_chaos(cfg):
            w = AsyncWriter(max_pending=1, retries=0)
            CK.save_checkpoint(d, 0, _tree(), writer=w)
            with pytest.raises(chaos.TransientWriteError):
                w.wait()
            w.close()
            assert CK.latest_step(d) is None     # tmp dir never promoted

    def test_partial_write_quarantined_at_restore(self):
        """A silently-truncated shard passes the save, then trips the
        integrity check at restore and falls back to the prior step."""
        with tempfile.TemporaryDirectory() as d:
            _save_steps(d, [10], nshards=2)
            cfg = chaos.ChaosConfig(writer_failures=1,
                                    writer_fault="partial")
            with chaos.use_chaos(cfg) as monkey:
                _save_steps(d, [20], nshards=2)
                assert any(e["kind"] == "partial-write"
                           for e in monkey.events)
            assert CK.latest_step(d) == 20       # damage is silent...
            _, step = CK.load_checkpoint(d, _tree())
            assert step == 10                    # ...until restore catches it
            assert CK.LAST_RESTORE_STATS["quarantine"][0]["step"] == 20


# ---------------------------------------------------------------------------
# async writer: retry/backoff, wait(timeout), close-time error surfacing
# ---------------------------------------------------------------------------

class TestAsyncWriterResilience:
    def test_close_reraises_error_from_final_task(self):
        """Regression: an error landing after the last submit/wait used
        to be swallowed by close() — the lost-checkpoint bug."""
        w = AsyncWriter()
        w.submit(lambda: (_ for _ in ()).throw(IOError("last write died")))
        with pytest.raises(IOError, match="last write died"):
            w.close()

    def test_retries_transient_then_succeeds(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient")

        with AsyncWriter(retries=3, backoff_s=0.001) as w:
            w.submit(flaky)
            w.wait()
        assert calls["n"] == 3
        assert w.n_retries == 2

    def test_retry_budget_exhausted_surfaces_error(self):
        w = AsyncWriter(retries=1, backoff_s=0.001)
        w.submit(lambda: (_ for _ in ()).throw(OSError("always")))
        with pytest.raises(OSError, match="always"):
            w.wait()
        assert w.n_retries == 1
        w.close()

    def test_non_retryable_errors_never_retry(self):
        calls = {"n": 0}

        def bug():
            calls["n"] += 1
            raise ValueError("a bug, not a flaky disk")

        w = AsyncWriter(retries=5, backoff_s=0.001)
        w.submit(bug)
        with pytest.raises(ValueError):
            w.wait()
        assert calls["n"] == 1 and w.n_retries == 0
        w.close()

    def test_custom_retryable_predicate(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 2:
                raise KeyError("weird but declared retryable")

        with AsyncWriter(retries=2, backoff_s=0.001,
                         retryable=lambda e: isinstance(e, KeyError)) as w:
            w.submit(flaky)
            w.wait()
        assert calls["n"] == 2

    def test_wait_timeout(self):
        import threading
        release = threading.Event()
        w = AsyncWriter()
        w.submit(release.wait)
        with pytest.raises(TimeoutError, match="still pending"):
            w.wait(timeout=0.05)
        release.set()
        w.wait(timeout=5)                        # drains fine afterwards
        w.close()

    def test_exit_with_body_exception_warns_about_masked_error(self):
        with pytest.raises(RuntimeError, match="body failed"), \
                warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            with AsyncWriter() as w:
                w.submit(lambda: (_ for _ in ()).throw(IOError("w died")))
                w._q.join()                      # let the failure land
                raise RuntimeError("body failed")
        assert any("masked" in str(c.message) for c in caught)
        assert isinstance(w.pending_error, IOError)


# ---------------------------------------------------------------------------
# serve-path graceful degradation: unrepresentable slab ships lossless
# ---------------------------------------------------------------------------

class TestWireFallback:
    def test_cusz_overflow_slab_falls_back_to_lossless(self):
        from repro.core import kvcache as KVC
        rng = np.random.default_rng(0)
        # spiky data + tiny outlier budget: cusz cannot represent it
        x = jnp.asarray((rng.standard_normal((2, 256, 8))
                         * (1 + 100 * (rng.random((2, 256, 8)) > 0.99)))
                        .astype(np.float32))
        parts = KVC.kv_wire_encode(
            x, 1, wire="cusz", source_dtype=jnp.float32,
            wire_cfg={"eb": 1e-4, "outlier_frac": 0.001, "nbins": 16})
        names = {p.header.codec for p in parts}
        assert "lossless" in names, names
        back = KVC.kv_wire_restore(parts, 1, dtype=jnp.float32)
        lossless = [i for i, p in enumerate(parts)
                    if p.header.codec == "lossless"]
        if len(lossless) == len(parts):
            np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
        else:                                    # mixed: lossless slabs exact
            step = x.shape[1] // len(parts)
            i = lossless[0]
            np.testing.assert_array_equal(
                np.asarray(back[:, i * step:(i + 1) * step]),
                np.asarray(x[:, i * step:(i + 1) * step]))

    def test_healthy_slab_still_ships_compressed(self):
        from repro.core import kvcache as KVC
        rng = np.random.default_rng(1)
        x = jnp.asarray(np.cumsum(rng.standard_normal((2, 256, 8)), axis=1)
                        .astype(np.float32) / 50)
        parts = KVC.kv_wire_encode(
            x, 1, wire="cusz", source_dtype=jnp.float32,
            wire_cfg={"eb": 1e-3, "outlier_frac": 1.0})
        assert {p.header.codec for p in parts} == {"cusz"}


# ---------------------------------------------------------------------------
# launch.env: the shared runtime setup every entrypoint and CI job uses
# ---------------------------------------------------------------------------

class TestLaunchEnv:
    def test_env_overrides_is_pure_and_merges(self):
        from repro.launch import env as E
        base = {"XLA_FLAGS": "--xla_dump_to=/tmp/d "
                             "--xla_force_host_platform_device_count=2"}
        ov = E.env_overrides(E.RuntimeConfig(host_device_count=8,
                                             nan_debug=True,
                                             preallocate=False),
                             base_env=base)
        flags = ov["XLA_FLAGS"].split()
        # unmanaged flags survive; the managed one is replaced, not duped
        assert "--xla_dump_to=/tmp/d" in flags
        assert flags.count("--xla_force_host_platform_device_count=8") == 1
        assert "--xla_force_host_platform_device_count=2" not in flags
        assert ov["JAX_DEBUG_NANS"] == "1"
        assert ov["XLA_PYTHON_CLIENT_PREALLOCATE"] == "false"
        assert base["XLA_FLAGS"].startswith("--xla_dump_to")  # untouched

    def test_removed_async_flags_scrubbed_never_emitted(self):
        """XLA aborts the whole process on unknown flags, and the
        per-op --xla_gpu_enable_async_* family was removed upstream:
        setup must scrub stale copies and never emit its own."""
        from repro.launch import env as E
        base = {"XLA_FLAGS": "--xla_gpu_enable_async_all_gather=true"}
        ov = E.env_overrides(E.RuntimeConfig(), base_env=base)
        assert "async_all_gather" not in ov["XLA_FLAGS"]
        assert "--xla_gpu_enable_latency_hiding_scheduler=true" \
            in ov["XLA_FLAGS"].split()

    def test_no_change_yields_empty_override(self):
        from repro.launch import env as E
        cfg = E.RuntimeConfig(async_collectives=False)
        ov = E.env_overrides(cfg, base_env={"XLA_FLAGS": ""})
        assert ov == {}

    def test_compile_cache_dir_prefers_the_environment(self):
        from repro.launch import env as E
        assert E.compile_cache_dir({E.CACHE_ENV: "/x/cache"}) == "/x/cache"
        assert E.compile_cache_dir({}) == E.CHECKOUT_CACHE
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert E.CHECKOUT_CACHE == os.path.join(root, ".jax_cache")

    def test_enable_compile_cache_sets_no_dir_when_env_names_one(
            self, monkeypatch):
        from repro.launch import env as E
        prev = jax.config.jax_compilation_cache_dir
        try:
            jax.config.update("jax_compilation_cache_dir", None)
            monkeypatch.setenv(E.CACHE_ENV, "/from/env")
            assert E.enable_compile_cache() == "/from/env"
            assert jax.config.jax_compilation_cache_dir is None
            monkeypatch.delenv(E.CACHE_ENV)
            assert E.enable_compile_cache() == E.CHECKOUT_CACHE
            assert jax.config.jax_compilation_cache_dir == E.CHECKOUT_CACHE
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)

    def test_from_args_round_trip(self):
        import argparse
        from repro.launch import env as E
        ap = argparse.ArgumentParser()
        E.add_arguments(ap)
        cfg = E.from_args(ap.parse_args(
            ["--host-devices", "8", "--nan-debug",
             "--no-async-collectives"]))
        assert cfg == E.RuntimeConfig(host_device_count=8, nan_debug=True,
                                      async_collectives=False)


# ---------------------------------------------------------------------------
# trainer integration: chaos armed end-to-end (small model, few steps)
# ---------------------------------------------------------------------------

class TestTrainerUnderChaos:
    def test_nan_burst_skipped_and_mitigation_wired(self):
        from repro import configs
        from repro.train.trainer import LoopConfig, Trainer
        from repro.train.train_step import TrainConfig

        cfg = configs.reduced("qwen2.5-3b", n_periods=1)
        policy = fault.MitigationPolicy(4)
        lcfg = LoopConfig(steps=6, batch=2, seq=16, mitigation=policy,
                          log_every=100)
        ccfg = chaos.ChaosConfig(nhosts=4, nan_steps=(3,),
                                 straggler_host=1, straggler_delay_s=0.01)
        with chaos.use_chaos(ccfg):
            hist = Trainer(cfg, TrainConfig(), lcfg).run()
        steps = [h["step"] for h in hist]
        assert 3 not in steps and len(steps) == 5   # NaN step skipped
        assert policy.n_skipped == 1
        # the straggler sim fed the policy real per-host durations
        assert policy.detector.host(1).n_observed > 0
