"""Train-step semantics + serving engine tests."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.models import model as M
from repro.optim import adamw
from repro.serve.engine import (ServeConfig, generate, load_params,
                                prefill, make_serve_step)
from repro.train.train_step import TrainConfig, make_train_step, loss_fn, \
    _microbatched_grads


class TestTrainStep:
    def test_microbatched_grads_match_full(self):
        cfg = configs.reduced("qwen2.5-3b", n_periods=1)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)).astype(np.int32))
        t1 = TrainConfig(microbatches=1)
        t4 = TrainConfig(microbatches=4)
        l1, g1 = _microbatched_grads(params, cfg, t1, toks, None)
        l4, g4 = _microbatched_grads(params, cfg, t4, toks, None)
        assert abs(float(l1) - float(l4)) < 1e-4   # both return the mean
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-2, atol=2e-4)

    def test_step_reduces_loss(self):
        cfg = configs.reduced("qwen3-4b", n_periods=1)
        params = M.init_params(jax.random.PRNGKey(1), cfg)
        tcfg = TrainConfig(adamw=adamw.AdamWConfig(lr=5e-3))
        opt = adamw.init(params, tcfg.adamw)
        step = jax.jit(make_train_step(cfg, tcfg))
        rng = np.random.default_rng(1)
        toks = jnp.asarray(rng.integers(0, 64, (8, 64)).astype(np.int32))
        losses = []
        for _ in range(8):
            loss, params, opt = step(params, opt, toks)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_podded_layout_no_compress_flattens(self):
        cfg = configs.reduced("qwen2.5-3b", n_periods=1)
        params = M.init_params(jax.random.PRNGKey(2), cfg)
        tcfg = TrainConfig(grad_compress="none", npods=2)
        opt = adamw.init(params, tcfg.adamw)
        step = jax.jit(make_train_step(cfg, tcfg))
        toks = jnp.zeros((2, 4, 32), jnp.int32)       # podded layout
        loss, params, opt = step(params, opt, toks)
        assert np.isfinite(float(loss))


class TestServe:
    def test_generate_greedy_deterministic(self):
        cfg = configs.reduced("qwen2.5-3b", n_periods=1)
        params = M.init_params(jax.random.PRNGKey(3), cfg)
        prompt = jnp.zeros((2, 8), jnp.int32)
        scfg = ServeConfig(s_max=64)
        a = np.asarray(generate(params, cfg, prompt, 8, scfg))
        b = np.asarray(generate(params, cfg, prompt, 8, scfg))
        np.testing.assert_array_equal(a, b)

    def test_prefill_then_decode_matches_forward(self):
        """prefill caches + one decode step == teacher-forced logits."""
        cfg = configs.reduced("qwen2.5-3b", n_periods=1)
        params = M.init_params(jax.random.PRNGKey(4), cfg)
        rng = np.random.default_rng(4)
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 9)).astype(np.int32))
        scfg = ServeConfig(s_max=32, compute_dtype=jnp.float32)
        last, caches, plen = prefill(params, cfg, toks[:, :8], scfg)
        step = make_serve_step(cfg, ServeConfig(s_max=32,
                                                compute_dtype=jnp.float32))
        lg, _ = step(params, toks[:, 8:9], caches, jnp.int32(8))
        full, _ = M.forward(params, cfg, toks, compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, -1]), rtol=2e-3,
                                   atol=2e-3)
        np.testing.assert_allclose(np.asarray(last),
                                   np.asarray(full[:, 7]), rtol=2e-3,
                                   atol=2e-3)

    def test_mamba_generate(self):
        cfg = configs.reduced("mamba2-1.3b", n_periods=2)
        params = M.init_params(jax.random.PRNGKey(5), cfg)
        prompt = jnp.zeros((2, 8), jnp.int32)
        toks = generate(params, cfg, prompt, 6, ServeConfig(s_max=32))
        assert toks.shape == (2, 6)

    def test_compressed_kv_serving(self):
        cfg = configs.reduced("qwen3-4b", n_periods=1)
        params = M.init_params(jax.random.PRNGKey(6), cfg)
        prompt = jnp.zeros((2, 8), jnp.int32)
        a = np.asarray(generate(params, cfg, prompt, 8,
                                ServeConfig(s_max=128)))
        b = np.asarray(generate(params, cfg, prompt, 8,
                                ServeConfig(s_max=128, compressed_kv=True)))
        assert (a == b).mean() > 0.6          # greedy mostly agrees


class TestEntrypoints:
    def test_serving_params_stored_at_compute_dtype(self):
        cfg = configs.reduced("qwen2.5-3b")
        scfg = ServeConfig(compute_dtype=jnp.bfloat16)
        got = load_params(jax.random.PRNGKey(3), cfg, scfg)
        want = M.init_params(jax.random.PRNGKey(3), cfg)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w.astype(jnp.bfloat16)))

    def test_train_launcher_returns_step_losses(self, monkeypatch):
        from repro.launch import env as launch_env
        from repro.launch import train as launch_train
        # keep this process's compiles out of the checkout's cache
        monkeypatch.setattr(launch_env, "enable_compile_cache",
                            lambda: launch_env.CHECKOUT_CACHE)
        losses = launch_train.main(
            ["--arch", "qwen2.5-3b", "--reduced", "--layers", "2",
             "--steps", "2", "--batch", "2", "--seq", "16"])
        assert len(losses) == 2 and np.all(np.isfinite(losses))

    def test_local_mesh_follows_device_count(self):
        from repro.launch.mesh import make_local_mesh
        n = jax.device_count()
        if n % 2:
            with pytest.raises(ValueError, match="do not split"):
                make_local_mesh()
        else:
            assert dict(make_local_mesh().shape) == {
                "pod": 2, "data": n // 2, "model": 1}
