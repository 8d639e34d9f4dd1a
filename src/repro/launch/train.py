"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
        --reduced --steps 20 --batch 8 --seq 128

On the production mesh (--mesh single|multi) the same script shards
params/optimizer/batch per repro.dist.sharding and runs the jitted step;
--reduced + --mesh host runs a real loop on one device, and --mesh local
splits every local device into two pods (four chips: the compressed
cross-pod gradient all-reduce on one host).  --layers keeps the published
widths and cuts the depth.  --lower-only stops after compile (the
dry-run path with real shapes)."""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.data import pipeline
from repro.dist import chaos, fault
from repro.dist import sharding as SH
from repro.dist.context import use_mesh, use_param_specs
from repro.io import checkpoint as ckpt_io
from repro.launch import env as launch_env
from repro.launch.mesh import (make_host_mesh, make_local_mesh,
                               make_production_mesh)
from repro.models import model as M
from repro.optim import adamw
from repro.train.train_step import TrainConfig, make_train_step


def main(argv=None):
    """Run the launcher on `argv` (default: the command line); returns
    the loss of every step that ran (empty under --lower-only)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N layers, widths unchanged")
    ap.add_argument("--mesh", default="host",
                    choices=["host", "local", "single", "multi"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "int8", "int16"])
    ap.add_argument("--weight-compress", default="none",
                    choices=["none", "int8"])
    ap.add_argument("--quantized-moments", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=100)
    ap.add_argument("--checkpoint-sync", action="store_true",
                    help="block the step loop on checkpoint writes "
                         "(default: async writer, bounded queue)")
    ap.add_argument("--checkpoint-shards", type=int, default=None,
                    help="per-host shard files per step "
                         "(default: jax.process_count())")
    ap.add_argument("--lower-only", action="store_true")
    ap.add_argument("--chaos", default=None,
                    help="fault-injection spec, e.g. "
                         "'straggler:host=1,delay=0.05;writer:failures=2' "
                         "(see repro.dist.chaos.from_spec)")
    ap.add_argument("--mitigate", action="store_true",
                    help="arm the straggler MitigationPolicy (rebalance/"
                         "exclude flagged hosts, skip NaN steps)")
    launch_env.add_arguments(ap)
    args = ap.parse_args(argv)

    launch_env.setup_runtime(launch_env.from_args(args))
    cfg = configs.reduced(args.arch) if args.reduced else configs.get(args.arch)
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    mesh = {"host": make_host_mesh, "local": make_local_mesh,
            "single": make_production_mesh,
            "multi": lambda: make_production_mesh(multi_pod=True)
            }[args.mesh]()
    npods = mesh.shape.get("pod", 1)
    tcfg = TrainConfig(
        microbatches=args.microbatches, grad_compress=args.grad_compress,
        weight_compress=args.weight_compress,
        npods=npods,
        adamw=adamw.AdamWConfig(lr=args.lr,
                                quantized_moments=args.quantized_moments))
    podded = tcfg.grad_compress != "none" and npods > 1

    pspecs = SH.param_specs(M.param_shapes(cfg), mesh)
    pshard = SH.param_shardings(M.param_shapes(cfg), mesh)
    # repro-lint: allow[jit-cache] launch entrypoint: built once per process
    step_fn = jax.jit(make_train_step(cfg, tcfg),
                      in_shardings=(pshard, None, None), donate_argnums=(0, 1))

    with use_mesh(mesh), use_param_specs(pspecs):
        if args.lower_only:
            toks = jax.ShapeDtypeStruct(
                (npods, args.batch // npods, args.seq) if podded
                else (args.batch, args.seq), jnp.int32)
            opt_shapes = jax.eval_shape(
                lambda p: adamw.init(p, tcfg.adamw), M.param_shapes(cfg))
            # repro-lint: allow[jit-cache] --lower-only path: compiles once
            # then returns; nothing to cache
            c = jax.jit(make_train_step(cfg, tcfg)).lower(
                M.param_shapes(cfg), opt_shapes, toks).compile()
            print("lowered+compiled OK;", c.memory_analysis())
            return []
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        params = jax.device_put(params, pshard)
        opt = adamw.init(params, tcfg.adamw)
        start = 0
        if args.checkpoint_dir and ckpt_io.latest_step(args.checkpoint_dir) is not None:
            (params, opt), start = ckpt_io.load_checkpoint(
                args.checkpoint_dir, (params, opt))
            start += 1
            print(f"resumed from step {start}")
        writer = None if args.checkpoint_sync or not args.checkpoint_dir \
            else ckpt_io.AsyncWriter(max_pending=1, retries=2)
        nhosts = max(1, jax.process_count())
        chaos_cfg = (chaos.from_spec(args.chaos, nhosts=nhosts)
                     if args.chaos else None)
        policy = (fault.MitigationPolicy(
                      chaos_cfg.nhosts if chaos_cfg is not None else nhosts)
                  if args.mitigate else None)
        losses = []
        try:
            with chaos.use_chaos(chaos_cfg) as monkey:
                for step in range(start, args.steps):
                    batch = pipeline.global_batch(mesh, cfg.vocab, args.batch,
                                                  args.seq, step, podded=podded)
                    t0 = time.perf_counter()
                    loss, params, opt = step_fn(params, opt, batch)
                    loss.block_until_ready()  # repro-lint: allow[host-sync] step-time fence
                    dt = time.perf_counter() - t0
                    if monkey is not None:
                        shares = policy.shares if policy is not None else None
                        dt, host_dts = monkey.inject_step(step, dt, shares)
                        if policy is not None:
                            policy.observe(step, host_dts)
                    bad = ((monkey is not None and monkey.nan_burst(step))
                           or fault.loss_is_bad(loss))
                    if bad and policy is not None:
                        policy.on_bad_loss(step, float("nan"))
                        print(f"step {step:5d}  skipped (bad loss)")
                        continue
                    losses.append(float(loss))
                    if step % 5 == 0 or step == args.steps - 1:
                        tps = args.batch * args.seq / dt
                        extra = ""
                        if policy is not None and (policy.excluded
                                                   or policy.events):
                            extra = (f"  shares={[round(float(s), 3) for s in policy.shares]}"
                                     f"  excluded={sorted(policy.excluded)}")
                        print(f"step {step:5d}  loss {losses[-1]:.4f}  "
                              f"{dt * 1e3:7.1f} ms  {tps:9.0f} tok/s{extra}")
                    if args.checkpoint_dir and (step + 1) % args.checkpoint_every == 0:
                        ckpt_io.save_checkpoint(
                            args.checkpoint_dir, step, (params, opt),
                            policy=ckpt_io.CheckpointPolicy(codec="cusz"),
                            nshards=args.checkpoint_shards, writer=writer)
        finally:
            if writer is not None:
                writer.close()     # drain + surface any async write failure
        return losses


if __name__ == "__main__":
    main()
