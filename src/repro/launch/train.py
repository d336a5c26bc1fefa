"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch resnet8 --steps 100
    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
        --steps 50 --ckpt-dir /tmp/ck

Real-hardware runs use full configs with the production mesh; on this CPU
container the --smoke flag selects the reduced configs (same code path:
pjit + sharding + fault-tolerant loop + checkpointing).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cbase
from repro.data.synthetic import SyntheticCifar, SyntheticTokens
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import make_train_step
from repro.models import model as M, resnet as R
from repro.parallel import ctx, sharding as shd
from repro.train import optimizer as opt_lib
from repro.train.loop import LoopConfig, run


def train_resnet(args):
    cfg = R.RESNET8 if args.arch == "resnet8" else R.RESNET20
    params = R.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = opt_lib.sgdm(lr=args.lr, total_steps=args.steps)
    opt_state = opt.init(params)
    pipe = SyntheticCifar(args.batch, seed=args.seed)

    @jax.jit
    def step(params, opt_state, i, batch):
        (loss, m), g = jax.value_and_grad(
            lambda p: R.loss_fn(p, cfg, batch), has_aux=True)(params)
        params, opt_state = opt.update(g, opt_state, params, i)
        return params, opt_state, m

    loop_cfg = LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every,
                          watchdog_s=args.watchdog_s)
    params, opt_state, metrics = run(
        loop_cfg, params=params, opt_state=opt_state, train_step=step,
        pipeline=pipe)
    print("final:", {k: float(v) for k, v in metrics.items()})


def train_lm(args):
    cfg = (cbase.get_smoke_config(args.arch) if args.smoke
           else cbase.get_config(args.arch))
    mesh = None
    if args.mesh_model > 1 or args.mesh_data > 1:
        mesh = make_local_mesh(args.mesh_data, args.mesh_model)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = opt_lib.adamw(lr=args.lr, total_steps=args.steps,
                        int8_state=args.int8_opt)
    opt_state = opt.init(params)
    pipe = SyntheticTokens(args.batch, args.seq, cfg.vocab_size,
                           seed=args.seed)
    step_fn = jax.jit(make_train_step(cfg, opt, grad_accum=args.grad_accum),
                      donate_argnums=(0, 1))
    shardings = None
    if mesh is not None:
        p_shard = shd.params_shardings(params, mesh)
        o_shard = shd.params_shardings(opt_state, mesh)
        params = jax.device_put(params, p_shard)
        opt_state = jax.device_put(opt_state, o_shard)
        shardings = (p_shard, o_shard)

    def wrapped(p, o, i, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        return step_fn(p, o, i, batch)

    loop_cfg = LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every,
                          watchdog_s=args.watchdog_s)
    cm = ctx.mesh_context(mesh) if mesh is not None else _null()
    with cm:
        params, opt_state, metrics = run(
            loop_cfg, params=params, opt_state=opt_state, train_step=wrapped,
            pipeline=pipe, shardings=shardings)
    print("final:", {k: float(v) for k, v in metrics.items()})


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--int8-opt", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--watchdog-s", type=float, default=0.0)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    args = ap.parse_args()
    if args.arch.startswith("resnet"):
        args.lr = args.lr or 0.05
        train_resnet(args)
    else:
        args.lr = args.lr or 1e-3
        train_lm(args)


if __name__ == "__main__":
    main()
