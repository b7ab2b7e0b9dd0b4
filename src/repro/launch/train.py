"""Training launcher: mesh + sharded state + data + checkpoint/restart.

CPU-scale example (also exercised in tests):
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b --smoke \
      --steps 20 --seq 64 --batch 8

Production shape (the multi-pod dry-run proves it lowers; on a real fleet the
same entry point runs under `jax.distributed.initialize`):
  python -m repro.launch.train --arch deepseek-coder-33b --seq 4096 --batch 256
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import manager as ckpt
from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import for_model
from repro.distrib import sharding as shd
from repro.distrib.fault import Heartbeat, StragglerMonitor
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import dp_axes_of, make_mesh, n_dp_of, tp_size_of
from repro.models import build
from repro.models.transformer import MeshCtx
from repro.optim import AdamW, cosine_schedule
from repro.training import TrainState, make_train_step


def make_mesh_from_args(args):
    n_dev = len(jax.devices())
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
    else:
        dims = (n_dev, 1)
    axes = ("pod", "data", "model")[3 - len(dims):]
    return make_mesh(dims, axes)


def make_sharded_train(model, opt, mesh, batch_shape):
    """Jitted ``(init_fn, step_fn, state_shard)`` for one training run.

    Parameters follow ``distrib.sharding``'s tensor-parallel specs over
    ``model``, AdamW moments add ZeRO-1 sharding over the data axes, and the
    batch shards over the data axes. ``init_fn(key)`` builds the state on
    the devices; ``step_fn(state, batch)`` donates the state and traces
    under the mesh, so each Pallas GEMM runs once per device (see
    ``kernels.ops``).
    """
    cfg = model.cfg
    dp_axes, tp, n_dp = dp_axes_of(mesh), tp_size_of(mesh), n_dp_of(mesh)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = shd.param_specs(params_shape, cfg, tp)
    pshard = shd.tree_shardings(pspecs, mesh)
    mom_specs = shd.zero1_specs(pspecs, params_shape, dp_axes, n_dp)
    oshard = shd.tree_shardings({"mu": mom_specs, "nu": mom_specs}, mesh)
    scalar = NamedSharding(mesh, P())
    state_shard = TrainState(scalar, pshard, oshard, scalar)
    bshard = shd.tree_shardings(shd.batch_specs(batch_shape, dp_axes), mesh)
    train_step = make_train_step(model, opt)

    def init_state(key):
        params = model.init(key)
        zero = jnp.zeros((), jnp.int32)
        return TrainState(zero, params, opt.init(params), zero)

    def step(state, batch):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return train_step(state, batch)

    init_fn = jax.jit(init_state, out_shardings=state_shard)
    step_fn = jax.jit(
        step,
        in_shardings=(state_shard, bshard),
        out_shardings=(state_shard, None),
        donate_argnums=(0,),
    )
    return init_fn, step_fn, state_shard


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="granite-3-8b")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--mesh", default="", help="e.g. 16,16 or 2,16,16")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--policy", default="")
    ap.add_argument(
        "--backend", choices=("", "xla", "pallas", "pallas_interpret"),
        default="", help="GEMM engine for fwd+bwd matmuls (default: config)",
    )
    ap.add_argument("--moe-impl", choices=("dense", "ep"), default="")
    ap.add_argument("--remat", choices=("none", "block"), default="")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    over = {}
    if args.policy:
        over["policy"] = args.policy
    if args.backend:
        over["backend"] = args.backend
    if args.moe_impl:
        over["moe_impl"] = args.moe_impl
    if args.remat:
        over["remat"] = args.remat
    if over:
        cfg = dataclasses.replace(cfg, **over)

    enable_compile_cache()
    mesh = make_mesh_from_args(args)
    mesh_ctx = MeshCtx(mesh=mesh, dp_axes=dp_axes_of(mesh), ep_axis="model")
    model = build(cfg, mesh_ctx)
    # cfg.policy/cfg.backend (incl. the CLI overrides above) became the
    # model's Engine; every GEMM in the traced step runs on it.
    print(f"engine: policy={model.engine.policy.name} backend={model.engine.backend}")

    opt = AdamW(lr=cosine_schedule(args.lr, args.warmup, args.steps))
    data = for_model(cfg, args.seq, args.batch, seed=args.seed)
    init_fn, step_fn, state_shard = make_sharded_train(
        model, opt, mesh, jax.eval_shape(lambda: data.batch(0))
    )

    start_step = 0
    if args.resume and args.ckpt_dir:
        state_shape = jax.eval_shape(init_fn, jax.random.PRNGKey(args.seed))
        step, state = ckpt.restore_latest(
            args.ckpt_dir, state_shape, shardings=state_shard
        )
        if state is None:
            state = init_fn(jax.random.PRNGKey(args.seed))
        else:
            start_step = int(step)
            print(f"resumed from step {start_step}")
    else:
        state = init_fn(jax.random.PRNGKey(args.seed))

    saver = ckpt.AsyncSaver()
    hb = Heartbeat(os.path.join(args.ckpt_dir or "/tmp/repro_hb", "hb"), 0)
    straggler = StragglerMonitor()

    it = data.iterate(start=start_step)
    t_last = time.time()
    for i in range(start_step, args.steps):
        # jit places host numpy against in_shardings (per-host slices under
        # multi-host runtimes arrive via make_array_from_process_local_data).
        batch = next(it)
        state, metrics = step_fn(state, batch)
        if (i + 1) % args.log_every == 0 or i + 1 == args.steps:
            loss = float(metrics["loss"])
            gn = float(metrics["grad_norm"])
            dt = time.time() - t_last
            t_last = time.time()
            flag = straggler.record(dt / args.log_every)
            print(
                f"step {i+1:6d} loss {loss:.4f} gnorm {gn:.3f} "
                f"({dt/args.log_every*1e3:.0f} ms/step{' STRAGGLER' if flag else ''})",
                flush=True,
            )
        hb.beat(i)
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            saver.save(args.ckpt_dir, i + 1, state)
    saver.wait()
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, state)
    print("done")


if __name__ == "__main__":
    main()
