"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out artifacts/dryrun]

Proves the distribution config is coherent without hardware: ShapeDtypeStruct
inputs (no allocation), ``.lower().compile()`` must succeed; the compiled
artifact yields memory_analysis (fits?), cost_analysis (FLOPs/bytes) and the
collective schedule (parsed from HLO) for EXPERIMENTS.md.
"""
# The container has ONE real CPU device; the dry-run builds the production
# mesh from 512 placeholder host devices. Must run before ANY other import.
import os

if "--real-devices" not in os.sys.argv:  # pragma: no branch
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, cell_is_runnable, get_config  # noqa: E402
from repro.distrib import sharding as shd  # noqa: E402
from repro.launch.mesh import (  # noqa: E402
    dp_axes_of,
    make_production_mesh,
    n_dp_of,
    tp_size_of,
)
from repro.models import build, decode_input_specs, train_input_specs  # noqa: E402
from repro.models.transformer import MeshCtx  # noqa: E402
from repro.optim import AdamW  # noqa: E402
from repro.roofline import analysis as ra  # noqa: E402
from repro.serving.steps import make_serve_steps  # noqa: E402
from repro.training import TrainState, make_train_step  # noqa: E402


def _apply_overrides(cfg, args):
    over = {}
    if args.moe_impl:
        over["moe_impl"] = args.moe_impl
    if args.remat:
        over["remat"] = args.remat
    if args.policy:
        over["policy"] = args.policy
    if args.kv_dtype:
        over["kv_cache_dtype"] = args.kv_dtype
    return dataclasses.replace(cfg, **over) if over else cfg


def lower_cell(arch: str, shape: str, mesh, *, args=None):
    """Returns (lowered, meta) for one cell on the given mesh."""
    cfg = get_config(arch)
    if args is not None:
        cfg = _apply_overrides(cfg, args)
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        return None, {"skipped": why}

    seq, batch, kind = SHAPES[shape]
    mode = getattr(args, "sharding", "tp") if args is not None else "tp"
    fsdp = mode == "fsdp"
    if fsdp:
        # FSDP/ZeRO-3: the whole mesh is data-parallel; parameters fully
        # sharded and gathered per use (beyond-paper §Perf optimization).
        dp_axes = tuple(mesh.axis_names)
        tp = 1
        n_dp = mesh.size
        mesh_ctx = MeshCtx(mesh=mesh, dp_axes=dp_axes, ep_axis=None, tp_axis=None)
    else:
        dp_axes = dp_axes_of(mesh)
        tp = tp_size_of(mesh)
        n_dp = n_dp_of(mesh)
        mesh_ctx = MeshCtx(mesh=mesh, dp_axes=dp_axes, ep_axis="model")
    model = build(cfg, mesh_ctx)

    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    if fsdp:
        pspecs = shd.fsdp_param_specs(params_shape, dp_axes, mesh.size)
    else:
        pspecs = shd.param_specs(params_shape, cfg, tp)
        if mode == "zero3":
            # hybrid: TP over 'model' + parameters additionally sharded over
            # the data axes (ZeRO-3) — the 512-chip configuration when the
            # global batch is smaller than the chip count.
            pspecs = shd.zero1_specs(pspecs, params_shape, dp_axes, n_dp)
    pshard = shd.tree_shardings(pspecs, mesh)

    meta = {
        "arch": arch, "shape": shape, "kind": kind,
        "seq": seq, "batch": batch,
        "engine": {
            "policy": model.engine.policy.name,
            "backend": model.engine.backend,
        },
        "mesh": dict(zip(mesh.axis_names, (mesh.shape[a] for a in mesh.axis_names))),
        "n_params": int(
            sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(params_shape))
        ),
    }

    if kind == "train":
        opt = AdamW(lr=1e-4)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        if fsdp:
            mom_specs = pspecs  # already fully sharded
        elif args is None or not args.no_zero1:
            mom_specs = shd.zero1_specs(pspecs, params_shape, dp_axes, n_dp)
        else:
            mom_specs = pspecs
        ospecs = {"mu": mom_specs, "nu": mom_specs}
        oshard = shd.tree_shardings(ospecs, mesh)
        state_shape = TrainState(
            jax.ShapeDtypeStruct((), jnp.int32), params_shape, opt_shape,
            jax.ShapeDtypeStruct((), jnp.int32),
        )
        state_shard = TrainState(
            NamedSharding(mesh, P()), pshard, oshard, NamedSharding(mesh, P())
        )
        batch_shape = train_input_specs(cfg, batch, seq)
        bspecs = shd.batch_specs(batch_shape, dp_axes)
        bshard = shd.tree_shardings(bspecs, mesh)
        step = make_train_step(model, opt)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(state_shard, bshard),
                out_shardings=(state_shard, None),
                donate_argnums=(0,),
            ).lower(state_shape, batch_shape)
        return lowered, meta

    # Serving kinds ---------------------------------------------------------
    prefill_step, decode_step = make_serve_steps(model)
    if kind == "prefill":
        batch_shape = train_input_specs(cfg, batch, seq)
        bspecs = shd.batch_specs(batch_shape, dp_axes)
        bshard = shd.tree_shardings(bspecs, mesh)
        max_len = seq if not cfg.is_encoder_decoder else max(seq // cfg.enc_dec_ratio, 1)
        fn = lambda p, b: prefill_step(p, b, max_len)  # noqa: E731
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                fn, in_shardings=(pshard, bshard), out_shardings=None
            ).lower(params_shape, batch_shape)
        return lowered, meta

    # decode: one new token against a cache of length `seq`.
    specs = decode_input_specs(cfg, batch, seq)
    cspecs = shd.cache_specs(specs["cache"], cfg, dp_axes, tp, batch, n_dp)
    cshard = shd.tree_shardings(cspecs, mesh)
    tshard = NamedSharding(mesh, P(dp_axes if batch % n_dp == 0 else None, None))
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            decode_step,
            in_shardings=(pshard, tshard, cshard),
            out_shardings=None,
            donate_argnums=(2,),
        ).lower(params_shape, specs["tokens"], specs["cache"])
    return lowered, meta


def run_cell(arch: str, shape: str, *, multi_pod: bool, args=None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape, mesh, args=args)
    if lowered is None:
        return dict(meta, status="skipped", mesh_kind="multi_pod" if multi_pod else "single_pod")
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    roof = ra.roofline_from_artifacts(cost, hlo, n_chips)
    from repro.roofline import hlo_cost as hc

    coll = hc.analyze(hlo).coll_by_kind

    out = dict(
        meta,
        status="ok",
        mesh_kind="multi_pod" if multi_pod else "single_pod",
        n_chips=n_chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", 0),
            output_bytes=getattr(mem, "output_size_in_bytes", 0),
            temp_bytes=getattr(mem, "temp_size_in_bytes", 0),
            peak_bytes=getattr(mem, "peak_memory_in_bytes", 0),
        ),
        cost=dict(
            flops=float(cost.get("flops", 0.0)),
            bytes_accessed=float(cost.get("bytes accessed", 0.0)),
        ),
        collectives={k: float(v) for k, v in coll.items()},
        roofline=roof.to_dict(),
    )
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--moe-impl", choices=("dense", "ep"))
    ap.add_argument("--sharding", choices=("tp", "fsdp", "zero3"), default="tp")
    ap.add_argument("--remat", choices=("none", "block"))
    ap.add_argument("--policy")
    ap.add_argument("--kv-dtype")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--real-devices", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'pod2' if mp else 'pod1'}"
            if args.tag:
                tag += f"__{args.tag}"
            try:
                res = run_cell(arch, shape, multi_pod=mp, args=args)
            except Exception as e:  # a failure here is a bug in the system
                failures += 1
                res = dict(
                    arch=arch, shape=shape, status="FAILED",
                    mesh_kind="multi_pod" if mp else "single_pod",
                    error=f"{type(e).__name__}: {e}",
                    traceback=traceback.format_exc(),
                )
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(res, f, indent=2)
            status = res["status"]
            extra = ""
            if status == "ok":
                r = res["roofline"]
                extra = (
                    f" flops={r['hlo_flops']:.3g} coll={r['coll_bytes']:.3g}B"
                    f" bottleneck={r['bottleneck']}"
                    f" compile={res['compile_s']}s"
                )
            elif status == "skipped":
                extra = f" ({res.get('skipped','')})"
            else:
                extra = f" {res.get('error','')}"
            print(f"[{status:7s}] {tag}{extra}", flush=True)

    if failures:
        raise SystemExit(f"{failures} dry-run cells FAILED")


if __name__ == "__main__":
    main()
