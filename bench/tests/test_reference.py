"""The plain reference against the program at smoke size, on the CPU: the
same weights from the seed, logits after prefill and through the paged
cache, top-8 routing, and the training loss and gradients; and the same
comparison failing when the computation runs a precision lower."""
from __future__ import annotations

import jax
import numpy as np
import pytest

import check
import common
from conftest import CHAT, fp32_config, make_root, tiny_config
from reference import decoder
from reference import train as ref_train
from serve import ServeCell

SEED = 2**31 + 5


def _serve(conf, seed=SEED, seconds=3.0, tmp=None):
    root = make_root(tmp, "tiny.chat", conf, CHAT, {}, [])
    cell = common.cell(common.spec(root), "tiny.chat", root)
    run = ServeCell(cell, seed, jax.devices()[:1], common.CompileLog())
    run.setup(seconds)
    run.window(seconds, None)
    run.free()
    return run.sample()


def test_weights_are_the_programs():
    from program import model_config
    from repro.models import build

    conf = tiny_config("granite-3-8b-serve", backend="xla")
    dims = decoder.Dims.from_config(conf)
    key = jax.random.PRNGKey(SEED)
    params = build(model_config(conf)).init(key)
    np.testing.assert_array_equal(np.asarray(params["embed"]["table"], np.float32),
                                  np.asarray(decoder.embed_table(dims, key)))
    units = params["decoder"]["units"]["b0"]
    for i in range(dims.layers):
        w = decoder.layer_weights(dims, key, i)
        for name, got in (("q", units["attn"]["q"]["w"]), ("o", units["attn"]["o"]["w"]),
                          ("up", units["ffn"]["up"]["w"]), ("down", units["ffn"]["down"]["w"])):
            np.testing.assert_array_equal(np.asarray(got[i], np.float32), np.asarray(w[name]))


def test_served_tokens_match_reference_in_fp32(tmp_path):
    """With the program in float32, every served token (the first one after
    prefill, the rest through the paged cache) is the reference's best."""
    conf = fp32_config()
    sample = _serve(conf, tmp=tmp_path)
    gaps = check.serve_gaps(conf, SEED, sample)
    assert len(gaps) >= 20
    assert gaps.max() < 1e-4


def test_moe_top8_matches_reference_in_fp32(tmp_path):
    conf = fp32_config(arch="granite-moe-1b-a400m")
    conf.update(num_local_experts=16, num_experts_per_tok=8, intermediate_size=32)
    sample = _serve(conf, tmp=tmp_path)
    gaps = check.serve_gaps(conf, SEED, sample)
    # Summation order differs: a token whose two best logits lie within
    # float32 rounding of each other (or an expert chosen on a near-tie)
    # may differ by a hair; every other served token is the reference's.
    assert np.median(gaps) == 0.0
    assert gaps.max() < 1e-2


@pytest.mark.parametrize("seed", [11, 2**31 + 12])
def test_int4_control_fails_where_the_program_passes(tmp_path, seed):
    """The configured precision (E4M3 operands) against the control (int4
    operands) on the same served requests: the mean gap, the number the
    chat cell compares, reads three times as much or more for the control,
    and a limit set between the two readings, by the rule the chip's
    limits follow, passes the one and fails the other."""
    conf = tiny_config("granite-3-8b-serve", backend="xla")
    sample = _serve(conf, seed=seed, tmp=tmp_path)
    prog = check.serve_gaps(conf, seed, sample).mean()
    ctrl = check.serve_gaps(conf, seed, sample, control="int4").mean()
    assert ctrl >= 3 * prog
    limit = {"logit_gap_mean": {"limit": 2 * prog}}
    assert check.judge({"logit_gap_mean": prog}, limit)[0]
    assert not check.judge({"logit_gap_mean": ctrl}, limit)[0]


OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}


def _leaf_norms(tree) -> dict:
    """Norms of the program's leaves, each stacked leaf split into its
    layers, under the names ``reference.train`` gives them."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        x = np.asarray(leaf, np.float32)
        if keys[0] in ("embed", "final_norm"):
            out[keys[0]] = float(np.linalg.norm(x))
            continue
        name = keys[-1] if keys[-1] not in ("w", "scale") else keys[-2]
        for i in range(x.shape[0]):
            out[f"layer{i}/{name}"] = float(np.linalg.norm(x[i]))
    return out


def _program_steps(conf, batches):
    """The program's first steps, by ``make_sharded_train`` on a 1x1 mesh:
    losses, the first gradient's leaf norms (read back from AdamW's first
    moment) and the leaf norms of the parameters' change."""
    import jax.numpy as jnp

    from program import model_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_sharded_train
    from repro.models import build
    from repro.models.transformer import MeshCtx
    from repro.optim import AdamW

    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    model = build(model_config(conf), MeshCtx(mesh=mesh, dp_axes=("data",), ep_axis="model"))
    opt = AdamW(**OPT)
    shape = {"tokens": jax.ShapeDtypeStruct(batches[0].shape, jnp.int32)}
    init_fn, step_fn, _ = make_sharded_train(model, opt, mesh, shape)
    key = jax.random.PRNGKey(SEED)
    state, out = init_fn(key), {"losses": []}
    for i, tokens in enumerate(batches):
        state, metrics = step_fn(state, {"tokens": tokens})
        out["losses"].append(float(metrics["loss"]))
        if i == 0:
            mu = _leaf_norms(state.opt_state["mu"])
            out["grad_norms"] = {k: v / (1 - OPT["b1"]) for k, v in mu.items()}
    moved = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                         state.params, model.init(key))
    out["change_norms"] = _leaf_norms(moved)
    return out


def _gaps(prog: dict, ref: dict) -> dict:
    """Worst relative loss gap; worst leaf gap of the first gradient's and
    the change's norms, over that leaf's or the median leaf's norm."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    out = {"loss_gap": loss}
    for name in ("grad_norms", "change_norms"):
        r = ref[name]
        med = float(np.median(list(r.values())))
        out[name] = max(abs(prog[name][k] - v) / max(v, med) for k, v in r.items())
    return out


def test_training_matches_reference_in_fp32():
    conf = fp32_config()
    rng = np.random.default_rng(SEED)
    batches = [rng.integers(1, conf["vocab_size"], (2, 64)).astype(np.int32) for _ in range(3)]
    dims = decoder.Dims.from_config(conf)
    ref = ref_train.steps(dims, jax.random.PRNGKey(SEED), batches, OPT)
    got = _gaps(_program_steps(conf, batches), ref)
    assert got["loss_gap"] < 1e-4
    assert got["grad_norms"] < 1e-2
    assert got["change_norms"] < 1e-2
    ctrl = _gaps(ref_train.steps(dims, jax.random.PRNGKey(SEED), batches, OPT, quant="int4"), ref)
    assert ctrl["grad_norms"] > 10 * max(got["grad_norms"], 1e-3)
