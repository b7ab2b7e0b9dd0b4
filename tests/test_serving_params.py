"""Serving from GEMM weights prepared once at load (``Transformer.serving_params``).

A tiny tied-embedding decoder under ``tpu_hfp8`` (E4M3 forward storage),
with a vocabulary that is not a multiple of the 128 lane: the prepared
weights must give the same logits and greedy tokens, bit for bit, as the
weights cast in every step; the padded vocabulary must never reach the
sampler; and the decode step traced on prepared weights must do no weight
preparation at all (``repro.obs.weight_prep``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build
from repro.obs import WeightPrep, weight_prep_tally
from repro.optim import AdamW
from repro.serving import Server, ServerConfig, sample_logits
from repro.training import TrainState, make_paged_serve_steps, make_train_step

E4M3 = jnp.float8_e4m3fn
VOCAB = 300  # pads to 384 on the lane
SLOTS, PAGE, MAX_LEN = 2, 16, 64
PAGES_PER_SLOT = MAX_LEN // PAGE
DECODE_STEPS = 4


def _cfg(backend="pallas_interpret", **kw):
    return dataclasses.replace(
        get_config("granite-3-8b", smoke=True), n_layers=2, d_model=128,
        n_heads=2, n_kv_heads=2, head_dim=64, d_ff=256, vocab_size=VOCAB,
        tie_embeddings=True, policy="tpu_hfp8", backend=backend,
        kv_cache_dtype="e4m3", **kw)


@pytest.fixture(scope="module")
def tiny():
    model = build(_cfg())
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return model, params, model.serving_params(params)


def _serve_greedy(model, params):
    """Prefill one prompt per slot, then greedy decode steps over both:
    every step's logits."""
    prefill, _, _, decode = make_paged_serve_steps(model, page_size=PAGE)
    prefill, decode = jax.jit(prefill), jax.jit(decode)
    pools = model.init_state_store(SLOTS, SLOTS * PAGES_PER_SLOT + 1, PAGE)
    table = np.arange(1, SLOTS * PAGES_PER_SLOT + 1, dtype=np.int32).reshape(
        SLOTS, PAGES_PER_SLOT)
    last = np.zeros((SLOTS, 1), np.int32)
    lens = np.zeros(SLOTS, np.int32)
    out = []
    for slot in range(SLOTS):
        prompt = (np.arange(5 + 3 * slot, dtype=np.int32) * 37 + 11) % VOCAB
        logits, pools = prefill(params, jnp.asarray(prompt[None]), pools,
                                jnp.asarray(table[slot]), jnp.int32(slot),
                                jnp.int32(0), jnp.int32(prompt.size))
        out.append(np.asarray(logits))
        last[slot, 0] = int(np.argmax(out[-1][0]))
        lens[slot] = prompt.size
    for _ in range(DECODE_STEPS):
        logits, pools = decode(params, jnp.asarray(last), pools,
                               jnp.asarray(table), jnp.asarray(lens),
                               jnp.ones(SLOTS, bool))
        out.append(np.asarray(logits))
        last = np.argmax(out[-1], axis=-1).astype(np.int32)[:, None]
        lens += 1
    return out


@pytest.mark.parametrize("backend", ["pallas_interpret", "xla"])
def test_prepared_greedy_tokens_match_unprepared(backend):
    """Prefill and decode on prepared weights give the same logits, and so
    the same greedy tokens, bit for bit, as on the bf16 weights the steps
    cast per call."""
    model = build(_cfg(backend))
    params = jax.jit(model.init)(jax.random.PRNGKey(1))
    prepared = model.serving_params(params)
    want, got = _serve_greedy(model, params), _serve_greedy(model, prepared)
    assert len(got) == SLOTS + DECODE_STEPS
    for step, (w, g) in enumerate(zip(want, got)):
        assert g.shape[-1] == VOCAB
        np.testing.assert_array_equal(g, w, err_msg=f"step {step}")
        np.testing.assert_array_equal(g.argmax(-1), w.argmax(-1))


def test_prepared_tree_holds_kernel_operands(tiny):
    """Every GEMM weight is E4M3; the tied table gains its transposed,
    lane-padded E4M3 unembedding, zero past the vocabulary; the router-free
    rest (norms) is the caller's, untouched."""
    _, params, prepared = tiny
    flat = jax.tree_util.tree_flatten_with_path(prepared)[0]
    dense = [a for p, a in flat if getattr(p[-1], "key", None) == "w"]
    assert dense and all(a.dtype == E4M3 for a in dense)
    unembed = prepared["embed"]["unembed"]
    assert unembed.shape == (128, 384) and unembed.dtype == E4M3
    np.testing.assert_array_equal(
        np.asarray(unembed[:, :VOCAB], np.float32),
        np.asarray(params["embed"]["table"].T.astype(E4M3), np.float32))
    assert not np.asarray(unembed[:, VOCAB:], np.float32).any()
    assert prepared["final_norm"]["scale"] is params["final_norm"]["scale"]


def test_logits_cut_to_vocab_and_no_padded_id_sampled(tiny):
    """The logits' last dim is the vocabulary, not the padded 384, so no
    sampler can draw a padded id: every draw at temperature 1 is in range."""
    model, _, prepared = tiny
    h = jax.random.normal(jax.random.PRNGKey(2), (8, 1, 128), jnp.bfloat16)
    logits = model.logits(prepared, h)
    assert logits.shape == (8, 1, VOCAB)
    ids = jax.vmap(lambda k: sample_logits(
        logits[:, 0], k, temperature=jnp.ones(8), top_k=jnp.zeros(8, jnp.int32),
        top_p=jnp.ones(8)))(jax.random.split(jax.random.PRNGKey(3), 64))
    assert int(ids.max()) < VOCAB


def test_embed_reads_bf16_table(tiny):
    """The lookup keeps reading the bf16 table: the same array, the same
    embeddings."""
    model, params, prepared = tiny
    assert prepared["embed"]["table"] is params["embed"]["table"]
    assert prepared["embed"]["table"].dtype == jnp.bfloat16
    tokens = jnp.asarray([[0, 7, VOCAB - 1]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(model.embed(prepared, tokens), np.float32),
        np.asarray(model.embed(params, tokens), np.float32))


def _train_step_params(policy, run):
    """The GEMM weights and the embedding of the params a training step
    returns: run, or only shaped (``jax.eval_shape``)."""
    model = build(dataclasses.replace(_cfg("xla"), policy=policy))
    params = jax.jit(model.init)(jax.random.PRNGKey(4))
    opt = AdamW(lr=1e-3)
    state = TrainState(jnp.zeros((), jnp.int32), params, opt.init(params),
                       jnp.zeros((), jnp.int32))
    batch = {"tokens": jnp.asarray(np.arange(2 * 16).reshape(2, 16) % VOCAB, jnp.int32)}
    step = make_train_step(model, opt)
    state, metrics = jax.jit(step)(state, batch) if run else jax.eval_shape(step, state, batch)
    if run:
        assert np.isfinite(float(metrics["loss"]))
    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    dense = [a for p, a in flat if getattr(p[-1], "key", None) == "w"]
    return model, dense, state.params["embed"]


def test_training_step_params_keep_their_format():
    """Training casts per call: its weights change every step, and a step
    returns GEMM weights in the format they are kept in (the policy's
    16-bit compute format, never E4M3) and no unembedding. ``tpu_hfp8``
    keeps bf16 weights; XLA's CPU backend has no bf16 x bf16 = f32 dot for
    its backward, so its step is shaped, not run. ``redmule_hfp8`` stores
    E4M3 forward like it and keeps fp16 weights: its step runs."""
    for policy, run, dtype in (("tpu_hfp8", False, jnp.bfloat16),
                               ("redmule_hfp8", True, jnp.float16)):
        model, dense, embed = _train_step_params(policy, run)
        assert "unembed" not in embed and embed["table"].dtype == dtype
        assert dense and all(a.dtype == model.dtype == dtype for a in dense), policy


@pytest.mark.parametrize("policy", ["tpu_bf16", "fp32"])
def test_preparation_returns_input_unchanged(tiny, policy):
    """Where the forward storage is no narrower than the weights, there is
    nothing to prepare: the input tree comes back as it is. So it does for a
    tree already prepared, and for weights stored in E4M3 (fp8_params)."""
    model, params, prepared = tiny
    assert model.serving_params(params, model.engine.with_policy(policy)) is params
    assert model.serving_params(prepared) is prepared
    fp8 = build(_cfg(fp8_params=True))
    fp8_params = jax.jit(fp8.init)(jax.random.PRNGKey(5))
    assert fp8.serving_params(fp8_params) is fp8_params


def test_weight_prep_counter_decode_step(tiny):
    """The decode step traced on prepared weights prepares none (0 calls);
    on the bf16 weights it prepares 2 layers x 7 GEMMs plus the logits."""
    model, params, _ = tiny
    server = Server(model, params, ServerConfig(
        num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_LEN))
    server.submit([3, 1, 4, 1, 5], max_new_tokens=3)
    while server.scheduler.has_work():
        server.step()
    assert server.engine.weight_prep["decode"] == WeightPrep(0, 0)
    assert server.engine.prepared_bytes > 0
    assert server.params["embed"]["unembed"].dtype == E4M3

    *_, decode = make_paged_serve_steps(model, page_size=PAGE)
    pools = jax.eval_shape(
        lambda: model.init_state_store(SLOTS, SLOTS * PAGES_PER_SLOT + 1, PAGE))
    i32 = jnp.int32
    with weight_prep_tally() as unprepared:
        jax.make_jaxpr(decode)(
            params, jnp.zeros((SLOTS, 1), i32), pools,
            jnp.zeros((SLOTS, PAGES_PER_SLOT), i32), jnp.zeros(SLOTS, i32),
            jnp.ones(SLOTS, bool))
    assert unprepared.calls == 2 * 7 + 1 and unprepared.bytes > 0


def _in_place_calls(jaxpr) -> int:
    """``redmule_gemm`` calls reading their weight from a layer stack in
    place (a prefetched layer index), nested programs included."""
    from jax.extend import core as jcore

    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and eqn.params["name"] == "redmule_gemm":
            n += eqn.params["grid_mapping"].num_index_operands
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    n += _in_place_calls(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    n += _in_place_calls(sub)
    return n


def test_decode_step_reads_prepared_layers_in_place(tiny):
    """On prepared weights the decode step's layer scan hands each of the
    layer's 7 GEMMs its weight stack and index (no slice copied out of the
    stack); on bf16 weights, which are cast per call, none."""
    model, params, prepared = tiny
    *_, decode = make_paged_serve_steps(model, page_size=PAGE)
    pools = jax.eval_shape(
        lambda: model.init_state_store(SLOTS, SLOTS * PAGES_PER_SLOT + 1, PAGE))
    i32 = jnp.int32

    def trace(p):
        return jax.make_jaxpr(decode)(
            p, jnp.zeros((SLOTS, 1), i32), pools,
            jnp.zeros((SLOTS, PAGES_PER_SLOT), i32), jnp.zeros(SLOTS, i32),
            jnp.ones(SLOTS, bool)).jaxpr

    assert _in_place_calls(trace(prepared)) == 7
    assert _in_place_calls(trace(params)) == 0


def test_drafter_params_prepared_by_the_same_function(tiny):
    """A speculative drafter's params go through the same preparation as
    the target's."""
    model, params, _ = tiny
    server = Server(model, params, ServerConfig(
        num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_LEN),
        draft_model=model, draft_params=params)
    assert server.drafter.prepared_bytes == server.engine.prepared_bytes > 0
    assert server.drafter.params["embed"]["unembed"].dtype == E4M3
