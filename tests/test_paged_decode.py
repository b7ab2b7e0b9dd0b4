"""Paged flash-decode kernel vs the XLA gather reference.

Parity discipline: the kernel (interpret mode) must match, slot for slot,
what `models.attention._online_attention` computes over the decode_cb-style
page-table gather — across storage dtype (fp32, bf16, fp8 E4M3 KV), slot
count / ragged lengths, and mask family (causal, sliding window, inactive
slots). On mismatch the offending tensors are dumped as `.npz` when
``REPRO_PARITY_DUMP`` points at a directory (the CI kernel-parity job
uploads them as artifacts).
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import Engine
from repro.kernels import ops, tuning
from repro.models import attention

# -- reference + case construction -------------------------------------------


def _gather_reference(q, k_pool, v_pool, page_table, seq_lens, *,
                      page_size, window, softcap):
    """The decode_cb gather path, verbatim: flat read indices over the page
    table, logical positions sentinel-masked past the decode position, then
    the shared online-softmax core under an fp32 XLA engine."""
    s, hq, hd = q.shape
    hkv = k_pool.shape[1]
    n_tok = page_table.shape[1] * page_size
    read_idx = (
        page_table[:, :, None] * page_size
        + jnp.arange(page_size, dtype=jnp.int32)[None, None, :]
    ).reshape(s, n_tok)
    lpos = jnp.arange(n_tok, dtype=jnp.int32)[None]
    k_pos = jnp.where(lpos <= seq_lens[:, None], lpos, attention.POS_SENTINEL)
    k = k_pool[read_idx].astype(jnp.float32)
    v = v_pool[read_idx].astype(jnp.float32)
    cfg = attention.AttnConfig(
        n_heads=hq, n_kv_heads=hkv, head_dim=hd, window=window, softcap=softcap
    )
    eng = Engine(policy="fp32", backend="xla")
    out = attention._online_attention(
        q[:, None].astype(jnp.float32), k, v, seq_lens[:, None], k_pos,
        cfg, eng,
    )
    return out[:, 0]  # (S, Hq, hd)


def _make_case(rng, *, s, hq, hkv, hd, page_size, pages_per_slot, n_pages,
               dtype, window=None, inactive=()):
    """Random decode step: shuffled physical pages, ragged lengths; window
    archs get their out-of-window pages recycled to NULL like the real
    allocator does."""
    q = jnp.asarray(rng.standard_normal((s, hq, hd)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((n_pages * page_size, hkv, hd)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((n_pages * page_size, hkv, hd)), dtype)
    avail = list(range(1, n_pages))
    rng.shuffle(avail)
    pt = np.zeros((s, pages_per_slot), np.int32)
    seq_lens = np.zeros(s, np.int32)
    active = np.ones(s, np.int32)
    idx = 0
    for si in range(s):
        n_pg = int(rng.integers(1, pages_per_slot + 1))
        for p in range(n_pg):
            pt[si, p] = avail[idx % len(avail)]
            idx += 1
        seq_lens[si] = int(rng.integers(0, n_pg * page_size))
        if window is not None:
            # Pages fully behind the window are freed by the allocator and
            # their table entries recycled to NULL — reproduce that here so
            # the kernel's NULL-skip is exercised against the reference's
            # window mask.
            for p in range(n_pg):
                if (p + 1) * page_size - 1 <= seq_lens[si] - window:
                    pt[si, p] = 0
    active[list(inactive)] = 0
    return (q, k_pool, v_pool, jnp.asarray(pt), jnp.asarray(seq_lens),
            jnp.asarray(active))


def _dump_on_mismatch(test_id, arrays):
    path = os.environ.get("REPRO_PARITY_DUMP", "")
    if not path:
        return None
    os.makedirs(path, exist_ok=True)
    fname = os.path.join(path, re.sub(r"[^\w.-]+", "_", test_id) + ".npz")
    np.savez(fname, **{k: np.asarray(v, np.float32) if v.dtype.kind not in "iub"
                       else np.asarray(v) for k, v in arrays.items()})
    return fname


def _assert_parity(got, want, active, case, *, tol, test_id):
    live = np.asarray(active, bool)
    g = np.asarray(got, np.float32)[live]
    w = np.asarray(want, np.float32)[live]
    try:
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol)
        # Inactive slots must come back as exact zeros (the server discards
        # them; zeros prove no stale VMEM state leaks across grid steps).
        if (~live).any():
            assert float(np.abs(np.asarray(got, np.float32)[~live]).max()) == 0.0
    except AssertionError:
        q, k_pool, v_pool, pt, seq_lens, act = case
        fname = _dump_on_mismatch(test_id, {
            "q": q, "k_pool": k_pool, "v_pool": v_pool, "page_table": pt,
            "seq_lens": seq_lens, "active": act, "got": got, "want": want,
        })
        if fname:
            raise AssertionError(f"parity mismatch; tensors dumped to {fname}")
        raise


# -- the parity grid ----------------------------------------------------------

# (s, hq, hkv, hd, page_size, pages_per_slot, n_pages, dtype, window,
#  inactive, tol); bigger interpret-mode grids run in the nightly slow job.
GRID = [
    # dtype sweep at a ragged mid-size shape, causal
    (4, 4, 2, 16, 8, 6, 16, "float32", None, (), 2e-4),
    (4, 4, 2, 16, 8, 6, 16, "bfloat16", None, (), 2e-2),
    (4, 4, 2, 16, 8, 6, 16, "float8_e4m3fn", None, (), 8e-2),
    # sliding window (out-of-window pages recycled to NULL)
    (4, 4, 2, 16, 8, 6, 16, "float32", 20, (), 2e-4),
    (4, 4, 2, 16, 8, 6, 16, "bfloat16", 12, (), 2e-2),
    (3, 8, 1, 32, 4, 8, 12, "float8_e4m3fn", 9, (), 8e-2),
    # inactive slots mixed into the batch
    (4, 4, 2, 16, 8, 6, 16, "float32", None, (1, 3), 2e-4),
    (6, 6, 3, 8, 4, 5, 24, "bfloat16", 10, (0, 4), 2e-2),
    # batch-size extremes
    (1, 8, 8, 32, 16, 4, 8, "bfloat16", None, (), 2e-2),
    (16, 4, 2, 16, 4, 4, 48, "float32", None, (5, 11), 2e-4),
    pytest.param((64, 4, 2, 16, 4, 4, 96, "bfloat16", None, (7, 30, 63), 2e-2),
                 marks=pytest.mark.slow),
    pytest.param((64, 8, 2, 32, 8, 8, 128, "float8_e4m3fn", 40, (0,), 8e-2),
                 marks=pytest.mark.slow),
]


def _ids(c):
    s, hq, hkv, hd, ps, P, n, dt, w, inact, _ = c
    return (f"s{s}-h{hq}.{hkv}x{hd}-ps{ps}xP{P}-{dt}"
            f"-w{w}-inact{len(inact)}")


@pytest.mark.parametrize("case", GRID, ids=_ids)
def test_kernel_matches_gather_reference(case, rng, request):
    s, hq, hkv, hd, ps, P, n, dt, w, inact, tol = case
    arrs = _make_case(rng, s=s, hq=hq, hkv=hkv, hd=hd, page_size=ps,
                      pages_per_slot=P, n_pages=n, dtype=jnp.dtype(dt),
                      window=w, inactive=inact)
    q, k_pool, v_pool, pt, seq_lens, active = arrs
    got = ops.paged_decode_attention(
        q, k_pool, v_pool, pt, seq_lens, active,
        page_size=ps, window=w, backend="pallas_interpret",
    )
    want = _gather_reference(q, k_pool, v_pool, pt, seq_lens,
                             page_size=ps, window=w, softcap=None)
    _assert_parity(got, want, active, arrs, tol=tol, test_id=request.node.name)


def test_kernel_softcap_matches_reference(rng, request):
    arrs = _make_case(rng, s=3, hq=4, hkv=2, hd=16, page_size=8,
                      pages_per_slot=4, n_pages=12, dtype=jnp.float32)
    q, k_pool, v_pool, pt, seq_lens, active = arrs
    got = ops.paged_decode_attention(
        q, k_pool, v_pool, pt, seq_lens, active,
        page_size=8, softcap=30.0, backend="pallas_interpret",
    )
    want = _gather_reference(q, k_pool, v_pool, pt, seq_lens,
                             page_size=8, window=None, softcap=30.0)
    _assert_parity(got, want, active, arrs, tol=2e-4,
                   test_id=request.node.name)


# -- properties ----------------------------------------------------------------


def test_page_table_permutation_invariance(rng):
    """Physical page placement must not matter: relabeling every page through
    a random permutation (pool rows moved to match) gives bitwise-identical
    output — each program DMAs the same values in the same order."""
    arrs = _make_case(rng, s=4, hq=4, hkv=2, hd=16, page_size=8,
                      pages_per_slot=5, n_pages=16, dtype=jnp.float32)
    q, k_pool, v_pool, pt, seq_lens, active = arrs
    kw = dict(page_size=8, backend="pallas_interpret")
    base = ops.paged_decode_attention(q, k_pool, v_pool, pt, seq_lens,
                                      active, **kw)
    perm = np.concatenate([[0], 1 + rng.permutation(15)])  # NULL stays 0
    ps = 8
    scatter = np.argsort(perm)  # old page p lives at row perm[p]
    k2 = np.asarray(k_pool).reshape(16, ps, 2, 16)[scatter].reshape(-1, 2, 16)
    v2 = np.asarray(v_pool).reshape(16, ps, 2, 16)[scatter].reshape(-1, 2, 16)
    pt2 = perm[np.asarray(pt)]
    pt2[np.asarray(pt) == 0] = 0
    got = ops.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k2), jnp.asarray(v2), jnp.asarray(pt2),
        seq_lens, active, **kw)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(got))


def test_null_page_contributes_zero_weight(rng):
    """Page 0 is the serving null page: pad/inactive writes land there, so
    the kernel must skip it entirely — poisoning its contents with huge
    values must not move any output bit."""
    arrs = _make_case(rng, s=4, hq=4, hkv=2, hd=16, page_size=8,
                      pages_per_slot=5, n_pages=12, dtype=jnp.float32,
                      window=16)
    q, k_pool, v_pool, pt, seq_lens, active = arrs
    assert (np.asarray(pt) == 0).any(), "case must contain NULL entries"
    kw = dict(page_size=8, window=16, backend="pallas_interpret")
    base = ops.paged_decode_attention(q, k_pool, v_pool, pt, seq_lens,
                                      active, **kw)
    kp = np.asarray(k_pool).copy()
    vp = np.asarray(v_pool).copy()
    kp[:8] = 1e4
    vp[:8] = -1e4
    got = ops.paged_decode_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), pt, seq_lens, active, **kw)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(got))


def test_block_choice_invariance(rng):
    """(pages_per_block, head_block) is a scheduling choice, not semantics:
    every tiling agrees up to online-softmax reassociation error. 16 KV
    heads leave two legal head blocks: 8 and the whole axis."""
    arrs = _make_case(rng, s=3, hq=32, hkv=16, hd=16, page_size=4,
                      pages_per_slot=8, n_pages=16, dtype=jnp.float32)
    q, k_pool, v_pool, pt, seq_lens, active = arrs
    kw = dict(page_size=4, backend="pallas_interpret")
    outs = [
        np.asarray(ops.paged_decode_attention(
            q, k_pool, v_pool, pt, seq_lens, active,
            pages_per_block=ppb, head_block=hb, **kw))
        for ppb, hb in ((1, 16), (2, 8), (4, 8), (8, 16), (3, 8))
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


# -- tuning table ---------------------------------------------------------------


def test_decode_attn_heuristic_fp8_doubles_pages():
    common = dict(pages_per_slot=64, n_kv_heads=8, page_size=16, head_dim=64)
    ppb8, _ = tuning.decode_attn_blocks(storage_dtype=jnp.float8_e4m3fn, **common)
    ppb16, _ = tuning.decode_attn_blocks(storage_dtype=jnp.bfloat16, **common)
    assert ppb8 == 2 * ppb16  # 1 B/elem pages: twice the pages per VMEM budget


def test_decode_attn_blocks_clamp():
    ppb, hb = tuning.decode_attn_blocks(
        pages_per_slot=3, n_kv_heads=5, page_size=8, head_dim=16,
        storage_dtype=jnp.float32, requested=(16, 4),
    )
    assert ppb <= 3 and 5 % hb == 0  # table width caps ppb; hb divides Hkv


def test_decode_attn_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_DECODE_ATTN_BLOCKS", "2,2")
    ppb, hb = tuning.decode_attn_blocks(
        pages_per_slot=8, n_kv_heads=4, page_size=8, head_dim=16,
        storage_dtype=jnp.float32,
    )
    assert (ppb, hb) == (2, 4)
    monkeypatch.setenv("REPRO_DECODE_ATTN_BLOCKS", "garbage")
    with pytest.warns(UserWarning, match="REPRO_DECODE_ATTN_BLOCKS"):
        ppb, hb = tuning.decode_attn_blocks(
            pages_per_slot=8, n_kv_heads=4, page_size=8, head_dim=16,
            storage_dtype=jnp.float32,
        )
    assert (ppb, hb) == (4, 4)  # falls back to the heuristic table


# -- end-to-end -----------------------------------------------------------------


def test_server_greedy_parity_with_kernel_backend():
    """Continuous batching with the pallas decode kernel must emit exactly
    the tokens the static path emits — the serving-level parity bar."""
    from repro.configs import get_config
    from repro.models import build
    from repro.serving import Server, ServerConfig, generate_static

    cfg = dataclasses.replace(get_config("granite-3-8b", smoke=True),
                              policy="fp32", kv_cache_dtype="fp32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    g = np.random.default_rng(7)
    prompts = [list(g.integers(0, cfg.vocab_size, size=n)) for n in (5, 9, 3)]
    server = Server(model, params, ServerConfig(
        num_slots=2, page_size=4, max_seq_len=24, prefill_chunk=8,
    ), backend="pallas_interpret")
    reqs = [server.submit(p, max_new_tokens=6) for p in prompts]
    results = server.run()
    for p, r in zip(prompts, reqs):
        ref, _ = generate_static(
            model, params, {"tokens": jnp.asarray([p], jnp.int32)},
            max_new_tokens=6,
        )
        assert results[r.rid].out_tokens == list(ref[0]), f"prompt len {len(p)}"


def test_pallas_decode_without_page_table_raises():
    """Under a Pallas backend a decode step runs the page-walk kernel; a
    caller that drops the page table gets an error, not the XLA gather."""
    cfg = attention.AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16)
    params = attention.init(jax.random.PRNGKey(0), 32, cfg, jnp.float32)
    pool = attention.init_paged_pool(5 * 4, cfg, jnp.float32)
    x = jnp.ones((2, 1, 32), jnp.float32)
    read_idx = jnp.arange(2 * 8, dtype=jnp.int32).reshape(2, 8)
    paged = attention.PagedInfo(
        write_idx=jnp.array([4, 12], jnp.int32), read_idx=read_idx,
        k_pos=jnp.where(jnp.arange(8)[None] <= 1, jnp.arange(8)[None],
                        attention.POS_SENTINEL),
        slots=jnp.arange(2, dtype=jnp.int32), starts=jnp.ones(2, jnp.int32),
        active=jnp.ones(2, bool),
    )
    positions = jnp.ones((2, 1), jnp.int32)
    eng = Engine(policy="fp32", backend="pallas_interpret")
    with pytest.raises(ValueError, match="pages"):
        attention.apply(params, x, positions, cfg, eng, cache=pool, paged=paged)
    out, _ = attention.apply(params, x, positions, cfg, eng.with_backend("xla"),
                             cache=pool, paged=paged)
    assert out.shape == (2, 1, 32)
