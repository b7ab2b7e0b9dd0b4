"""Trace-time contracts for the serving steps of every zoo architecture.

The continuous-batching stack leans on invariants that are checkable
*without running anything* — the software analogues of RedMulE's statically
provable no-stall schedule. For every decoder-only arch this module
abstract-traces (``jax.eval_shape`` / ``jax.make_jaxpr``) the three serving
step kinds the scheduler drives — prefill chunk, batched multi-slot
prefill, all-slots decode — plus the speculative verify step, against ShapeDtypeStruct stand-ins (no weights, no device memory),
and asserts:

1. **static shapes** — each step traces at a fixed input signature and
   produces fp32 logits of the documented shape; a data-dependent shape
   aborts the trace and is reported as a violation;
2. **pools are shape-preserving** — the output StateStore pytree has
   exactly the input's structure, shapes and dtypes (a step that grows or
   retypes a pool would silently recompile every call);
3. **backend-conditional lowering** — the traced jaxpr contains a
   ``pallas_call`` iff the engine backend is a pallas one;
4. **fp8 storage discipline** — with ``kv_cache_dtype="e4m3"`` every KV
   pool leaf stays ``float8_e4m3fn`` in AND out, and any fp8-storage
   precision policy accumulates in fp32 (the paper's fp8-storage /
   wide-accumulate split);
5. **bounded compile count** — the batched-prefill row bucketing maps
   every possible group size into ``P_BUCKETS``, so the number of compiled
   signatures is bounded by ``len(P_BUCKETS)``.

An optional HBM-bytes budget reuses ``roofline/hlo_cost.py``: the decode
step is actually compiled (CPU backend) and its fusion-aware HBM traffic
per step must not exceed the budget.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.models import build
from repro.serving.cache import _is_kv_leaf
from repro.serving.engine import P_BUCKETS, EngineCore
from repro.serving.steps import make_paged_serve_steps, make_spec_verify_steps

try:  # jax >= 0.4.36 canonical home; fall back for older trees
    from jax.extend import core as _jcore
except ImportError:  # pragma: no cover
    from jax import core as _jcore

# Contract-trace geometry: tiny but structurally faithful (multiple slots,
# multiple pages per slot, a chunk smaller than the prompt, verify width
# k+1 > 1). Shapes only — never allocated.
NUM_SLOTS = 4
PAGE_SIZE = 8
PAGES_PER_SLOT = 4
NUM_PAGES = NUM_SLOTS * PAGES_PER_SLOT + 1  # + the null page
CHUNK = 8
VERIFY_T = 4  # draft depth k=3 -> k+1 scored positions

# The serving steps the scheduler can drive, with their documented logits
# contracts (shape is resolved per-arch below).
STEP_KINDS = ("prefill_chunk", "prefill_batch", "decode", "verify")


@dataclasses.dataclass(frozen=True)
class ContractViolation:
    arch: str
    backend: str
    step: str
    contract: str
    detail: str

    def __str__(self) -> str:
        return (
            f"{self.arch} [{self.backend}] {self.step}: "
            f"{self.contract} — {self.detail}"
        )


def _iter_jaxprs(obj):
    if isinstance(obj, _jcore.Jaxpr):
        yield obj
    elif isinstance(obj, _jcore.ClosedJaxpr):
        yield obj.jaxpr
    elif isinstance(obj, (list, tuple)):
        for o in obj:
            yield from _iter_jaxprs(o)


def jaxpr_has_pallas_call(jaxpr) -> bool:
    """Recursively scan a (Closed)Jaxpr for a ``pallas_call`` primitive."""
    for j in _iter_jaxprs(jaxpr):
        for eqn in j.eqns:
            if "pallas_call" in eqn.primitive.name:
                return True
            for v in eqn.params.values():
                if any(jaxpr_has_pallas_call(s) for s in _iter_jaxprs(v)):
                    return True
    return False


def _leaf_specs(tree):
    return [
        (jax.tree_util.keystr(path), tuple(leaf.shape), jnp.dtype(leaf.dtype))
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]


def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


class _BucketProbe:
    """Minimal stand-in carrying the one field ``EngineCore``'s bucketing
    reads, so the contract exercises the REAL policy methods (borrowed as
    class attributes below — ``bucket_for`` calls ``self.allowed_buckets``)."""

    allowed_buckets = EngineCore.allowed_buckets
    bucket_for = EngineCore.bucket_for

    def __init__(self, num_slots: int):
        self.config = dataclasses.make_dataclass(
            "Cfg", [("num_slots", int)]
        )(num_slots)


def check_bucket_policy(num_slots: int = NUM_SLOTS) -> list[str]:
    """Every possible prefill group size 1..num_slots must bucket into
    ``P_BUCKETS``; the distinct-signature count is bounded by its length."""
    probe = _BucketProbe(num_slots)
    problems: list[str] = []
    allowed = probe.allowed_buckets()
    if not set(allowed) <= set(P_BUCKETS):
        problems.append(f"allowed buckets {allowed} escape P_BUCKETS {P_BUCKETS}")
    seen = set()
    for n in range(1, num_slots + 1):
        b = probe.bucket_for(n)
        seen.add(b)
        if b not in P_BUCKETS:
            problems.append(f"group size {n} bucketed to {b} ∉ P_BUCKETS")
    if len(seen) > len(P_BUCKETS):
        problems.append(
            f"{len(seen)} distinct batched-prefill signatures > "
            f"len(P_BUCKETS) = {len(P_BUCKETS)}"
        )
    return problems


def _build_model(arch: str, *, backend: Optional[str], fp8_kv: bool,
                 smoke: bool):
    cfg = get_config(arch, smoke=smoke)
    repl = {}
    if backend is not None:
        repl["backend"] = backend
    if fp8_kv:
        repl["kv_cache_dtype"] = "e4m3"
    if repl:
        cfg = dataclasses.replace(cfg, **repl)
    return cfg, build(cfg)


def _step_inputs(model, params, pools, vocab: int):
    """(step_name -> (fn, args, expected_logits_shape)) for one arch."""
    prefill_chunk, prefill_batch, decode = make_paged_serve_steps(
        model, page_size=PAGE_SIZE)
    verify, _commit = make_spec_verify_steps(model, page_size=PAGE_SIZE)
    i32, b1 = jnp.int32, jnp.bool_
    p_max = EngineCore.allowed_buckets(_BucketProbe(NUM_SLOTS))[-1]
    row = _spec((PAGES_PER_SLOT,), i32)
    scalar = _spec((), i32)
    table = _spec((NUM_SLOTS, PAGES_PER_SLOT), i32)
    lens = _spec((NUM_SLOTS,), i32)
    act = _spec((NUM_SLOTS,), b1)
    return {
        "prefill_chunk": (
            prefill_chunk,
            (params, _spec((1, CHUNK), i32), pools, row, scalar, scalar,
             scalar),
            (1, vocab),
        ),
        "prefill_batch": (
            prefill_batch,
            (params, _spec((p_max, CHUNK), i32), pools,
             _spec((p_max, PAGES_PER_SLOT), i32), _spec((p_max,), i32),
             _spec((p_max,), i32), _spec((p_max,), i32), _spec((p_max,), b1)),
            (p_max, vocab),
        ),
        "decode": (
            decode,
            (params, _spec((NUM_SLOTS, 1), i32), pools, table, lens, act),
            (NUM_SLOTS, vocab),
        ),
        "verify": (
            verify,
            (params, _spec((NUM_SLOTS, VERIFY_T), i32), pools, table, lens,
             lens, act),
            (NUM_SLOTS, VERIFY_T, vocab),
        ),
    }


def check_arch(arch: str, *, backend: Optional[str] = None,
               fp8_kv: bool = False, smoke: bool = True,
               hbm_budget_bytes: Optional[float] = None,
               steps: Sequence[str] = STEP_KINDS) -> list[ContractViolation]:
    """All step contracts for one arch; empty list = clean.

    Non-CB architectures (enc-dec, VLM) are vacuously clean — they serve
    through the static path, which has no paged step contract.
    """
    cfg, model = _build_model(arch, backend=backend, fp8_kv=fp8_kv,
                              smoke=smoke)
    bname = cfg.backend
    out: list[ContractViolation] = []

    def bad(step, contract, detail):
        out.append(ContractViolation(arch, bname, step, contract, detail))

    if not model.supports_cb():
        return out

    # fp8 policy discipline holds whether or not pools are fp8.
    policy = model.engine.policy
    if policy.fp8_storage and jnp.dtype(policy.acc) != jnp.dtype(jnp.float32):
        bad("*", "fp8-accumulation",
            f"policy {policy.name} stores fp8 but accumulates in "
            f"{jnp.dtype(policy.acc).name}, not fp32")

    try:
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        pools = jax.eval_shape(
            lambda: model.init_state_store(NUM_SLOTS, NUM_PAGES, PAGE_SIZE)
        )
    except Exception as e:  # noqa: BLE001 — report, don't crash the CLI
        bad("init", "static-shapes", f"abstract init failed: {e!r}")
        return out

    if cfg.kv_cache_dtype == "e4m3":
        want = jnp.dtype(jnp.float8_e4m3fn)
        for path, leaf in jax.tree_util.tree_flatten_with_path(pools)[0]:
            if _is_kv_leaf(path) and jnp.dtype(leaf.dtype) != want:
                bad("pools", "fp8-storage",
                    f"KV leaf {jax.tree_util.keystr(path)} is "
                    f"{jnp.dtype(leaf.dtype).name}, expected e4m3")

    pools_sig = _leaf_specs(pools)
    expect_pallas = "pallas" in bname
    step_map = _step_inputs(model, params, pools, cfg.vocab_size)
    for step in steps:
        if step not in step_map:
            continue
        fn, args, logits_shape = step_map[step]
        try:
            jaxpr = jax.make_jaxpr(fn)(*args)
            logits_aval, pools_out = jax.eval_shape(fn, *args)
        except Exception as e:  # noqa: BLE001
            bad(step, "static-shapes", f"abstract trace failed: {e!r}")
            continue
        if tuple(logits_aval.shape) != logits_shape:
            bad(step, "static-shapes",
                f"logits shape {tuple(logits_aval.shape)}, contract says "
                f"{logits_shape}")
        if jnp.dtype(logits_aval.dtype) != jnp.dtype(jnp.float32):
            bad(step, "static-shapes",
                f"logits dtype {jnp.dtype(logits_aval.dtype).name}, "
                "contract says float32 (sampling filters assume it)")
        if _leaf_specs(pools_out) != pools_sig:
            got, want_ = _leaf_specs(pools_out), pools_sig
            diff = [
                f"{g} != {w}" for g, w in zip(got, want_) if g != w
            ] or [f"{len(got)} leaves vs {len(want_)}"]
            bad(step, "pools-preserved",
                "output pools differ from input: " + "; ".join(diff[:3]))
        has_pallas = jaxpr_has_pallas_call(jaxpr)
        if has_pallas != expect_pallas:
            bad(step, "backend-conditional-pallas",
                f"pallas_call {'present' if has_pallas else 'absent'} with "
                f"backend={bname}")

    for problem in check_bucket_policy(NUM_SLOTS):
        bad("prefill_batch", "bounded-signatures", problem)

    if hbm_budget_bytes is not None and "decode" in steps:
        fn, args, _ = step_map["decode"]
        got = step_hbm_bytes(fn, *args)
        if got > hbm_budget_bytes:
            bad("decode", "hbm-budget",
                f"{got / 1e6:.2f} MB per step > budget "
                f"{hbm_budget_bytes / 1e6:.2f} MB")
    return out


def step_hbm_bytes(fn, *arg_specs) -> float:
    """Fusion-aware HBM bytes of one compiled step, via the scan-aware HLO
    cost model (``repro.roofline.hlo_cost``). Compiles for the local
    backend — CPU is fine; the byte model is backend-portable."""
    from repro.roofline import hlo_cost

    compiled = jax.jit(fn).lower(*arg_specs).compile()
    return hlo_cost.analyze(compiled.as_text()).bytes


def cb_archs() -> list[str]:
    """Zoo archs served by continuous batching (decoder-only families)."""
    out = []
    for arch in ARCH_IDS:
        cfg = get_config(arch, smoke=True)
        if not cfg.is_encoder_decoder and cfg.family not in ("vlm", "audio"):
            out.append(arch)
    return out


def check_zoo(*, backends: Sequence[str] = ("xla", "pallas_interpret"),
              archs: Optional[Sequence[str]] = None,
              fp8_kv_variants: bool = True,
              hbm_budget_bytes: Optional[float] = None,
              ) -> tuple[list[ContractViolation], int]:
    """Run every contract over the zoo. Returns (violations, n_checked)
    where n_checked counts (arch, backend, variant) cells traced."""
    violations: list[ContractViolation] = []
    checked = 0
    for arch in (archs if archs is not None else cb_archs()):
        for backend in backends:
            violations.extend(check_arch(
                arch, backend=backend,
                hbm_budget_bytes=hbm_budget_bytes if backend == "xla" else None,
            ))
            checked += 1
        if fp8_kv_variants:
            cfg = get_config(arch, smoke=True)
            model = build(cfg)
            if model.supports_cb() and model.cb_profile().needs_kv_pages:
                violations.extend(check_arch(arch, fp8_kv=True))
                checked += 1
    return violations, checked
