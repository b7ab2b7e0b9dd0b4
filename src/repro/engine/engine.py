"""The ``Engine`` handle: one object, every RedMulE operation.

The paper's pitch is that one datapath serves plain GEMM, the Table 1
semiring GEMM-Ops, and mixed-precision training (Sec. 2.4, 4.2). This module
is the software mirror of that claim: an immutable, pytree-registerable
:class:`Engine` bundles everything a matrix operation needs —

  - the :class:`~repro.core.precision.PrecisionPolicy` (storage/compute/
    accumulate formats, the hybrid-FP8 training rule),
  - the execution backend (``xla`` | ``pallas`` | ``pallas_interpret``),
  - the Pallas tile selection (``block_m/n/k``; ``None`` defers to
    ``repro.kernels.tuning``),
  - the paper's datapath design parameters (L, H, P — consumed by the perf
    model and tile geometry, absorbing the old ``RedMulEConfig``),

and exposes the operations as methods: :meth:`Engine.matmul`,
:meth:`Engine.linear`, :meth:`Engine.gemm_op` (all seven Table 1 ops,
differentiable — see ``repro.engine.autodiff``), and :meth:`Engine.closure`
(semiring fixpoint by repeated squaring — see ``repro.engine.closure``).

Ambient selection uses :func:`engine_scope`, a ``contextvars``-based scope
(race-free under threads and asyncio, unlike the module global it replaces):

    eng = Engine(policy="tpu_hfp8", backend="pallas")
    with engine_scope(eng):
        ...  # current_engine() inside resolves to eng

Engines contain no arrays: as a pytree they flatten to zero leaves with the
engine itself as (hashable) aux data, so they can ride inside jit argument
pytrees, ``lax.scan`` closures and ``shard_map`` bodies as static structure.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.precision import PrecisionPolicy, TPU_BF16, get_policy
from repro.core.semiring import GemmOp

BACKENDS = ("xla", "pallas", "pallas_interpret")


def _check_backend(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; expected one of {BACKENDS}")
    return name


class LayerWeight(NamedTuple):
    """One layer's GEMM weight, left in the stack of every layer's.

    A serving step scans its stacked layers; a weight the scan sliced out
    would be copied out of the stack before each kernel call. Handed to
    :meth:`Engine.linear` as ``LayerWeight(stack, index)``, the kernel
    reads the layer's tiles in place. Forward only: serving steps take no
    gradients.
    """

    stack: jnp.ndarray  # (L, K, N)
    index: jnp.ndarray  # () int32

    @property
    def shape(self) -> tuple[int, ...]:
        return self.stack.shape[1:]

    @property
    def dtype(self):
        return self.stack.dtype


@dataclasses.dataclass(frozen=True)
class Engine:
    """Immutable handle for the RedMulE engine (numerics + execution)."""

    policy: PrecisionPolicy | str = TPU_BF16
    backend: str = "xla"
    # Pallas BlockSpec tiles; None defers to the repro.kernels.tuning layer.
    block_m: int | None = None
    block_n: int | None = None
    block_k: int | None = None
    # Paper datapath parameters (Sec. 4.1): L x H CE array, P pipe stages.
    L: int = 12
    H: int = 4
    P: int = 3

    def __post_init__(self):
        if isinstance(self.policy, str):
            object.__setattr__(self, "policy", get_policy(self.policy))
        _check_backend(self.backend)
        if self.backend == "pallas" and self.policy.compute == jnp.float16:
            # Mosaic on TPU v5e cannot legalize the fp16 casts of the
            # kernel's datapath ('tpu.pack_subelements').
            raise ValueError(
                f"policy {self.policy.name!r} computes in fp16, which the "
                "Pallas TPU kernel cannot compile on v5e; use backend='xla' "
                "or 'pallas_interpret', or a bf16-compute policy "
                "(tpu_hfp8, tpu_bf16)"
            )

    # -- geometry ----------------------------------------------------------
    @property
    def tile_cols(self) -> int:
        """H*(P+1): the column width of one datapath tile (paper Sec. 4.3)."""
        return self.H * (self.P + 1)

    @property
    def blocks(self) -> tuple[int | None, int | None, int | None]:
        return (self.block_m, self.block_n, self.block_k)

    # -- functional updates ------------------------------------------------
    def replace(self, **kw) -> "Engine":
        if isinstance(kw.get("policy"), str):
            kw["policy"] = get_policy(kw["policy"])
        return dataclasses.replace(self, **kw)

    def with_backend(self, backend: str) -> "Engine":
        return self.replace(backend=backend)

    def with_policy(self, policy: PrecisionPolicy | str) -> "Engine":
        return self.replace(policy=policy)

    # -- operations --------------------------------------------------------
    def matmul(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """z = a @ b under the policy, differentiable with the hybrid-FP8
        rule (E4M3 forward / E5M2 backward). a: (..., M, K); b: (K, N) or
        broadcast-batched (..., K, N)."""
        return _autodiff.mp_matmul(a, b, self)

    def linear(self, x: jnp.ndarray, w: jnp.ndarray | LayerWeight,
               b: jnp.ndarray | None = None) -> jnp.ndarray:
        """y = x @ w (+ b) through the engine. x: (..., K), w: (K, N), or
        a :class:`LayerWeight` in the forward storage format, read in
        place: the same operands and kernel call as :meth:`matmul`'s
        forward, not differentiable."""
        if not isinstance(w, LayerWeight):
            y = self.matmul(x, w)
        else:
            pol = self.policy
            if jnp.dtype(w.dtype) != jnp.dtype(pol.storage_fwd):
                raise ValueError(
                    f"a LayerWeight is read as stored: {w.dtype} is not the "
                    f"{pol.name} forward storage format {pol.storage_fwd}")
            y = _kernel_ops.gemm_op(
                x.astype(pol.compute).astype(pol.storage_fwd), w.stack,
                policy=pol, block_m=self.block_m, block_n=self.block_n,
                block_k=self.block_k, backend=self.backend,
                operand_quant=False, layer=w.index,
            )
        if b is not None:
            y = y + b.astype(y.dtype)
        return y

    def weight_prep_bytes(self, x: jnp.ndarray, w, *,
                          relaid: bool = False) -> int:
        """Bytes a product of x with the weight ``w`` moves to bring w into
        the form this engine's kernel reads (``kernels.ops``); 0 for a
        weight stored that way."""
        if isinstance(w, LayerWeight):
            w = jax.ShapeDtypeStruct(w.shape, w.dtype)
        return _kernel_ops.weight_prep_bytes(
            x, w, policy=self.policy, backend=self.backend,
            blocks=self.blocks, relaid=relaid,
        )

    def gemm_op(self, x: jnp.ndarray, w: jnp.ndarray,
                y: jnp.ndarray | None = None,
                op: str | GemmOp = "matmul") -> jnp.ndarray:
        """Full GEMM-Op surface (paper Table 1): Z = star(Y, star_k(circ(X, W))).

        Differentiable for every op: (mul, add) uses the hybrid-FP8 GEMM
        VJP; the semiring ops use tropical subgradients (argmin/argmax
        indicator routing) — see ``repro.engine.autodiff``.
        """
        return _autodiff.gemm_op(x, w, y, op, self)

    def closure(self, a: jnp.ndarray, op: str | GemmOp = "apsp", *,
                max_steps: int | None = None,
                include_diagonal: bool = True) -> jnp.ndarray:
        """Semiring closure a* by repeated squaring (APSP, max-capacity, ...).

        Runs D <- star(D, D circ-star D) under ``lax.while_loop`` with early
        exit at the fixpoint; ceil(log2(V-1)) engine calls worst-case.
        """
        return _closure_fn(self, a, op, max_steps=max_steps,
                           include_diagonal=include_diagonal)


# Engines flatten to zero leaves: pure static structure for jit/vmap/scan.
jax.tree_util.register_pytree_node(
    Engine,
    lambda e: ((), e),
    lambda aux, _: aux,
)


def as_engine(obj: Any) -> Engine:
    """Coerce an Engine / PrecisionPolicy / policy name into an Engine.

    A bare policy keeps the ambient engine's execution settings (backend,
    tiles) and swaps the numerics — the migration path for pre-Engine code
    that passed ``PrecisionPolicy`` objects around.
    """
    if isinstance(obj, Engine):
        return obj
    if isinstance(obj, PrecisionPolicy):
        return current_engine().replace(policy=obj)
    if isinstance(obj, str):
        return current_engine().replace(policy=get_policy(obj))
    raise TypeError(
        f"cannot interpret {type(obj).__name__} as an Engine; pass an "
        "Engine, a PrecisionPolicy, or a policy name"
    )


# ---------------------------------------------------------------------------
# Ambient engine: contextvars, not a module global — jit tracing happens at
# Python time, so a scope wrapping the traced region is race-free across
# threads and asyncio tasks (the future async serving path).
# ---------------------------------------------------------------------------

DEFAULT_ENGINE = Engine()

_AMBIENT: contextvars.ContextVar[Engine | None] = contextvars.ContextVar(
    "repro_engine_ambient", default=None
)


def ambient_engine() -> Engine | None:
    """The innermost active ``engine_scope`` engine, or None."""
    return _AMBIENT.get()


def current_engine(default: Engine | None = None) -> Engine:
    """Ambient engine, else ``default``, else :data:`DEFAULT_ENGINE`."""
    amb = _AMBIENT.get()
    if amb is not None:
        return amb
    return default if default is not None else DEFAULT_ENGINE


def set_ambient_engine(engine: Engine | None) -> Engine | None:
    """Set the ambient engine for the current context; returns the previous
    one. Prefer :func:`engine_scope`; this exists for the deprecated
    ``set_default_backend`` shim and REPL use."""
    prev = _AMBIENT.get()
    _AMBIENT.set(engine)
    return prev


@contextlib.contextmanager
def engine_scope(engine: Engine):
    """Scoped ambient engine (trace-time: wrap the code being jit-traced)."""
    if not isinstance(engine, Engine):
        engine = as_engine(engine)
    token = _AMBIENT.set(engine)
    try:
        yield engine
    finally:
        _AMBIENT.reset(token)


# Imported last: autodiff/closure are pure functions over Engine values and
# must not import this module at module scope (no cycle).
from repro.engine import autodiff as _autodiff  # noqa: E402
from repro.engine.closure import closure as _closure_fn  # noqa: E402
from repro.kernels import ops as _kernel_ops  # noqa: E402
