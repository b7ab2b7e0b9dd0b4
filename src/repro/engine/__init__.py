"""repro.engine — the public API of the RedMulE engine.

One :class:`Engine` handle bundles precision policy, execution backend,
tile selection and datapath parameters, and exposes every operation the
paper's datapath serves: ``matmul`` / ``linear`` (hybrid-FP8 differentiable
GEMM), ``gemm_op`` (all seven Table 1 semiring ops, differentiable via
tropical subgradients), and ``closure`` (semiring fixpoint by repeated
squaring). Ambient selection goes through the ``contextvars``-based
:func:`engine_scope`. See docs/DESIGN.md for the full API contract.

The pre-Engine surface (``repro.core.redmule.mp_matmul`` / ``linear`` /
``gemm_op`` / ``use_backend``) survives as deprecated shims over this
module.
"""
from repro.engine.closure import closure
from repro.engine.engine import (
    BACKENDS,
    DEFAULT_ENGINE,
    Engine,
    LayerWeight,
    ambient_engine,
    as_engine,
    current_engine,
    engine_scope,
    resolve_engine,
    set_ambient_engine,
)

__all__ = [
    "BACKENDS",
    "DEFAULT_ENGINE",
    "Engine",
    "LayerWeight",
    "ambient_engine",
    "as_engine",
    "closure",
    "current_engine",
    "engine_scope",
    "resolve_engine",
    "set_ambient_engine",
]
