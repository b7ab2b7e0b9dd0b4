"""Trace-time count of the weight preparation inside a jitted step.

A GEMM weight stored other than as the kernel reads it (bf16 where the
kernel takes E4M3, a tied table that must be transposed, a K or N the tile
pads) is prepared again by every call of the step that reads it. The model
reports each such call while the step is traced
(:func:`count_weight_prep`); a step factory opens a tally around the trace
(:func:`weight_prep_tally`) and keeps it per compiled step kind
(``EngineCore.weight_prep``). Nothing is counted at run time: the number is
a property of the compiled program, and weights prepared once at load
(``Transformer.serving_params``) bring a decode step's count to 0.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses


@dataclasses.dataclass
class WeightPrep:
    """GEMM calls of one traced step whose weight the step prepares, and
    the bytes that preparation reads and writes."""

    calls: int = 0
    bytes: int = 0


_TALLY: contextvars.ContextVar[WeightPrep | None] = contextvars.ContextVar(
    "repro_weight_prep", default=None
)


@contextlib.contextmanager
def weight_prep_tally():
    """Count the weight preparation traced inside the block into a fresh
    :class:`WeightPrep`; an enclosing tally does not see it."""
    tally = WeightPrep()
    token = _TALLY.set(tally)
    try:
        yield tally
    finally:
        _TALLY.reset(token)


def count_weight_prep(nbytes: int, calls: int = 1) -> None:
    """Record ``calls`` GEMM calls whose weight preparation moves ``nbytes``
    in all; nothing where ``nbytes`` is 0 or no tally is open."""
    tally = _TALLY.get()
    if tally is not None and nbytes:
        tally.calls += calls
        tally.bytes += nbytes
