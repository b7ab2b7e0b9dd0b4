"""The comparison that decides ``correct``: what the timed path produced,
against the plain float32 reference in ``reference/``.

A configuration file names its reference module with ``"reference":
"<module>"``, the file ``bench/reference/<module>.py`` of the run's checkout
(``decoder`` where the key is absent); a new architecture adds its own file.
The module imports nothing of the program and provides:

- ``Dims.from_config(conf)``: the widths the reference runs, read from the
  configuration file's published keys (never from ``program``), hashable;
- ``logits_at(dims, key, tokens, rows, cols, quant=None)``: float32 logits
  (N, V) at positions (rows[i], cols[i]) of the token rows (B, S), with
  weights drawn from ``key`` (``jax.random.PRNGKey(seed)``) as the program's
  ``model.init`` draws them, rounded to the served storage type and held in
  float32, every product at ``Precision.HIGHEST``, one layer's weights at a
  time so that it fits on the chip the program has left. ``quant`` names
  the control's lower precision (``"int4"``), computed in place of float32.

Served cells: for a sample of the requests the window finished, the
reference runs once over each prompt with its served tokens. A served token
is the program's greedy choice; its gap is how far the reference's logit of
that token lies below the reference's best logit at the same position. The
readings are the widest gap over the sample (``logit_gap``) and the mean
gap over its served tokens (``logit_gap_mean``); a cell's limits file names
those it compares.

Each cell's limits live in ``checks/<workload>.json``, with the readings
they were set from.
"""
from __future__ import annotations

import os

import numpy as np

import common
from common import ROOT, BenchError, load_json

PAD = 128  # rows of the reference batch are padded to a multiple of this


def limits(workload: str, root: str = ROOT) -> dict:
    path = os.path.join(root, "bench", "checks", workload + ".json")
    if not os.path.exists(path):
        raise BenchError(f"no limits for {workload!r} at {path}")
    return load_json(path)["limits"]


def served_positions(seqs):
    """Token rows (B, S) of prompt + served tokens, and the (row, column)
    of each served token's logits with the token itself."""
    longest = max(len(p) + len(s) for p, s in seqs)
    width = -(-longest // PAD) * PAD
    tokens = np.zeros((len(seqs), width), np.int32)
    rows, cols, served = [], [], []
    for r, (prompt, out) in enumerate(seqs):
        seq = list(prompt) + list(out)
        tokens[r, :len(seq)] = seq
        for j, tok in enumerate(out):
            rows.append(r)
            cols.append(len(prompt) - 1 + j)  # logits here chose out[j]
            served.append(tok)
    return tokens, np.array(rows), np.array(cols), np.array(served)


def serve_gaps(conf: dict, seed: int, seqs, control: str | None = None,
               root: str = ROOT) -> np.ndarray:
    """Gap of every served token below the reference's best logit, by the
    configuration's reference module under ``root``. With ``control``, the
    tokens judged are those the reference computed at that precision puts
    first at the same positions (the control's reading)."""
    import jax

    ref_mod = common.reference(conf, root)
    dims = ref_mod.Dims.from_config(conf)
    key = jax.random.PRNGKey(seed)
    tokens, rows, cols, served = served_positions(seqs)
    ref = np.asarray(ref_mod.logits_at(dims, key, tokens, rows, cols))
    if control is not None:
        low = np.asarray(ref_mod.logits_at(dims, key, tokens, rows, cols, quant=control))
        served = low.argmax(-1)
    return ref.max(-1) - ref[np.arange(len(served)), served]


def judge(readings: dict, lim: dict) -> tuple[bool, list[tuple[str, float, float]]]:
    """Every number against its limit: (correct, [(name, value, limit)])."""
    rows = [(name, float(readings[name]), float(lim[name]["limit"])) for name in lim]
    ok = all(np.isfinite(v) and v <= limit for _, v, limit in rows)
    return ok, rows
