"""The operations and bytes a step requires, from the configuration's widths
and the step's token counts and contexts, never from the compiled program:
a roofline share then reads the same work whatever implements it.

- A matrix product counts at its logical M, N and K, with no padding, and
  2 M N K operations.
- A mixture of experts counts the ``num_experts_per_tok`` experts each token
  is routed to, not all of them.
- A step reads each weight it needs once, at its stored width (bfloat16,
  2 bytes); the KV cache is read at its stored width (E4M3, 1 byte) over the
  live context only.
- The GEMM kernel's own work counts its operands at the width the engine
  hands the kernel (E4M3, 1 byte each; the cast from the stored weights runs
  outside the kernel) and its output at 2 bytes.
"""
from __future__ import annotations

import dataclasses

WEIGHT_BYTES = 2  # bfloat16 storage of the served weights
KV_BYTES = 1  # E4M3 KV pages
OPERAND_BYTES = 1  # E4M3 (forward) / E5M2 (backward) GEMM operands
OUT_BYTES = 2  # bfloat16 GEMM output


@dataclasses.dataclass(frozen=True)
class Widths:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    experts: int = 0
    top_k: int = 0

    @classmethod
    def of(cls, conf: dict) -> "Widths":
        return cls(
            layers=conf["num_hidden_layers"], d=conf["hidden_size"],
            heads=conf["num_attention_heads"], kv_heads=conf["num_key_value_heads"],
            head_dim=conf.get("head_dim", conf["hidden_size"] // conf["num_attention_heads"]),
            ff=conf["intermediate_size"], vocab=conf["vocab_size"],
            experts=conf.get("num_local_experts", 0),
            top_k=conf.get("num_experts_per_tok", 0),
        )

    def projections(self) -> list[tuple[int, int]]:
        """(K, N) of each weight matrix one token multiplies in one layer."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        mats = [(self.d, q), (self.d, kv), (self.d, kv), (q, self.d)]
        n_ffn = self.top_k if self.experts else 1
        return mats + n_ffn * [(self.d, self.ff), (self.d, self.ff), (self.ff, self.d)]

    def layer_weights_read(self, tokens: int) -> int:
        """Weight elements one layer must read for ``tokens`` tokens: every
        matrix, with at least ``top_k`` experts and at most all of them."""
        attn = sum(k * n for k, n in self.projections()[:4])
        if not self.experts:
            return attn + 3 * self.d * self.ff
        held = min(self.experts, self.top_k * max(tokens, 1))
        return attn + held * 3 * self.d * self.ff + self.d * self.experts


def gemm(m: int, k: int, n: int, a_bytes=OPERAND_BYTES, b_bytes=OPERAND_BYTES,
         out_bytes=OUT_BYTES) -> tuple[float, float]:
    """(operations, bytes) of one (m, k) @ (k, n) product."""
    return 2.0 * m * k * n, float(m * k * a_bytes + k * n * b_bytes + m * n * out_bytes)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: operations at peak or bytes at
    full bandwidth, whichever is longer."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def attention_pairs(start: int, n: int) -> int:
    """(query, key) pairs of a causal chunk of ``n`` tokens from ``start``."""
    return n * start + n * (n + 1) // 2


def attention_gemms(w: Widths, n: int, ctx: int, pairs: int) -> list:
    """Scores and values of one KV head: its group's ``n`` query rows
    against ``ctx`` keys, over the ``pairs`` causal (query, key) pairs."""
    g, hd = w.heads // w.kv_heads, w.head_dim
    scores = (2.0 * hd * g * pairs,
              float(g * n * hd + ctx * hd) * OPERAND_BYTES + g * pairs * OUT_BYTES)
    values = (2.0 * hd * g * pairs,
              float(g * pairs + ctx * hd) * OPERAND_BYTES + g * n * hd * OUT_BYTES)
    return [scores, values]


def decode_step(w: Widths, contexts: list[int]) -> dict:
    """One decode step over ``len(contexts)`` rows; ``contexts[i]`` counts
    the tokens row i attends to, its new one included."""
    rows = len(contexts)
    ctx = sum(contexts)
    per_tok = sum(k * n for k, n in w.projections())
    attn_flops = 4.0 * w.heads * w.head_dim * ctx  # scores and values
    kv_read = 2.0 * w.kv_heads * w.head_dim * ctx * KV_BYTES
    flops = w.layers * (2.0 * rows * per_tok + attn_flops) + 2.0 * rows * w.d * w.vocab
    weights = w.layers * w.layer_weights_read(rows) + w.vocab * w.d
    step_bytes = weights * WEIGHT_BYTES + w.layers * kv_read
    gemm_s = [gemm(rows, k, n) for k, n in w.projections()]
    return {
        "flops": flops,
        "bytes": step_bytes,
        "gemms": w.layers * gemm_s + [gemm(rows, w.d, w.vocab)],
        "attn": (w.layers * attn_flops,
                 w.layers * (kv_read + 2.0 * rows * w.heads * w.head_dim * OUT_BYTES)),
    }


def prefill_chunk(w: Widths, start: int, n: int) -> dict:
    """One prefill chunk of ``n`` prompt tokens from position ``start``; the
    logits of its last token only."""
    pairs = attention_pairs(start, n)
    per_tok = sum(k * kk for k, kk in w.projections())
    attn_flops = 4.0 * w.heads * w.head_dim * pairs
    flops = w.layers * (2.0 * n * per_tok + attn_flops) + 2.0 * w.d * w.vocab
    kv = 2.0 * w.kv_heads * w.head_dim * (start + n) * KV_BYTES
    weights = w.layers * w.layer_weights_read(n) + w.vocab * w.d
    gemms = [gemm(n, k, kk) for k, kk in w.projections()]
    per_layer = gemms + w.kv_heads * attention_gemms(w, n, start + n, pairs)
    return {
        "flops": flops,
        "bytes": weights * WEIGHT_BYTES + w.layers * kv,
        "gemms": w.layers * per_layer + [gemm(1, w.d, w.vocab)],
    }


def gemms_roofline_s(gemms, peak: dict) -> float:
    """Each product at its own roofline, summed: every kernel call takes at
    least its own operations or bytes."""
    return sum(roofline_s(f, b, peak) for f, b in gemms)
