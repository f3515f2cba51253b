"""Operation and byte counts kept with the benchmark.

They are computed from what a cell declares (its write sets, its model's
sizes), never from the shapes the program hands its kernels, so a count
stays the same whatever implements the work.
"""
from __future__ import annotations

import numpy as np


def range_pages(lo, hi, page_words: int) -> int:
    """Pages of a page-aligned array that the word ranges [lo, hi) touch,
    summed over the workers."""
    lo, hi = np.asarray(lo), np.asarray(hi)
    return int(np.sum(np.where(hi > lo, (hi - 1) // page_words
                               - lo // page_words + 1, 0)))


def flush_min_bytes(written_cells: int) -> int:
    """The least bytes a barrier flush must move: one dirty bit per
    (worker, page) the iteration wrote, read once, and the candidate
    mask over the same cells written once."""
    return 2 * (-(-written_cells // 8))


def train_matmul_params(m: dict) -> int:
    """Parameters that multiply activations in a matmul: every layer's
    projections and the output head (the embedding is a lookup)."""
    L, d, f, V = (m["num_hidden_layers"], m["hidden_size"],
                  m["intermediate_size"], m["vocab_size"])
    Hq, Hkv, D = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    per_layer = d * Hq * D + 2 * d * Hkv * D + Hq * D * d + 3 * d * f
    return L * per_layer + d * V


def train_step_flops(m: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step, forward and backward: 6 per
    matmul parameter and token, plus attention's 12 * layers * heads *
    head_dim * seq per token (scores and values, causal mask not
    subtracted), as PaLM's MFU counts them.  Recomputation is not
    counted."""
    tokens = batch * seq
    attn = 12 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * m["head_dim"] * seq
    return float(tokens * (6 * train_matmul_params(m) + attn))
