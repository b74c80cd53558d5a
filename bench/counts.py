"""Operations and bytes that each share divides by, derived from shapes.

Counts are of the work the algorithm needs, not of what a program
happens to execute: a causal prefill needs half of the S x S score
matrix, and a decode step reads the pages it attends, not the whole pool.
A multiply-add is 2 operations.

The model counts are those of a dense GQA + SwiGLU decoder, which an
architecture's file (``archs/<model_type>.py``) takes where they fit.
Model sizes use the configuration file's keys (Hugging Face names):
``hidden_size`` d, ``intermediate_size`` f, ``num_hidden_layers`` L,
``num_attention_heads`` H, ``num_key_value_heads`` K, ``head_dim`` h,
``vocab_size`` V.
"""

from __future__ import annotations

from typing import Any, Dict

BF16_BYTES = 2


def dense_layer_params(c: Dict[str, Any]) -> int:
    """Weights of one dense GQA + SwiGLU decoder layer that multiply each
    token: q (d x Hh), k and v (d x Kh each), o (Hh x d), gate, up and
    down (3 d f).  Norm scales are elementwise and left out."""
    d, f, h = c["hidden_size"], c["intermediate_size"], c["head_dim"]
    nh, nk = c["num_attention_heads"], c["num_key_value_heads"]
    return d * nh * h + 2 * d * nk * h + nh * h * d + 3 * d * f


def attn_flops(c: Dict[str, Any], n_keys: float) -> float:
    """Score and value products of one query token against ``n_keys``
    keys in one layer: 2 H h n (q.k) + 2 H h n (p.v)."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"] * n_keys


def prefill_flops(c: Dict[str, Any], plen: int) -> float:
    """One request's prefill: every layer's weights for ``plen`` tokens,
    causal attention (token t attends t + 1 keys: sum = plen(plen+1)/2),
    and the head for the last token only (prefill returns one row of
    logits)."""
    L = c["num_hidden_layers"]
    keys = plen * (plen + 1) / 2.0
    return (L * (2.0 * dense_layer_params(c) * plen + attn_flops(c, keys))
            + 2.0 * c["hidden_size"] * c["vocab_size"])


def decode_flops(c: Dict[str, Any], ctx: int) -> float:
    """One decoded token at cache position ``ctx`` (it attends ctx + 1
    keys, its own included): every layer's weights, attention and the
    head."""
    L = c["num_hidden_layers"]
    return (L * (2.0 * dense_layer_params(c) + attn_flops(c, ctx + 1))
            + 2.0 * c["hidden_size"] * c["vocab_size"])


def page_bytes(c: Dict[str, Any], page_size: int) -> int:
    """Bytes of one KV page of one layer in bf16: keys and values,
    ``page_size`` positions x K heads x h."""
    return 2 * page_size * c["num_key_value_heads"] * c["head_dim"] * BF16_BYTES


def paged_decode_bytes(c: Dict[str, Any], page_size: int,
                       pages_walked: int) -> float:
    """Bytes the paged decode kernel must read over all layers, given the
    service's ``serve.pages_attended`` count (pages walked per slot,
    summed over ticks; the same pages in every layer).  Queries, the new
    cell and the output are under 1% of it and left out."""
    return float(c["num_hidden_layers"] * page_bytes(c, page_size)
                 * pages_walked)


# --- tiled QR (paper section 4.1; Buttari et al. 2009 counts) ----------------

def qr_task_flops(b: int) -> Dict[str, float]:
    """Operations of each tile task on b x b tiles, Householder form
    without the T factors: GEQRF factors a tile (4/3 b^3), LARFT applies
    its reflectors to a tile of the same row (2 b^3), TSQRF factors a
    triangle stacked on a tile (2 b^3), SSRFT applies those to a pair of
    tiles (4 b^3)."""
    b3 = float(b) ** 3
    return {"geqrf": 4.0 / 3.0 * b3, "larft": 2.0 * b3,
            "tsqrf": 2.0 * b3, "ssrft": 4.0 * b3}


def qr_task_counts(p: int) -> Dict[str, int]:
    """Tasks of a p x p tile QR: step k factors tile (k,k), updates the
    p-k-1 tiles to its right, factors the p-k-1 tiles below it, and
    updates the (p-k-1)^2 tiles of the trailing matrix."""
    r = range(p)
    return {"geqrf": p,
            "larft": sum(p - k - 1 for k in r),
            "tsqrf": sum(p - k - 1 for k in r),
            "ssrft": sum((p - k - 1) ** 2 for k in r)}


def qr_plan_flops(n: int, b: int) -> float:
    """All tile tasks of one n x n QR in b x b tiles; tends to the dense
    Householder count 4/3 n^3."""
    f = qr_task_flops(b)
    return sum(f[t] * k for t, k in qr_task_counts(n // b).items())
