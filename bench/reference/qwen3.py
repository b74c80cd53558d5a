"""Plain float32 reference of the Qwen3 dense decoder, and its control.

Written from the published description (Qwen3 technical report,
arXiv:2505.09388, and the Hugging Face ``Qwen3ForCausalLM``): pre-norm
RMSNorm blocks, grouped-query attention with RMSNorm on each head's query
and key before rotary embedding (rotate-half form), causal softmax
attention scaled by ``head_dim ** -0.5``, SwiGLU feed-forward, a final
RMSNorm and an output head.  Sizes, ``rope_theta``, ``rms_norm_eps`` and
whether the head is tied come from the configuration file as it is run.
It imports nothing of the program.

Weights are the benchmark's own (``drivers/serve.py make_weights``, each
leaf drawn as ``archs/qwen3.py init_leaf`` says), in the nested layout
the program takes: ``embed.tok`` (V, d), ``embed.head``
(d, V) where the head is untied (a tied head reads ``embed.tok``
transposed), ``final_norm.scale``, and under ``layers`` every tensor stacked on
a leading layer axis: ``attn_norm.scale``, ``attn.{wq,wk,wv,wo}``,
``attn.{q_norm,k_norm}.scale``, ``mlp_norm.scale``,
``mlp.{w_gate,w_up,w_down}``.

``mode="f32"`` computes every product in float32 at ``HIGHEST``
precision.  ``mode="fp8"`` is the control: the same forward with every
matrix product's operands rounded to float8 e4m3 (weights scaled per
tensor, activations per row), the precision step below the served
bfloat16.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 after scaling the largest magnitude along
    ``axis`` (None: the whole tensor) to the format's largest value."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, mode):
    """x (..., k) @ w (k, n) in float32; ``fp8`` rounds both first."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (B, T, heads, h), pos (T,): rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv              # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, w, dims, mode):
    """One decoder layer on h (B, T, d) float32."""
    nh, nk, hd, eps, theta = dims
    b, t, _ = h.shape
    pos = jnp.arange(t)
    x = _rms(h, w["attn_norm"]["scale"], eps)
    a = w["attn"]
    q = _mm(x, a["wq"], mode).reshape(b, t, nh, hd)
    k = _mm(x, a["wk"], mode).reshape(b, t, nk, hd)
    v = _mm(x, a["wv"], mode).reshape(b, t, nk, hd)
    q = _rope(_rms(q, a["q_norm"]["scale"], eps), pos, theta)
    k = _rope(_rms(k, a["k_norm"]["scale"], eps), pos, theta)
    rep = nh // nk
    q = q.reshape(b, t, nk, rep, hd)
    if mode == "fp8":
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, precision=HI) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if mode == "fp8":
        p = _q8(p, -1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v, precision=HI)
    h = h + _mm(o.reshape(b, t, nh * hd), a["wo"], mode)
    x = _rms(h, w["mlp_norm"]["scale"], eps)
    m = w["mlp"]
    g = _mm(x, m["w_gate"], mode)
    u = _mm(x, m["w_up"], mode)
    return h + _mm(jax.nn.silu(g) * u, m["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def hidden_at(weights, tokens, rows, cols, *, dims, mode):
    """Final-normed hidden states (P, d) at positions ``(rows, cols)``
    of ``tokens`` (B, T), layer by layer under ``lax.scan``."""
    eps = dims[3]
    h = weights["embed"]["tok"][tokens].astype(jnp.float32)

    def body(h, w):
        return _layer(h, w, dims, mode), None

    h, _ = jax.lax.scan(body, h, weights["layers"])
    return _rms(h[rows, cols], weights["final_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("mode", "chunk"))
def logit_stats(weights, hid, targets, *, mode, chunk=128):
    """Per row of ``hid`` (P, d): the best logit, the logit of
    ``targets`` and the argmax, in chunks of rows."""
    emb = weights["embed"]
    head = emb["head"] if "head" in emb else emb["tok"].T
    p = hid.shape[0]

    def one(args):
        x, tgt = args
        lg = _mm(x, head, mode)
        return (lg.max(-1), jnp.take_along_axis(lg, tgt[:, None], 1)[:, 0],
                lg.argmax(-1).astype(jnp.int32))

    out = jax.lax.map(one, (hid.reshape(p // chunk, chunk, -1),
                            targets.reshape(p // chunk, chunk)))
    return tuple(o.reshape(p) for o in out)


def dims_of(c: Dict[str, Any]) -> Tuple:
    return (c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], float(c["rms_norm_eps"]), float(c["rope_theta"]))


def served_gaps(weights, c: Dict[str, Any], seqs, *, control: bool = False,
                pad_t: int = 512, pad_p: int = 1024) -> Dict[str, np.ndarray]:
    """Gaps of served tokens below the reference's best logit.

    ``seqs`` is a list of ``(prompt, served)`` int arrays.  The reference
    reads each prompt with its served tokens (all but the last as input)
    once, and at the position before each served token takes
    ``best - logit(served)`` (``gap``).  With ``control`` it also runs the
    fp8 forward and takes the gap of the token that fp8 puts first
    (``control_gap``).  Returns arrays over all served tokens."""
    if bool(c["tie_word_embeddings"]) != ("head" not in weights["embed"]):
        raise ValueError("weights do not match tie_word_embeddings")
    dims = dims_of(c)
    t = max(len(p) + len(s) - 1 for p, s in seqs)
    t = -(-t // pad_t) * pad_t
    tokens = np.zeros((len(seqs), t), np.int32)
    rows, cols, tgts = [], [], []
    for b, (p, s) in enumerate(seqs):
        full = np.concatenate([p, s[:-1]]).astype(np.int32)
        tokens[b, :len(full)] = full
        n = len(p)
        rows += [b] * len(s)
        cols += list(range(n - 1, n - 1 + len(s)))
        tgts += list(s)
    n_tok = len(tgts)
    if n_tok > pad_p:
        raise ValueError(f"{n_tok} served tokens, more than {pad_p}")
    pad = pad_p - n_tok
    rows = jnp.asarray(rows + [0] * pad, jnp.int32)
    cols = jnp.asarray(cols + [0] * pad, jnp.int32)
    tgts = jnp.asarray(tgts + [0] * pad, jnp.int32)
    tokens = jnp.asarray(tokens)
    hid = hidden_at(weights, tokens, rows, cols, dims=dims, mode="f32")
    best, at_tgt, _ = logit_stats(weights, hid, tgts, mode="f32")
    out = {"gap": np.asarray(best - at_tgt)[:n_tok]}
    if control:
        hid8 = hidden_at(weights, tokens, rows, cols, dims=dims, mode="fp8")
        _, _, top8 = logit_stats(weights, hid8, tgts, mode="fp8")
        _, at_top8, _ = logit_stats(weights, hid, top8, mode="f32")
        out["control_gap"] = np.asarray(best - at_top8)[:n_tok]
    return out
