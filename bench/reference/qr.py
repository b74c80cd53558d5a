"""Plain references for the R factor of a QR factorization.

:func:`r_float64` is LAPACK's Householder QR through NumPy, in float64 on
the host.  :func:`r_householder` is a plain unblocked Householder QR in
JAX, column by column; its one matrix product per column (``v^T A``) runs
at the precision given, so with ``precision="high"`` it is the control:
float32 products made of three bfloat16 passes (``a_hi b_hi + a_hi b_lo
+ a_lo b_hi``, written out with the bfloat16 parts rounded on the bits so
every platform and compiler computes the same), the step below the
``HIGHEST`` float32 the configuration states.

R is unique up to the sign of each row; :func:`normalize` makes its
diagonal non-negative so two factors compare entry by entry.  Neither
function imports anything of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def r_float64(a) -> np.ndarray:
    return np.linalg.qr(np.asarray(a, np.float64), mode="r")


def normalize(r) -> np.ndarray:
    r = np.asarray(r, np.float64)
    s = np.where(np.diag(r) < 0, -1.0, 1.0)
    return np.triu(r) * s[:, None]


def rel_err(r, r_ref) -> float:
    """Frobenius distance of the normalized factors, relative to R."""
    a, b = normalize(r), normalize(r_ref)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _bf16(x):
    """x (finite float32) rounded to bfloat16 (to nearest, ties to even),
    kept as float32.  Done on the bits, so no compiler can skip it as
    excess precision."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _dot3(v, a):
    """v^T a in float32 from three bfloat16 passes: with ``x_hi`` the
    bfloat16 part of ``x`` and ``x_lo`` that of the rest, ``v_hi a_hi +
    v_hi a_lo + v_lo a_hi``.  Every operand holds bfloat16 values, so
    each product is exact at ``HIGHEST`` and the loss is the passes'."""
    vh = _bf16(v)
    ah = _bf16(a)
    vl = _bf16(v - vh)
    al = _bf16(a - ah)

    def d(x, y):
        return jnp.dot(x, y, precision=HI)
    return d(vh, ah) + d(vh, al) + d(vl, ah)


@functools.partial(jax.jit, static_argnames=("precision",))
def r_householder(a, precision: str = "highest"):
    """Upper-triangular R of the square float32 ``a``."""
    n = a.shape[0]
    rows = jnp.arange(n)

    def dot(v, m):
        if precision == "high":
            return _dot3(v, m)
        return jnp.dot(v, m, precision=HI)

    def body(k, a):
        x = jnp.where(rows >= k, a[:, k], 0.0)
        xk = x[k]
        alpha = -jnp.where(xk < 0, -1.0, 1.0) * jnp.sqrt(jnp.sum(x * x))
        v = x.at[k].add(-alpha)
        vv = jnp.sum(v * v)
        w = dot(v, a)
        return a - jnp.outer(v, w) * jnp.where(vv > 0, 2.0 / vv, 0.0)

    return jnp.triu(jax.lax.fori_loop(0, n, body, a.astype(jnp.float32)))
