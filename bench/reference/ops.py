"""What every family's plain reference shares: matrix products in float32 at
``Precision.HIGHEST`` (on a TPU a float32 product is otherwise computed in
bfloat16), or with both operands rounded to float8 for the control
(``prec="fp8"``: e4m3, one scale per tensor, the precision below the served
bfloat16); RMSNorm; and grouped-query attention in blocks of queries, so that
a published width fits one chip. It imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
NEG = -1e30


def fp8(x):
    """``x`` rounded to float8 e4m3 under one per-tensor scale, in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(eq, a, b, prec):
    if prec == "fp8":
        a, b = fp8(a), fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def attention(q, k, v, n_past, prec):
    """One sequence. ``q``: [n, h, dh] at positions ``n_past + i``; ``k``,
    ``v``: [n_past + n, kh, dh], the past then the new keys. Query head ``j``
    reads key/value head ``j // (h / kh)``; the softmax is scaled by
    ``1/sqrt(dh)``. Causal over the new keys, every past key visible.
    Queries go in blocks of ``Q_BLOCK``."""
    n, h, dh = q.shape
    kh = k.shape[1]
    g = h // kh
    qb = min(n, Q_BLOCK)
    pad = (-n) % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, kh, g, dh)
    nb = qp.shape[0]
    kidx = jnp.arange(k.shape[0])

    @jax.checkpoint  # a backward pass recomputes one block at a time
    def block(args):
        qc, b = args
        qi = n_past + b * qb + jnp.arange(qb)  # absolute query positions
        s = mm("qkgd,tkd->kgqt", qc, k, prec) / np.sqrt(dh)
        s = jnp.where(kidx[None, None, None, :] <= qi[None, None, :, None], s, NEG)
        p = jax.nn.softmax(s, -1)
        return mm("kgqt,tkd->qkgd", p, v, prec)

    o = jax.lax.map(block, (qp, jnp.arange(nb)))
    return o.reshape(nb * qb, h, dh)[:n]


def upcast(tree):
    """Every leaf of ``tree`` in float32."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)
