"""Plain float32 reference of a Qwen3-style decoder, from the published
description (Qwen3 technical report; the ``Qwen3ForCausalLM`` config keys):

    h   = x + Wo . attn(rope(qnorm(h1 Wq)), rope(knorm(h1 Wk)), h1 Wv),
          h1 = rmsnorm(x) * ln1
    out = h + W_down (silu(h2 W_gate) * h2 W_up),  h2 = rmsnorm(h) * ln2

with grouped-query attention (query head ``j`` reads key/value head
``j // (heads / kv_heads)``), RMSNorm over the last axis with weight and
``rms_norm_eps``, qk-norm over each head's ``head_dim``, RoPE with the
rotate-half pairing (``x[:d/2]`` with ``x[d/2:]``) at ``rope_theta``, softmax
scaled by ``1/sqrt(head_dim)``, and logits ``rmsnorm(x) * ln_f`` times the
embedding (tied) or the head (untied).

It imports nothing of the program. Weights, caches and tokens come from
:mod:`bench.seeded`, the draws the program is given, upcast to float32. Matrix products run at ``Precision.HIGHEST`` (on a TPU a float32
product is otherwise computed in bfloat16). It runs one layer at a time, and
attention in blocks of queries, so that a published width fits one chip.

``prec="fp8"`` is the control: the same computation with both operands of
every product rounded to float8 (e4m3, one scale per tensor), the precision
below the served bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import seeded

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


def rope(x, pos, theta):
    """``x``: [..., n, heads, d]; ``pos``: [n] absolute positions."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv  # [n, d/2]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, n_past, prec):
    """One sequence. ``q``: [n, h, dh] at positions ``n_past + i``; ``k``,
    ``v``: [n_past + n, kh, dh], the past then the new keys. Causal over the
    new keys, every past key visible. Queries go in blocks of ``Q_BLOCK``."""
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


def layer(w, x, pos, past_k, past_v, m, prec):
    """One decoder layer over ``x`` [b, n, d] at positions ``pos`` [n], with
    ``past_k``/``past_v`` [b, t, kh, dh] (t may be 0). Returns the layer's
    output and its new keys and values [b, n, kh, dh] (after qk-norm and
    RoPE, as a cache holds them)."""
    h1 = rmsnorm(x, w["ln1"], m.eps)
    q = mm("bnd,dhk->bnhk", h1, w["wq"], prec)
    k = mm("bnd,dhk->bnhk", h1, w["wk"], prec)
    v = mm("bnd,dhk->bnhk", h1, w["wv"], prec)
    q = rope(rmsnorm(q, w["q_norm"], m.eps), pos, m.rope_theta)
    k = rope(rmsnorm(k, w["k_norm"], m.eps), pos, m.rope_theta)
    t = past_k.shape[1]
    keys = jnp.concatenate([past_k, k], 1)
    vals = jnp.concatenate([past_v, v], 1)
    o = jax.vmap(lambda a, b, c: attention(a, b, c, t, prec))(q, keys, vals)
    x = x + mm("bnhk,hkd->bnd", o, w["wo"], prec)
    h2 = rmsnorm(x, w["ln2"], m.eps)
    up = mm("bnd,df->bnf", h2, w["w_up"], prec)
    gate = mm("bnd,df->bnf", h2, w["w_gate"], prec)
    return x + mm("bnf,fd->bnd", jax.nn.silu(gate) * up, w["w_down"], prec), k, v


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


class Reference:
    """The reference for one configuration and seed, run at the precisions
    asked for (``"f32"``, and ``"fp8"`` for the control) side by side."""

    def __init__(self, m, seed: int, precisions=("f32",)):
        self.m = m
        self.precisions = tuple(precisions)
        self.wkey = seeded.stream(seed, seeded.WEIGHTS)
        self.ckey = seeded.stream(seed, seeded.CACHE)
        dt = jnp.dtype(m.dtype)  # drawn as the program is given them
        self._layer_w = jax.jit(lambda k: _f32(seeded.layer_weights(k, m, dt)))
        self._outer_w = jax.jit(lambda k: _f32(seeded.outer_weights(k, m, dt)))
        self._layer = jax.jit(partial(layer, m=m), static_argnames="prec")
        self._embed = jax.jit(lambda e, t: e[t])
        self._cache = jax.jit(partial(seeded.cache_layer, m=m, dtype=dt),
                              static_argnames=("batch", "length", "fill"))

    def cache(self, i: int, batch: int, length: int, fill: int):
        """Layer ``i``'s drawn cache, float32, cut to its ``fill`` positions."""
        k, v = self._cache(seeded.cache_key(self.ckey, i), batch=batch,
                           length=length, fill=fill)
        return k[:, :fill].astype(jnp.float32), v[:, :fill].astype(jnp.float32)

    def run(self, tokens, start: int, past=None, on_layer=None):
        """Run ``tokens`` [b, n] at positions ``start + i`` over the past
        given by ``past(i) -> (k, v)`` (None: no past). ``on_layer(i, kv)``
        receives each layer's new keys and values per precision. Returns the
        final hidden states per precision, [b, n, d], after ``ln_f``."""
        m = self.m
        outer = self._outer_w(self.wkey)
        tokens = jnp.asarray(tokens)
        x0 = self._embed(outer["embed"], tokens)
        xs = {p: x0 for p in self.precisions}
        pos = start + jnp.arange(tokens.shape[1])
        b = tokens.shape[0]
        empty = jnp.zeros((b, 0, m.kv_heads, m.head_dim), jnp.float32)
        for i in range(m.layers):
            w = self._layer_w(seeded.layer_key(self.wkey, i))
            pk, pv = past(i) if past is not None else (empty, empty)
            kv = {}
            for p in self.precisions:
                xs[p], k, v = self._layer(w, xs[p], pos, pk, pv, prec=p)
                kv[p] = (k, v)
            if on_layer is not None:
                on_layer(i, kv)
            del w, pk, pv, kv
        return {p: rmsnorm(x, outer["ln_f"], m.eps) for p, x in xs.items()}

    def logits(self, hidden: dict) -> dict:
        """Logits per precision from :meth:`run`'s hidden states."""
        outer = self._outer_w(self.wkey)
        w = outer["embed"].T if self.m.tied else outer["lm_head"]
        return {p: _head(h, w, prec=p) for p, h in hidden.items()}


@partial(jax.jit, static_argnames="prec")
def _head(h, w, prec):
    return mm("bnd,dv->bnv", h, w, prec)
