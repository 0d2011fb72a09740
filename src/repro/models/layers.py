"""Shared neural-net layers (pure functional JAX).

Conventions
-----------
* Params are built as :class:`Param` leaves carrying ``(value, logical_axes)``;
  ``split_params`` separates them into a value tree + spec tree. Logical axes
  are resolved to mesh ``PartitionSpec`` s by ``repro.sharding.plan``.
* Layer-stacked params carry a leading ``"layers"`` axis and are consumed by
  ``jax.lax.scan`` so the HLO is depth-independent.
* Attention is *chunked flash-style* (two-level scan, online softmax, f32
  accumulators) so 32k-token prefill never materialises an S x S matrix.
  This jnp implementation is also the oracle for ``kernels/flash_attention``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Param plumbing
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Param:
    value: jax.Array
    axes: Tuple[Optional[str], ...]


jax.tree_util.register_pytree_node(
    Param,
    lambda p: ((p.value,), tuple(p.axes)),
    lambda aux, ch: Param(ch[0], aux),
)


def _is_param(x) -> bool:
    return isinstance(x, Param)


def split_params(tree):
    """(tree of Param) -> (tree of arrays, tree of logical-axis tuples)."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=_is_param)
    specs = jax.tree.map(lambda p: tuple(p.axes), tree, is_leaf=_is_param)
    return values, specs


def par(key, shape, axes, dtype, scale: float = 0.02) -> Param:
    assert len(shape) == len(axes), (shape, axes)
    v = scale * jax.random.normal(key, shape, dtype=jnp.float32)
    return Param(v.astype(dtype), tuple(axes))


def ones(shape, axes, dtype) -> Param:
    return Param(jnp.ones(shape, dtype=dtype), tuple(axes))


def zeros(shape, axes, dtype) -> Param:
    return Param(jnp.zeros(shape, dtype=dtype), tuple(axes))


def keygen(key):
    while True:
        key, sub = jax.random.split(key)
        yield sub


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(dt) * w


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., s, h, d]; positions: [..., s] (absolute token positions)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., :, None].astype(jnp.float32) * inv_freq  # [..., s, d/2]
    ang = ang[..., :, None, :]  # broadcast over heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked flash-style attention (GQA-aware, causal / sliding-window)
# ---------------------------------------------------------------------------
NEG_INF = -1e30


def _block_mask(qpos, kpos, causal: bool, window: Optional[int]):
    """[qc, kc] additive mask in f32."""
    m = jnp.zeros((qpos.shape[0], kpos.shape[0]), jnp.float32)
    if causal:
        m = jnp.where(qpos[:, None] >= kpos[None, :], m, NEG_INF)
    if window is not None:
        m = jnp.where(qpos[:, None] - kpos[None, :] < window, m, NEG_INF)
    return m


def chunked_attention(
    q: jax.Array,  # [b, sq, h, d]
    k: jax.Array,  # [b, sk, kh, d]
    v: jax.Array,  # [b, sk, kh, d]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset=0,  # absolute position of q[0] (decode: cache length)
    q_chunk: int = 512,
    k_chunk: int = 512,
) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    g = h // kh
    q_chunk = min(q_chunk, sq)
    k_chunk = min(k_chunk, sk)
    # pad to multiples
    def pad_to(x, c, axis):
        s = x.shape[axis]
        r = (-s) % c
        if r == 0:
            return x, s
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, r)
        return jnp.pad(x, pad), s

    q_, _ = pad_to(q, q_chunk, 1)
    k_, _ = pad_to(k, k_chunk, 1)
    v_, _ = pad_to(v, k_chunk, 1)
    nq, nk = q_.shape[1] // q_chunk, k_.shape[1] // k_chunk
    scale = 1.0 / np.sqrt(d)

    # [b, kh, g, s, d] grouped layout (no kv repeat materialised)
    qg = q_.reshape(b, nq, q_chunk, kh, g, d).transpose(1, 0, 3, 4, 2, 5)  # [nq,b,kh,g,qc,d]
    kg = k_.reshape(b, nk, k_chunk, kh, d).transpose(1, 0, 3, 2, 4)  # [nk,b,kh,kc,d]
    vg = v_.reshape(b, nk, k_chunk, kh, d).transpose(1, 0, 3, 2, 4)

    kpos_all = jnp.arange(nk * k_chunk).reshape(nk, k_chunk)
    kvalid = kpos_all < sk  # padded keys are invalid

    def q_step(_, qi):
        qc, qidx = qi
        qpos = q_offset + qidx * q_chunk + jnp.arange(q_chunk)

        @jax.checkpoint  # flash bwd: recompute P per kv block, never stack it
        def kv_step(carry, ki):
            m, l, acc = carry
            kc, vc, kpos, kval = ki
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qc, kc, preferred_element_type=jnp.float32)
            s = s * scale
            mask = _block_mask(qpos, kpos, causal, window)
            mask = jnp.where(kval[None, :], mask, NEG_INF)
            s = s + mask  # [b,kh,g,qc,kc] + [qc,kc]
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum("bkgqc,bkcd->bkgqd", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kh, g, q_chunk, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kg, vg, kpos_all, kvalid))
        l = jnp.where(l == 0.0, 1.0, l)
        o = (acc / l[..., None]).astype(q.dtype)  # [b,kh,g,qc,d]
        return None, o

    # flash-style memory: recompute the kv scan in backward instead of saving
    # per-block probabilities (otherwise AD stores the full S x S matrix)
    q_step = jax.checkpoint(q_step)
    _, out = jax.lax.scan(q_step, None, (qg, jnp.arange(nq)))
    # out: [nq, b, kh, g, qc, d] -> [b, sq, h, d]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * q_chunk, h, d)
    return out[:, :sq]


def chunked_attention_tri(
    q: jax.Array,  # [b, s, h, d]
    k: jax.Array,  # [b, s, kh, d]
    v: jax.Array,
    *,
    window: Optional[int] = None,
    chunk: int = 512,
) -> jax.Array:
    """Causal self-attention over a *static lower-triangular pair list*.

    The plain two-level scan computes all nq x nk blocks and masks half of
    them; here the scan runs over exactly the (qi, ki<=qi) block pairs (a
    static Python list), so fully-masked blocks are never computed:
    ~0.5x attention FLOPs for causal, O(s*w) for sliding-window (band pairs).
    Rows are qi-major; each step updates the row's online-softmax state and
    (re)writes the row output — the final write per row wins.
    """
    b, s, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n = q.shape[1] // chunk
    scale = 1.0 / np.sqrt(d)
    qg = q.reshape(b, n, chunk, kh, g, d).transpose(1, 0, 3, 4, 2, 5)  # [n,b,kh,g,C,d]
    kg = k.reshape(b, n, chunk, kh, d).transpose(1, 0, 3, 2, 4)  # [n,b,kh,C,d]
    vg = v.reshape(b, n, chunk, kh, d).transpose(1, 0, 3, 2, 4)

    w_chunks = None if window is None else (window + chunk - 1) // chunk
    pairs = [(qi, ki) for qi in range(n) for ki in range(n)
             if ki <= qi and (w_chunks is None or qi - ki <= w_chunks)]
    qi_a = jnp.asarray([p[0] for p in pairs], jnp.int32)
    ki_a = jnp.asarray([p[1] for p in pairs], jnp.int32)
    first_a = jnp.asarray([i == 0 or pairs[i][0] != pairs[i - 1][0]
                           for i in range(len(pairs))])

    m0 = jnp.full((b, kh, g, chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g, chunk), jnp.float32)
    a0 = jnp.zeros((b, kh, g, chunk, d), jnp.float32)
    out0 = jnp.zeros((n, b, kh, g, chunk, d), q.dtype)

    @jax.checkpoint
    def step(carry, xs):
        m, l, acc, out = carry
        qi, ki, first = xs
        m = jnp.where(first, m0, m)
        l = jnp.where(first, l0, l)
        acc = jnp.where(first, a0, acc)
        qc = jax.lax.dynamic_index_in_dim(qg, qi, 0, keepdims=False)
        kc = jax.lax.dynamic_index_in_dim(kg, ki, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(vg, ki, 0, keepdims=False)
        sco = jnp.einsum("bkgqd,bkcd->bkgqc", qc, kc,
                         preferred_element_type=jnp.float32) * scale
        qpos = qi * chunk + jnp.arange(chunk)
        kpos = ki * chunk + jnp.arange(chunk)
        mask = jnp.where(qpos[:, None] >= kpos[None, :], 0.0, NEG_INF)
        if window is not None:
            mask = jnp.where(qpos[:, None] - kpos[None, :] < window, mask, NEG_INF)
        mask = jnp.where(kpos[None, :] < s, mask, NEG_INF)  # padded keys
        sco = sco + mask
        m_new = jnp.maximum(m, sco.max(-1))
        p = jnp.exp(sco - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bkcd->bkgqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        l_safe = jnp.where(l_new == 0.0, 1.0, l_new)
        o_row = (acc_new / l_safe[..., None]).astype(q.dtype)
        out = jax.lax.dynamic_update_index_in_dim(out, o_row, qi, 0)
        return (m_new, l_new, acc_new, out), None

    (_, _, _, out), _ = jax.lax.scan(step, (m0, l0, a0, out0),
                                     (qi_a, ki_a, first_a))
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, n * chunk, h, d)
    return out[:, :s]


def decode_attention(
    q: jax.Array,  # [b, 1, h, d]
    k: jax.Array,  # [b, S, kh, d]  (cache, possibly partially filled)
    v: jax.Array,
    kv_len: jax.Array,  # [b] number of valid cache entries
) -> jax.Array:
    """Single-token attention over a cache. f32 softmax, no S x S anything."""
    b, _, h, d = q.shape
    _, S, kh, _ = k.shape
    g = h // kh
    qg = q.reshape(b, kh, g, d)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k, preferred_element_type=jnp.float32)
    s = s / np.sqrt(d)
    valid = jnp.arange(S)[None, :] < kv_len[:, None]  # [b,S]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block params + apply
# ---------------------------------------------------------------------------
def init_attention(keys, cfg, dtype, lora_rank: int = 0):
    """Params for one attention block (optionally with LoRA adapter slots)."""
    d, h, kh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim()
    p = {
        "wq": par(next(keys), (d, h, dh), ("embed", "heads", "head_dim"), dtype),
        "wk": par(next(keys), (d, kh, dh), ("embed", "kv_heads", "head_dim"), dtype),
        "wv": par(next(keys), (d, kh, dh), ("embed", "kv_heads", "head_dim"), dtype),
        "wo": par(next(keys), (h, dh, d), ("heads", "head_dim", "embed"), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = ones((dh,), ("head_dim",), dtype)
        p["k_norm"] = ones((dh,), ("head_dim",), dtype)
    if lora_rank:
        r = lora_rank
        for nm, (fi, fo, ax) in {
            "wq": (d, h * dh, ("heads",)),
            "wk": (d, kh * dh, ("kv_heads",)),
            "wv": (d, kh * dh, ("kv_heads",)),
            "wo": (h * dh, d, ("embed",)),
        }.items():
            p[f"{nm}_lora_a"] = par(next(keys), (fi, r), (ax[0] if nm == "wo" else "embed", "lora_rank"), dtype)
            p[f"{nm}_lora_b"] = zeros((r, fo), ("lora_rank", ax[0] if nm != "wo" else "embed"), dtype)
    return p


def _proj_lora(x, w3, la, lb):
    """y = x @ w3 (+ LoRA delta); w3: [d, heads, head_dim] input projection."""
    y = jnp.einsum("bsd,dhk->bshk", x, w3)
    if la is not None:
        delta = (x @ la) @ lb
        y = y + delta.reshape(y.shape)
    return y


def attention_block(
    p,
    x: jax.Array,  # [b, s, d]
    cfg,
    *,
    positions: jax.Array,  # [b, s] absolute positions (or [s])
    causal: bool = True,
    window: Optional[int] = None,
    cache=None,  # dict(k, v, len) for decode; None for full attention
    constrain=lambda a, kind: a,
    use_lora: bool = False,
):
    """Returns (out [b,s,d], new_cache)."""
    b, s, d = x.shape
    h, kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim()

    def la(nm):
        return (p.get(f"{nm}_lora_a"), p.get(f"{nm}_lora_b")) if use_lora else (None, None)

    with jax.named_scope("attn_qkv"):
        q = _proj_lora(x, p["wq"], *la("wq"))
        k = _proj_lora(x, p["wk"], *la("wk"))
        v = _proj_lora(x, p["wv"], *la("wv"))
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        if positions.ndim == 1:
            positions = jnp.broadcast_to(positions[None, :], (b, s))
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        q = constrain(q, "heads")
        k = constrain(k, "kv")
        v = constrain(v, "kv")

    new_cache = None
    if cache is None:
        with jax.named_scope("attn_core"):
            if causal and getattr(constrain, "attn_impl", "chunked") == "tri":
                o = chunked_attention_tri(q, k, v, window=window)
            else:
                o = chunked_attention(q, k, v, causal=causal, window=window)
    else:
        kc, vc, ln = cache["k"], cache["v"], cache["len"]
        if s == 1:
            # single-token decode: insert then attend (SWA uses a ring buffer)
            S = kc.shape[1]
            with jax.named_scope("kv_write"):
                if window is not None and S <= window:
                    slot = ln % S
                else:
                    slot = jnp.minimum(ln, S - 1)
            kv_len = jnp.minimum(ln + 1, S)
            sp = getattr(constrain, "sp_decode", None)
            if sp is not None:
                # the sequence-parallel kernel inserts and attends in one call
                with jax.named_scope("attn_core"):
                    o, kc, vc = sp(q, k, v, kc, vc, slot, kv_len)
            else:
                with jax.named_scope("kv_write"):
                    idx = slot[:, None]
                    bidx = jnp.arange(b)[:, None]
                    kc = kc.at[bidx, idx].set(k)
                    vc = vc.at[bidx, idx].set(v)
                with jax.named_scope("attn_core"):
                    o = decode_attention(q, kc, vc, kv_len)
            new_cache = {"k": kc, "v": vc, "len": ln + 1}
        else:
            # prefill: write cache (ring-rotated when SWA window < prompt) and
            # run chunked attention over the full prompt
            S = kc.shape[1]
            with jax.named_scope("kv_write"):
                if s > S:  # SWA: keep only the last S keys, at t % S slots
                    idx = np.arange(s - S, s) % S
                    kc = kc.at[:, idx].set(k[:, -S:])
                    vc = vc.at[:, idx].set(v[:, -S:])
                else:
                    kc = jax.lax.dynamic_update_slice_in_dim(kc, k, 0, axis=1)
                    vc = jax.lax.dynamic_update_slice_in_dim(vc, v, 0, axis=1)
            with jax.named_scope("attn_core"):
                o = chunked_attention(q, k, v, causal=causal, window=window)
            new_cache = {"k": kc, "v": vc, "len": cache["len"] + s}
    with jax.named_scope("attn_out"):
        o = constrain(o, "heads")
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        lao, lbo = la("wo")
        if lao is not None:
            out = out + (o.reshape(b, s, -1) @ lao) @ lbo
    return out, new_cache


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def init_mlp(keys, d: int, ff: int, dtype, lora_rank: int = 0):
    p = {
        "wi": par(next(keys), (d, ff), ("embed", "ffn"), dtype),
        "wg": par(next(keys), (d, ff), ("embed", "ffn"), dtype),
        "wo": par(next(keys), (ff, d), ("ffn", "embed"), dtype),
    }
    if lora_rank:
        r = lora_rank
        p["wi_lora_a"] = par(next(keys), (d, r), ("embed", "lora_rank"), dtype)
        p["wi_lora_b"] = zeros((r, ff), ("lora_rank", "ffn"), dtype)
        p["wo_lora_a"] = par(next(keys), (ff, r), ("ffn", "lora_rank"), dtype)
        p["wo_lora_b"] = zeros((r, d), ("lora_rank", "embed"), dtype)
    return p


def mlp_block(p, x, constrain=lambda a, k: a, use_lora: bool = False):
    hpre = x @ p["wi"]
    if use_lora and "wi_lora_a" in p:
        hpre = hpre + (x @ p["wi_lora_a"]) @ p["wi_lora_b"]
    hid = jax.nn.silu(x @ p["wg"]) * hpre
    hid = constrain(hid, "ffn")
    out = hid @ p["wo"]
    if use_lora and "wo_lora_a" in p:
        out = out + (hid @ p["wo_lora_a"]) @ p["wo_lora_b"]
    return out
