"""The family of ``model_type`` ``qwen3``: decoder-only Qwen3-style models
(GQA, qk-norm, RoPE, SwiGLU, RMSNorm, tied or untied head), everything of
the benchmark that depends on that architecture:

* :func:`from_dict`: the sizes of a configuration, and its refusals;
* the program mapping: :func:`arch_config` (the program's ``ArchConfig``),
  :func:`make_params` (the program's parameter tree from the seed) and
  :func:`make_cache` (a drawn decode cache), :func:`written` (the cache
  entries a check compares) and :data:`SCOPES` (the named scopes of the
  program's step);
* the draws of weights and caches (:func:`layer_weights`,
  :func:`outer_weights`, :func:`cache_layer`), shared by the program and
  the reference;
* :class:`Reference`, the plain float32 reference;
* :func:`decode_flops`, :func:`decode_bytes`, :func:`prefill_flops`: the
  operations and bytes a step needs.

The reference follows the published description (Qwen3 technical report;
the ``Qwen3ForCausalLM`` config keys):

    h   = x + Wo . attn(rope(qnorm(h1 Wq)), rope(knorm(h1 Wk)), h1 Wv),
          h1 = rmsnorm(x) * ln1
    out = h + W_down (silu(h2 W_gate) * h2 W_up),  h2 = rmsnorm(h) * ln2

with grouped-query attention, RMSNorm over the last axis with weight and
``rms_norm_eps``, qk-norm over each head's ``head_dim``, RoPE with the
rotate-half pairing (``x[:d/2]`` with ``x[d/2:]``) at ``rope_theta``, and
logits ``rmsnorm(x) * ln_f`` times the embedding (tied) or the head
(untied). It imports nothing of the program: weights, caches and tokens come
from the draws below, the ones the program is given, upcast to float32. It
runs one layer at a time (``bench/reference/ops.py``).

Counts are what the algorithm requires, not what a program computes: a
multiply-add is 2 FLOPs; attention counts ``QK^T`` and ``PV`` over the keys
each query may see (causal: position ``p`` sees ``p + 1`` keys); recomputed
work and masked-out blocks are not counted. Bytes are those a step must move
through HBM at least once: the weights it reads, the cache it reads and the
cache it writes.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from bench import program, seeded
from bench.reference.ops import attention, mm, rmsnorm, upcast
from bench.spec import CellError

#: the program's named scopes (``src/repro/models``), innermost wins
SCOPES = ("embed", "layers", "norm", "attn_qkv", "kv_write", "attn_core",
          "attn_out", "mlp", "head")

#: RMS of the drawn cache contents: qk-normed keys have unit RMS; values are
#: a unit-RMS input times ``initializer_range`` 0.02 over 1,024 inputs
K_RMS, V_RMS = 1.0, 0.64


@dataclass(frozen=True)
class Model:
    name: str
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    rope_theta: float
    eps: float
    init_std: float
    dtype: str


def from_dict(name: str, c: dict) -> Model:
    """The sizes of config ``name`` from its ``config.json`` keys."""
    if c.get("use_sliding_window") or c.get("rope_scaling") or c.get("attention_bias"):
        raise CellError(f"config {name}: sliding windows, rope scaling and "
                        f"attention biases are not implemented")
    if c["num_attention_heads"] % c["num_key_value_heads"]:
        raise CellError(f"config {name}: heads not a multiple of kv heads")
    return Model(
        name=name, layers=c["num_hidden_layers"], d=c["hidden_size"],
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], ff=c["intermediate_size"],
        vocab=c["vocab_size"], tied=bool(c["tie_word_embeddings"]),
        rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        init_std=float(c["initializer_range"]), dtype=c["torch_dtype"])


# -- draws ------------------------------------------------------------------

def layer_shapes(m) -> dict:
    d, h, kh, dh, ff = m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    return {"ln1": (d,), "wq": (d, h, dh), "wk": (d, kh, dh),
            "wv": (d, kh, dh), "q_norm": (dh,), "k_norm": (dh,),
            "wo": (h, dh, d), "ln2": (d,), "w_gate": (d, ff),
            "w_up": (d, ff), "w_down": (ff, d)}


def _draw(key, shape, m, dtype):
    if len(shape) == 1:
        return seeded.norm_weight(key, shape, dtype)
    return seeded.uniform(key, shape, m.init_std).astype(dtype)


def layer_weights(key, m, dtype=jnp.bfloat16) -> dict:
    """The weights of the layer whose key is ``key``
    (:func:`bench.seeded.layer_key`)."""
    return {name: _draw(jax.random.fold_in(key, i), shape, m, dtype)
            for i, (name, shape) in enumerate(sorted(layer_shapes(m).items()))}


def outer_weights(key, m, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm and (untied) output head under the weights key."""
    w = {"embed": _draw(jax.random.fold_in(key, 0), (m.vocab, m.d), m, dtype),
         "ln_f": _draw(jax.random.fold_in(key, 2), (m.d,), m, dtype)}
    if not m.tied:
        w["lm_head"] = _draw(jax.random.fold_in(key, 1), (m.d, m.vocab), m, dtype)
    return w


def cache_layer(key, m, batch: int, length: int, fill: int, dtype=jnp.bfloat16):
    """``(k, v)`` of one layer's cache, ``[batch, length, kv_heads,
    head_dim]``: drawn for positions below ``fill``, zero above."""
    shape = (batch, length, m.kv_heads, m.head_dim)
    kk, kv = jax.random.split(key)
    live = (jnp.arange(length) < fill)[None, :, None, None]
    k = jnp.where(live, seeded.uniform(kk, shape, K_RMS), 0.0)
    v = jnp.where(live, seeded.uniform(kv, shape, V_RMS), 0.0)
    return k.astype(dtype), v.astype(dtype)


# -- the program mapping ----------------------------------------------------

def arch_config(m):
    """The program's ``ArchConfig`` for model ``m``."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=m.name, family="dense", n_layers=m.layers, d_model=m.d,
        n_heads=m.heads, n_kv_heads=m.kv_heads, d_ff=m.ff, vocab=m.vocab,
        d_head=m.head_dim, qk_norm=True, rope_theta=m.rope_theta,
        norm_eps=m.eps, tie_embeddings=m.tied, dtype=m.dtype)


def _program_tree(layers: dict, outer: dict) -> dict:
    tree = {
        "embed": outer["embed"],
        "blocks": {
            "ln1": layers["ln1"],
            "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")},
            "ln2": layers["ln2"],
            "mlp": {"wi": layers["w_up"], "wg": layers["w_gate"],
                    "wo": layers["w_down"]},
        },
        "ln_f": outer["ln_f"],
    }
    if "lm_head" in outer:
        tree["lm_head"] = outer["lm_head"]
    return tree


def make_params(m, params_sds, seed: int):
    """The program's parameters, made on the device in one jitted call from
    the seed, already under their shardings."""
    dtype = jnp.dtype(m.dtype)

    def make(wkey):
        layers = jax.lax.map(
            lambda i: layer_weights(seeded.layer_key(wkey, i), m, dtype),
            jnp.arange(m.layers))
        return _program_tree(layers, outer_weights(wkey, m, dtype))

    return program.on_device(make, seeded.stream(seed, seeded.WEIGHTS),
                             params_sds, "parameter")


def make_cache(m, cache_sds, seed: int, fill: int):
    """A decode cache drawn from the seed up to ``fill`` positions, with
    ``len`` = ``fill``, under the cache's sharding."""
    _, batch, length = cache_sds["k"].shape[:3]
    dtype = cache_sds["k"].dtype

    def make(ckey):
        k, v = jax.lax.map(
            lambda i: cache_layer(seeded.cache_key(ckey, i), m, batch, length,
                                  fill, dtype),
            jnp.arange(m.layers))
        return {"k": k, "v": v, "len": jnp.full((batch,), fill, jnp.int32)}

    return program.on_device(make, seeded.stream(seed, seeded.CACHE), cache_sds,
                             "cache")


def written(cache, start: int, n: int) -> dict:
    """The entries of the program's cache at positions ``start`` to
    ``start + n - 1``, ``{name: [layers, batch, n, ...]}``, as
    :meth:`Reference.run` gives them for one layer."""
    return {k: cache[k][:, :, start:start + n] for k in ("k", "v")}


# -- the reference ----------------------------------------------------------

def rope(x, pos, theta):
    """``x``: [..., n, heads, d]; ``pos``: [n] absolute positions."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv  # [n, d/2]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


class Reference:
    """The reference for one configuration and seed, run at the precisions
    asked for (``"f32"``, and ``"fp8"`` for the control) side by side."""

    def __init__(self, m, seed: int, precisions=("f32",)):
        self.m = m
        self.precisions = tuple(precisions)
        self.wkey = seeded.stream(seed, seeded.WEIGHTS)
        self.ckey = seeded.stream(seed, seeded.CACHE)
        dt = jnp.dtype(m.dtype)  # drawn as the program is given them
        self._layer_w = jax.jit(lambda k: upcast(layer_weights(k, m, dt)))
        self._outer_w = jax.jit(lambda k: upcast(outer_weights(k, m, dt)))
        self._layer = jax.jit(partial(layer, m=m), static_argnames="prec")
        self._embed = jax.jit(lambda e, t: e[t])
        self._cache = jax.jit(partial(cache_layer, m=m, dtype=dt),
                              static_argnames=("batch", "length", "fill"))

    def cache(self, i: int, batch: int, length: int, fill: int):
        """Layer ``i``'s drawn cache, float32, cut to its ``fill`` positions."""
        k, v = self._cache(seeded.cache_key(self.ckey, i), batch=batch,
                           length=length, fill=fill)
        return k[:, :fill].astype(jnp.float32), v[:, :fill].astype(jnp.float32)

    def run(self, tokens, start: int, past=None, on_layer=None):
        """Run ``tokens`` [b, n] at positions ``start + i`` over the past
        given by ``past(i)`` (:meth:`cache`; None: no past).
        ``on_layer(i, entries)`` receives each layer's new cache entries per
        precision, ``{precision: {name: [b, n, ...]}}`` (:func:`written`'s
        names). Returns the final hidden states per precision, [b, n, d],
        after ``ln_f``."""
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
                kv[p] = {"k": k, "v": v}
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


# -- counts -----------------------------------------------------------------

def layer_matmul_params(m) -> int:
    """Weights of one layer that enter a matrix product."""
    d, h, kh, dh, ff = m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    return d * h * dh + 2 * d * kh * dh + h * dh * d + 3 * d * ff


def params(m) -> int:
    """All parameters: embedding, layers with their norms, final norm and
    (untied) output head."""
    per_layer = layer_matmul_params(m) + 2 * m.d + 2 * m.head_dim
    head = 0 if m.tied else m.vocab * m.d
    return m.vocab * m.d + m.layers * per_layer + m.d + head


def attention_flops(m, keys_seen: int) -> int:
    """QK^T and PV of one query over ``keys_seen`` keys, all layers."""
    return m.layers * 4 * m.heads * m.head_dim * keys_seen


def forward_flops(m, tokens: int, keys_seen_total: int, logits_rows: int) -> int:
    """A forward pass over ``tokens`` positions whose queries see
    ``keys_seen_total`` keys in all, with ``logits_rows`` rows of logits."""
    return (2 * tokens * m.layers * layer_matmul_params(m)
            + attention_flops(m, keys_seen_total)
            + 2 * logits_rows * m.d * m.vocab)


def prefill_flops(m, batch: int, length: int) -> int:
    """A causal prefill of ``batch`` prompts of ``length`` tokens that
    returns the last position's logits."""
    return batch * forward_flops(m, length, length * (length + 1) // 2, 1)


def decode_flops(m, batch: int, context: int) -> int:
    """One decode step of ``batch`` sequences whose new token lands at
    position ``context`` (it sees ``context + 1`` keys)."""
    return batch * forward_flops(m, 1, context + 1, 1)


def kv_bytes_per_token(m, itemsize: int = 2) -> int:
    return m.layers * 2 * m.kv_heads * m.head_dim * itemsize


def decode_bytes(m, batch: int, context: int, itemsize: int = 2) -> int:
    """HBM bytes one decode step must move: every weight it reads (the whole
    output matrix, but only ``batch`` rows of an untied embedding), the
    cache's ``context`` positions read once, and one position written."""
    weights = params(m) - (0 if m.tied else (m.vocab - batch) * m.d)
    cache = batch * (context + 1) * kv_bytes_per_token(m, itemsize)
    return weights * itemsize + cache
