"""Dense decoder-only transformer (llama3 / qwen3 / stablelm / llava backbone).

Layers are stacked on a leading axis and consumed with ``jax.lax.scan`` so the
lowered HLO is depth-independent (critical for 94-layer dry-run compiles).

Each layer boundary runs under a ``jax.named_scope`` from one fixed vocabulary:
``embed``, ``layers`` (the layer scan), ``norm``, ``attn_qkv``, ``kv_write``,
``attn_core``, ``attn_out``, ``mlp``, ``head``. The scopes reach only the
compiled HLO's metadata (each op's ``op_name`` path), which a profiler trace of
the step is split by; the program computes the same with or without them.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.layers import Param, keygen, ones, par


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def stack_layers(init_one, key, n_layers: int):
    """vmap an init over layer keys, then tag the leading axis as 'layers'."""
    ks = jax.random.split(key, n_layers)
    stacked = jax.vmap(init_one)(ks)
    return jax.tree.map(
        lambda p: Param(p.value, ("layers", *p.axes)), stacked, is_leaf=L._is_param
    )


def init_dense(cfg, key):
    dt = _dtype(cfg)
    keys = keygen(key)
    d = cfg.d_model

    def one_layer(k):
        lk = keygen(k)
        if cfg.moe is not None:
            from repro.models.moe import init_moe_mlp

            mlp = init_moe_mlp(lk, d, cfg.moe, dt)
        else:
            mlp = L.init_mlp(lk, d, cfg.d_ff, dt)
        return {
            "ln1": ones((d,), ("embed",), dt),
            "attn": L.init_attention(lk, cfg, dt),
            "ln2": ones((d,), ("embed",), dt),
            "mlp": mlp,
        }

    params = {
        "embed": par(next(keys), (cfg.vocab, d), ("vocab", "embed"), dt),
        "blocks": stack_layers(one_layer, next(keys), cfg.n_layers),
        "ln_f": ones((d,), ("embed",), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = par(next(keys), (d, cfg.vocab), ("embed", "vocab"), dt)
    if cfg.frontend:
        params["frontend_proj"] = par(next(keys), (1024, d), (None, "embed"), dt)
    return params


def _embed_inputs(cfg, params, batch, constrain):
    """Token (+ frontend stub) embedding. Returns (x [b,s,d], positions [b,s])."""
    with jax.named_scope("embed"):
        tok = batch["tokens"]
        x = jnp.take(params["embed"], tok, axis=0)
        if cfg.frontend:
            fe = batch["frontend"].astype(x.dtype) @ params["frontend_proj"]
            x = jnp.concatenate([fe, x], axis=1)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        return constrain(x, "hidden"), positions


def _logits(cfg, params, x):
    with jax.named_scope("head"):
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))


def _layer_body(cfg, constrain, x, lp, lcache, positions, window):
    """Returns (out, aux_loss, new_cache)."""
    with jax.named_scope("norm"):
        xn = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
    a, new_cache = L.attention_block(
        lp["attn"], xn, cfg,
        positions=positions, causal=True, window=window,
        cache=lcache, constrain=constrain,
    )
    h = x + a
    with jax.named_scope("norm"):
        hn = L.rmsnorm(h, lp["ln2"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        if cfg.moe is not None:
            from repro.models.moe import moe_block

            m, aux = moe_block(lp["mlp"], hn, cfg.moe, constrain)
        else:
            m, aux = L.mlp_block(lp["mlp"], hn, constrain), jnp.float32(0.0)
    out = h + m
    return constrain(out, "hidden"), aux, new_cache


def dense_forward(
    cfg,
    params,
    batch,
    *,
    cache=None,  # {"k": [L,b,S,kh,dh], "v": ..., "len": [b]} or None
    constrain=lambda a, k: a,
    remat: str = "none",
):
    """Returns (hidden [b,s,d], new_cache)."""
    if cache is None:
        x, positions = _embed_inputs(cfg, params, batch, constrain)
    else:
        # decode: single new token at position cache["len"]
        with jax.named_scope("embed"):
            tok = batch["tokens"]  # [b, 1]
            x = jnp.take(params["embed"], tok, axis=0)
            positions = cache["len"][:, None] + jnp.zeros_like(tok)
            x = constrain(x, "hidden")

    def body(carry, xs):
        x, aux = carry
        lp, lc = xs
        out, a, nc = _layer_body(cfg, constrain, x, lp, lc, positions, cfg.swa_window)
        return (out, aux + a), nc

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )

    aux0 = jnp.float32(0.0)
    if cache is None:
        with jax.named_scope("layers"):
            (x, aux), _ = jax.lax.scan(
                lambda c, lp: body(c, (lp, None)), (x, aux0), params["blocks"]
            )
        new_cache = None
    else:
        lcaches = {"k": cache["k"], "v": cache["v"],
                   "len": jnp.broadcast_to(cache["len"], (cfg.n_layers, *cache["len"].shape))}
        with jax.named_scope("layers"):
            (x, aux), new_lc = jax.lax.scan(body, (x, aux0), (params["blocks"], lcaches))
        new_cache = {"k": new_lc["k"], "v": new_lc["v"], "len": cache["len"] + 1}
    with jax.named_scope("head"):
        x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return x, aux / cfg.n_layers, new_cache


AUX_LOSS_COEF = 0.01


def ce_loss(cfg, params, x, tgt, constrain, loss_chunk: int = 0):
    """Cross-entropy on hidden states. ``loss_chunk`` > 0 scans the sequence in
    chunks so the [B, S, vocab] logits tensor is never materialised (a DSE
    memory-term knob)."""

    def one(xc, tc):
        logits = constrain(_logits(cfg, params, xc), "logits")
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        mask = (tc >= 0).astype(jnp.float32)
        return (nll * mask).sum(), mask.sum()

    b, s, _ = x.shape
    if loss_chunk and s > loss_chunk and s % loss_chunk == 0:
        n = s // loss_chunk
        xs = x.reshape(b, n, loss_chunk, -1).transpose(1, 0, 2, 3)
        ts = tgt.reshape(b, n, loss_chunk).transpose(1, 0, 2)

        def body(carry, xt):
            tot, cnt = carry
            nll, m = one(*xt)
            return (tot + nll, cnt + m), None

        (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), (xs, ts))
    else:
        tot, cnt = one(x, tgt)
    return tot / jnp.maximum(cnt, 1.0), cnt


def dense_loss(cfg, params, batch, constrain=lambda a, k: a, remat: str = "none",
               loss_chunk: int = 0):
    x, aux, _ = dense_forward(cfg, params, batch, constrain=constrain, remat=remat)
    if cfg.frontend:
        x = x[:, -batch["tokens"].shape[1]:]  # loss only on text positions
    ce, tokens = ce_loss(cfg, params, x, batch["targets"], constrain, loss_chunk)
    loss = ce + (AUX_LOSS_COEF * aux if cfg.moe is not None else 0.0)
    return loss, {"loss": ce, "aux": aux, "tokens": tokens}


def init_dense_cache(cfg, batch_size: int, max_len: int, dtype):
    kh, dh = cfg.n_kv_heads, cfg.head_dim()
    S = min(max_len, cfg.swa_window) if cfg.swa_window else max_len
    return {
        "k": jnp.zeros((cfg.n_layers, batch_size, S, kh, dh), dtype),
        "v": jnp.zeros((cfg.n_layers, batch_size, S, kh, dh), dtype),
        "len": jnp.zeros((batch_size,), jnp.int32),
    }


def dense_prefill(cfg, params, batch, cache, constrain=lambda a, k: a):
    """Populate the cache from a prompt; returns (last-token logits, cache)."""
    x, positions = _embed_inputs(cfg, params, batch, constrain)
    s = x.shape[1]

    def body(carry, xs):
        x, aux = carry
        lp, lc = xs
        out, a, nc = _layer_body(cfg, constrain, x, lp, lc, positions, cfg.swa_window)
        return (out, aux + a), nc

    lcaches = {"k": cache["k"], "v": cache["v"],
               "len": jnp.broadcast_to(cache["len"], (cfg.n_layers, *cache["len"].shape))}
    with jax.named_scope("layers"):
        (x, _), new_lc = jax.lax.scan(body, (x, jnp.float32(0.0)), (params["blocks"], lcaches))
    with jax.named_scope("head"):
        x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = _logits(cfg, params, x[:, -1:])
    return logits, {"k": new_lc["k"], "v": new_lc["v"], "len": cache["len"] + s}


def dense_decode(cfg, params, batch, cache, constrain=lambda a, k: a):
    x, _, new_cache = dense_forward(cfg, params, batch, cache=cache, constrain=constrain)
    return _logits(cfg, params, x), new_cache
