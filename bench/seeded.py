"""Everything a run makes from ``--seed``: the streams that weights, decode
caches and tokens are drawn from, and the draws every family shares.

A family (``bench/families/<model_type>.py``) draws its weights one layer at
a time, in a layout of the benchmark's own, from the key
:func:`layer_key` gives, so that the program's stacked parameters and the
reference's layer-by-layer weights are the same draws: the program's copy is
``lax.map`` of the same function over the layers, made on the device in one
jitted call; the reference calls it for one layer at a time. Values are
rounded to the served dtype (bfloat16) and the reference upcasts them. On
the CPU the two copies are equal bit for bit; on a TPU two compilations can
round a few values to neighbouring bfloat16 numbers (PERF.md, Open
questions).

Streams are separated by ``fold_in`` of a fixed purpose number, so adding a
stream never changes another one. Jitted generators take the stream's key as
an argument, never the seed, so one compiled program serves every seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

WEIGHTS, CACHE, TOKENS = 0, 1, 2


def root_key(seed: int):
    """The key of a run: any whole number ``seed`` >= 0, 64 bits and more."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.key(seed % 2**32)
    for word in _words(seed // 2**32):
        key = jax.random.fold_in(key, word)
    return key


def _words(n: int):
    while n:
        yield n % 2**32
        n //= 2**32


def stream(seed: int, purpose: int):
    return jax.random.fold_in(root_key(seed), purpose)


def uniform(key, shape, rms: float):
    """Uniform draws of RMS ``rms`` on a grid of 2**16 points, made with
    integer operations and one float multiply."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    u = (bits >> 16).astype(jnp.int32) - 32768
    return u.astype(jnp.float32) * (rms * 3**0.5 / 32768)


def norm_weight(key, shape, dtype):
    """An RMSNorm weight: 1 + q / 512, q uniform on [-128, 128)."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    q = (bits >> 24).astype(jnp.int32) - 128
    return (1.0 + q.astype(jnp.float32) * 2.0**-9).astype(dtype)


def layer_key(wkey, i):
    """The key of layer ``i`` (an int or a traced index) under the weights
    key ``wkey`` (``stream(seed, WEIGHTS)``)."""
    return jax.random.fold_in(jax.random.fold_in(wkey, 3), i)


def cache_key(ckey, i):
    """The key of layer ``i``'s cache under ``stream(seed, CACHE)``."""
    return jax.random.fold_in(ckey, i)


def first_tokens(seed: int, m, batch: int) -> np.ndarray:
    """The first decode input of each sequence, ``[batch, 1]`` int32."""
    return prompt(seed, -1, m, batch, 1)


def prompt(seed: int, index: int, m, batch: int, length: int) -> np.ndarray:
    """Prompt ``index`` of a run, ``[batch, length]`` int32, made on the host
    as a tokenizer's output would arrive."""
    rng = np.random.default_rng([seed, TOKENS, index + 1])
    return rng.integers(0, m.vocab, (batch, length), dtype=np.int32)
