"""The system under test, as the benchmark drives it: the jitted step that the
engine's measured tier runs (``repro.launch.dryrun.build_cell``), built for
the benchmark's own configuration and shape, and its inputs made on the
device from the seed under each input's sharding.

This module is the only one of the benchmark that imports the program. It
maps the benchmark's weights (``bench.seeded``) into the program's parameter
tree; the reference never sees that tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import seeded


def arch_config(m):
    """The program's ``ArchConfig`` for model ``m``."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=m.name, family="dense", n_layers=m.layers, d_model=m.d,
        n_heads=m.heads, n_kv_heads=m.kv_heads, d_ff=m.ff, vocab=m.vocab,
        d_head=m.head_dim, qk_norm=True, rope_theta=m.rope_theta,
        norm_eps=m.eps, tie_embeddings=m.tied, dtype=m.dtype)


def build(m, workload: str, kind: str, length: int, batch: int, mesh):
    """``(jitted step, its abstract args)`` under the baseline plan."""
    from repro.configs.base import ShapeCell
    from repro.launch import dryrun

    cfg = arch_config(m)
    cell = ShapeCell(workload, kind, length, batch)
    built, why = dryrun.build_cell(m.name, workload, mesh, cfg=cfg, cell=cell)
    if built is None:
        raise RuntimeError(f"the program does not support {workload}: {why}")
    return built


def _sharding(tree):
    return jax.tree.map(lambda s: s.sharding, tree)


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


def canonical(tree: dict) -> dict:
    """The program's parameter tree in the benchmark's layout,
    ``{"layers": {name: [layers, ...]}, "outer": {name: ...}}``."""
    b = tree["blocks"]
    layers = {"ln1": b["ln1"], "ln2": b["ln2"], **b["attn"],
              "w_up": b["mlp"]["wi"], "w_gate": b["mlp"]["wg"], "w_down": b["mlp"]["wo"]}
    return {"layers": layers,
            "outer": {k: v for k, v in tree.items() if k != "blocks"}}


def make_params(m, params_sds, seed: int):
    """The program's parameters, made on the device in one jitted call from
    the seed, already under their shardings."""
    dtype = jnp.dtype(m.dtype)

    def make(wkey):
        layers = jax.lax.map(
            lambda i: seeded.layer_weights(seeded.layer_key(wkey, i), m, dtype),
            jnp.arange(m.layers))
        return _program_tree(layers, seeded.outer_weights(wkey, m, dtype))

    want = jax.tree.structure(params_sds)
    got = jax.tree.structure(jax.eval_shape(make, seeded.stream(0, seeded.WEIGHTS)))
    if got != want:
        raise RuntimeError(f"the program's parameter tree changed:\n{want}\n"
                           f"the benchmark maps its weights to\n{got}")
    return jax.jit(make, out_shardings=_sharding(params_sds))(
        seeded.stream(seed, seeded.WEIGHTS))


def make_cache(m, cache_sds, seed: int, fill: int):
    """A decode cache drawn from the seed up to ``fill`` positions, with
    ``len`` = ``fill``, under the cache's sharding."""
    _, batch, length = cache_sds["k"].shape[:3]
    dtype = cache_sds["k"].dtype

    def make(ckey):
        k, v = jax.lax.map(
            lambda i: seeded.cache_layer(seeded.cache_key(ckey, i), m, batch,
                                         length, fill, dtype),
            jnp.arange(m.layers))
        return {"k": k, "v": v, "len": jnp.full((batch,), fill, jnp.int32)}

    return jax.jit(make, out_shardings=_sharding(cache_sds))(
        seeded.stream(seed, seeded.CACHE))


def empty_cache(cache_sds):
    """A zeroed cache under the cache's sharding (prefill)."""
    return jax.jit(lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_sds),
                   out_shardings=_sharding(cache_sds))()
