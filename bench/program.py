"""The system under test, as the benchmark drives it: the jitted step that the
engine's measured tier runs (``repro.launch.dryrun.build_cell``), built for
the program's configuration of a model (its family's ``arch_config``) and
the cell's shape, and its inputs made on the device from the seed under each
input's sharding.

This module and the families' program mappings (``make_params``,
``make_cache``, ``arch_config`` in ``bench/families/``) are the only parts
of the benchmark that touch the program; the reference never sees its tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def build(cfg, workload: str, kind: str, length: int, batch: int, mesh):
    """``(jitted step, its abstract args)`` for the program's ``ArchConfig``
    ``cfg`` under the baseline plan."""
    from repro.configs.base import ShapeCell
    from repro.launch import dryrun

    cell = ShapeCell(workload, kind, length, batch)
    built, why = dryrun.build_cell(cfg.name, workload, mesh, cfg=cfg, cell=cell)
    if built is None:
        raise RuntimeError(f"the program does not support {workload}: {why}")
    return built


def shardings(tree):
    return jax.tree.map(lambda s: s.sharding, tree)


def on_device(make, key, sds, what: str):
    """``make(key)``, made on the device in one jitted call, already under
    the shardings of ``sds``, the program's abstract ``what``; refused where
    ``make`` gives another tree than the program takes."""
    want = jax.tree.structure(sds)
    got = jax.tree.structure(jax.eval_shape(make, key))
    if got != want:
        raise RuntimeError(f"the program's {what} tree changed:\n{want}\n"
                           f"the benchmark makes\n{got}")
    return jax.jit(make, out_shardings=shardings(sds))(key)


def empty_cache(cache_sds):
    """A zeroed cache under the cache's sharding (prefill)."""
    return jax.jit(lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_sds),
                   out_shardings=shardings(cache_sds))()
