"""The model step's layer scopes (``jax.named_scope`` in ``repro.models``):
every scope of the vocabulary reaches the optimized HLO's metadata of the
serve steps that ``build_cell`` makes, and the scopes change nothing but that
metadata, so the compiled program is the same with or without them."""
import contextlib
import re

import jax
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import ShapeCell
from repro.launch import dryrun

SCOPES = {"embed", "layers", "norm", "attn_qkv", "kv_write", "attn_core",
          "attn_out", "mlp", "head"}
METADATA = re.compile(r', metadata=\{(?:[^}"]|"[^"]*")*\}')
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(autouse=True)
def no_compile_cache():
    """JAX's persistent cache keys a program without its metadata: a hit
    would hand the scoped step the executable of the unscoped one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def step_hlo(kind: str, single_mesh) -> str:
    cfg = reduced(get_config("qwen3-0.6b"))
    cell = ShapeCell("tiny", kind, 256, 2)
    (fn, args), why = dryrun.build_cell(cfg.name, "tiny", single_mesh, cfg=cfg, cell=cell)
    assert why is None, why
    return fn.lower(*args).compile().as_text()


def strip(hlo: str) -> str:
    """``hlo`` less its metadata and its tables of source locations, which
    sit between the module's first line and its first computation."""
    lines = METADATA.sub("", hlo).splitlines()
    if "FileNames" in lines:
        start = lines.index("FileNames")
        end = next(i for i in range(start, len(lines))
                   if lines[i].startswith(("%", "ENTRY")))
        lines = lines[:start] + lines[end:]
    return "\n".join(lines)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_every_scope_reaches_the_optimized_hlo(kind, single_mesh):
    segments = {seg for name in OP_NAME.findall(step_hlo(kind, single_mesh))
                for seg in name.split("/")}
    # a prompt fills the prefill cache whole: its write is the identity, which
    # XLA removes, and the cache leaves the layer scan as the scan's output
    want = SCOPES - {"kv_write"} if kind == "prefill" else SCOPES
    assert want <= segments, sorted(want - segments)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_scopes_change_only_metadata(kind, single_mesh, monkeypatch):
    scoped = step_hlo(kind, single_mesh)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = step_hlo(kind, single_mesh)
    assert not any(seg in SCOPES for name in OP_NAME.findall(plain)
                   for seg in name.split("/"))
    assert scoped != plain
    assert strip(scoped) == strip(plain)
