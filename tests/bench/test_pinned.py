"""The qwen3 family gives the numbers the benchmark gave before model families
were found by ``model_type``, recorded then in ``data/qwen3_pinned.json``: at
``bench_fixtures.TINY`` (tied, and untied) on the CPU,

* the program's parameter tree and the drawn decode cache, by a SHA-256 of
  every leaf's bytes: the draws are integer operations and one rounding to
  bfloat16, so they are equal bit for bit;
* the reference's float32 and fp8 logits and its cache entries, over a drawn
  past (decode) and over none (prefill), by a sketch: the norm and eight
  projections on fixed Gaussian directions, within 1e-5 of the norm. Float32
  sums may differ in their last bits between CPUs; a changed draw, mapping or
  equation moves them by far more;
* ``decode_flops``, ``decode_bytes`` and ``prefill_flops`` at both published
  configurations, at the cells' sizes, exactly.
"""
import hashlib
import json

import jax
import numpy as np
import pytest

from bench_fixtures import REPO, TINY
from bench import config, program, seeded
from bench.families import qwen3
from bench.harness import chip_mesh

PINNED = json.loads((REPO / "tests" / "bench" / "data" / "qwen3_pinned.json").read_text())
SEED, CONTEXT, FILL, BATCH = (PINNED[k] for k in ("seed", "context", "fill", "batch"))
CONFIGS = {"tied": TINY, "untied": dict(TINY, tie_word_embeddings=False)}


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_flatten_with_path(tree)[0],
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def sketch(a) -> list:
    """Norm and 8 projections on fixed Gaussian directions of a float array."""
    x = np.asarray(a, np.float64).ravel()
    r = np.random.default_rng(x.size).standard_normal((8, x.size))
    return [float(np.linalg.norm(x)), *map(float, r @ x)]


@pytest.mark.parametrize("tag", sorted(CONFIGS))
def test_program_draws_are_pinned(tag):
    m = qwen3.from_dict("tiny", CONFIGS[tag])
    mesh = chip_mesh(1, allow_cpu=True)
    with mesh:
        _, (p_sds, _, c_sds) = program.build(qwen3.arch_config(m), "t", "decode",
                                             CONTEXT, BATCH, mesh)
        params = qwen3.make_params(m, p_sds, SEED)
        cache = qwen3.make_cache(m, c_sds, SEED, FILL)
    assert digest(params) == PINNED["digests"][f"{tag}/params"]
    assert digest(cache) == PINNED["digests"][f"{tag}/cache"]


@pytest.mark.parametrize("run", ["decode", "prefill"])
@pytest.mark.parametrize("tag", sorted(CONFIGS))
def test_reference_readings_are_pinned(tag, run):
    m = qwen3.from_dict("tiny", CONFIGS[tag])
    ref = qwen3.Reference(m, SEED, ("f32", "fp8"))
    toks = seeded.prompt(SEED, 3, m, BATCH, 6 if run == "decode" else 16)
    kv = {}
    if run == "decode":
        hid = ref.run(toks, FILL, past=lambda i: ref.cache(i, BATCH, CONTEXT, FILL),
                      on_layer=kv.__setitem__)
    else:
        hid = ref.run(toks, 0, on_layer=kv.__setitem__)
    logits = ref.logits(hid)
    got = {}
    for p in ("f32", "fp8"):
        got[f"{tag}/{run}/logits/{p}"] = logits[p]
        for i in range(m.layers):
            for name, x in kv[i][p].items():
                got[f"{tag}/{run}/cache/{p}/{name}/{i}"] = x
    want = {k: v for k, v in PINNED["sketches"].items() if k.startswith(f"{tag}/{run}/")}
    assert sorted(got) == sorted(want)
    for k, w in want.items():
        s = sketch(got[k])
        assert np.allclose(s, w, rtol=0, atol=1e-5 * w[0]), (k, s, w)


@pytest.mark.parametrize("name", ["qwen3-0.6b", "qwen3-8b"])
def test_counts_are_pinned(name):
    m, fam = config.load(REPO / "bench" / "configs" / f"{name}.json")
    assert fam.SCOPES == qwen3.SCOPES
    assert {"decode_flops": fam.decode_flops(m, 2, 24576),
            "decode_bytes": fam.decode_bytes(m, 2, 24576),
            "prefill_flops": fam.prefill_flops(m, 1, 16384)} == PINNED["counts"][name]
