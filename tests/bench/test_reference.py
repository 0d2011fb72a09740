"""The plain float32 reference (bench/reference) against the program's own
model code (``repro.models`` through ``dryrun.build_cell``) at a tiny width
on the same seeded weights, both in float32: prefill's last-position logits
and the cache it writes, and decode through a drawn cache.

Tolerance: 2e-4 relative L2. Both sides compute the same float32 arithmetic;
they differ in the order of reductions (the program's attention is a chunked
online softmax over blocks, the reference's a plain softmax) and in fusion,
which moves results by about 1e-6 relative at this size. A wrong head
mapping, RoPE pairing, norm or mask moves them by 1e-1 or more."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_fixtures import TINY  # noqa: F401 (puts the checkout on sys.path)
from bench import program, seeded
from bench.families import qwen3
from bench.kinds.decode import rel_err
from bench.families.qwen3 import Reference
from bench.reference.ops import fp8

TOL = 2e-4


@pytest.fixture(scope="module")
def model():
    return qwen3.from_dict("tiny", dict(TINY, torch_dtype="float32",
                                         tie_word_embeddings=False))


@pytest.fixture(scope="module")
def mesh():
    from bench.harness import chip_mesh

    return chip_mesh(1, allow_cpu=True)


def test_prefill_matches_reference(model, mesh):
    seed, length = 5, 48
    fn, (p_sds, _, c_sds) = program.build(qwen3.arch_config(model), "t", "prefill",
                                          length, 2, mesh)
    params = qwen3.make_params(model, p_sds, seed)
    toks = seeded.prompt(seed, 0, model, 2, length)
    logits, cache = fn(params, {"tokens": jnp.asarray(toks)}, program.empty_cache(c_sds))
    kv = {}
    ref = Reference(model, seed)
    hid = ref.run(toks, 0, on_layer=lambda i, x: kv.__setitem__(i, x["f32"]))
    want = ref.logits({"f32": hid["f32"][:, -1:]})["f32"]
    assert rel_err(logits, want) < TOL
    for i in range(model.layers):
        assert rel_err(cache["k"][i], kv[i]["k"]) < TOL
        assert rel_err(cache["v"][i], kv[i]["v"]) < TOL


def test_decode_through_drawn_cache_matches_reference(model, mesh):
    seed, context, fill, steps = 9, 64, 40, 5
    fn, (p_sds, _, c_sds) = program.build(qwen3.arch_config(model), "t", "decode",
                                          context, 2, mesh)
    params = qwen3.make_params(model, p_sds, seed)
    cache = qwen3.make_cache(model, c_sds, seed, fill)
    tok = seeded.first_tokens(seed, model, 2)
    inputs, outs = [], []
    for _ in range(steps):
        inputs.append(tok)
        logits, cache = fn(params, {"tokens": jnp.asarray(tok)}, cache)
        outs.append(logits)
        tok = np.asarray(jnp.argmax(logits[:, -1], -1))[:, None].astype(np.int32)
    kv = {}
    ref = Reference(model, seed)
    hid = ref.run(np.concatenate(inputs, 1), fill,
                  past=lambda i: ref.cache(i, 2, context, fill),
                  on_layer=lambda i, x: kv.__setitem__(i, x["f32"]))
    want = ref.logits(hid)["f32"]
    assert rel_err(jnp.concatenate(outs, 1), want) < TOL
    for i in range(model.layers):
        assert rel_err(cache["k"][i, :, fill:fill + steps], kv[i]["k"]) < TOL
        assert rel_err(cache["v"][i, :, fill:fill + steps], kv[i]["v"]) < TOL
    assert int(cache["len"][0]) == fill + steps


def test_stacked_weights_equal_layer_weights(model):
    """On the CPU the program's copy (``lax.map`` over layers) and the
    reference's (one layer at a time) are the same numbers, bit for bit."""
    m = dataclasses.replace(model, dtype="bfloat16")
    wkey = seeded.stream(2**33 + 1, seeded.WEIGHTS)
    stacked = jax.jit(lambda k: jax.lax.map(
        lambda i: qwen3.layer_weights(seeded.layer_key(k, i), m), jnp.arange(m.layers)))(wkey)
    for i in range(m.layers):
        one = qwen3.layer_weights(seeded.layer_key(wkey, i), m)
        for name, x in one.items():
            assert np.array_equal(np.asarray(stacked[name][i]), np.asarray(x)), name


def test_fp8_rounds_to_three_mantissa_bits():
    x = jnp.linspace(-3.0, 3.0, 1001)
    err = float(jnp.max(jnp.abs(fp8(x) - x)) / 3.0)
    # e4m3 keeps 3 mantissa bits: at most half a step of 2**-3 relative
    assert 1e-3 < err <= 2**-4
