"""``correct`` comes out false when the timed path is broken, and true when
it is not: the harness runs end to end on the CPU at a tiny size, with the
look for a chip skipped, under the cells' own limits. Each fault below is
planted in the program's step underneath the harness, once for each fault a
one-chip cell of that kind can have (a prefill of one prompt has no half
batch to leave out; no one-chip cell has an exchange between chips):

* ``state_unchanged``: the step returns the cache it was given;
* ``half_batch``: the second half of the batch is not computed, its outputs
  copied from the first half;
* ``answer_altered``: the answer is altered where the program produces it
  (decode: the served token is one past the argmax; prefill: the largest
  logit is moved to the smallest);
* ``exchange_left_out``: for the four-chip decode cell, the all-reduce over
  the model axis is left out, so a chip's output projections keep only its
  own shard's part: every layer's attention output and MLP down projection
  lose the half of their inputs (heads, ffn) that the other shard holds.

The control test reads the fp8 reference in the program's place and sees at
least one number over its limit."""
import json
import time

import jax
import jax.numpy as jnp
import pytest

from bench_fixtures import CONTROL_SIZE, REPO, tiny_root, write_root  # noqa: F401 (puts the checkout on sys.path)
from bench import harness, program, spec


def _broken(fault):
    real = program.build

    def build(*a, **k):
        fn, args = real(*a, **k)

        def step(params, batch, cache):
            if fault == "exchange_left_out":
                b = params["blocks"]
                wo, down = b["attn"]["wo"], b["mlp"]["wo"]
                h, f = wo.shape[1] // 2, down.shape[1] // 2
                b = dict(b, attn=dict(b["attn"], wo=wo.at[:, h:].set(0)),
                         mlp=dict(b["mlp"], wo=down.at[:, f:].set(0)))
                return fn(dict(params, blocks=b), batch, cache)
            logits, new = fn(params, batch, cache)
            if fault == "state_unchanged":
                return logits, cache
            if fault == "half_batch":
                half = logits.shape[0] // 2
                return logits.at[half:].set(logits[:half]), new
            vocab = jnp.arange(logits.shape[-1])
            top = jnp.argmax(logits, -1)[..., None]
            if a[2] == "decode":  # serve the token after the argmax
                return jnp.where(vocab == (top + 1) % vocab.size,
                                 logits.max() + 1, logits), new
            return jnp.where(vocab == top, logits.min(), logits), new

        return jax.jit(step), args

    return build


def _run(root, workload, seed=11):
    args = harness.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", "0.2", "--trace", "0"])
    return harness.run(args, time.perf_counter(), root=root, allow_cpu=True)


@pytest.mark.parametrize("workload", ["tiny.decode", "tiny.prefill"])
def test_sound_run_is_correct(tiny_root, workload):
    result = _run(tiny_root, workload)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("workload,fault", [
    ("tiny.decode", "state_unchanged"), ("tiny.decode", "half_batch"),
    ("tiny.decode", "answer_altered"), ("tiny.prefill", "state_unchanged"),
    ("tiny.prefill", "answer_altered")])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, workload, fault):
    monkeypatch.setattr(program, "build", _broken(fault))
    result = _run(tiny_root, workload)
    assert not result["correct"], result["checks"]


def test_exchange_left_out_is_not_correct(tmp_path, monkeypatch):
    """Under the four-chip cell's own limits, at the width and depth where
    errors grow as they do at the published size (``CONTROL_SIZE``)."""
    x4 = json.loads((REPO / "bench" / "limits" / "qwen3-8b.decode_32k.x4.json").read_text())
    root = write_root(tmp_path, config=CONTROL_SIZE, limits={"decode": x4})
    assert _run(root, "tiny.decode")["correct"]
    monkeypatch.setattr(program, "build", _broken("exchange_left_out"))
    result = _run(root, "tiny.decode")
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("workload", ["tiny.decode", "tiny.prefill"])
def test_control_fails_a_limit(tmp_path, workload):
    root = write_root(tmp_path, config=CONTROL_SIZE)
    cell = spec.load_cell(workload, root)
    harness.place_compile_cache(root)
    mesh = harness.chip_mesh(1, allow_cpu=True)
    with mesh:
        loop = harness.make_loop(cell, mesh, 23)
        loop.setup()
        harness.run_window(loop, 0.2, harness.CompileCounter())
        readings = loop.check(loop.finish(), ("f32", "fp8"))
    assert all(v <= cell.limits[k] for k, v in readings["f32"].items()), readings
    assert any(v > cell.limits[k] for k, v in readings["fp8"].items()), readings
