"""Closed-loop decode: ``batch`` sequences whose cache is drawn from the seed
and filled to ``fill`` of ``context`` positions; each step decodes one token
per sequence through the program's decode step, takes the argmax on the
device, reads the tokens back on the host and feeds them to the next step,
as a serving loop does.

Check: once the window has closed, every token the run served (warm-up and
window, all sequences) is compared with the reference, which runs over the
same first tokens and served tokens through the same drawn cache:

* ``logit_gap``: the widest gap by which a served token's reference logit
  lies below the reference's best logit at that position;
* ``kv_err``: the worst, over layers and over the cache's entries (the
  family's ``written``: K and V for qwen3), relative L2 error of the
  entries the program wrote for the served positions.

The control (``fp8``) reads, at the same positions, the gap of the token the
fp8 reference puts first, and the error of its cache entries.

Everything that depends on the model comes from the cell's family
(``bench/families/<model_type>.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import peaks, program, seeded


def refuse(family, model, mix: dict, chips: int, seconds: float) -> str | None:
    """Why the mix's cache cannot hold a window of ``seconds``, or None. The
    cache has to hold the warm-up and every step the window could take at
    the least time a step can take: its FLOPs and bytes (the family's
    counts) at the peaks of the fastest chip ``bench/peaks.py`` knows."""
    b, fill = mix["batch"], mix["fill"]
    flops = max(p.flops for p in peaks.PEAKS.values()) * chips
    bw = max(p.hbm_bw for p in peaks.PEAKS.values()) * chips
    least = max(family.decode_flops(model, b, fill) / flops,
                family.decode_bytes(model, b, fill) / bw)
    need = mix["warmup_steps"] + math.ceil(seconds / least) + 1
    room = mix["context"] - 1 - fill
    if need > room:
        return (f"a cache of {mix['context']} filled to {fill} holds {room} "
                f"steps; warm-up and {seconds} s at the least step time of "
                f"{least * 1e3:.3f} ms need {need}: lower the fill")
    return None


class Decode:
    def __init__(self, family, model, mix: dict, mesh, seed: int):
        self.family, self.m, self.mix, self.mesh, self.seed = family, model, mix, mesh, seed
        self.batch, self.context, self.fill = mix["batch"], mix["context"], mix["fill"]
        self.steps = 0

    def setup(self):
        fam, m = self.family, self.m
        self.fn, (p_sds, b_sds, c_sds) = program.build(
            fam.arch_config(m), self.mix["name"], "decode", self.context,
            self.batch, self.mesh)
        self.tok_sharding = b_sds["tokens"].sharding
        self.params = fam.make_params(m, p_sds, self.seed)
        self.cache = fam.make_cache(m, c_sds, self.seed, self.fill)
        self.argmax = jax.jit(lambda lg: jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None],
                              out_shardings=self.tok_sharding)
        self.first = seeded.first_tokens(self.seed, m, self.batch)
        self.host_tok = self.first
        self.served = []
        for _ in range(self.mix["warmup_steps"]):
            self.step()

    def step(self) -> int:
        """One decode step; returns the tokens it served."""
        if self.fill + self.steps >= self.context - 1:
            raise RuntimeError("the decode cache is full: raise the context or "
                               "lower the fill")
        with TraceAnnotation("bench.inputs"):
            tok = jax.device_put(self.host_tok, self.tok_sharding)
        with TraceAnnotation("bench.dispatch"):
            logits, self.cache = self.fn(self.params, {"tokens": tok}, self.cache)
            nxt = self.argmax(logits)
        with TraceAnnotation("bench.readback"):
            self.host_tok = np.asarray(nxt)
        self.served.append(self.host_tok)
        self.steps += 1
        return self.batch

    def work(self, first: int, last: int) -> tuple:
        """``(flops, bytes)`` that steps ``first`` to ``last - 1`` need."""
        ctx = [self.fill + i for i in range(first, last)]
        fam = self.family
        return (sum(fam.decode_flops(self.m, self.batch, c) for c in ctx),
                sum(fam.decode_bytes(self.m, self.batch, c) for c in ctx))

    def finish(self) -> dict:
        """The run's outputs; frees the program's state."""
        kv = jax.device_get(self.family.written(self.cache, self.fill, self.steps))
        out = {"served": np.concatenate(self.served, 1), "kv": kv}
        del self.params, self.cache
        return out

    def check(self, out: dict, precisions=("f32",)) -> dict:
        """``{precision: {number: reading}}`` of the run's outputs."""
        m = self.m
        served = out["served"]  # [b, n]: the token served at each step
        n = served.shape[1]
        # padded to a multiple of 64 positions, so that runs of about the
        # same length share the reference's compiled programs; the padding
        # comes after every served position and, being causal, changes none
        inputs = np.concatenate([self.first, served[:, :-1]], 1)
        inputs = np.pad(inputs, ((0, 0), (0, -n % 64)))
        ref = self.family.Reference(m, self.seed, precisions)
        kv_err = {p: 0.0 for p in precisions}
        prog_kv = out["kv"]

        def on_layer(i, kv):
            want = {k: x[:, :n] for k, x in kv["f32"].items()}
            for p in precisions:
                got = ({k: prog_kv[k][i] for k in want} if p == "f32"
                       else {k: x[:, :n] for k, x in kv[p].items()})
                for k, r in want.items():
                    kv_err[p] = max(kv_err[p], rel_err(got[k], r))

        hid = ref.run(inputs, self.fill,
                      past=lambda i: ref.cache(i, self.batch, self.context, self.fill),
                      on_layer=on_layer)
        all_logits = {p: x[:, :n] for p, x in ref.logits(hid).items()}
        logits = all_logits["f32"]
        best = logits.max(-1)
        readings = {"f32": {"logit_gap": _gap(logits, best, served),
                            "kv_err": kv_err["f32"]}}
        for p in precisions:
            if p != "f32":
                pick = jnp.argmax(all_logits[p], -1)
                readings[p] = {"logit_gap": _gap(logits, best, pick),
                               "kv_err": kv_err[p]}
        return readings


def rel_err(got, want) -> float:
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(jnp.asarray(got, jnp.float32) - want)
                 / jnp.linalg.norm(want))


def _gap(logits, best, tokens) -> float:
    at = jnp.take_along_axis(logits, jnp.asarray(tokens)[..., None], -1)[..., 0]
    return float(jnp.max(best - at))


Loop = Decode  # the step loop the harness runs for this kind
