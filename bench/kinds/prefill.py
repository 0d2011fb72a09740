"""Prefill: each step is a new prompt of ``length`` tokens per row, drawn from
the seed on the host, into an emptied cache (its ``len`` reset to 0) through
the program's prefill step; the last position's logits are read back to
the host, where the first token is drawn.

Check: once the window has closed, prompts drawn from the seed among those
served (``check_prompts`` of them, the last one always among them) are run
through the reference:

* ``logits_err``: the worst error of the last-position logits read back,
  over the sampled prompts: the largest absolute difference over the
  vocabulary, relative to the reference's largest logit (one wrong logit
  shows as much as a wrong vector);
* ``kv_err``: the worst, over layers and over the cache's entries (the
  family's ``written``: K and V for qwen3), relative L2 error of the cache
  the program wrote for the last prompt (every position).

The control (``fp8``) reads the same two numbers for the fp8 reference.

Everything that depends on the model comes from the cell's family
(``bench/families/<model_type>.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import program, seeded
from bench.kinds.decode import rel_err


def max_err(got, want) -> float:
    """Largest absolute difference relative to the largest ``|want|``."""
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32) - want))
                 / jnp.max(jnp.abs(want)))


class Prefill:
    def __init__(self, family, model, mix: dict, mesh, seed: int):
        self.family, self.m, self.mix, self.mesh, self.seed = family, model, mix, mesh, seed
        self.batch, self.length = mix["batch"], mix["length"]
        self.steps = 0

    def setup(self):
        fam, m = self.family, self.m
        self.fn, (p_sds, b_sds, c_sds) = program.build(
            fam.arch_config(m), self.mix["name"], "prefill", self.length,
            self.batch, self.mesh)
        self.tok_sharding = b_sds["tokens"].sharding
        self.len_sharding = c_sds["len"].sharding
        self.params = fam.make_params(m, p_sds, self.seed)
        self.cache = program.empty_cache(c_sds)
        self.logits = []
        for _ in range(self.mix["warmup_steps"]):
            self.step()

    def step(self) -> int:
        """One prompt per row; returns the prompt tokens it processed."""
        with TraceAnnotation("bench.inputs"):
            toks = seeded.prompt(self.seed, self.steps, self.m, self.batch, self.length)
            batch = {"tokens": jax.device_put(toks, self.tok_sharding)}
            self.cache["len"] = jax.device_put(np.zeros(self.batch, np.int32),
                                               self.len_sharding)
        with TraceAnnotation("bench.dispatch"):
            logits, self.cache = self.fn(self.params, batch, self.cache)
        with TraceAnnotation("bench.readback"):
            self.logits.append(np.asarray(logits))
        self.steps += 1
        return self.batch * self.length

    def work(self, first: int, last: int) -> tuple:
        n = last - first
        return n * self.family.prefill_flops(self.m, self.batch, self.length), None

    def finish(self) -> dict:
        rng = np.random.default_rng([self.seed, self.steps])
        k = min(self.mix["check_prompts"], self.steps)
        picks = sorted({self.steps - 1, *rng.choice(self.steps - 1, k - 1, replace=False)}
                       if k > 1 else {self.steps - 1})
        out = {"picks": picks,
               "logits": {i: self.logits[i] for i in picks},
               "kv": self.family.written(self.cache, 0, self.length)}
        del self.params, self.cache, self.logits
        return out

    def check(self, out: dict, precisions=("f32",)) -> dict:
        ref = self.family.Reference(self.m, self.seed, precisions)
        worst = {p: {"logits_err": 0.0, "kv_err": 0.0} for p in precisions}
        last = out["picks"][-1]
        for i in out["picks"]:
            toks = seeded.prompt(self.seed, i, self.m, self.batch, self.length)

            def on_layer(li, kv, keep=i == last):
                if not keep:
                    return
                want = kv["f32"]
                for p in precisions:
                    got = ({k: out["kv"][k][li] for k in want} if p == "f32"
                           else kv[p])
                    for k, r in want.items():
                        worst[p]["kv_err"] = max(worst[p]["kv_err"], rel_err(got[k], r))

            hid = ref.run(toks, 0, on_layer=on_layer)
            lg = ref.logits({p: h[:, -1:] for p, h in hid.items()})
            del hid
            for p in precisions:
                got = out["logits"][i] if p == "f32" else lg[p]
                worst[p]["logits_err"] = max(worst[p]["logits_err"],
                                             max_err(got, lg["f32"]))
        return worst


Loop = Prefill  # the step loop the harness runs for this kind
