"""Device time of a traced step split by the program's layer scopes.

The model step runs each layer boundary under a ``jax.named_scope``
(``src/repro/models``). The compiled HLO keeps the scope path of every
instruction in its metadata, ``op_name="jit(decode_step)/layers/while/body/
closed_call/attn_core/dot_general"``; a fusion keeps its root's, and one whose
root has none (a multi-output fusion's tuple) takes the scope its fused
instructions share. So each leaf device operation of the step's module is
mapped to the innermost scope on its path of the vocabulary of the cell's
family (``SCOPES`` of ``bench/families/<model_type>.py``); a scope that one
family's program adds counts under itself:

* an operation under ``layers`` and under no scope inside it counts under
  ``layers``: the scan's slicing of its inputs and stacking of its outputs;
* an operation whose path holds no scope of the vocabulary, or that has no
  ``op_name`` at all (the copies XLA inserts), counts as ``unscoped``;
* operations of other modules (the harness's argmax, the zeroed cache) are
  left out: an operation counts where the module's own event on the
  ``XLA Modules`` line holds its midpoint, since instruction names repeat
  across modules.

Leaf operations are those :func:`bench.trace.reduce` counts per operation
(a loop is not counted beside its body), clipped to the window; seconds are
means over the devices traced. The TPU's trace names each operation but
carries no ``op_name``, so the map comes from the step's optimized HLO
(:func:`step_hlo`), compiled after the window (:func:`split`, which
``bench/harness.py`` calls in a traced run; its readers are
:data:`METRICS`).

    PYTHONPATH=src python3 -m bench.scopes --workload <name> --seed <n> --seconds <s>

runs one cell as ``bench/run.py --trace 1`` does, up to the trace, and prints
one JSON line: the device time per traced step in every scope, the readers of
``bench/metrics/`` that read it, the traced and untraced steps' rates, and
the seconds the split takes after the window (``split_s``: the compile and
the map) beside those of the harness's own reduction (``summarize_s``).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

from bench import trace as tr

UNSCOPED = "unscoped"
MODULES_LINE = r"^XLA Modules$"
METRICS = ("layer_carry_ms.decode", "unscoped_ms.decode", "attention_ms.decode",
           "kv_write_ms.decode", "mlp_ms.decode", "attention_ms.prefill",
           "mlp_ms.prefill")

_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) \(")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([^\s=]+) = ")
_CALLS = re.compile(r"calls=%([^\s,]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def scope_of(op_name: str | None, vocab) -> str:
    """The innermost scope of the vocabulary ``vocab`` on the path
    ``op_name``, else :data:`UNSCOPED`."""
    for seg in reversed((op_name or "").split("/")):
        if seg in vocab:
            return seg
    return UNSCOPED


def hlo_scopes(hlo: str, vocab) -> tuple[str, dict]:
    """``(module name, {instruction: scope})`` of an optimized HLO text, every
    computation's instructions included, by the scopes of ``vocab``. A
    fusion whose root carries no ``op_name`` (a multi-output fusion's tuple)
    counts under the one scope its fused instructions share, else as
    unscoped."""
    lines = hlo.splitlines()
    m = _MODULE.match(lines[0]) if lines else None
    if not m:
        raise ValueError("not an HLO module's text")
    named, calls, members, comp = {}, {}, defaultdict(list), None
    for line in lines:
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        ins = _INSTRUCTION.match(line)
        if not ins:
            continue
        name, op = ins.group(1), _OP_NAME.search(line)
        named[name] = scope_of(op.group(1), vocab) if op else None
        members[comp].append(name)
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    out = {}
    for name, scope in named.items():
        if scope is None and name in calls:
            inner = {named[i] for i in members[calls[name]]} - {None}
            scope = inner.pop() if len(inner) == 1 else None
        out[name] = scope or UNSCOPED
    return m.group(1), out


def module_ops(planes, module: str) -> dict:
    """``{device: [(op, start_s, end_s)]}``: the ``XLA Ops`` of each device
    whose midpoint lies in an execution of ``module`` on its ``XLA Modules``
    line (events named ``<module>`` or ``<module>(<id>)``); the midpoint, as
    an operation's ends and its module's can differ in the last digit."""
    runs = {dev: tr.union((a, b) for name, a, b in evs
                          if name.split("(", 1)[0] == module)
            for dev, evs in tr.device_ops(planes, line_re=MODULES_LINE).items()}
    out = {}
    for dev, evs in tr.device_ops(planes).items():
        spans = runs.get(dev, [])
        starts = [a for a, _ in spans]
        kept = []
        for name, a, b in evs:
            i = bisect.bisect_right(starts, (a + b) / 2) - 1
            if i >= 0 and (a + b) / 2 <= spans[i][1]:
                kept.append((name, a, b))
        if kept:
            out[dev] = kept
    return out


def by_scope(ops_by_device: dict, scopes: dict, window) -> dict:
    """Seconds of leaf operation time in each scope over ``window``, mean
    over the devices (:func:`bench.trace.reduce`'s per-operation time); an
    operation missing from ``scopes`` is unscoped."""
    out = defaultdict(float)
    if ops_by_device:
        for name, s in tr.reduce(ops_by_device, [], window).op_s.items():
            out[scopes.get(name, UNSCOPED)] += s
    return dict(out)


def split(loop, planes, vocab) -> dict:
    """Seconds of the traced window's leaf operations of the step loop's
    step in each scope of ``vocab`` (:func:`by_scope`), by the map of a
    fresh compile of the step (:func:`step_hlo`): after the window, before
    the loop frees its state."""
    module, mapping = hlo_scopes(step_hlo(loop), vocab)
    return by_scope(module_ops(planes, module), mapping, tr.step_window(planes))


def ms_per_step(run, kind: str, scope: str):
    """A reader's value: ms per traced step of ``kind`` in ``scope``, or None
    where there is no trace, no scope map, no operation of the step carries
    a scope (a program without scopes) or the scope holds no time."""
    scope_s = run.trace.scope_s if run.trace is not None else None
    if run.kind != kind or not scope_s or not run.traced_steps:
        return None
    if not any(v > 0 for k, v in scope_s.items() if k != UNSCOPED):
        return None
    value = scope_s.get(scope, 0.0)
    return 1e3 * value / run.traced_steps if value > 0 else None


def step_hlo(loop) -> str:
    """The optimized HLO of a step loop's jitted step, lowered against its
    live state (a prefill step takes the prompt, a decode step one token)
    and compiled afresh. JAX's persistent compile cache keys a program
    without its metadata, so a hit can hand back an executable compiled
    from another source tree, with that tree's ``op_name`` paths or none;
    the compiler is deterministic, so a fresh compile of the same program
    names its instructions as the executable that ran did."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    n = loop.length if loop.mix["kind"] == "prefill" else 1
    tokens = jax.ShapeDtypeStruct((loop.batch, n), "int32", sharding=loop.tok_sharding)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()  # else the executable that ran is found in memory
    try:
        return loop.fn.lower(loop.params, {"tokens": tokens}, loop.cache).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def main(argv=None) -> int:
    import argparse
    import json
    import tempfile
    import time

    import numpy as np

    from bench import harness, peaks, spec

    ap = argparse.ArgumentParser(prog="python3 -m bench.scopes",
                                 description="one traced run of a cell, split by scope")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    cell = spec.load_cell(args.workload, spec.ROOT, args.seconds)
    harness.place_compile_cache(spec.ROOT)
    mesh = harness.chip_mesh(cell.chips)
    dev = mesh.devices.flat[0]
    loop = harness.make_loop(cell, mesh, args.seed)
    k = cell.mix["trace_steps"]
    with mesh:
        loop.setup()
        first = loop.steps
        setup_s = time.perf_counter() - t_start
        with tempfile.TemporaryDirectory() as tmp:
            window_s, items, step_s, planes = harness.run_window(
                loop, args.seconds, harness.CompileCounter(), trace_steps=k,
                trace_dir=tmp)
        t0 = time.perf_counter()
        summary = harness.summarize(planes)
        t1 = time.perf_counter()
        summary.scope_s = split(loop, planes, cell.family.SCOPES)
        split_s, summarize_s = time.perf_counter() - t1, t1 - t0
    n = min(k, len(step_s))
    run = harness.Run(kind=cell.mix["kind"], chips=mesh.size,
                      peak=peaks.peak_for(dev.device_kind), setup_s=setup_s,
                      window_s=window_s, steps=len(step_s), items=items,
                      step_s=step_s, trace=summary, traced_steps=n,
                      traced_work=loop.work(first, first + n))
    metrics = {}
    for name in [m["name"] for m in cell.per_layer] + list(METRICS):
        value = spec.load_reader(spec.ROOT, name)(run)
        if value is not None:
            metrics[name] = value
    per_step = 1e3 / n
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": mesh.size},
        "metrics": metrics,
        "scope_ms": {s: summary.scope_s.get(s, 0.0) * per_step
                     for s in (*cell.family.SCOPES, UNSCOPED)},
        "scope_sum_ms": sum(summary.scope_s.values()) * per_step,
        "leaf_ms": sum(summary.op_s.values()) * per_step,
        "busy_ms": summary.busy_s * per_step,
        "window_ms": summary.window_s * per_step,
        "traced_steps": n,
        "traced_steps_per_s": n / sum(step_s[:n]),
        "untraced_steps_per_s": ((len(step_s) - n) / sum(step_s[n:])
                                 if len(step_s) > n else None),
        "step_ms_median": float(np.median(step_s)) * 1e3,
        "summarize_s": summarize_s, "split_s": split_s, "setup_s": setup_s,
        "breakdown": summary.breakdown(),
    }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
