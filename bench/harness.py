"""One run of one cell: set-up, a measured window, an optional trace, the check
against the reference, and the result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``, from the start of the process to the first
timed step) places the compile cache, finds the chips, builds the program's
step and its inputs from the seed and warms up the shapes the window uses.
The window runs whole steps until ``--seconds`` have passed; rates are all
the work of the window over its length, tails are over all its steps, and a
compilation inside it fails the run. With ``--trace 1`` the profiler records
the first ``trace_steps`` steps of the window, and the per-layer metrics are
read from that trace; after the window and the reading of the device's peak
memory, the step is compiled afresh for the map of its operations to the
program's layer scopes (``bench/scopes.py``), which splits the trace by
scope. Then the program's state is freed and the reference checks the run's
outputs.

The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit,
which also end standard error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from bench import peaks, scopes, spec, trace as tr

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoChip(RuntimeError):
    """Fewer chips than the cell asks for, or none at all."""


@dataclass
class Run:
    """What a metric reader reads (``bench/metrics/<name>.py``)."""
    kind: str
    chips: int
    peak: peaks.Peak | None  # None off a TPU: no device metric is read
    setup_s: float
    window_s: float
    steps: int
    items: int
    step_s: list
    trace: tr.Summary | None = None
    traced_steps: int = 0
    traced_work: tuple = (0, None)


def parse(argv=None):
    ap = argparse.ArgumentParser(prog="python3 bench/run.py",
                                 description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def place_compile_cache(root: Path) -> str:
    """JAX's persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else ``<checkout>/.jax_cache``, a fixed path; every program is
    cached, however short its compile."""
    import jax

    path = os.environ.get(CACHE_ENV) or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chip_mesh(chips: int, allow_cpu: bool = False):
    """A (data, model) mesh of ``chips`` devices: 1x1 or 2x2. Raises
    :class:`NoChip` without a TPU (``allow_cpu`` is for tests)."""
    import jax
    from jax.sharding import AxisType

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU: jax's devices are {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax sees {len(devs)}")
    layout = {1: (1, 1), 4: (2, 2)}[chips]
    return jax.make_mesh(layout, ("data", "model"), devices=devs[:chips],
                         axis_types=(AxisType.Auto, AxisType.Auto))


class CompileCounter:
    """Counts jax compilations (trace, lowering or backend compile) while
    armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw):
        if self.armed and event.startswith("/jax/core/compile/"):
            self.count += 1


def make_loop(cell: spec.Cell, mesh, seed: int):
    return cell.loop(cell.family, cell.model, cell.mix, mesh, seed)


def run_window(loop, seconds: float, counter: CompileCounter, trace_steps=0,
               trace_dir=None):
    """Whole steps until ``seconds`` have passed. Returns ``(window_s,
    items, step_s, planes)``; ``planes`` is the trace of the first
    ``trace_steps`` steps when ``trace_dir`` is given."""
    import jax
    from jax.profiler import TraceAnnotation

    step_s, items, traced = [], 0, None
    counter.armed = True
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    t0 = now = time.perf_counter()
    while now - t0 < seconds:
        with TraceAnnotation("bench.step"):
            items += loop.step()
        t = time.perf_counter()
        step_s.append(t - now)
        now = t
        if trace_dir is not None and len(step_s) == trace_steps:
            jax.profiler.stop_trace()  # writing the trace is not window time
            traced, trace_dir = trace_dir, None
            now = time.perf_counter()
            t0 += now - t
    if trace_dir is not None:
        jax.profiler.stop_trace()
        traced = trace_dir
    counter.armed = False
    if counter.count:
        raise RuntimeError(f"{counter.count} compilations inside the window")
    window_s = sum(step_s)
    planes = tr.load(tr.find_xplane(traced)) if traced else None
    return window_s, items, step_s, planes


def summarize(planes) -> tr.Summary:
    return tr.reduce(tr.device_ops(planes), tr.host_spans(planes), tr.step_window(planes),
                     tr.device_ops(planes, line_re=tr.ASYNC_LINE))


def memory_peak(mesh) -> int:
    peak = 0
    for d in mesh.devices.flat:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(args, t_start: float, root: Path = spec.ROOT, allow_cpu: bool = False) -> dict:
    """One run; returns the result object. ``allow_cpu`` skips the look for
    a chip (tests)."""
    # refuses before device work; the cache's room for the window is reckoned
    # at the chip's peaks, so a run on the CPU (tests) does not ask for it
    cell = spec.load_cell(args.workload, root, None if allow_cpu else args.seconds)
    place_compile_cache(root)
    mesh = chip_mesh(cell.chips, allow_cpu)
    dev = mesh.devices.flat[0]
    peak = peaks.peak_for(dev.device_kind) if dev.platform == "tpu" else None
    counter = CompileCounter()
    loop = make_loop(cell, mesh, args.seed)
    with mesh:
        loop.setup()
        first_step = loop.steps
        setup_s = time.perf_counter() - t_start
        with tempfile.TemporaryDirectory() as tmp:
            window_s, items, step_s, planes = run_window(
                loop, args.seconds, counter,
                trace_steps=cell.mix["trace_steps"],
                trace_dir=tmp if args.trace else None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": mesh.size, "memory_peak_bytes": memory_peak(mesh)}
    r = Run(kind=cell.mix["kind"], chips=mesh.size, peak=peak, setup_s=setup_s,
            window_s=window_s, steps=len(step_s), items=items, step_s=step_s)
    if planes is not None:
        r.trace = summarize(planes)
        r.trace.scope_s = scopes.split(loop, planes, cell.family.SCOPES)
        r.traced_steps = min(cell.mix["trace_steps"], len(step_s))
        r.traced_work = loop.work(first_step, first_step + r.traced_steps)
        device.update(busy_s=r.trace.busy_s, window_s=r.trace.window_s)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cell.reader(m["name"])(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    outputs = loop.finish()
    readings = loop.check(outputs)["f32"]
    checks = {k: {"value": v, "limit": cell.limits[k]} for k, v in readings.items()}
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    result = {"correct": bool(correct), "attempted": items, "failed": 0,
              "metrics": metrics, "device": device}
    if r.trace is not None:
        result["breakdown"] = r.trace.breakdown()
    result["checks"] = checks
    print(f"window: {len(step_s)} steps in {window_s:.6f} s, step median "
          f"{float(np.median(step_s)):.6f} s, max {max(step_s):.6f} s",
          file=sys.stderr)
    return result


def main(t_start: float, argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args, t_start)
    except (NoChip, spec.CellError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
