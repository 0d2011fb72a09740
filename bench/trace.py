"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read from the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but jax (``ProfileData``), into plain lists: each device's operations
as ``(name, start_s, end_s)`` and the harness's own host spans (``bench.*``,
written with ``jax.profiler.TraceAnnotation``) on the same clock. From those:

* busy: the union of a device's operation intervals inside the window, a
  loop's whole span included (the device is running it); idle is the window
  less busy;
* per-operation device time: the sum of each operation name's durations,
  leaving out operations that contain others on the same line (a loop and
  its body's operations), so nothing counts twice; an operation is named by
  its HLO instruction name (``fusion.12``, ``copy.3``);
* collective time: the union of collective operations (all-reduce,
  all-gather, reduce-scatter, collective-permute, all-to-all), an
  asynchronous one from its start to its end as the ``Async XLA Ops`` line
  has it, and the part of that union during which the device runs no other
  leaf operation (exposed): a container, such as the layer scan's
  ``while``, spans its body's collectives too and hides none of them;
* idle gaps by host activity: each stretch of the window in which the
  device is idle, split by the innermost (shortest) host span covering it,
  summed by span name; idle time under no span is ``(no span)``;
* per-scope device time (``scope_s``): the step's leaf operations by the
  program's layer scopes, set by the harness from ``bench/scopes.py``.

Device numbers are means over the devices traced.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

TPU_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
ASYNC_LINE = r"^Async XLA Ops$"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)
NO_SPAN = "(no span)"


def load(path) -> list:
    """``[(plane_name, [(line_name, [(event, start_s, end_s), ...]), ...])]``
    of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                   for e in line.events]
            lines.append((line.name, evs))
        out.append((plane.name, lines))
    return out


def find_xplane(directory) -> Path:
    found = sorted(Path(directory).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def device_ops(planes, plane_re=TPU_PLANE, line_re=OPS_LINE, skip_re=None,
               per_line=False) -> dict:
    """``{device: [(op, start_s, end_s)]}`` from the lines matching
    ``line_re`` of the planes matching ``plane_re``, one device per plane
    (or per line); events whose name matches ``skip_re`` are left out."""
    out = defaultdict(list)
    for pname, lines in planes:
        if not re.search(plane_re, pname):
            continue
        for lname, evs in lines:
            if re.search(line_re, lname):
                key = f"{pname}/{lname}" if per_line else pname
                out[key].extend((op_name(e[0]), e[1], e[2]) for e in evs
                                if not (skip_re and re.search(skip_re, e[0])))
    return {k: sorted(v, key=lambda e: e[1]) for k, v in out.items() if v}


def op_name(event: str) -> str:
    """``fusion.12`` of ``%fusion.12 = bf16[...] fusion(...)``."""
    return event.split(" = ", 1)[0].lstrip("%")


def containers(ops) -> set:
    """Indices of the operations (sorted by start) whose interval holds the
    next operation's whole interval."""
    return {i for i in range(len(ops) - 1)
            if ops[i + 1][1] < ops[i][2] and ops[i + 1][2] <= ops[i][2]}


def host_spans(planes, prefix="bench.") -> list:
    """The harness's host spans, ``[(name, start_s, end_s)]``."""
    return sorted((e for pname, lines in planes if pname.startswith("/host:")
                   for _, evs in lines for e in evs if e[0].startswith(prefix)),
                  key=lambda e: e[1])


def step_window(planes) -> tuple:
    """``(start_s, end_s)`` from the first traced step's start to the last
    one's end (the harness's ``bench.step`` spans)."""
    steps = [s for s in host_spans(planes) if s[0] == "bench.step"]
    return steps[0][1], steps[-1][2]


def union(intervals) -> list:
    """Disjoint, sorted ``[(start, end)]`` covering ``intervals``."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base, minus) -> list:
    """``base`` less ``minus``; both disjoint and sorted."""
    out, j = [], 0
    for a, b in base:
        cur = a
        while j < len(minus) and minus[j][1] <= cur:
            j += 1
        k = j
        while k < len(minus) and minus[k][0] < b:
            if minus[k][0] > cur:
                out.append((cur, minus[k][0]))
            cur = max(cur, minus[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def attribute(gaps, spans) -> dict:
    """Seconds of ``gaps`` under each host span name (innermost wins)."""
    out = defaultdict(float)
    for a, b in gaps:
        over = [s for s in spans if s[1] < b and s[2] > a]
        cuts = sorted({a, b, *(t for s in over for t in s[1:] if a < t < b)})
        for lo, hi in zip(cuts, cuts[1:]):
            cover = [s for s in over if s[1] <= lo and s[2] >= hi]
            name = min(cover, key=lambda s: s[2] - s[1])[0] if cover else NO_SPAN
            out[name] += hi - lo
    return dict(out)


@dataclass
class Summary:
    window_s: float
    devices: int
    busy_s: float
    op_s: dict = field(default_factory=dict)
    collective_s: float = 0.0
    collective_exposed_s: float = 0.0
    idle_by_span: dict = field(default_factory=dict)
    scope_s: dict = field(default_factory=dict)  # {scope: seconds}, bench/scopes.py

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(ops_by_device: dict, spans: list, window, async_by_device=None) -> Summary:
    """Reduce the devices' operations and the host spans over ``window``
    (``(start_s, end_s)``) to a :class:`Summary`; ``async_by_device`` holds
    each device's asynchronous operations, start to end, which count only
    towards collective time."""
    lo, hi = window
    n = len(ops_by_device)
    if not n:
        raise ValueError("the trace holds no device operations")
    busy = coll = exposed = 0.0
    op_s = defaultdict(float)
    idle = defaultdict(float)
    for dev, ops in ops_by_device.items():
        spans_async = (async_by_device or {}).get(dev, [])
        ops = sorted(((name, a, b) for name, a, b in ops if b > lo and a < hi),
                     key=lambda e: (e[1], -e[2]))
        iv = union(clip([(a, b) for _, a, b in ops], lo, hi))
        busy += length(iv)
        outer = containers(ops)
        for i, (name, a, b) in enumerate(ops):
            if i not in outer:
                op_s[name] += min(b, hi) - max(a, lo)
        c = union(clip([(a, b) for name, a, b in ops + spans_async
                        if COLLECTIVE.search(name)], lo, hi))
        other = union(clip([(a, b) for i, (name, a, b) in enumerate(ops)
                            if i not in outer and not COLLECTIVE.search(name)],
                           lo, hi))
        coll += length(c)
        exposed += length(subtract(c, other))
        for k, v in attribute(subtract([(lo, hi)], iv), spans).items():
            idle[k] += v
    return Summary(window_s=hi - lo, devices=n, busy_s=busy / n,
                   op_s={k: v / n for k, v in op_s.items()},
                   collective_s=coll / n, collective_exposed_s=exposed / n,
                   idle_by_span={k: v / n for k, v in idle.items()})
