"""The trace reduction (bench/trace.py): on a hand-made trace whose numbers
are worked out below, and on a small trace recorded on the CPU (two host
devices, a sharded matmul with an all-reduce, the harness's host spans),
kept in tests/bench/data. The recorded trace's numbers are not device
metrics; they check the arithmetic against a brute-force reading."""
from pathlib import Path

import numpy as np
import pytest

import bench_fixtures  # noqa: F401 (puts the checkout on sys.path)
from bench import trace as tr

DATA = Path(__file__).parent / "data" / "cpu_2dev.xplane.pb"
CPU_SKIP = r"^(ThreadpoolListener|end: |PjRtCpu|Handle inputs|CommonPjRt)"


def test_union_subtract_clip():
    assert tr.union([(3, 4), (0, 2), (1, 2.5), (5, 5)]) == [(0, 2.5), (3, 4)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert tr.clip([(-1, 1), (2, 3), (9, 11)], 0, 10) == [(0, 1), (2, 3), (9, 10)]


def test_reduce_hand_made_trace():
    ops = {"d0": [("fusion.1", 0.0, 2.0), ("all-reduce.1", 1.0, 4.0),
                  ("fusion.2", 5.0, 6.0)],
           "d1": [("fusion.1", 0.0, 10.0)]}
    spans = [("bench.step", 0.0, 10.0), ("bench.dispatch", 0.0, 1.0),
             ("bench.readback", 6.0, 9.0)]
    s = tr.reduce(ops, spans, (0.0, 10.0))
    # d0 busy [0,4] u [5,6] = 5 s, d1 busy 10 s: mean 7.5
    assert s.busy_s == pytest.approx(7.5)
    assert s.idle_s == pytest.approx(2.5)
    # per op, mean over two devices: fusion.1 (2 + 10) / 2
    assert s.op_s == pytest.approx({"fusion.1": 6.0, "all-reduce.1": 1.5,
                                    "fusion.2": 0.5})
    # the all-reduce [1,4] overlaps fusion.1 on [1,2]: 2 s exposed on d0
    assert s.collective_s == pytest.approx(1.5)
    assert s.collective_exposed_s == pytest.approx(1.0)
    # d0 idle [4,5] under bench.step only, [6,9] under bench.readback,
    # [9,10] under bench.step: halved over two devices
    assert s.idle_by_span == pytest.approx({"bench.step": 1.0, "bench.readback": 1.5})
    b = s.breakdown(top=2)
    assert b["device_ops"] == [["fusion.1", 6.0], ["all-reduce.1", 1.5]]
    assert b["idle_gaps"][0] == ["bench.readback", 1.5]


def test_containers_count_once_and_names_are_short():
    planes = [("/device:TPU:0", [("XLA Ops", [
        ("%while.2 = (s32[]) while(...)", 0.0, 10.0),
        ("%fusion.1 = bf16[8] fusion(...)", 1.0, 4.0),
        ("%copy.3 = bf16[8] copy(...)", 4.0, 9.0),
        ("%fusion.1 = bf16[8] fusion(...)", 11.0, 12.0)])])]
    ops = tr.device_ops(planes)
    assert [e[0] for e in ops["/device:TPU:0"]] == ["while.2", "fusion.1", "copy.3", "fusion.1"]
    s = tr.reduce(ops, [], (0.0, 12.0))
    assert s.busy_s == pytest.approx(11.0)
    # the loop holds fusion.1 and copy.3: its own time is not counted again
    assert s.op_s == pytest.approx({"fusion.1": 4.0, "copy.3": 5.0})


def test_collective_inside_a_loop_is_exposed():
    """The loop spans its body's all-reduce, but runs nothing beside it: the
    all-reduce is exposed except where a leaf operation overlaps it."""
    planes = [("/device:TPU:0", [("XLA Ops", [
        ("%while.2 = (s32[]) while(...)", 0.0, 10.0),
        ("%fusion.1 = bf16[8] fusion(...)", 1.0, 4.0),
        ("%all-reduce.7 = bf16[8] all-reduce(...)", 3.0, 6.0),
        ("%copy.3 = bf16[8] copy(...)", 6.0, 9.0)])])]
    s = tr.reduce(tr.device_ops(planes), [], (0.0, 10.0))
    assert s.collective_s == pytest.approx(3.0)
    # fusion.1 overlaps [3, 4]; [4, 6] runs under the loop alone
    assert s.collective_exposed_s == pytest.approx(2.0)
    assert s.busy_s == pytest.approx(10.0)


def test_asynchronous_collective_counts_from_start_to_end():
    """An asynchronous all-reduce runs from its start to its end on the
    ``Async XLA Ops`` line; the ``XLA Ops`` line holds its start and the wait
    for it. Compute hides the part it overlaps; the wait is exposed."""
    planes = [("/device:TPU:0", [
        ("XLA Ops", [("%all-reduce-start.1 = ...", 2.0, 2.5),
                     ("%fusion.4 = ...", 2.5, 5.0),
                     ("%all-reduce-done.1 = ...", 5.0, 7.0)]),
        ("Async XLA Ops", [("%all-reduce-start.1 = ...", 2.0, 7.0),
                           ("%copy-start.2 = ...", 0.0, 9.0)])])]
    s = tr.reduce(tr.device_ops(planes), [], (0.0, 10.0),
                  tr.device_ops(planes, line_re=tr.ASYNC_LINE))
    assert s.collective_s == pytest.approx(5.0)
    assert s.collective_exposed_s == pytest.approx(2.5)
    # the asynchronous line counts towards no busy or per-operation time
    assert s.busy_s == pytest.approx(5.0)
    assert s.op_s == pytest.approx({"all-reduce-start.1": 0.5, "fusion.4": 2.5,
                                    "all-reduce-done.1": 2.0})


def test_window_clips_operations():
    s = tr.reduce({"d": [("op", -5.0, 1.0), ("op", 9.0, 20.0)]}, [], (0.0, 10.0))
    assert s.busy_s == pytest.approx(2.0)
    assert s.op_s == pytest.approx({"op": 2.0})
    assert s.idle_by_span == pytest.approx({tr.NO_SPAN: 8.0})


def test_no_device_operations_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({}, [], (0.0, 1.0))


def _brute(ops, lo, hi, step=1e-7):
    """Busy and exposed collective time by sampling the window; an operation
    that holds another one's whole interval hides no collective."""
    t = np.arange(lo, hi, step) + step / 2
    busy = np.zeros_like(t, bool)
    coll = np.zeros_like(t, bool)
    other = np.zeros_like(t, bool)
    for i, (name, a, b) in enumerate(ops):
        inside = (t >= a) & (t < b)
        busy |= inside
        holds = any(j != i and a <= c and d <= b and (c, d) != (a, b)
                    for j, (_, c, d) in enumerate(ops))
        if tr.COLLECTIVE.search(name):
            coll |= inside
        elif not holds:
            other |= inside
    return busy.sum() * step, (coll & ~other).sum() * step, coll.sum() * step


def test_recorded_cpu_trace():
    planes = tr.load(DATA)
    spans = tr.host_spans(planes)
    steps = [s for s in spans if s[0] == "bench.step"]
    assert len(steps) == 3
    assert {s[0] for s in spans} == {"bench.step", "bench.inputs",
                                     "bench.dispatch", "bench.readback"}
    window = (steps[0][1], steps[-1][2])
    ops = tr.device_ops(planes, plane_re=r"^/host:CPU$",
                        line_re=r"^tf_XLAPjRtCpuClient", skip_re=CPU_SKIP,
                        per_line=True)
    assert len(ops) == 3
    assert any(tr.COLLECTIVE.search(n) for v in ops.values() for n, _, _ in v)
    s = tr.reduce(ops, spans, window)
    busy = exposed = coll = 0.0
    for v in ops.values():
        b, e, c = _brute(v, *window)
        busy, exposed, coll = busy + b, exposed + e, coll + c
    n = len(ops)
    assert s.busy_s == pytest.approx(busy / n, abs=2e-6)
    assert s.collective_s == pytest.approx(coll / n, abs=2e-6)
    assert s.collective_exposed_s == pytest.approx(exposed / n, abs=2e-6)
    assert 0 < s.collective_exposed_s <= s.collective_s
    assert 0 < s.busy_s < s.window_s
    assert sum(s.idle_by_span.values()) == pytest.approx(s.idle_s, rel=1e-9)
    # sleeping in bench.inputs leaves every device idle for about 3 ms a step
    assert s.idle_by_span["bench.inputs"] > 3 * 0.003 * 0.9
    per_op = 0.0
    for v in ops.values():
        v = sorted((e for e in v if e[2] > window[0] and e[1] < window[1]),
                   key=lambda e: (e[1], -e[2]))
        outer = tr.containers(v)
        per_op += sum(min(b, window[1]) - max(a, window[0])
                      for i, (_, a, b) in enumerate(v) if i not in outer)
    assert sum(s.op_s.values()) == pytest.approx(per_op / n, rel=1e-9)
