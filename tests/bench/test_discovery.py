"""Cells are found by name: a configuration, a mix, a step loop and a metric
dropped in as new files are picked up, and a cell whose files are missing,
or whose decode cache cannot hold the window, is refused before any device
work."""
import json
import time

import pytest

from bench_fixtures import REPO, TINY, tiny_root  # noqa: F401 (puts the checkout on sys.path)
from bench import harness, spec


KIND = '''"""decode_tagged: the decode loop, counting one item more a step, so
that a test can see it ran."""
from bench.kinds.decode import Decode, refuse  # noqa: F401 (the room check)


class Loop(Decode):
    def step(self):
        return super().step() + 1
'''


def _add_cell(root, config="tiny2", mix="decode_tiny2", metric="steps_seen",
              kind="decode_tagged", fill=40):
    (root / "bench" / "configs" / f"{config}.json").write_text(
        json.dumps(dict(TINY, num_hidden_layers=3)))
    (root / "bench" / "kinds" / f"{kind}.py").write_text(KIND)
    (root / "bench" / "traffic" / f"{mix}.json").write_text(json.dumps(
        {"kind": kind, "batch": 2, "context": 1024, "fill": fill,
         "warmup_steps": 1, "trace_steps": 2}))
    (root / "bench" / "metrics" / f"{metric}.py").write_text(
        '"""steps_seen: steps in the window."""\n\n\ndef read(run):\n'
        '    return float(run.steps)\n')
    name = f"{config}.{mix}"
    (root / "bench" / "limits" / f"{name}.json").write_text(
        (root / "bench" / "limits" / "tiny.decode.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config, "source": "test",
                             "file": f"bench/configs/{config}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": name, "config": config, "traffic": mix,
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": metric, "unit": "steps", "better": "higher",
                                "bound": 0.01, "source": "host_clock",
                                "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


def test_dropped_in_files_are_found(tiny_root):
    name = _add_cell(tiny_root)
    cell = spec.load_cell(name, tiny_root)
    assert cell.model.layers == 3 and cell.mix["fill"] == 40
    assert cell.loop.__module__ == "bench_kind_decode_tagged"
    # metrics that list their cells apply to those alone; setup_s to all
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "steps_seen"}
    args = harness.parse(["--workload", name, "--seed", "7", "--seconds", "0.2",
                          "--trace", "0"])
    result = harness.run(args, time.perf_counter(), root=tiny_root, allow_cpu=True)
    assert result["metrics"]["steps_seen"]["value"] >= 1
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checks"
    # the dropped-in step loop ran the cell: batch 2 and one more a step
    assert result["attempted"] == 3 * result["metrics"]["steps_seen"]["value"]


@pytest.mark.parametrize("missing", ["config", "traffic", "kind", "metric", "limits"])
def test_missing_file_is_refused_before_device_work(tiny_root, monkeypatch, missing):
    name = _add_cell(tiny_root)
    path = {"config": "bench/configs/tiny2.json",
            "traffic": "bench/traffic/decode_tiny2.json",
            "kind": "bench/kinds/decode_tagged.py",
            "metric": "bench/metrics/steps_seen.py",
            "limits": f"bench/limits/{name}.json"}[missing]
    (tiny_root / path).unlink()

    def no_device(*_a, **_k):
        raise AssertionError("device work before the cell was refused")

    monkeypatch.setattr(harness, "chip_mesh", no_device)
    monkeypatch.setattr(harness, "place_compile_cache", no_device)
    args = harness.parse(["--workload", name, "--seed", "7", "--seconds", "0.2",
                          "--trace", "0"])
    with pytest.raises(spec.CellError, match=missing if missing != "metric" else "steps_seen"):
        harness.run(args, time.perf_counter(), root=tiny_root, allow_cpu=True)


def test_unknown_workload_is_refused(tiny_root):
    with pytest.raises(spec.CellError):
        spec.load_cell("nope", tiny_root)


def test_decode_cache_without_room_for_the_window_is_refused(tiny_root):
    # the tiny model's least step time on a chip is microseconds: a cache of
    # 1,024 holds no window, however short, and no fill makes it hold 0.2 s
    name = _add_cell(tiny_root, fill=40)
    with pytest.raises(spec.CellError, match="lower the fill"):
        spec.load_cell(name, tiny_root, seconds=0.2)
    assert spec.load_cell(name, tiny_root, seconds=1e-6).mix["fill"] == 40


@pytest.mark.parametrize("workload", [w["name"] for w in json.loads(
    (REPO / "BENCHMARK.json").read_text())["workloads"]])
def test_benchmark_cells_hold_their_window(workload):
    """Every cell of the benchmark loads, and a decode cell's cache holds the
    longest window a check may ask for (51 s) at the least step time."""
    cell = spec.load_cell(workload, REPO, seconds=51)
    assert callable(cell.loop.step)
