"""A model family joins the benchmark as files: ``bench/families/<model_type>.py``
is found by the configuration's ``model_type``, and a ``model_type`` with no
such file is refused before any device work, with exit code 2 and no
traceback."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench_fixtures import REPO, TINY, tiny_root  # noqa: F401 (a fixture)
from bench import harness


def _add_family(root, model_type="qwen3_twin"):
    """A second family, a copy of qwen3's under another ``model_type``, with a
    configuration, traffic, limits and its cells in BENCHMARK.json, each a
    new file or a new entry."""
    bench = root / "bench"
    shutil.copy(bench / "families" / "qwen3.py", bench / "families" / f"{model_type}.py")
    (bench / "configs" / "twin.json").write_text(
        json.dumps(dict(TINY, model_type=model_type)))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "twin", "source": "test", "reduced": [],
                            "file": "bench/configs/twin.json", "why": "test"})
    for kind in ("decode", "prefill"):
        (bench / "traffic" / f"{kind}_twin.json").write_text(
            (bench / "traffic" / f"{kind}_tiny.json").read_text())
        (bench / "limits" / f"twin.{kind}.json").write_text(
            (bench / "limits" / f"tiny.{kind}.json").read_text())
        spec["workloads"].append({"name": f"twin.{kind}", "config": "twin",
                                  "traffic": f"{kind}_twin", "chips": 1, "why": "test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if f"tiny.{kind}" in m.get("workloads", ()):
                m["workloads"].append(f"twin.{kind}")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def _run(root, workload, seed):
    args = harness.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", "0.2", "--trace", "0"])
    return harness.run(args, time.perf_counter(), root=root, allow_cpu=True)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_a_second_family_joins_by_files_alone(tiny_root, kind):
    _add_family(tiny_root)
    twin = _run(tiny_root, f"twin.{kind}", 31)
    qwen = _run(tiny_root, f"tiny.{kind}", 31)
    assert twin["correct"] and qwen["correct"], (twin["checks"], qwen["checks"])
    assert {k: c["limit"] for k, c in twin["checks"].items()} == {
        k: c["limit"] for k, c in qwen["checks"].items()}
    assert set(twin["metrics"]) == set(qwen["metrics"])


@pytest.mark.parametrize("change,message", [
    ({"model_type": "nosuch"}, "bench/families/nosuch.py is missing"),
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope scaling"),
], ids=["no family file", "refused by its family"])
def test_a_model_type_without_a_family_exits_2(tmp_path, change, message):
    """Refused before any device work, exit 2, no result and no traceback:
    a ``model_type`` with no family file, and a configuration its family
    refuses."""
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    cfg = tmp_path / "bench" / "configs" / "qwen3-0.6b.json"
    cfg.write_text(json.dumps(dict(json.loads(cfg.read_text()), **change)))
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-0.6b.decode_32k", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert r.returncode == 2, r.stderr
    assert r.stdout.strip() == ""
    assert message in r.stderr
    assert "Traceback" not in r.stderr
