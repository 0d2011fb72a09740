"""Finding a cell's files by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration and
traffic mix and lists the metrics; everything else is a file of its own,
found by name and refused before any device work when it is missing:

* ``bench/configs/<config>.json``: the configuration (the ``file`` of its
  ``configs`` entry);
* ``bench/families/<model_type>.py``: the family of the configuration's
  ``model_type``, which owns everything that depends on the architecture:
  ``from_dict(name, raw)``, the sizes and the refusals; ``arch_config``,
  ``make_params`` and ``make_cache``, the program's step, parameter tree and
  drawn decode cache; its draws of weights and cache; its ``Reference``;
  ``decode_flops``, ``decode_bytes`` and ``prefill_flops``; ``written``, the
  cache entries a check compares; and ``SCOPES``, the named scopes of its
  program;
* ``bench/traffic/<traffic>.json``: the mix, read by the step loop its ``kind``
  names;
* ``bench/kinds/<kind>.py``: that step loop, its class ``Loop`` (made as
  ``Loop(family, model, mix, mesh, seed)``), and, where the kind has one,
  ``refuse(family, model, mix, chips, seconds)``, which says why a mix
  cannot run a window of ``seconds`` (or returns None);
* ``bench/metrics/<metric>.py``: one reader per metric, with ``read(run)``;
* ``bench/limits/<workload>.json``: the limit of each number the cell's
  check compares.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class CellError(ValueError):
    """A cell that cannot run: unknown, a file it needs is missing, or its
    configuration refused by its family."""


@dataclass
class Cell:
    name: str
    chips: int
    model: object  # the family's ``from_dict`` of the configuration
    family: object  # the module bench/families/<model_type>.py
    mix: dict
    end_to_end: list
    per_layer: list
    limits: dict
    root: Path
    loop: type

    def reader(self, metric: str):
        return load_reader(self.root, metric)


def need(path: Path, what: str) -> Path:
    if not path.is_file():
        raise CellError(f"{what}: {path} is missing")
    return path


def module(path: Path, prefix: str, name: str):
    """The module of the file ``path``, executed afresh; it is registered in
    ``sys.modules`` before it runs, as a dataclass in it needs."""
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = need(root / "bench" / "metrics" / f"{metric}.py", f"metric {metric}")
    return module(path, "bench_metric_", metric).read


def load_kind(root: Path, kind):
    """The module ``bench/kinds/<kind>.py``."""
    if not isinstance(kind, str) or not NAME.match(kind):
        raise CellError(f"kind {kind!r} is not a name")
    path = need(root / "bench" / "kinds" / f"{kind}.py", f"kind {kind}")
    return module(path, "bench_kind_", kind)


def _applies(metric: dict, workload: str, e2e_names=()) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def load_cell(workload: str, root: Path = ROOT, seconds: float | None = None) -> Cell:
    """The cell ``workload`` of ``root``'s ``BENCHMARK.json``, with every file
    it needs read; raises :class:`CellError` where one is missing, where its
    family refuses the configuration, or where its kind refuses the mix for
    a window of ``seconds``."""
    root = Path(root)
    bench = json.loads(need(root / "BENCHMARK.json", "benchmark").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {workload}: unknown config {w['config']!r}")
    from bench import config  # not at the top: config imports this module

    model, family = config.load(need(root / configs[w["config"]]["file"],
                                     f"config {w['config']}"), root)
    mix = json.loads(need(root / "bench" / "traffic" / f"{w['traffic']}.json",
                          f"traffic {w['traffic']}").read_text())
    mix["name"] = w["traffic"]
    kind = load_kind(root, mix.get("kind"))
    if seconds is not None and hasattr(kind, "refuse"):
        why = kind.refuse(family, model, mix, w["chips"], seconds)
        if why:
            raise CellError(f"workload {workload}: {why}")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload, names)]
    for m in e2e + per_layer:
        load_reader(root, m["name"])
    limits = json.loads(need(root / "bench" / "limits" / f"{workload}.json",
                             f"limits of {workload}").read_text())
    return Cell(name=workload, chips=w["chips"], model=model, family=family, mix=mix,
                end_to_end=e2e, per_layer=per_layer, limits=limits, root=root,
                loop=kind.Loop)
