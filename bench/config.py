"""A model configuration of the benchmark: the published ``config.json`` keys
of one model, read by the family module of its ``model_type``
(``bench/families/<model_type>.py``, see ``bench/spec.py``) into the sizes
the harness, the counters and the reference use. A ``model_type`` with no
family file, or a configuration its family refuses, is a
:class:`bench.spec.CellError`."""
from __future__ import annotations

import json
from pathlib import Path

from bench import spec


def load(path: Path, root: Path = spec.ROOT):
    """``(model, family)`` of the configuration file at ``path``: the family
    module ``bench/families/<model_type>.py`` of ``root``, and its
    ``from_dict`` of the file's keys."""
    path = Path(path)
    raw = json.loads(path.read_text())
    model_type = raw.get("model_type")
    if not isinstance(model_type, str) or not spec.NAME.match(model_type):
        raise spec.CellError(f"config {path.stem}: model_type {model_type!r} is not a name")
    family = spec.module(spec.need(root / "bench" / "families" / f"{model_type}.py",
                                   f"family of model_type {model_type}"),
                         "bench_family_", model_type)
    return family.from_dict(path.stem, raw), family
