"""A checkout root holding a tiny configuration, tiny mixes and the
benchmark's own step loops, metric readers and limits, so the harness runs
end to end on the CPU in seconds."""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for _p in (str(REPO / "src"), str(REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY = {
    "source": "a tiny Qwen3-shaped configuration for tests",
    "reduced": [], "model_type": "qwen3", "attention_bias": False,
    "head_dim": 16, "hidden_size": 64, "initializer_range": 0.02,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "tie_word_embeddings": True,
    "torch_dtype": "bfloat16", "use_sliding_window": False, "vocab_size": 512,
}
#: a size at which the fp8 control's errors grow to what they are at the
#: published widths: they grow with width and depth (at 2 layers of width 64
#: the control reads kv_err 0.06, under the cells' limits; at 28 layers of
#: width 256 it reads 0.31, as qwen3-0.6b does at 0.35)
CONTROL_SIZE = dict(TINY, hidden_size=256, intermediate_size=768, head_dim=64,
                    num_hidden_layers=28)

MIXES = {
    "decode_tiny": {"kind": "decode", "batch": 2, "context": 4096, "fill": 3072,
                    "warmup_steps": 2, "trace_steps": 4},
    "prefill_tiny": {"kind": "prefill", "batch": 1, "length": 64,
                     "warmup_steps": 2, "trace_steps": 2, "check_prompts": 2},
}


def write_root(root: Path, config=TINY, mixes=MIXES, limits=None) -> Path:
    """A checkout root with ``tiny.decode`` and ``tiny.prefill`` cells, the
    benchmark's step loops, families, metric readers and its limits (or
    ``limits``)."""
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "limits").mkdir()
    shutil.copytree(REPO / "bench" / "metrics", root / "bench" / "metrics")
    for d in ("kinds", "families"):
        shutil.copytree(REPO / "bench" / d, root / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    cells = []
    for mix, body in mixes.items():
        (root / "bench" / "traffic" / f"{mix}.json").write_text(json.dumps(body))
        kind = body["kind"]
        name = f"tiny.{kind}"
        cells.append({"name": name, "config": "tiny", "traffic": mix, "chips": 1,
                      "why": "test"})
        real_cell = next(w["name"] for w in real["workloads"]
                         if w["traffic"].startswith(kind))
        lim = (limits or {}).get(kind) or json.loads(
            (REPO / "bench" / "limits" / f"{real_cell}.json").read_text())
        (root / "bench" / "limits" / f"{name}.json").write_text(json.dumps(lim))

    def retarget(metrics):
        out = []
        for m in metrics:
            m = dict(m)
            if "workloads" in m:
                m["workloads"] = [f"tiny.{k}" for k in ("decode", "prefill")
                                  if any(k in w for w in m["workloads"])]
            out.append(m)
        return out

    bench = dict(real, configs=[{"name": "tiny", "source": "test",
                                 "file": "bench/configs/tiny.json",
                                 "reduced": [], "why": "test"}],
                 workloads=cells, end_to_end=retarget(real["end_to_end"]),
                 per_layer=retarget(real["per_layer"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return write_root(tmp_path)
