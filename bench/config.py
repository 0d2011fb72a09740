"""A model configuration of the benchmark: the published ``config.json`` keys
of one model, read into the sizes the harness, the counters and the reference
use. Only decoder-only Qwen3-style models (``model_type`` ``qwen3``: GQA,
qk-norm, RoPE, SwiGLU, RMSNorm, tied or untied head) are understood; any other
``model_type`` is refused."""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

SUPPORTED = ("qwen3",)


@dataclass(frozen=True)
class Model:
    name: str
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    rope_theta: float
    eps: float
    init_std: float
    dtype: str


def from_dict(name: str, c: dict) -> Model:
    """The sizes of config ``name`` from its ``config.json`` keys."""
    if c.get("model_type") not in SUPPORTED:
        raise ValueError(f"config {name}: model_type {c.get('model_type')!r} "
                         f"is not one of {SUPPORTED}")
    if c.get("use_sliding_window") or c.get("rope_scaling") or c.get("attention_bias"):
        raise ValueError(f"config {name}: sliding windows, rope scaling and "
                         f"attention biases are not implemented")
    if c["num_attention_heads"] % c["num_key_value_heads"]:
        raise ValueError(f"config {name}: heads not a multiple of kv heads")
    return Model(
        name=name, layers=c["num_hidden_layers"], d=c["hidden_size"],
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], ff=c["intermediate_size"],
        vocab=c["vocab_size"], tied=bool(c["tie_word_embeddings"]),
        rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        init_std=float(c["initializer_range"]), dtype=c["torch_dtype"])


def load(path: Path) -> tuple[Model, dict]:
    """``(Model, raw dict)`` of the configuration file at ``path``."""
    raw = json.loads(Path(path).read_text())
    return from_dict(Path(path).stem if path else "", raw), raw
