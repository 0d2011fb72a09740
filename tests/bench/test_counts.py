"""FLOP and byte counters (qwen3's family), the peaks table and the no-chip
path."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import bench_fixtures  # noqa: F401 (puts the checkout on sys.path)
from bench import config, peaks
from bench.families import qwen3

REPO = Path(__file__).resolve().parents[2]


def qwen(name):
    return config.load(REPO / "bench" / "configs" / f"{name}.json")[0]


QWEN3_8B = dict(json.loads((REPO / "bench" / "configs" / "qwen3-0.6b.json").read_text()),
                hidden_size=4096, intermediate_size=12288, num_attention_heads=32,
                num_hidden_layers=36, tie_word_embeddings=False)


def test_qwen3_0_6b_parameters():
    m = qwen("qwen3-0.6b")
    # per layer: q 1024*16*128 + k,v 2*1024*8*128 + o 16*128*1024
    #            + SwiGLU 3*1024*3072 = 2,097,152*3 + 9,437,184 = 15,728,640
    assert qwen3.layer_matmul_params(m) == 15_728_640
    # 151,936*1024 embedding (tied) + 28*(15,728,640 + 2*1024 + 2*128) + 1024
    assert qwen3.params(m) == 155_582_464 + 28 * 15_730_944 + 1024 == 596_049_920


def test_qwen3_8b_parameters():
    m = qwen3.from_dict("qwen3-8b", QWEN3_8B)
    # q,o 2*4096*32*128 + k,v 2*4096*8*128 + SwiGLU 3*4096*12288
    assert qwen3.layer_matmul_params(m) == 33_554_432 + 8_388_608 + 150_994_944
    # untied: embedding and head 2*151,936*4096, norms 2*4096+2*128 a layer
    assert qwen3.params(m) == 2 * 622_329_856 + 36 * (192_937_984 + 8448) + 4096
    assert qwen3.params(m) == 8_190_735_360


def test_per_token_flops():
    m = qwen("qwen3-0.6b")
    # a token at position 0 sees 1 key: 2*28*15,728,640 matmul FLOPs,
    # 4*28*16*128 attention, 2*1024*151,936 logits
    assert qwen3.decode_flops(m, 1, 0) == 880_803_840 + 229_376 + 311_164_928
    # at position 30,720 it sees 30,721 keys
    assert qwen3.decode_flops(m, 2, 30_720) == 2 * (
        880_803_840 + 229_376 * 30_721 + 311_164_928)
    # a 16,384-token causal prefill: keys seen 16,384*16,385/2 in all, and
    # one row of logits
    assert qwen3.prefill_flops(m, 1, 16_384) == (
        16_384 * 880_803_840 + 229_376 * 134_225_920 + 311_164_928)


def test_decode_bytes():
    m = qwen("qwen3-0.6b")
    # K and V of 28 layers, 8 heads of 128, bf16: 114,688 B a position
    assert qwen3.kv_bytes_per_token(m) == 114_688
    # all weights (the tied embedding is the head) + the cache read and written
    assert qwen3.decode_bytes(m, 2, 30_720) == 2 * 596_049_920 + 2 * 30_721 * 114_688
    m8 = qwen3.from_dict("qwen3-8b", QWEN3_8B)
    # untied: of the embedding only the 2 rows looked up are read
    assert qwen3.decode_bytes(m8, 2, 0) == 2 * (
        8_190_735_360 - (151_936 - 2) * 4096) + 2 * 1 * qwen3.kv_bytes_per_token(m8)


def test_peaks_table():
    p = peaks.peak_for("TPU v5 lite")
    assert p.flops == 197e12 and p.hbm_bw == 819e9
    with pytest.raises(peaks.UnknownChip):
        peaks.peak_for("TPU v99")


def test_unsupported_model_type_is_refused(tmp_path):
    """A ``model_type`` with no family file under ``bench/families``."""
    path = tmp_path / "x.json"
    path.write_text(json.dumps(dict(QWEN3_8B, model_type="llama")))
    with pytest.raises(ValueError, match="bench/families/llama.py is missing"):
        config.load(path)


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-0.6b.decode_32k", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr
