"""FLOP and byte counts of the shares, against hand counts at both
configurations' published shapes."""

import json
from pathlib import Path

import pytest

from bench.spec import CODE, load_module

REPO = Path(__file__).resolve().parents[2]
counts = load_module(CODE / "counts" / "dense_decoder.py")


def conf(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())


PHI4 = conf("phi4-mini-3.8b")
# deepseek-llm-7b-base's published sizes (Hugging Face config): multi-head
# attention, an untied LM head
DEEPSEEK = {"hidden_size": 4096, "intermediate_size": 11008,
            "num_hidden_layers": 30, "num_attention_heads": 32,
            "num_key_value_heads": 32, "head_dim": 128, "vocab_size": 102400,
            "tie_word_embeddings": False}


def test_phi4_weights_by_hand():
    # q 3072x3072, k and v 3072x1024 each, o 3072x3072, MLP 3 x 3072x8192
    layer = 3072 * 3072 + 2 * 3072 * 1024 + 3072 * 3072 + 3 * 3072 * 8192
    assert counts.layer_params(PHI4) == layer == 100_663_296
    # tied: the LM head is the 200,064 x 3072 embedding; 65 norms of 3072
    assert counts.weight_bytes(PHI4) == 2 * (32 * layer + 200_064 * 3072
                                             + 65 * 3072)
    # the program holds 3.836 B parameters
    assert 32 * layer + 200_064 * 3072 == pytest.approx(3.836e9, rel=1e-3)


def test_deepseek_weights_by_hand():
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert counts.layer_params(DEEPSEEK) == layer == 202_375_168
    # untied: the 102,400 x 4096 LM head is read, the input embedding not
    assert counts.weight_bytes(DEEPSEEK) == 2 * (30 * layer + 102_400 * 4096
                                                 + 61 * 4096)


def test_int8_kv_bytes_by_hand():
    # K and V, 8 heads of 128 int8 values and one f32 scale, 32 layers
    assert counts.kv_bytes_per_token(PHI4, "int8") == 2 * 32 * 8 * 132
    assert counts.kv_bytes_per_token(PHI4, "int8") == 67_584
    assert counts.kv_bytes_per_token(DEEPSEEK, "int8") == 2 * 30 * 32 * 132
    assert counts.kv_bytes_per_token(DEEPSEEK, "bf16") == 2 * 30 * 32 * 256


def test_decode_token_flops_by_hand():
    ctx = 600
    layer = counts.layer_params(PHI4)
    attend = 2 * 2 * 24 * 128 * ctx  # Q.K and P.V, 24 query heads
    head = 2 * 3072 * 200_064
    assert counts.step_flops(PHI4, [ctx], []) == 32 * (2 * layer + attend) \
        + head


def test_prefill_chunk_flops_by_hand():
    # 3 tokens at offset 5 attend 6, 7 and 8 positions; the chunk ends the
    # prompt, so one LM head row
    layer = counts.layer_params(DEEPSEEK)
    attend = 2 * 2 * 32 * 128 * (6 + 7 + 8)
    head = 2 * 4096 * 102_400
    assert counts.step_flops(DEEPSEEK, [], [(5, 3, True)]) == \
        30 * (3 * 2 * layer + attend) + head
    assert counts.step_flops(DEEPSEEK, [], [(5, 3, False)]) == \
        30 * (3 * 2 * layer + attend)


def test_step_and_attend_bytes_by_hand():
    per_tok = 67_584
    w = counts.weight_bytes(PHI4)
    # two decoding slots at 100 and 300 positions, a chunk [256, 512)
    assert counts.step_bytes(PHI4, "int8", [100, 300], [(256, 256, False)]) \
        == w + (100 + 300 + 512) * per_tok
    # q and out, f32, 24 heads x 128 per layer per slot
    io = 2 * 4 * 24 * 128 * 32 * 2
    assert counts.attend_bytes(PHI4, "int8", [100, 300]) == \
        io + 400 * per_tok
    assert counts.attend_flops(PHI4, 100) == 4 * 24 * 128 * 100 * 32
