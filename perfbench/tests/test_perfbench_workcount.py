"""Work counts against hand counts of the configurations."""
import json
from pathlib import Path

import pytest

import workcount as w
from arch import dense_gqa as a

CONFIGS = Path(__file__).resolve().parent.parent / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def danube_layers(n):
    return dict(cfg("h2o-danube-1.8b"), num_hidden_layers=n)


#: Mistral-Large-Instruct-2407's widths with three of its 88 layers
MISTRAL_LARGE_3 = {"hidden_size": 12288, "intermediate_size": 28672,
                   "num_attention_heads": 96, "num_key_value_heads": 8,
                   "head_dim": 128, "num_hidden_layers": 3,
                   "vocab_size": 32768, "sliding_window": None}


def test_danube_per_layer_and_total():
    c = cfg("h2o-danube-1.8b")
    assert a.params_per_layer(c) == 69_473_280           # 69.47 M
    assert a.params_total(c) == 1_831_201_280             # 1.831 B
    assert 2 * a.params_total(c) == 3_662_402_560         # bytes in bf16
    assert a.kv_bytes_per_token(c) == 61_440


def test_danube_training_stage():
    # one pipeline stage of 6 layers with the embedding and the head
    c = danube_layers(6)
    assert a.params_total(c) == 580_682_240               # 580.7 M
    # 2.99 GFLOP a trained token in matmuls
    assert 6 * a.matmul_flops_per_token(c) / 2 == pytest.approx(2.992e9,
                                                                rel=1e-3)


def test_mistral_large_stage():
    c = MISTRAL_LARGE_3
    # q, o 12288x12288; k, v 12288x1024; gate, up, down 12288x28672
    assert a.params_per_layer(c) == 1_384_144_896
    # three layers and both pipeline ends: 9.9 GB in bf16
    assert a.params_total(c) == 3 * 1_384_144_896 + 2 * 32768 * 12288 + 12288
    assert a.kv_bytes_per_token(c) == 12_288


def test_attention_counts_only_the_mask():
    c = cfg("h2o-danube-1.8b")
    assert w.context(c, 0) == 1
    assert w.context(c, 4095) == 4096
    assert w.context(c, 9000) == 4096                     # window
    assert w.context(MISTRAL_LARGE_3, 9000) == 9001
    assert a.attn_flops(c, 99) == 24 * 4 * 32 * 80 * 100


def test_mmm_and_roofline():
    assert w.mmm(16, 2560, 6912) == (2 * 16 * 2560 * 6912,
                                     2 * (16 * 2560 + 2560 * 6912
                                          + 16 * 6912))
    # a 16-row product is bandwidth bound, a 4096-row one compute bound
    t_small = w.roofline_seconds([(16, 2560, 6912)], 197e12, 819e9)
    assert t_small == pytest.approx(w.mmm(16, 2560, 6912)[1] / 819e9)
    t_big = w.roofline_seconds([(4096, 2560, 6912)], 197e12, 819e9)
    assert t_big == pytest.approx(w.mmm(4096, 2560, 6912)[0] / 197e12)


def test_program_calls_cover_every_layer():
    c = cfg("h2o-danube-1.8b")
    dec = a.mmm_calls_decode(c, 16)
    assert len(dec) == 24 * 7 + 1
    assert sum(2 * m * k * n for m, k, n in dec) == \
        16 * a.matmul_flops_per_token(c)
    assert a.decode_flops(c, [10]) == a.matmul_flops_per_token(c) + \
        a.attn_flops(c, 10)
    ch = a.prefill_chunk_flops(c, 32, 256)
    assert ch == 32 * a.matmul_flops_per_token(c, with_head=False) + sum(
        a.attn_flops(c, p) for p in range(256, 288)) + 2 * 2560 * 32000
