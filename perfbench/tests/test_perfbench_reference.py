"""The plain reference's own properties at a small size on the CPU."""
import numpy as np

from arch.dense_gqa import make_params
from reference import dense_gqa

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 1, "vocab_size": 256, "sliding_window": 8,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
        "torch_dtype": "bfloat16"}


def test_padded_positions_give_the_same_logits():
    params = make_params(TINY, 5)
    toks = list(np.random.default_rng(0).integers(0, 256, 20))
    at = [3, 9, 19]
    a = np.asarray(dense_gqa.logits(TINY, params, toks, at, 3, bucket=16))
    b = np.asarray(dense_gqa.logits(TINY, params, toks, at, 12, bucket=32))
    assert a.shape == b.shape == (3, 256)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_window_hides_keys_beyond_it():
    # one layer: a query at p sees keys p-7..p, so token 0 moves the
    # logits up to position 7 and none from position 8 on
    params = make_params(TINY, 6)
    toks = list(np.random.default_rng(1).integers(1, 256, 12))
    other = [0] + toks[1:]
    at = list(range(12))
    a = np.asarray(dense_gqa.logits(TINY, params, toks, at, 12, bucket=16))
    b = np.asarray(dense_gqa.logits(TINY, params, other, at, 12, bucket=16))
    assert np.abs(a[7] - b[7]).max() > 1e-3
    np.testing.assert_array_equal(a[8:], b[8:])
