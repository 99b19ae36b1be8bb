"""The comparison that decides ``correct``, driven through a whole run of a
small serving cell on the CPU: a sound run passes, a run whose served
tokens are altered where they are produced fails, and the fp8 control
(the fp8 reference's tokens in the program's place) fails.  The run skips only the harness's look for a chip."""
import json
import time

import pytest

import control
import run
import serve

TINY = {"architecture": "dense_gqa", "reference": "dense_gqa",
        "source": "test", "hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "num_hidden_layers": 2, "vocab_size": 2048, "sliding_window": None,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "hidden_act": "silu",
        "torch_dtype": "bfloat16"}
MIX = {"rate_per_s": 10.0, "ramp_s": 0.5, "tail_s": 10.0,
       "shape_seed": 1,
       "system_prompts": {"count": 2, "tokens": 32, "zipf_s": 1.2},
       "prompt_tokens": {"uniform": [24, 24]},
       "answer_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
       "slots": 4, "check": {"min_requests": 4, "min_served_tokens": 128,
                            "bucket": 64}}
#: from CPU readings at this size over 6 seeds: sound runs 0.008–0.021,
#: the fp8 control 0.149–0.362
LIMIT = 0.05
SPEC = {"workloads": [{"name": "tiny.chat", "config": "tiny",
                       "traffic": "tiny", "chips": 1}],
        "configs": [{"name": "tiny", "file": "perfbench/configs/tiny.json"}],
        "end_to_end": [{"name": "itl_p50_ms", "unit": "ms"},
                       {"name": "itl_p90_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("cell")
    for sub, name, body in (("configs", "tiny", TINY), ("traffic", "tiny", MIX),
                            ("cells", "tiny.chat",
                             {"checks": {"logit_gap": {"limit": LIMIT}}})):
        d = r / "perfbench" / sub
        d.mkdir(parents=True, exist_ok=True)
        (d / f"{name}.json").write_text(json.dumps(body))
    return r


def one_run(root, seed):
    return run.run_cell(SPEC, "tiny.chat", seed, 3.0, False, {}, DEVICE,
                        time.perf_counter(), root=root)


def test_sound_run_is_correct(root):
    res = one_run(root, 1)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p50_ms", "itl_p90_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["limit"] == LIMIT


def test_altered_token_is_not_correct(root, monkeypatch):
    from repro.serve import engine

    sample = engine.sample_tokens

    def off_by_one(logits, key, temperature):
        return (sample(logits, key, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_tokens", off_by_one)
    res = one_run(root, 2)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > LIMIT


def test_fp8_control_is_not_correct(root, monkeypatch):
    monkeypatch.setattr(serve, "run", control.fp8_run(serve))
    res = one_run(root, 3)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > LIMIT
