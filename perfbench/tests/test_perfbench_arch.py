"""The lookup of each configuration's adapter by its ``architecture``, and
the numbers the dense adapter has to keep: the seed's weights leaf for
leaf, and the work of the calls the traced runs read.  A second
architecture, made only in this file, runs a whole cell through
``run.run_cell`` with no edit to the harness."""
import dataclasses
import hashlib
import json
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import program
import run
import serve
import weights
import workcount
from arch import dense_gqa
from reference import dense_gqa as dense_ref
from test_perfbench_faults import DEVICE, LIMIT, SPEC, TINY, root  # noqa: F401

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

#: sha256 (first 16 hex digits) of each leaf of the tree made for ``TINY``,
#: read from the tree the harness made before adapters existed
TINY_LEAVES = {
    7: {
        "['embed']": "1896836dfbd7354a",
        "['final_norm']": "3f3dc9cf3491ea42",
        "['stages'][0][0]['attn']['wk']": "4a3177817a780928",
        "['stages'][0][0]['attn']['wo']": "5a465098e68a67df",
        "['stages'][0][0]['attn']['wq']": "b9c39a9a4631bdd9",
        "['stages'][0][0]['attn']['wv']": "dfb20edabd1dac0c",
        "['stages'][0][0]['ffn']['wd']": "db0018c72a5b534b",
        "['stages'][0][0]['ffn']['wg']": "0dbf6558291e76aa",
        "['stages'][0][0]['ffn']['wu']": "41f9dbbdafad0084",
        "['stages'][0][0]['ln1']": "75580368d4a5f030",
        "['stages'][0][0]['ln2']": "4db0539c2b6088fc",
        "['unembed']": "32b33018b6ab9b2f",
    },
    2**31 + 12345: {
        "['embed']": "7907bfb6e02ec0d4",
        "['final_norm']": "fa8bbc6011597bf4",
        "['stages'][0][0]['attn']['wk']": "d6627b704c9296cb",
        "['stages'][0][0]['attn']['wo']": "6125fa6826b28101",
        "['stages'][0][0]['attn']['wq']": "7d23866e994ff67a",
        "['stages'][0][0]['attn']['wv']": "767590ab4dce0bd6",
        "['stages'][0][0]['ffn']['wd']": "048836fd9e89a4dc",
        "['stages'][0][0]['ffn']['wg']": "64bbda511fff3e3d",
        "['stages'][0][0]['ffn']['wu']": "9ca25f009db7b64b",
        "['stages'][0][0]['ln1']": "3f843b3f75a8df66",
        "['stages'][0][0]['ln2']": "df1a818eb098b2ad",
        "['unembed']": "553e8411665812c3",
    },
}


def danube():
    return json.loads((run.HERE / "configs" / "h2o-danube-1.8b.json"
                       ).read_text())


def test_dense_adapter_is_found_by_name():
    assert program.adapter(TINY) is dense_gqa
    assert program.adapter(danube()) is dense_gqa


@pytest.mark.parametrize("seed", sorted(TINY_LEAVES))
def test_dense_params_are_the_seeds_leaf_for_leaf(seed):
    params = program.adapter(TINY).make_params(TINY, seed)
    assert isinstance(params["stages"], list)
    assert isinstance(params["stages"][0], tuple)
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        assert a.dtype == jnp.bfloat16
        got[jax.tree_util.keystr(path)] = hashlib.sha256(
            a.tobytes()).hexdigest()[:16]
    assert got == TINY_LEAVES[seed]


#: (active lanes' positions, operations) of a decode step at danube's
#: widths in a program of 8 slots, and the MMM kernel's least time at the
#: v5e's peaks, over all 8 rows whatever the lanes
DECODE = [([0], 3_498_557_440),
          ([10, 500, 2559], 11_249_909_760),
          ([3, 40, 700, 1500, 2000, 2200, 2400, 2559], 30_790_615_040)]
DECODE_MMM_S = 0.004293243155067152
#: (chunk length, first position, operations, MMM kernel's least time)
CHUNK = [(32, 0, 106_996_695_040, 0.0043560278583638515),
         (32, 256, 109_009_960_960, 0.0043560278583638515),
         (7, 1785, 26_582_794_240, 0.004290011741147749),
         (1, 0, 3_498_557_440, 0.004274167873015876),
         (16, 2528, 63_489_310_720, 0.004313777543345539)]


@pytest.mark.parametrize("positions,flops", DECODE)
def test_danube_decode_work(positions, flops):
    work = program.adapter(danube()).decode_work(danube(), positions, 8)
    assert work["flops"] == flops
    assert set(work["kernels"]) == {"_mmm_impl"}
    assert workcount.kernel_seconds(
        work["kernels"]["_mmm_impl"], V5E["bf16_flops_per_s"],
        V5E["hbm_bytes_per_s"]) == DECODE_MMM_S


@pytest.mark.parametrize("c,p0,flops,mmm_s", CHUNK)
def test_danube_chunk_work(c, p0, flops, mmm_s):
    work = program.adapter(danube()).chunk_work(danube(), c, p0)
    assert work["flops"] == flops
    assert set(work["kernels"]) == {"_mmm_impl"}
    assert workcount.kernel_seconds(
        work["kernels"]["_mmm_impl"], V5E["bf16_flops_per_s"],
        V5E["hbm_bytes_per_s"]) == mmm_s


# --- a second architecture, made only here ---------------------------------

def _toy_arch():
    """``arch.toy``: the dense decoder with a plain GELU MLP (no gate), as
    GPT-2-style models have, and a second kernel event to count."""
    m = types.ModuleType("arch.toy")

    def arch_config(cfg):
        a = dense_gqa.arch_config(dict(cfg, hidden_act="silu"))
        stage = a.stages[0]
        block = dataclasses.replace(stage.pattern[0], act="gelu")
        return dataclasses.replace(
            a, stages=(dataclasses.replace(stage, pattern=(block,)),))

    def build(cfg, key):
        p = dense_gqa._build(cfg, key)
        del p["stages"][0][0]["ffn"]["wg"]
        return p

    def make_params(cfg, seed):
        return jax.jit(lambda k: build(cfg, k))(weights.base_key(seed))

    def calls(cfg, rows, head_rows):
        g = workcount.dims(cfg)
        layer = dense_gqa.layer_matmuls(cfg, rows)
        del layer[4]                                   # no gate
        return layer * g["L"] + [(head_rows, g["d"], g["V"])]

    def work(cfg, rows, head_rows, positions):
        flops = sum(2 * m * k * n for m, k, n in calls(cfg, 1, 1))
        attn = sum(dense_gqa.attn_flops(cfg, p) for p in positions)
        return {"flops": len(positions) * flops + attn,
                "kernels": {"_mmm_impl": [workcount.mmm(*c) for c in
                                          calls(cfg, rows, head_rows)],
                            "toy_attn": [(attn, 1)]}}

    m.arch_config = arch_config
    m.make_params = make_params
    m.decode_work = lambda cfg, positions, slots: work(cfg, slots, slots,
                                                       positions)
    m.chunk_work = lambda cfg, c, p0: work(cfg, c, 1, range(p0, p0 + c))
    return m


def _toy_reference():
    """``reference.toy``: the plain float32 forward pass of ``arch.toy``,
    from the dense reference's parts."""
    m = types.ModuleType("reference.toy")

    def logits(cfg, params, tokens, at, at_len, fp8=False, bucket=512):
        g = workcount.dims(cfg)
        eps = cfg["rms_norm_eps"]
        mm = lambda x, w: dense_ref.matmul(x, w, fp8)  # noqa: E731
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        s = x.shape[0]
        stack = params["stages"][0][0]
        for i in range(g["L"]):
            p = jax.tree.map(lambda a: a[i], stack)
            h = dense_ref.rms_norm(x, p["ln1"], eps)
            q = mm(h, p["attn"]["wq"]).reshape(s, g["h"], g["dh"])
            k = mm(h, p["attn"]["wk"]).reshape(s, g["kv"], g["dh"])
            v = mm(h, p["attn"]["wv"]).reshape(s, g["kv"], g["dh"])
            q = dense_ref.rope(q, cfg["rope_theta"])
            k = dense_ref.rope(k, cfg["rope_theta"])
            att = dense_ref.attention(q, k, v, g["window"], fp8)
            x = x + mm(att, p["attn"]["wo"])
            h = dense_ref.rms_norm(x, p["ln2"], eps)
            up = jax.nn.gelu(mm(h, p["ffn"]["wu"]), approximate=True)
            x = x + mm(up, p["ffn"]["wd"])
        x = dense_ref.rms_norm(x, params["final_norm"], eps)
        return mm(x[jnp.asarray(at)], params["unembed"])[:, :g["V"]]

    m.logits = logits
    return m


TOY = dict(TINY, architecture="toy", reference="toy", hidden_act="gelu")
TOY_SPEC = dict(SPEC, workloads=[{"name": "toy.chat", "config": "toy",
                                  "traffic": "tiny", "chips": 1}],
                configs=[{"name": "toy", "file": "perfbench/configs/toy.json"}])


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setitem(sys.modules, "arch.toy", _toy_arch())
    monkeypatch.setitem(sys.modules, "reference.toy", _toy_reference())


def test_second_architecture_runs_a_cell_by_files_alone(root, toy):  # noqa: F811
    (root / "perfbench" / "configs" / "toy.json").write_text(json.dumps(TOY))
    (root / "perfbench" / "cells" / "toy.chat.json").write_text(json.dumps(
        {"checks": {"logit_gap": {"limit": LIMIT}}}))
    res = run.run_cell(TOY_SPEC, "toy.chat", 11, 3.0, False, {}, DEVICE,
                       time.perf_counter(), root=root)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    params = program.adapter(TOY).make_params(TOY, 11)
    assert "wg" not in params["stages"][0][0]["ffn"]


class _Meta:
    prompt, pos = [0] * 40, 32


class _Engine:
    """The two host calls ``CallLog`` wraps, and what it reads of them."""
    chunk_tokens = 32
    _meta = {0: _Meta()}

    def decode_step(self, tok, pos, active, key, temperature=0.0):
        return "decoded"

    def continue_admission(self, slot):
        return "chunked"


def test_call_log_reads_every_kernel_the_adapter_names(toy):
    arch = sys.modules["arch.toy"]
    log = serve.CallLog(TOY, 4, V5E)
    engine = _Engine()
    log.wrap(engine)
    t0 = time.perf_counter()
    assert engine.decode_step(None, [5, 9, 0, 0], [1, 1, 0, 0], None) == \
        "decoded"
    assert engine.decode_step(None, [6, 10, 0, 0], [1, 1, 0, 0], None) == \
        "decoded"
    assert engine.continue_admission(0) == "chunked"
    log.unwrap(engine)
    assert engine.decode_step(None, [], [], None) == "decoded"
    calls = log.per_program(t0, time.perf_counter())
    assert calls["jit__decode_fn"]["n"] == 2
    assert calls["jit__chunk_fn"]["n"] == 1

    def least(work):
        return {k: workcount.kernel_seconds(w, V5E["bf16_flops_per_s"],
                                            V5E["hbm_bytes_per_s"])
                for k, w in work["kernels"].items()}

    d1, d2 = (arch.decode_work(TOY, p, 4) for p in ([5, 9], [6, 10]))
    dec = calls["jit__decode_fn"]
    assert dec["flops_mean"] == (d1["flops"] + d2["flops"]) / 2
    assert dec["roof_s_mean"] == pytest.approx(
        {k: (least(d1)[k] + least(d2)[k]) / 2 for k in least(d1)})
    ch = arch.chunk_work(TOY, 8, 32)
    assert calls["jit__chunk_fn"]["flops_mean"] == ch["flops"]
    assert calls["jit__chunk_fn"]["roof_s_mean"] == least(ch)
    assert set(dec["roof_s_mean"]) == {"_mmm_impl", "toy_attn"}


def test_unknown_architecture_stops_before_any_weights(root, monkeypatch):  # noqa: F811
    nope = dict(TINY, architecture="nope")
    (root / "perfbench" / "configs" / "nope.json").write_text(
        json.dumps(nope))

    def no_weights(seed):
        raise AssertionError("weights were made")

    monkeypatch.setattr(weights, "base_key", no_weights)
    spec = dict(SPEC, workloads=[{"name": "tiny.chat", "config": "nope",
                                  "traffic": "tiny", "chips": 1}],
                configs=[{"name": "nope",
                          "file": "perfbench/configs/nope.json"}])
    with pytest.raises(ValueError, match="perfbench/arch/nope.py"):
        run.run_cell(spec, "tiny.chat", 1, 3.0, False, {}, DEVICE,
                     time.perf_counter(), root=root)
    with pytest.raises(ValueError, match="perfbench/arch/nope.py"):
        program.adapter(nope)
