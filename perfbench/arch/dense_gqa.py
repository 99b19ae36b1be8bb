"""Adapter of a dense decoder with grouped-query attention (Llama / Mistral
family): RMSNorm, rotary embedding, causal attention with an optional
sliding window, SwiGLU.  Maps its configuration file onto the program's
``ArchConfig``, makes its weights from the seed, and counts the work of its
calls from shapes (``workcount.py`` has the rules of counting).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import weights
import workcount

# --- the program's configuration ------------------------------------------


def arch_config(cfg: dict):
    """A dense GQA configuration file → the program's ``ArchConfig``."""
    from repro.configs.base import ArchConfig, AttnConfig, BlockSpec, Stage

    if cfg["hidden_act"] != "silu":
        raise ValueError(f"dense_gqa adapter: no {cfg['hidden_act']} FFN")
    h = cfg["num_attention_heads"]
    attn = AttnConfig(n_heads=h, n_kv_heads=cfg["num_key_value_heads"],
                      head_dim=cfg.get("head_dim") or cfg["hidden_size"] // h,
                      window=cfg.get("sliding_window"),
                      rope_theta=float(cfg["rope_theta"]))
    block = BlockSpec(kind="attn", attn=attn, d_ff=cfg["intermediate_size"],
                      act="swiglu")
    return ArchConfig(
        name=cfg.get("name", "bench"), family="dense",
        d_model=cfg["hidden_size"], vocab_size=cfg["vocab_size"],
        stages=(Stage(pattern=(block,), repeats=cfg["num_hidden_layers"]),),
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype=cfg.get("torch_dtype", "bfloat16"),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        source=cfg["source"])


# --- weights ----------------------------------------------------------------

#: (path in the program's block dict, shape key, input width key)
LAYER_LEAVES = (
    (("ln1",), "gain"),
    (("ln2",), "gain"),
    (("attn", "wq"), ("d", "q")),
    (("attn", "wk"), ("d", "kv")),
    (("attn", "wv"), ("d", "kv")),
    (("attn", "wo"), ("q", "d")),
    (("ffn", "wg"), ("d", "f")),
    (("ffn", "wu"), ("d", "f")),
    (("ffn", "wd"), ("f", "d")),
)


def widths(cfg: dict) -> dict:
    g = workcount.dims(cfg)
    return {"d": g["d"], "q": g["h"] * g["dh"], "kv": g["kv"] * g["dh"],
            "f": g["f"], "V": g["V"]}


def layer_leaf(cfg: dict, key, i: int, layer, dtype=jnp.bfloat16):
    """Leaf ``i`` of ``LAYER_LEAVES`` for one layer (``layer`` may be
    traced)."""
    w = widths(cfg)
    _, kind = LAYER_LEAVES[i]
    shape = (w["d"],) if kind == "gain" else (w[kind[0]], w[kind[1]])
    k = jax.random.fold_in(jax.random.fold_in(key, i + 16), layer)
    return weights.draw(k, shape, kind == "gain", dtype)


def top_leaves(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    w = widths(cfg)
    k = [jax.random.fold_in(key, j) for j in range(3)]
    return {
        "embed": (jax.random.normal(k[0], (w["V"], w["d"]), jnp.float32)
                  ).astype(dtype),
        "unembed": weights.draw(k[1], (w["d"], w["V"]), False, dtype),
        "final_norm": weights.draw(k[2], (w["d"],), True, dtype),
    }


def _build(cfg: dict, key):
    n_layers = cfg["num_hidden_layers"]
    block: dict = {}
    for i, (path, _) in enumerate(LAYER_LEAVES):
        stacked = jax.vmap(lambda l, i=i: layer_leaf(cfg, key, i, l))(
            jnp.arange(n_layers))
        node = block
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = stacked
    out = top_leaves(cfg, key)
    out["stages"] = [(block,)]
    return out


def make_params(cfg: dict, seed: int):
    """The program's parameter tree for ``cfg``, from ``seed``."""
    return jax.jit(lambda k: _build(cfg, k))(weights.base_key(seed))


# --- work counts ------------------------------------------------------------

#: the kernel event of the MMM record, which runs every projection
MMM = "_mmm_impl"


def layer_matmuls(cfg: dict, rows: int):
    """The (M, K, N) products of one decoder layer over ``rows`` tokens, in
    the order the layer runs them: q, k, v, o, gate, up, down."""
    g = workcount.dims(cfg)
    d, qd, kvd, f = g["d"], g["h"] * g["dh"], g["kv"] * g["dh"], g["f"]
    return [(rows, d, qd), (rows, d, kvd), (rows, d, kvd), (rows, qd, d),
            (rows, d, f), (rows, d, f), (rows, f, d)]


def params_per_layer(cfg: dict) -> int:
    g = workcount.dims(cfg)
    mats = sum(k * n for _, k, n in layer_matmuls(cfg, 1))
    return mats + 2 * g["d"]                      # two norm gains


def params_total(cfg: dict) -> int:
    g = workcount.dims(cfg)
    embed = g["V"] * g["d"] * (1 if cfg.get("tie_word_embeddings") else 2)
    return g["L"] * params_per_layer(cfg) + embed + g["d"]


def kv_bytes_per_token(cfg: dict, itemsize: int = workcount.BF16) -> int:
    g = workcount.dims(cfg)
    return g["L"] * 2 * g["kv"] * g["dh"] * itemsize


def attn_flops(cfg: dict, pos: int) -> int:
    """Forward attention operations of one query at ``pos``, all layers:
    scores and weighted sum, 2·dh each per head and key."""
    g = workcount.dims(cfg)
    return g["L"] * 4 * g["h"] * g["dh"] * workcount.context(cfg, pos)


def matmul_flops_per_token(cfg: dict, with_head: bool = True) -> int:
    g = workcount.dims(cfg)
    n = g["L"] * sum(2 * k * n for _, k, n in layer_matmuls(cfg, 1))
    return n + (2 * g["d"] * g["V"] if with_head else 0)


def prefill_chunk_flops(cfg: dict, c: int, p0: int) -> int:
    """One prefill chunk of ``c`` tokens at positions p0..p0+c-1, with the
    one row of logits the chunk's last token needs."""
    g = workcount.dims(cfg)
    return (c * matmul_flops_per_token(cfg, with_head=False)
            + sum(attn_flops(cfg, p) for p in range(p0, p0 + c))
            + 2 * g["d"] * g["V"])


def decode_flops(cfg: dict, positions) -> int:
    """One decode step over the active lanes, each at its position."""
    return sum(matmul_flops_per_token(cfg) + attn_flops(cfg, p)
               for p in positions)


def mmm_calls_decode(cfg: dict, lanes: int):
    g = workcount.dims(cfg)
    return layer_matmuls(cfg, lanes) * g["L"] + [(lanes, g["d"], g["V"])]


def mmm_calls_chunk(cfg: dict, c: int):
    g = workcount.dims(cfg)
    return layer_matmuls(cfg, c) * g["L"] + [(1, g["d"], g["V"])]


@functools.lru_cache(maxsize=256)
def _mmm_work(calls: tuple) -> tuple:
    return tuple(workcount.mmm(*c) for c in calls)


def decode_work(cfg: dict, positions, slots: int) -> dict:
    """One decode step: operations over the active lanes at ``positions``;
    the MMM kernel's calls over all ``slots`` rows, which it multiplies
    whether a lane is active or not."""
    return {"flops": decode_flops(cfg, positions),
            "kernels": {MMM: _mmm_work(tuple(mmm_calls_decode(cfg, slots)))}}


def chunk_work(cfg: dict, c: int, p0: int) -> dict:
    """One prefill chunk of ``c`` tokens from position ``p0``."""
    return {"flops": prefill_chunk_flops(cfg, c, p0),
            "kernels": {MMM: _mmm_work(tuple(mmm_calls_chunk(cfg, c)))}}
