"""Weights from the seed, made on the device in one jitted call, in the
layout and type the program serves them in.

Each leaf, and each layer of a stacked leaf, has its own key folded from
the seed, so the reference can make any one layer again on its own.
Matrices are normal with std fan_in^-1/2; norm gains are 1 + 0.1·normal,
so a gain that the program dropped or misapplied would show.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import workcount

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


def base_key(seed: int) -> jax.Array:
    """A key from a seed of any size (64 bits are used)."""
    seed = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def widths(cfg: dict) -> dict:
    g = workcount.dims(cfg)
    return {"d": g["d"], "q": g["h"] * g["dh"], "kv": g["kv"] * g["dh"],
            "f": g["f"], "V": g["V"]}


def _leaf(key, shape, gain: bool, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    w = 1.0 + 0.1 * z if gain else z * shape[0] ** -0.5
    return w.astype(dtype)


def layer_leaf(cfg: dict, key, i: int, layer, dtype=jnp.bfloat16):
    """Leaf ``i`` of ``LAYER_LEAVES`` for one layer (``layer`` may be
    traced)."""
    w = widths(cfg)
    _, kind = LAYER_LEAVES[i]
    shape = (w["d"],) if kind == "gain" else (w[kind[0]], w[kind[1]])
    k = jax.random.fold_in(jax.random.fold_in(key, i + 16), layer)
    return _leaf(k, shape, kind == "gain", dtype)


def top_leaves(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    w = widths(cfg)
    k = [jax.random.fold_in(key, j) for j in range(3)]
    return {
        "embed": (jax.random.normal(k[0], (w["V"], w["d"]), jnp.float32)
                  ).astype(dtype),
        "unembed": _leaf(k[1], (w["d"], w["V"]), False, dtype),
        "final_norm": _leaf(k[2], (w["d"],), True, dtype),
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
    return jax.jit(lambda k: _build(cfg, k))(base_key(seed))
