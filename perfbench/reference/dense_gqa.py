"""Plain reference of a dense decoder with grouped-query attention
(Llama / Mistral family): RMSNorm, rotary embedding (half rotation),
causal attention with an optional sliding window, SwiGLU, untied head.

Written from the published description in ``jax.numpy`` and float32 at
``highest`` matmul precision, one sequence at a time, with no cache, no
batching and no kernel.  It imports nothing of the program: the weights
come from the architecture's adapter (``arch/dense_gqa.py``, drawn by
``weights.py``) and the sizes from the configuration file.

``fp8=True`` is the control: every matrix product takes its two
operands rounded to float8 e4m3 (activations scaled per row, weights per
column), and keys and values are rounded the same way; norms, softmax and
the residual stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import workcount

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
#: widest f32 copy of a weight block made at once (elements)
BLOCK_ELEMS = 1 << 26
#: queries scored at once
QUERY_BLOCK = 512


def _fp8(x, axis):
    """``x`` rounded to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def matmul(x, w, fp8: bool):
    """x (S, K) f32 @ w (K, N) in the served type, in column blocks so that
    no whole f32 copy of a large weight is made."""
    k, n = w.shape
    step = min(n, max(128, BLOCK_ELEMS // k // 128 * 128))
    if fp8:
        x = _fp8(x, axis=-1)
    outs = []
    for c0 in range(0, n, step):
        wb = w[:, c0:c0 + step].astype(jnp.float32)
        if fp8:
            wb = _fp8(wb, axis=0)
        outs.append(jnp.dot(x, wb, precision=HIGHEST))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


def rms_norm(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def rope(x, theta):
    """x (S, H, dh); rotates the two halves of each head."""
    s, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, window, fp8: bool):
    """q (S, H, dh), k/v (S, KV, dh) → (S, H·dh), causal and windowed."""
    s, h, dh = q.shape
    kvh = k.shape[1]
    rep = h // kvh
    if fp8:
        k, v = _fp8(k, -1), _fp8(v, -1)
    qg = (q * dh ** -0.5).reshape(s, kvh, rep, dh)
    kpos = jnp.arange(s)
    nb = -(-s // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - s
    qb = jnp.pad(qg, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        nb, QUERY_BLOCK, kvh, rep, dh)

    def block(args):
        i, qi = args
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        sc = jnp.einsum("qgrd,kgd->grqk", qi, k, precision=HIGHEST)
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok = ok & (kpos[None, :] > qpos[:, None] - window)
        sc = jnp.where(ok, sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(nb * QUERY_BLOCK, h * dh)[:s]


def layer(cfg: dict, x, p: dict, fp8: bool):
    g = workcount.dims(cfg)
    eps = cfg["rms_norm_eps"]
    s = x.shape[0]
    h = rms_norm(x, p["ln1"], eps)
    q = matmul(h, p["attn"]["wq"], fp8).reshape(s, g["h"], g["dh"])
    k = matmul(h, p["attn"]["wk"], fp8).reshape(s, g["kv"], g["dh"])
    v = matmul(h, p["attn"]["wv"], fp8).reshape(s, g["kv"], g["dh"])
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    x = x + matmul(attention(q, k, v, g["window"], fp8), p["attn"]["wo"],
                   fp8)
    h = rms_norm(x, p["ln2"], eps)
    gate = matmul(h, p["ffn"]["wg"], fp8)
    up = matmul(h, p["ffn"]["wu"], fp8)
    return x + matmul(jax.nn.silu(gate) * up, p["ffn"]["wd"], fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _logits(params, tokens, at, cfg_items, fp8):
    cfg = dict(cfg_items)
    x = params["embed"][tokens].astype(jnp.float32)
    block = params["stages"][0][0]

    def body(x, lp):
        return layer(cfg, x, lp, fp8), None

    x, _ = jax.lax.scan(body, x, block)
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    return matmul(x[at], params["unembed"], fp8)[:, :cfg["vocab_size"]]


def _hashable(cfg: dict):
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_hidden_layers",
            "vocab_size", "sliding_window", "rope_theta", "rms_norm_eps",
            "torch_dtype")
    return tuple((k, cfg.get(k)) for k in keys)


def logits(cfg: dict, params, tokens, at, at_len: int, fp8: bool = False,
           bucket=512):
    """Logits (len(at), V) at 0-based positions ``at`` of one sequence.
    The sequence is padded at its end to a multiple of ``bucket``, which
    the causal mask keeps from every position in ``at``, and ``at`` to
    ``at_len`` entries, so that one program serves every sequence of a
    bucket."""
    n, m = len(tokens), len(at)
    if m > at_len:
        raise ValueError(f"{m} positions, more than at_len={at_len}")
    size = -(-n // bucket) * bucket
    toks = jnp.zeros((size,), jnp.int32).at[:n].set(jnp.asarray(tokens,
                                                                jnp.int32))
    pos = jnp.zeros((at_len,), jnp.int32).at[:m].set(jnp.asarray(at,
                                                                 jnp.int32))
    return _logits(params, toks, pos, _hashable(cfg), fp8)[:m]
