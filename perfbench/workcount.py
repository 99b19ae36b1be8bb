"""Operations and bytes of the work the benchmark asks for, from shapes.

Every count is what the algorithm needs, not what a kernel pads to: a
matrix product (M, K) @ (K, N) is 2·M·K·N operations and reads and writes
(M·K + K·N + M·N) elements once.  Attention counts only the scores and the
weighted sum that the causal (and sliding-window) mask keeps.  The sizes
come from a configuration file in ``configs/`` (Hugging Face key names).
"""
from __future__ import annotations

BF16 = 2


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "kv": kv, "dh": dh, "f": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "window": cfg.get("sliding_window")}


def layer_matmuls(cfg: dict, rows: int):
    """The (M, K, N) products of one decoder layer over ``rows`` tokens, in
    the order the layer runs them: q, k, v, o, gate, up, down."""
    g = dims(cfg)
    d, qd, kvd, f = g["d"], g["h"] * g["dh"], g["kv"] * g["dh"], g["f"]
    return [(rows, d, qd), (rows, d, kvd), (rows, d, kvd), (rows, qd, d),
            (rows, d, f), (rows, d, f), (rows, f, d)]


def params_per_layer(cfg: dict) -> int:
    g = dims(cfg)
    mats = sum(k * n for _, k, n in layer_matmuls(cfg, 1))
    return mats + 2 * g["d"]                      # two norm gains


def params_total(cfg: dict) -> int:
    g = dims(cfg)
    embed = g["V"] * g["d"] * (1 if cfg.get("tie_word_embeddings") else 2)
    return g["L"] * params_per_layer(cfg) + embed + g["d"]


def kv_bytes_per_token(cfg: dict, itemsize: int = BF16) -> int:
    g = dims(cfg)
    return g["L"] * 2 * g["kv"] * g["dh"] * itemsize


def context(cfg: dict, pos: int) -> int:
    """Keys a query at 0-based position ``pos`` attends to."""
    w = dims(cfg)["window"]
    return pos + 1 if not w else min(pos + 1, w)


def attn_flops(cfg: dict, pos: int) -> int:
    """Forward attention operations of one query at ``pos``, all layers:
    scores and weighted sum, 2·dh each per head and key."""
    g = dims(cfg)
    return g["L"] * 4 * g["h"] * g["dh"] * context(cfg, pos)


def matmul_flops_per_token(cfg: dict, with_head: bool = True) -> int:
    g = dims(cfg)
    n = g["L"] * sum(2 * k * n for _, k, n in layer_matmuls(cfg, 1))
    return n + (2 * g["d"] * g["V"] if with_head else 0)


def prefill_chunk_flops(cfg: dict, c: int, p0: int) -> int:
    """One prefill chunk of ``c`` tokens at positions p0..p0+c-1, with the
    one row of logits the chunk's last token needs."""
    g = dims(cfg)
    return (c * matmul_flops_per_token(cfg, with_head=False)
            + sum(attn_flops(cfg, p) for p in range(p0, p0 + c))
            + 2 * g["d"] * g["V"])


def decode_flops(cfg: dict, positions) -> int:
    """One decode step over the active lanes, each at its position."""
    return sum(matmul_flops_per_token(cfg) + attn_flops(cfg, p)
               for p in positions)


def mmm(m: int, k: int, n: int, itemsize: int = BF16):
    """(operations, bytes) of one (M, K) @ (K, N) product."""
    return 2 * m * k * n, (m * k + k * n + m * n) * itemsize


def mmm_calls_decode(cfg: dict, lanes: int):
    g = dims(cfg)
    calls = layer_matmuls(cfg, lanes) * g["L"]
    return calls + [(lanes, g["d"], g["V"])]


def mmm_calls_chunk(cfg: dict, c: int):
    g = dims(cfg)
    return layer_matmuls(cfg, c) * g["L"] + [(1, g["d"], g["V"])]


def roofline_seconds(calls, peak_flops: float, peak_bw: float) -> float:
    """Least time the chip needs for ``calls``: per call the larger of its
    operations at peak rate and its bytes at peak bandwidth."""
    t = 0.0
    for m, k, n in calls:
        fl, by = mmm(m, k, n)
        t += max(fl / peak_flops, by / peak_bw)
    return t
