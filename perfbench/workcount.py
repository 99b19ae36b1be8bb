"""Operations and bytes of the work the benchmark asks for, from shapes.

Every count is what the algorithm needs, not what a kernel pads to: a
matrix product (M, K) @ (K, N) is 2·M·K·N operations and reads and writes
(M·K + K·N + M·N) elements once.  Attention counts only the scores and the
weighted sum that the causal (and sliding-window) mask keeps.  The sizes
come from a configuration file in ``configs/`` (Hugging Face key names);
what each architecture's calls multiply is its adapter's (``arch/``).
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


def context(cfg: dict, pos: int) -> int:
    """Keys a query at 0-based position ``pos`` attends to."""
    w = dims(cfg)["window"]
    return pos + 1 if not w else min(pos + 1, w)


def mmm(m: int, k: int, n: int, itemsize: int = BF16):
    """(operations, bytes) of one (M, K) @ (K, N) product."""
    return 2 * m * k * n, (m * k + k * n + m * n) * itemsize


def kernel_seconds(work, peak_flops: float, peak_bw: float) -> float:
    """Least time the chip needs for a kernel's calls, given as (operations,
    bytes) each: per call the larger of its operations at peak rate and its
    bytes at peak bandwidth."""
    t = 0.0
    for fl, by in work:
        t += max(fl / peak_flops, by / peak_bw)
    return t


def roofline_seconds(calls, peak_flops: float, peak_bw: float) -> float:
    """``kernel_seconds`` of (M, K, N) matrix products."""
    return kernel_seconds((mmm(m, k, n) for m, k, n in calls), peak_flops,
                          peak_bw)
