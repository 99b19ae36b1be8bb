"""The benchmark's one view of the system under test: build the program's
model configuration from a configuration file, and note which kernel
record served each alias.  Everything else the benchmark drives through
the program's own entry points (``PagedEngine``, ``StepScheduler``)."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def arch_config(cfg: dict):
    """A dense GQA configuration file → the program's ``ArchConfig``."""
    from repro.configs.base import ArchConfig, AttnConfig, BlockSpec, Stage

    if cfg["architecture"] != "dense_gqa" or cfg["hidden_act"] != "silu":
        raise ValueError(f"no adapter for {cfg['architecture']}")
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


class Selections:
    """Records which record the runtime selected for each alias while the
    cell's programs are traced (selection happens at trace time only)."""

    def __init__(self):
        from repro.core.c2mpi import halo_session

        self.seen = {}
        self._session = halo_session()
        self._orig = self._session._select

        def select(alias, args, overrides):
            rec = self._orig(alias, args, overrides)
            name = f"{rec.platform}/{getattr(rec.fn, '__name__', '?')}"
            self.seen.setdefault(alias, set()).add(name)
            return rec

        self._session._select = select

    def line(self) -> str:
        return "; ".join(f"{a}: {', '.join(sorted(n))}"
                         for a, n in sorted(self.seen.items()))
