"""Paged decode (DESIGN.md §14): ``Model.paged_decode_step`` reads each
layer's view through the block table inside the layer scan and writes back
only the new entries; it must match, bit for bit, the dense decode on views
gathered from the same arenas."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve.engine import PagedEngine, sample_tokens
from repro.serve.kvcache import _is_spec, gather_views

SLOTS, MAX_LEN, BS = 4, 48, 8


def _reference(model, layout, params, paged, tables, tok, pos, active):
    """gather_views → the dense ``Model.decode_step`` → each active lane's
    written entry stored at its block and offset."""
    logits, views = jax.jit(lambda p, c, *a: model.decode_step(
        p, gather_views(layout, c, a[0], BS), *a[1:]))(
        params, paged, jnp.asarray(tables), jnp.asarray(tok),
        jnp.asarray(pos), jnp.asarray(active))

    def write(ls, arena, view):
        view = np.asarray(view)
        if ls.kind == "lane":
            return view
        arena = np.array(arena)
        for b in np.flatnonzero(active):
            slot = pos[b] % ls.length
            entry = np.take(view[:, b], slot, axis=ls.seq_axis - 1)
            arena[:, tables[b, slot // BS], slot % BS] = entry.reshape(
                entry.shape[0], -1)
        return arena

    return logits, jax.tree.map(write, layout, paged, views, is_leaf=_is_spec)


# (config, layers per stage): reduced configs stack at most 2 layers and run
# them straight-line; 3 layers run the stage as a lax.scan
@pytest.mark.parametrize("name,repeats", [
    ("h2o-danube-1.8b", None), ("h2o-danube-1.8b", 3), ("gemma3-4b", None),
    ("zamba2-1.2b", None), ("deepseek-v2-236b", None)])
def test_paged_decode_matches_gathered_dense_decode(name, repeats):
    """Random arena contents and lane states, one inactive lane, lanes at
    different depths (one past the 32-slot ring wrap where a config has
    sliding-window layers): logits, greedy tokens and every arena leaf are
    bit-identical to the reference, and the null block stays zero."""
    cfg = get_config(name).reduced()
    if repeats:
        cfg = dataclasses.replace(cfg, stages=tuple(
            dataclasses.replace(s, repeats=repeats) for s in cfg.stages))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    eng = PagedEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                      block_size=BS)
    layout = eng.layout

    leaves, treedef = jax.tree.flatten(eng.paged)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    specs = jax.tree.leaves(layout, is_leaf=_is_spec)
    filled = []
    for ls, leaf, k in zip(specs, leaves, keys):
        x = jax.random.normal(k, leaf.shape, jnp.float32).astype(leaf.dtype)
        if ls.kind == "seq":
            x = x.at[:, 0].set(0)                 # the null block
        filled.append(x)
    paged = jax.tree.unflatten(treedef, filled)

    # every lane owns distinct blocks; lane 2 is inactive
    rng = np.random.default_rng(3)
    owned = rng.permutation(np.arange(1, eng.num_blocks))
    tables = owned[:SLOTS * eng.blocks_per_lane].reshape(
        SLOTS, eng.blocks_per_lane).astype(np.int32)
    pos = np.array([3, 40, 17, 47], np.int32)
    active = np.array([True, True, False, True])
    tok = rng.integers(0, cfg.vocab_size, (SLOTS, 1)).astype(np.int32)

    ref_logits, ref_paged = _reference(model, layout, params, paged, tables,
                                       tok, pos, active)
    logits, _ = jax.jit(
        lambda *a: model.paged_decode_step(a[0], a[1], layout, *a[2:], BS))(
        params, paged, jnp.asarray(tables), jnp.asarray(tok),
        jnp.asarray(pos), jnp.asarray(active))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))

    new_paged, nxt = eng._decode(params, paged, jnp.asarray(tok),
                                 jnp.asarray(tables), jnp.asarray(pos),
                                 jnp.asarray(active), jax.random.PRNGKey(0),
                                 0.0)
    greedy = sample_tokens(ref_logits, jax.random.PRNGKey(0), 0.0)
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(greedy))
    for ls, got, want in zip(specs, jax.tree.leaves(new_paged),
                             jax.tree.leaves(ref_paged)):
        np.testing.assert_array_equal(np.asarray(got), want)
        if ls.kind == "seq":
            assert not np.asarray(got)[:, 0].any(), "null block written"
