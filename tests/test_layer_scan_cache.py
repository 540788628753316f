"""The layer scan carries the stacked decoding cache and writes only the
new token rows: bitwise parity with an unrolled per-layer loop, and the
step executables' temporaries on the CPU compiler."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.analog import AnalogConfig, AnalogCtx
from repro.kernels import dispatch
from repro.models import build
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve import scheduler as S
from repro.serve.decode import serve_step

FAMILIES = ["phi-3-mini-4k", "dbrx-132b", "mamba2-130m", "jamba-v0.1-52b"]
B, MAX_LEN, BLOCK = 4, 32, 8
POS = np.array([5, 9, 3, 12], np.int32)       # per-slot write cursors
START = np.array([0, 2, 1, 0], np.int32)
MASKED = 1                                    # the fully-masked row


def _build(arch):
    cfg = get_config(arch).reduce()
    if cfg.num_experts:   # no-drop capacity: see test_decode for semantics
        cfg = dataclasses.replace(cfg,
                                  capacity_factor=float(cfg.num_experts))
    cfg, params, _ = build(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _filled_caches(cfg, paged, dtype=jnp.float32):
    """A slot cache with random contents, live cursors and (paged) every
    row's blocks private: row ``b``'s logical block ``i`` is physical
    block ``1 + b * nb + i``."""
    snaps = 2 if paged and cfg.family in ("ssm", "hybrid") else 0
    caches = T.init_caches(cfg, B, MAX_LEN, dtype, per_slot=True,
                           paged=paged, kv_block_size=BLOCK,
                           state_snaps=snaps)
    rng = np.random.default_rng(1)

    def fill(path, c):
        name = jax.tree_util.keystr(path)
        if "'pos'" in name:
            return jnp.broadcast_to(jnp.asarray(POS), c.shape)
        if "'start'" in name:
            return jnp.broadcast_to(jnp.asarray(START), c.shape)
        if "tbl'" in name:
            nb = c.shape[-1]
            t = 1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb)
            return jnp.broadcast_to(jnp.asarray(t), c.shape)
        return jnp.asarray(rng.standard_normal(c.shape) * 0.5, c.dtype)

    return jax.tree_util.tree_map_with_path(fill, caches), snaps > 0


def _unrolled(params, cfg, acfg, tokens, caches, pos_offset, seq_mask):
    """The reference: a Python loop over the layers, each run on its own
    unstacked cache (``layer=()``), the results stacked back."""
    ctx = AnalogCtx(key=None, training=False)
    x, positions = T.embed_inputs(params, cfg, {"tokens": tokens})
    positions = positions + pos_offset
    n = jax.tree.leaves(params["blocks"])[0].shape[0]
    new = []
    for i in range(n):
        p_i = jax.tree.map(lambda a: a[i], params["blocks"])
        c_i = jax.tree.map(lambda a: a[i], caches)
        if cfg.family == "hybrid":
            x, _, c_i = T._hybrid_sb_apply(p_i, x, cfg, acfg, ctx, positions,
                                           c_i, (), seq_mask)
        elif cfg.family == "ssm":
            x, _, c_i = T.apply_mamba_layer(p_i, x, cfg, acfg, ctx, c_i,
                                            "none", seq_mask)
        else:
            x, _, c_i = T.apply_attn_layer(
                p_i, x, cfg, acfg, ctx, positions, c_i,
                "moe" if cfg.family == "moe" else "dense", seq_mask)
        new.append(c_i)
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    logits, _ = T.apply_lm_head(params, cfg, acfg, ctx, x)
    return logits, jax.tree.map(lambda *a: jnp.stack(a), *new)


def _assert_masked_row_untouched(old, new, cfg, paged, snaps):
    axes, kinds = T.cache_slot_spec(cfg, paged=paged, state_snaps=snaps)
    flat = lambda t: jax.tree_util.tree_leaves_with_path(t)
    tbl = next((np.asarray(leaf) for path, leaf in flat(old)
                if "'tbl'" in jax.tree_util.keystr(path)), None)
    for (path, o), n, ax, kind in zip(flat(old), jax.tree.leaves(new),
                                      jax.tree.leaves(axes),
                                      jax.tree.leaves(kinds)):
        name = jax.tree_util.keystr(path)
        o, n = np.asarray(o), np.asarray(n)
        if kind == "pool":       # the row's own blocks, on the block axis
            blocks = np.unique(tbl[(0,) * (tbl.ndim - 2) + (MASKED,)])
            o, n = np.take(o, blocks, axis=1), np.take(n, blocks, axis=1)
        elif kind == "spool":
            continue             # no slot axis; untouched by the model
        else:
            o, n = np.take(o, MASKED, axis=ax), np.take(n, MASKED, axis=ax)
        np.testing.assert_array_equal(n, o, err_msg=name)


@pytest.mark.parametrize("layout", ["contiguous", "paged", "paged-kernels"])
@pytest.mark.parametrize("arch", FAMILIES)
def test_layer_scan_matches_unrolled_layers(arch, layout, monkeypatch):
    """One ``serve_step`` and one chunked-prefill ``forward`` with caches
    return logits and every cache leaf bitwise equal to a per-layer loop
    over the same layer functions on unstacked caches; the fully-masked
    row's cache comes back untouched. ``paged-kernels`` runs the paged
    ops' Pallas kernels (interpret mode), which the scan hands the whole
    stacked pool and an offset block table."""
    cfg, params = _build(arch)
    paged = layout != "contiguous"
    if layout == "paged-kernels":
        for op in ("paged_decode_attention", "paged_prefill_attention"):
            monkeypatch.setattr(dispatch, op, functools.partial(
                getattr(dispatch, op), impl="kernel"))
    acfg = AnalogConfig(mode="analog", train_noise=False)
    caches, snaps = _filled_caches(cfg, paged)
    rng = np.random.default_rng(2)
    off = jnp.asarray(POS - START)[:, None]
    ctx = AnalogCtx(key=None, training=False)
    masks = {1: np.array([[1], [0], [1], [1]], np.float32),
             4: np.array([[1, 1, 1, 1], [0, 0, 0, 0], [0, 1, 1, 1],
                          [1, 1, 1, 1]], np.float32)}
    for s, mask in masks.items():
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, s)),
                           jnp.int32)
        mask = jnp.asarray(mask)
        if s == 1:
            got_logits, got = jax.jit(
                lambda p, c: serve_step(p, cfg, acfg, toks, c, off,
                                        seq_mask=mask))(params, caches)
            got_logits = got_logits[:, None]
        else:
            got_logits, _, got = jax.jit(
                lambda p, c: T.forward(p, cfg, acfg, ctx, {"tokens": toks},
                                       caches=c, pos_offset=off,
                                       seq_mask=mask))(params, caches)
        ref_logits, ref = jax.jit(
            lambda p, c: _unrolled(p, cfg, acfg, toks, c, off, mask))(
                params, caches)
        np.testing.assert_array_equal(np.asarray(got_logits),
                                      np.asarray(ref_logits))
        assert jax.tree.structure(got) == jax.tree.structure(caches)
        for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(ref)):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(r),
                err_msg=f"S={s} {jax.tree_util.keystr(path)}")
        _assert_masked_row_untouched(caches, got, cfg, paged, snaps)


def test_step_temporaries_hold_no_whole_cache_copy():
    """Compiled on the CPU at 4 layers, 8 slots x 256, f32 KV (f32: the
    CPU compiler widens a bf16 scatter through the whole buffer), the
    decode step's and the mixed step's temporaries stay below two layers'
    K+V: no layer copies the stacked cache, and the mixed step's slot
    gather and scatter move only the compact prefill batch's rows."""
    cfg = dataclasses.replace(get_config("phi-3-mini-4k").reduce(),
                              num_layers=4)
    cfg, params, _ = build(cfg, jax.random.PRNGKey(0))
    acfg = AnalogConfig(mode="off")
    scfg = S.SchedulerConfig(num_slots=8, max_len=256,
                             cache_dtype=jnp.float32)
    eng = S.ServeEngine(params, cfg, acfg, scfg)
    eng._refresh_device_state()
    kv = sum(eng.caches[n].nbytes for n in ("k", "v"))
    two_layers = 2 * kv // cfg.num_layers
    flags = dict(cfg=cfg, acfg=acfg, use_top_k=False, use_top_p=False, k=1)

    def temp(jitted, *args, **kw):
        return jitted.lower(params, eng.caches, *eng._decode_args(), *args,
                            **flags, **kw).compile().memory_analysis(
                            ).temp_size_in_bytes

    assert temp(S._step_jit) < two_layers
    pb, c = eng.prefill_batch, scfg.prefill_chunk
    mixed = temp(S._mixed_step_jit,
                 pf_idx=jnp.arange(pb, dtype=jnp.int32),
                 pf_toks=jnp.zeros((pb, c), jnp.int32),
                 pf_mask=jnp.ones((pb, c), jnp.float32),
                 pf_off=jnp.zeros((pb,), jnp.int32), paged=False)
    assert mixed < two_layers


def _whole_leaf_gather(caches, idx, axes):
    """The reference gather: ``take`` along each leaf's slot axis."""
    return jax.tree.map(
        lambda c, ax: c if ax < 0 else jnp.take(c, idx, axis=ax),
        caches, axes)


def _whole_leaf_scatter(caches, sub, idx, axes):
    """The reference scatter: the whole leaf moved slot-major, its ``idx``
    rows set, and moved back."""
    def scat(c, s, ax):
        if ax < 0:
            return s
        cm = jnp.moveaxis(c, ax, 0).at[idx].set(jnp.moveaxis(s, ax, 0))
        return jnp.moveaxis(cm, 0, ax)

    return jax.tree.map(scat, caches, sub, axes)


ROW_LAYOUTS = {"contiguous": {}, "paged": dict(paged=True),
               "paged-int8": dict(paged=True, kv_bits=8),
               "paged-snaps": dict(paged=True, state_snaps=2)}
ROW_CASES = [(arch, layout) for arch in FAMILIES for layout in ROW_LAYOUTS
             if layout != "paged-snaps"
             or get_config(arch).family in ("ssm", "hybrid")]


def _random_like(tree, seed):
    rng = np.random.default_rng(seed)
    return jax.tree.map(lambda c: jnp.asarray(
        rng.standard_normal(c.shape) * 4, c.dtype), tree)


@pytest.mark.parametrize("idx", [[2, 0, 3], [1]],
                         ids=["unsorted-filler", "one-row"])
@pytest.mark.parametrize("arch,layout", ROW_CASES)
def test_slot_gather_scatter_match_whole_leaf_forms(arch, layout, idx):
    """The mixed step's per-row slot gather and scatter return every leaf
    bitwise equal to the whole-leaf ``take`` / slot-major ``.at[].set``
    forms: for an unsorted compact batch whose last row is a filler slot,
    and for the one-row batch of the drafter's prefill."""
    cfg = get_config(arch).reduce()
    kw = ROW_LAYOUTS[layout]
    caches = _random_like(T.init_caches(cfg, B, MAX_LEN, jnp.bfloat16,
                                        per_slot=True, kv_block_size=BLOCK,
                                        **kw), 3)
    axes, _ = T.cache_slot_spec(cfg, paged=kw.get("paged", False),
                                kv_bits=kw.get("kv_bits", 0),
                                state_snaps="state_snaps" in kw)
    idx = jnp.asarray(idx, jnp.int32)
    gather = jax.jit(lambda c, i: S._gather_rows(c, i, axes))
    ref_gather = jax.jit(lambda c, i: _whole_leaf_gather(c, i, axes))
    sub = gather(caches, idx)
    ref_sub = ref_gather(caches, idx)
    assert jax.tree.structure(sub) == jax.tree.structure(ref_sub)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(sub),
                            jax.tree.leaves(ref_sub)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(r),
            err_msg=f"gather {jax.tree_util.keystr(path)}")

    new_sub = _random_like(ref_sub, 4)
    scatter = jax.jit(lambda c, s, i: S._scatter_rows(c, s, i, axes))
    ref_scatter = jax.jit(lambda c, s, i: _whole_leaf_scatter(c, s, i, axes))
    got = scatter(caches, new_sub, idx)
    ref = ref_scatter(caches, new_sub, idx)
    assert jax.tree.structure(got) == jax.tree.structure(caches)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(ref)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(r),
            err_msg=f"scatter {jax.tree_util.keystr(path)}")
