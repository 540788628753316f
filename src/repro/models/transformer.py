"""Model assembly for all 10 assigned architectures.

One functional decoder LM covering the dense / moe / hybrid / vlm / audio /
ssm families. Layers are stacked with ``jax.lax.scan`` over layer-stacked
params (keeps HLO size O(1) in depth — essential for the 512-device dry-run)
with optional ``jax.checkpoint`` remat on the block body.

Heterogeneous (Jamba) stacks scan over *super-blocks* of ``attn_every``
layers: 1 attention + 7 mamba mixers with alternating dense/MoE FFNs,
unrolled inside the scan body (DESIGN.md §3).

Fused-kernel note: with ``AnalogConfig.use_pallas`` the per-layer weight
slices the scan body hands to ``analog_linear`` execute on the fused Pallas
analog-MVM kernel (interpret-mode on CPU). This composes with everything
here — ``lax.scan`` over stacked layers, ``jax.checkpoint`` remat (the
custom-VJP fused op recomputes its Pallas forward under remat), and the
``vmap`` over experts in ``models.moe`` (Pallas' batching rule adds a grid
dimension). See ``repro.kernels.dispatch`` for the dispatch rules.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.analog import (AnalogConfig, AnalogCtx, analog_linear,
                               init_linear, linear_labels)
from repro.distributed.sharding import shard_hint
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MoE


# ---------------------------------------------------------------------------
# per-layer blocks
# ---------------------------------------------------------------------------

def _init_ffn(key, cfg, kind: str, dtype):
    """Init the FFN of one block: dense MLP or MoE by ``kind``."""
    if kind == "moe":
        return MoE.init_moe(key, cfg, dtype)
    return L.init_mlp(key, cfg, dtype)


def _ffn_labels(p, kind: str):
    """Labels for one block FFN, dispatching on ``kind``."""
    return MoE.moe_labels(p) if kind == "moe" else L.mlp_labels(p)


def _apply_ffn(p, x, cfg, acfg, ctx, kind: str):
    """Apply one block FFN (dense or MoE). Returns (y, stats)."""
    if kind == "moe":
        return MoE.moe(p, x, cfg, acfg, ctx)
    return L.mlp(p, x, cfg, acfg, ctx)


def init_attn_layer(key, cfg, ffn_kind: str, dtype):
    """Init one pre-norm attention block (ln1/attn/ln2/ffn)."""
    k1, k2 = jax.random.split(key)
    return {"ln1": L.init_norm(cfg.d_model, cfg.norm, dtype),
            "attn": L.init_attention(k1, cfg, dtype),
            "ln2": L.init_norm(cfg.d_model, cfg.norm, dtype),
            "ffn": _init_ffn(k2, cfg, ffn_kind, dtype)}


def attn_layer_labels(p, ffn_kind: str):
    """Labels mirroring ``init_attn_layer`` structure."""
    return {"ln1": L.norm_labels(p["ln1"]),
            "attn": L.attention_labels(p["attn"]),
            "ln2": L.norm_labels(p["ln2"]),
            "ffn": _ffn_labels(p["ffn"], ffn_kind)}


def apply_attn_layer(p, x, cfg, acfg, ctx, positions, cache, ffn_kind: str,
                     seq_mask=None, layer: tuple = ()):
    """One attention block with residuals. Returns (x, stats, cache).

    ``cache`` is the layer-stacked cache and ``layer`` this block's leading
    indices in it (see ``layers.attention``)."""
    h, st_a, new_cache = L.attention(
        p["attn"], L.apply_norm(p["ln1"], x, cfg.norm), cfg, acfg, ctx,
        positions, cache, seq_mask, layer)
    x = x + h
    h, st_f = _apply_ffn(p["ffn"], L.apply_norm(p["ln2"], x, cfg.norm),
                         cfg, acfg, ctx, ffn_kind)
    x = shard_hint(x + h, "batch", "seq", "embed")
    return x, {"attn": st_a, "ffn": st_f}, new_cache


def init_mamba_layer(key, cfg, ffn_kind: str, dtype):
    """Init one mamba block (ln1/mixer, optional ln2/ffn)."""
    k1, k2 = jax.random.split(key)
    p = {"ln1": L.init_norm(cfg.d_model, cfg.norm, dtype),
         "mixer": M.init_mamba(k1, cfg, dtype)}
    if ffn_kind != "none":
        p["ln2"] = L.init_norm(cfg.d_model, cfg.norm, dtype)
        p["ffn"] = _init_ffn(k2, cfg, ffn_kind, dtype)
    return p


def mamba_layer_labels(p, ffn_kind: str):
    """Labels mirroring ``init_mamba_layer`` structure."""
    lab = {"ln1": L.norm_labels(p["ln1"]),
           "mixer": M.mamba_labels(p["mixer"])}
    if ffn_kind != "none":
        lab["ln2"] = L.norm_labels(p["ln2"])
        lab["ffn"] = _ffn_labels(p["ffn"], ffn_kind)
    return lab


def apply_mamba_layer(p, x, cfg, acfg, ctx, cache, ffn_kind: str,
                      seq_mask=None, layer: tuple = ()):
    """One mamba block with residuals. Returns (x, stats, cache).

    ``cache`` is the layer-stacked cache and ``layer`` this block's leading
    indices in it (see ``mamba2.mamba``)."""
    h, st_m, new_cache = M.mamba(
        p["mixer"], L.apply_norm(p["ln1"], x, cfg.norm), cfg, acfg, ctx, cache,
        seq_mask=seq_mask, layer=layer)
    x = x + h
    stats = {"mixer": st_m}
    if ffn_kind != "none":
        h, st_f = _apply_ffn(p["ffn"], L.apply_norm(p["ln2"], x, cfg.norm),
                             cfg, acfg, ctx, ffn_kind)
        x = x + h
        stats["ffn"] = st_f
    # serve-only gather ("skip" in training), mirroring the attn layer's
    # "embed" hint: out_proj's column-parallel output must be whole before
    # the next layer's norm reduces over d_model (bitwise-TP contract)
    x = shard_hint(x, "batch", "seq", "serve_act")
    return x, stats, new_cache


# ---------------------------------------------------------------------------
# stacks (uniform scan / hybrid super-block scan)
# ---------------------------------------------------------------------------

def _stacked_init(fn, key, n):
    """vmap an init over n fresh keys → layer-stacked params."""
    return jax.vmap(fn)(jax.random.split(key, n))


def init_blocks(key, cfg, dtype):
    """Init the family-specific layer stack (scan-stacked params)."""
    fam = cfg.family
    if fam in ("dense", "vlm", "audio"):
        return _stacked_init(
            lambda k: init_attn_layer(k, cfg, "dense", dtype), key,
            cfg.num_layers)
    if fam == "moe":
        return _stacked_init(
            lambda k: init_attn_layer(k, cfg, "moe", dtype), key,
            cfg.num_layers)
    if fam == "ssm":
        return _stacked_init(
            lambda k: init_mamba_layer(k, cfg, "none", dtype), key,
            cfg.num_layers)
    if fam == "hybrid":
        n_sb = cfg.num_layers // cfg.attn_every

        def init_sb(k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            half = cfg.attn_every // 2
            return {
                "attn": init_attn_layer(k1, cfg, "dense", dtype),
                "mamba": _stacked_init(
                    lambda kk: init_mamba_layer(kk, cfg, "none", dtype),
                    k2, cfg.attn_every - 1),
                "dense_ffn": _stacked_init(
                    lambda kk: {"ln2": L.init_norm(cfg.d_model, cfg.norm, dtype),
                                "ffn": _init_ffn(kk, cfg, "dense", dtype)},
                    k3, half - 1),
                "moe_ffn": _stacked_init(
                    lambda kk: {"ln2": L.init_norm(cfg.d_model, cfg.norm, dtype),
                                "ffn": _init_ffn(kk, cfg, "moe", dtype)},
                    k4, half),
            }

        return _stacked_init(init_sb, key, n_sb)
    raise ValueError(fam)


def blocks_labels(params_blocks, cfg):
    """Labels share the stacked structure (string leaves broadcast fine)."""
    fam = cfg.family
    one = jax.tree.map(lambda t: t[0] if hasattr(t, "shape") else t,
                       params_blocks)
    if fam in ("dense", "vlm", "audio"):
        lab = attn_layer_labels(one, "dense")
    elif fam == "moe":
        lab = attn_layer_labels(one, "moe")
    elif fam == "ssm":
        lab = mamba_layer_labels(one, "none")
    elif fam == "hybrid":
        inner = jax.tree.map(lambda t: t[0] if hasattr(t, "shape") else t, one)
        lab = {
            "attn": attn_layer_labels(one["attn"], "dense"),
            "mamba": mamba_layer_labels(inner["mamba"], "none"),
            "dense_ffn": {"ln2": L.norm_labels(inner["dense_ffn"]["ln2"]),
                          "ffn": _ffn_labels(inner["dense_ffn"]["ffn"],
                                             "dense")},
            "moe_ffn": {"ln2": L.norm_labels(inner["moe_ffn"]["ln2"]),
                        "ffn": _ffn_labels(inner["moe_ffn"]["ffn"], "moe")},
        }
    else:
        raise ValueError(fam)
    return lab


def _hybrid_sb_apply(p_sb, x, cfg, acfg, ctx, positions, cache, layer,
                     seq_mask=None):
    """One Jamba super-block: layers 0..attn_every-1, attn at the middle.

    ``cache`` is the stacked hybrid cache and ``layer`` this super-block's
    leading indices in it (``(sb,)`` in the scan): the attention layer
    works at ``layer`` of the ``attn`` leaves, mamba layer ``m`` at
    ``(*layer, m)`` of the ``mamba`` leaves; the updated cache is
    returned.

    Returned stats mirror the super-block's param structure (attn / mamba /
    dense_ffn / moe_ffn with stacked sub-stats) so the trainer's input-range
    rules can walk params and stats in lockstep.
    """
    half = cfg.attn_every // 2
    st_attn, st_mamba, st_dense, st_moe = None, [], [], []
    m_idx = 0
    take = lambda t, i: jax.tree.map(lambda a: a[i], t)
    for j in range(cfg.attn_every):
        ffn_kind = "moe" if j % 2 == 1 else "dense"
        ctx_j = dataclasses.replace(
            ctx, key=None if ctx.key is None else jax.random.fold_in(ctx.key, j))
        if j == half:
            c = None if cache is None else cache["attn"]
            x, st_attn, nc = apply_attn_layer(p_sb["attn"], x, cfg, acfg,
                                              ctx_j, positions, c, "dense",
                                              seq_mask, layer)
            if cache is not None:
                cache = {**cache, "attn": nc}
        else:
            mp = take(p_sb["mamba"], m_idx)
            c = None if cache is None else cache["mamba"]
            x, st_m, nc = apply_mamba_layer(mp, x, cfg, acfg, ctx_j, c, "none",
                                            seq_mask, layer + (m_idx,))
            if cache is not None:
                cache = {**cache, "mamba": nc}
            st_mamba.append(st_m)
            m_idx += 1
            if ffn_kind == "moe":
                fp = take(p_sb["moe_ffn"], j // 2)
            else:
                fp = take(p_sb["dense_ffn"], j // 2 - (1 if j > half else 0))
            h, st_f = _apply_ffn(
                fp["ffn"], L.apply_norm(fp["ln2"], x, cfg.norm),
                cfg, acfg, ctx_j, ffn_kind)
            x = x + h
            (st_moe if ffn_kind == "moe" else st_dense).append({"ffn": st_f})

    stack = lambda lst: jax.tree.map(lambda *xs: jnp.stack(xs), *lst)
    stats = {"attn": st_attn, "mamba": stack(st_mamba),
             "dense_ffn": stack(st_dense), "moe_ffn": stack(st_moe)}
    return x, stats, cache


def apply_blocks(params_blocks, x, cfg, acfg: AnalogConfig, ctx: AnalogCtx,
                 positions, caches=None, remat: bool = False, seq_mask=None):
    """Scan the layer stack. Returns (x, stats_stacked, new_caches).

    The parameters are the scan's ``xs``; the layer-stacked decoding
    cache rides in the carry with the layer index, whole. Each layer
    writes only its new token rows (and its O(1)-per-slot state) into the
    stacked, donated buffers at its own index and reads its entry back
    (``layers.attention``, ``mamba2.mamba``), so no layer copies its part
    of the cache. (The executable around the scan may still copy the
    whole cache into the loop's layout and back, where the device's
    default layout of a leaf is another: ``docs/serving.md``.) Training
    passes ``caches=None``.

    ``seq_mask`` [B, S] marks valid (non-pad) positions; it is forwarded to
    the stateful mamba mixers so masked tokens leave the SSM/conv state
    untouched, and to the attention layers, where *fully-masked rows* drop
    their cache writes and freeze their cursor (left-pad columns of active
    rows are still handled by the slot cache's ``start`` markers — see
    ``layers.attention``). The serving engine's fused mixed step leans on
    the fully-masked-row contract to advance decode slots and prefill
    chunks of admitting slots in one dispatch.
    """
    fam = cfg.family
    ffn_kind = {"dense": "dense", "vlm": "dense", "audio": "dense",
                "moe": "moe", "ssm": "none", "hybrid": None}[fam]

    def body(carry, p_l):
        x, idx, cache = carry
        ctx_l = dataclasses.replace(
            ctx, key=None if ctx.key is None
            else jax.random.fold_in(ctx.key, idx))
        if fam == "hybrid":
            x, stats, cache = _hybrid_sb_apply(p_l, x, cfg, acfg, ctx_l,
                                               positions, cache, (idx,),
                                               seq_mask)
        elif fam == "ssm":
            x, stats, cache = apply_mamba_layer(p_l, x, cfg, acfg, ctx_l,
                                                cache, ffn_kind, seq_mask,
                                                (idx,))
        else:
            x, stats, cache = apply_attn_layer(p_l, x, cfg, acfg, ctx_l,
                                               positions, cache, ffn_kind,
                                               seq_mask, (idx,))
        return (x, idx + 1, cache), stats

    if remat:
        # remat=True/'dots': save non-batched matmul outputs (XLA default
        # trade); remat='nothing': full recompute — minimum live activations
        # (the §Perf memory lever for the 30B+ train cells).
        policy = (None if remat == "nothing"
                  else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        body = jax.checkpoint(body, policy=policy)

    (x, _, new_caches), stats = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.int32), caches), params_blocks)
    return x, stats, new_caches


# ---------------------------------------------------------------------------
# whole model
# ---------------------------------------------------------------------------

def init_model(key, cfg, dtype=jnp.float32):
    """Returns (params, labels)."""
    keys = jax.random.split(key, 6)
    emb_scale = cfg.d_model ** -0.5
    params: dict[str, Any] = {}
    labels: dict[str, Any] = {}

    if cfg.family == "audio":
        params["embed"] = {"codebooks": (
            jax.random.normal(keys[0],
                              (cfg.num_codebooks, cfg.vocab_size, cfg.d_model),
                              jnp.float32) * emb_scale).astype(dtype)}
        labels["embed"] = {"codebooks": "digital"}
    else:
        params["embed"] = {"tokens": (
            jax.random.normal(keys[0], (cfg.padded_vocab, cfg.d_model),
                              jnp.float32) * emb_scale).astype(dtype)}
        labels["embed"] = {"tokens": "digital"}

    if cfg.family == "vlm":
        params["projector"] = init_linear(keys[1], cfg.vit_dim, cfg.d_model,
                                          use_bias=True, dtype=dtype)
        labels["projector"] = linear_labels(params["projector"])

    params["blocks"] = init_blocks(keys[2], cfg, dtype)
    labels["blocks"] = blocks_labels(params["blocks"], cfg)

    params["final_norm"] = L.init_norm(cfg.d_model, cfg.norm, dtype)
    labels["final_norm"] = L.norm_labels(params["final_norm"])

    if cfg.family == "audio":
        params["lm_head"] = init_linear(
            keys[3], cfg.d_model, cfg.num_codebooks * cfg.vocab_size,
            use_bias=False, dtype=dtype)
        labels["lm_head"] = linear_labels(params["lm_head"])
    elif not cfg.tie_embeddings:
        params["lm_head"] = init_linear(keys[3], cfg.d_model,
                                        cfg.padded_vocab, use_bias=False,
                                        dtype=dtype)
        labels["lm_head"] = linear_labels(params["lm_head"])
    return params, labels


def embed_inputs(params, cfg, inputs) -> tuple[jax.Array, jax.Array]:
    """→ (x [B,S,d], positions [B,S]). Handles modality frontends (stubs)."""
    if cfg.family == "audio":
        tok = inputs["tokens"]                       # [B, S, K]
        emb = params["embed"]["codebooks"]           # [K, V, d]
        x = sum(emb[k][tok[..., k]] for k in range(cfg.num_codebooks))
        bsz, s = tok.shape[:2]
    elif cfg.family == "vlm":
        text = params["embed"]["tokens"][inputs["tokens"]]
        x = text
        bsz, s = inputs["tokens"].shape
    else:
        x = params["embed"]["tokens"][inputs["tokens"]]
        bsz, s = inputs["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (bsz, s))
    return x, positions


def apply_lm_head(params, cfg, acfg: AnalogConfig, ctx: AnalogCtx,
                  x: jax.Array):
    """Project hidden states to (vocab-sliced) logits. Returns (logits, stats).

    Factored out of ``forward`` so the chunked-vocab loss can apply it to
    sequence slices without materializing [B, S, V]."""
    stats = {}
    if cfg.family == "audio":
        logits, st = analog_linear(params["lm_head"], x, acfg, ctx)
        stats["lm_head"] = st
        logits = logits.reshape(*x.shape[:-1], cfg.num_codebooks,
                                cfg.vocab_size)
    elif cfg.tie_embeddings:
        logits = jnp.matmul(x, params["embed"]["tokens"].T.astype(x.dtype),
                            preferred_element_type=jnp.float32).astype(x.dtype)
        logits = logits[..., :cfg.vocab_size]
    else:
        logits, st = analog_linear(params["lm_head"], x, acfg, ctx)
        stats["lm_head"] = st
        logits = logits[..., :cfg.vocab_size]
    # serve-only gather ("skip" in training; no-op on audio's 4-D logits):
    # a vocab-sharded lm_head output is collected before sampling so the
    # softmax/top-k reductions run locally on every shard (bitwise TP)
    logits = shard_hint(logits, "batch", "seq", "serve_act")
    return logits.astype(jnp.float32), stats


def forward(params, cfg, acfg: AnalogConfig, ctx: AnalogCtx, inputs,
            caches=None, pos_offset: Optional[jax.Array] = None,
            remat: bool = False, last_only: bool = False,
            return_hidden: bool = False, seq_mask=None):
    """Full forward. Returns (logits, stats, new_caches).

    ``inputs``: {"tokens": ...} (+ "patch_embeds" for vlm). For decode pass
    single-token inputs plus ``caches`` and ``pos_offset``. ``last_only``
    computes the LM head for the final position only (prefill: avoids the
    [B, S, V] logits tensor entirely). ``return_hidden`` skips the LM head
    and returns post-final-norm hidden states (chunked-loss path).

    Continuous-batching extensions: ``pos_offset`` may be per-row ([B, 1])
    so request slots decode at heterogeneous positions, and ``seq_mask``
    [B, S] marks left-pad positions of a chunked prefill as
    state-transparent (see :func:`apply_blocks`).
    """
    x, positions = embed_inputs(params, cfg, inputs)
    x = shard_hint(x, "batch", "seq", "embed")
    stats: dict[str, Any] = {}

    if cfg.family == "vlm" and "patch_embeds" in inputs:
        pe, st = analog_linear(params["projector"], inputs["patch_embeds"],
                               acfg, ctx)
        stats["projector"] = st
        x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
        bsz, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (bsz, s))

    if pos_offset is not None:
        positions = positions + pos_offset

    x, st_blocks, new_caches = apply_blocks(
        params["blocks"], x, cfg, acfg, ctx, positions, caches, remat,
        seq_mask)
    stats["blocks"] = st_blocks

    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    if last_only:
        x = x[:, -1:]
    if return_hidden:
        return x, stats, new_caches

    logits, st = apply_lm_head(params, cfg, acfg, ctx, x)
    stats.update(st)
    return logits, stats, new_caches


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, max_len: int, dtype=jnp.float32,
                per_slot: bool = False, paged: bool = False,
                kv_block_size: int = 16, kv_blocks: int | None = None,
                kv_bits: int = 0, state_snaps: int = 0):
    """Stacked per-layer decoding caches: every leaf leads with the layer
    axis (hybrid: the super-block axis), and ``apply_blocks`` carries the
    whole stack through its layer scan, each layer writing its own index.

    ``per_slot=True`` builds the continuous-batching slot layout: the
    attention caches carry per-row write cursors (``pos``/``start`` [B])
    instead of one shared scalar position, and every leaf keeps the slot
    dimension at a fixed, known axis so one request's state can be
    gathered/scattered by the scheduler (see :func:`cache_slot_spec`).

    ``paged=True`` swaps the attention leaves for the block-paged pool
    layout of ``layers.init_cache``: per-layer pools of ``kv_blocks``
    physical ``kv_block_size``-token blocks (int8 + scales when
    ``kv_bits=8``) and a per-slot block table; every layer shares the same
    logical→physical mapping, so one host-side allocation covers the
    stack. SSM leaves are untouched (their state is O(1) per slot already).

    ``state_snaps > 0`` adds per-layer ``conv_snap``/``ssm_snap`` snapshot
    pools to every mamba cache (ssm/hybrid prefix caching — see
    ``mamba2.init_mamba_cache``); attention-only families ignore it.
    """
    fam = cfg.family
    attn_kw = dict(paged=paged, kv_block_size=kv_block_size,
                   kv_blocks=kv_blocks, kv_bits=kv_bits)

    def stack(tree, n):
        return jax.tree.map(lambda t: jnp.broadcast_to(t, (n,) + t.shape), tree)

    if fam in ("dense", "vlm", "audio", "moe"):
        return stack(L.init_cache(cfg, batch, max_len, dtype, per_slot,
                                  **attn_kw), cfg.num_layers)
    if fam == "ssm":
        return stack(M.init_mamba_cache(cfg, batch, dtype,
                                        state_snaps=state_snaps),
                     cfg.num_layers)
    if fam == "hybrid":
        n_sb = cfg.num_layers // cfg.attn_every
        sb = {"attn": L.init_cache(cfg, batch, max_len, dtype, per_slot,
                                   **attn_kw),
              "mamba": stack(M.init_mamba_cache(cfg, batch, dtype,
                                                state_snaps=state_snaps),
                             cfg.attn_every - 1)}
        return stack(sb, n_sb)
    raise ValueError(fam)


def cache_slot_spec(cfg, paged: bool = False, kv_bits: int = 0,
                    state_snaps: bool = False):
    """Companion trees for the slot cache: ``(axes, kinds)``.

    ``axes`` mirrors the ``init_caches(per_slot=True)`` structure with the
    integer axis of the slot (request) dimension at each leaf — ``-1``
    marks pool-wide leaves that have *no* slot dimension and are passed
    through whole (the paged KV pools). ``kinds`` labels each leaf:
    ``"start"`` (per-slot first-valid index, set to the left-pad count at
    admission), ``"pos"`` (per-slot write cursor — set to the prefix-hit
    skip point at admission, so a cached prefix is never re-prefilled),
    ``"state"`` (zeroed at admission), ``"table"`` / ``"wtable"`` (the
    slot's read / write block-table rows, written from the allocator's
    admission result — ``wtable`` redirects shared prefix-hit blocks to
    the sink) or ``"pool"`` (shared physical storage — left untouched at
    admission except for the optional copy-on-write block copy; stale
    blocks are never attended because the ``start <= j <= pos`` mask
    bounds every read, and every pool leaf keeps its block axis at
    position 1, right after the stacked layer axis, which is what the
    COW copy indexes). The scheduler uses these to gather one slot's
    cache row, run a prefill chunk on it, and scatter it back — without
    hard-coding the pytree layout of any model family.

    ``state_snaps=True`` (ssm/hybrid prefix caching) adds the
    ``conv_snap``/``ssm_snap`` leaves of
    ``init_caches(state_snaps > 0)``: kind ``"spool"`` with axis ``-1`` —
    pool-wide like the paged KV leaves, passed through gathers whole and
    never touched at admission except by the scheduler's explicit
    snapshot capture/restore copies (which use the sibling ``"state"``
    leaf's slot axis as the snapshot-slot axis).
    """
    fam = cfg.family
    if paged:
        attn_axes = {"kp": -1, "vp": -1, "tbl": 1, "wtbl": 1, "pos": 1,
                     "start": 1}
        attn_kinds = {"kp": "pool", "vp": "pool", "tbl": "table",
                      "wtbl": "wtable", "pos": "pos", "start": "start"}
        if kv_bits == 8:
            attn_axes.update(ks=-1, vs=-1)
            attn_kinds.update(ks="pool", vs="pool")
    else:
        attn_axes = {"k": 1, "v": 1, "pos": 1, "start": 1}
        attn_kinds = {"k": "state", "v": "state", "pos": "pos",
                      "start": "start"}
    mamba_axes = {"conv": 1, "ssm": 1}
    mamba_kinds = {"conv": "state", "ssm": "state"}
    if state_snaps:
        mamba_axes.update(conv_snap=-1, ssm_snap=-1)
        mamba_kinds.update(conv_snap="spool", ssm_snap="spool")
    if fam in ("dense", "vlm", "audio", "moe"):
        return attn_axes, attn_kinds
    if fam == "ssm":
        return mamba_axes, mamba_kinds
    if fam == "hybrid":
        # hybrid mamba leaves carry an extra leading per-super-block stack
        # dimension, shifting the slot axis by one (pool-wide -1 leaves
        # have no slot axis to shift)
        axes = {"attn": attn_axes,
                "mamba": {k: (v + 1 if v >= 0 else v)
                          for k, v in mamba_axes.items()}}
        kinds = {"attn": attn_kinds, "mamba": mamba_kinds}
        return axes, kinds
    raise ValueError(fam)
