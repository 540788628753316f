"""Shared model layers: norms, RoPE, GQA attention, MLPs.

Every projection matmul routes through :func:`repro.core.analog_linear`; the
attention computation itself, norms, activations and residual adds stay in
high precision ("digital units" in the paper's heterogeneous accelerator).

Because ``analog_linear`` is the single MVM entry point, setting
``AnalogConfig.use_pallas`` routes *every* projection here through the fused
Pallas tile op (``repro.kernels.dispatch``) with no changes to this module:
the dispatch layer flattens the ``[B, S, K]`` activations these blocks hand
it, works on the per-layer ``[K, N]`` weight slices ``lax.scan`` carves out
of the stacked ``[L, K, N]`` parameters, and drops to decode-shape blocks
(``bm = 8``) for the single-token ``x.shape[1] == 1`` branch of
:func:`attention`. Pytree structure (params, stats, caches) is unchanged
either way — verified by the ``tests/test_kernel_dispatch.py`` parity suite.

All blocks return ``(y, stats)`` where ``stats`` mirrors the linear-site
structure of their params (x_std / clip_frac per site) — consumed by the
input-range EMA-init/decay rules in the trainer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.analog import (AnalogConfig, AnalogCtx, analog_linear,
                               init_linear, linear_labels)
from repro.distributed.sharding import shard_hint
from repro.kernels import dispatch

# ---------------------------------------------------------------------------
# norms (digital)
# ---------------------------------------------------------------------------


def init_norm(d: int, kind: str, dtype=jnp.float32) -> dict:
    """Init RMSNorm/LayerNorm params (digital — scale/bias only)."""
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_labels(p: dict) -> dict:
    """Clipping/optimizer labels for a norm site (all digital)."""
    return {k: "digital" for k in p}


def apply_norm(p: dict, x: jax.Array, kind: str, eps: float = 1e-5) -> jax.Array:
    """Apply RMSNorm or LayerNorm in fp32, returning the input dtype."""
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (xf * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (xf * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (digital)
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply rotary embeddings. x [..., S, H, hd], positions [..., S]."""
    if theta <= 0:                       # jamba: no positional embeddings
        return x
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (digital math, analog projections)
# ---------------------------------------------------------------------------


def init_attention(key, cfg, dtype=jnp.float32) -> dict:
    """Init GQA attention params (fused qkv or split q/k/v analog sites)."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    hd = cfg.head_dim
    p = {"o": init_linear(ko, cfg.num_heads * hd, cfg.d_model,
                          use_bias=False, dtype=dtype)}
    if getattr(cfg, "fused_qkv", True):
        qkv_out = (cfg.num_heads + 2 * cfg.num_kv_heads) * hd
        p["qkv"] = init_linear(kq, cfg.d_model, qkv_out,
                               use_bias=cfg.qkv_bias, dtype=dtype)
    else:
        p["q"] = init_linear(kq, cfg.d_model, cfg.num_heads * hd,
                             use_bias=cfg.qkv_bias, dtype=dtype)
        p["k"] = init_linear(kk, cfg.d_model, cfg.num_kv_heads * hd,
                             use_bias=cfg.qkv_bias, dtype=dtype)
        p["v"] = init_linear(kv, cfg.d_model, cfg.num_kv_heads * hd,
                             use_bias=cfg.qkv_bias, dtype=dtype)
    return p


def attention_labels(p: dict) -> dict:
    """Labels for attention params: one linear-site label set per proj."""
    return {k: linear_labels(v) for k, v in p.items()}


def _split_qkv(qkv: jax.Array, cfg):
    """Split a fused qkv projection into per-head q, k, v tensors."""
    hd = cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
    q = q.reshape(*q.shape[:-1], nq, hd)
    k = k.reshape(*k.shape[:-1], nkv, hd)
    v = v.reshape(*v.shape[:-1], nkv, hd)
    return q, k, v


def _gqa_scores_softmax_v(q, k, v, mask, scale):
    """q [B,S,H,hd], k/v [B,T,KV,hd] → [B,S,H,hd] (digital FP math)."""
    nq, nkv = q.shape[-2], k.shape[-2]
    group = nq // nkv
    qg = q.reshape(*q.shape[:-2], nkv, group, q.shape[-1])
    logits = jnp.einsum("bsngh,btnh->bnsgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(mask[:, None, :, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnsgt,btnh->bsngh", probs, v.astype(jnp.float32))
    return out.reshape(*q.shape).astype(q.dtype)


def _chunked_causal_attention(q, k, v, scale, q_chunk=512, kv_chunk=1024):
    """Flash-style online-softmax attention over KV chunks.

    Never materializes the [S, S] score matrix — required for the 32k-prefill
    and 4k-train shapes to fit HBM in the dry-run. Pure jax.lax, so it shards
    under pjit (S is *not* sharded; heads/batch are).
    """
    b, s, nq, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    group = nq // nkv
    nq_c = (s + q_chunk - 1) // q_chunk
    nk_c = (t + kv_chunk - 1) // kv_chunk
    s_pad, t_pad = nq_c * q_chunk, nk_c * kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))

    qg = qp.reshape(b, nq_c, q_chunk, nkv, group, hd).astype(jnp.float32)
    kc = kp.reshape(b, nk_c, kv_chunk, nkv, hd).astype(jnp.float32)
    vc = vp.reshape(b, nk_c, kv_chunk, nkv, hd).astype(jnp.float32)

    def q_block(qi, q_blk):
        # online softmax over kv chunks for one q chunk
        def kv_step(carry, inp):
            kj, (k_blk, v_blk) = inp

            def compute(c):
                m, l, acc = c
                logits = jnp.einsum("bsngh,btnh->bnsgt", q_blk, k_blk) * scale
                q_pos = qi * q_chunk + jnp.arange(q_chunk)
                k_pos = kj * kv_chunk + jnp.arange(kv_chunk)
                causal = q_pos[:, None] >= k_pos[None, :]
                valid = (k_pos < t)[None, :]
                logits = jnp.where((causal & valid)[None, None, :, None, :],
                                   logits, -1e30)
                m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
                p = jnp.exp(logits - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bnsgt,btnh->bnsgh", p, v_blk)
                return m_new, l_new, acc_new

            # Fully-masked future chunks (first kv position past this q
            # chunk's last position) are skipped at runtime: lax.cond is a
            # real branch under scan, so causal prefill does ~half the
            # chunk matmuls the full sweep did.
            live = kj * kv_chunk <= qi * q_chunk + q_chunk - 1
            return jax.lax.cond(live, compute, lambda c: c, carry), None

        m0 = jnp.full((b, nkv, q_chunk, group), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, nkv, q_chunk, group), jnp.float32)
        a0 = jnp.zeros((b, nkv, q_chunk, group, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk_c), (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0))))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 1, 2)        # [b, q_chunk, nkv, group, hd]

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq_c), jnp.moveaxis(qg, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s_pad, nq, hd)
    return out[:, :s].astype(q.dtype)


def attention(p: dict, x: jax.Array, cfg, acfg: AnalogConfig, ctx: AnalogCtx,
              positions: jax.Array, cache: dict | None = None,
              seq_mask: jax.Array | None = None, layer: tuple = ()):
    """GQA attention block. Returns (y, stats, new_cache).

    ``cache`` is the whole layer-stacked decoding cache and ``layer`` the
    leading indices of this layer in it (``(i,)`` for a uniform stack,
    empty for a single layer's cache; see ``layer_slice``): the layer
    writes only its new token rows into the stacked buffers, at
    ``[*layer, b, pos[b] + i]``, reads its own entry back for the scores,
    and returns the whole stacked cache. The per-row leaves (``pos``) are
    written back at ``layer``; every other leaf passes through untouched.
    That is what lets ``transformer.apply_blocks`` carry the cache through
    its layer scan without a copy per layer.

    ``seq_mask`` [B, S] (1 = real token) applies to the slot-cache layouts
    only and makes *fully-masked rows* cache-transparent: their K/V writes
    are dropped (contiguous: out-of-range index + ``mode="drop"``; paged:
    redirected to the reserved sink block) and their ``pos`` cursor does
    not advance — the contract the serving engine's fused mixed
    prefill/decode step relies on, where a decode substep must not touch
    rows that are mid-prefill and vice versa. Rows with at least one real
    token behave exactly as before (left-pad columns still write; the
    ``start`` marker keeps them unattended), so all-active callers are
    bit-identical to the unmasked path.

    Three cache layouts (see ``init_cache``; shapes are one layer's):

    * legacy (``pos`` scalar): ``{"k": [B, T, KV, hd], "v": ..., "pos": ()}``
      — batched lockstep serving; decode writes one token at the shared
      ``pos`` and attends over the full statically-shaped buffer.
    * slot mode (``pos`` [B]): ``{"k", "v", "pos": [B], "start": [B]}`` —
      the continuous-batching layout. Every row is an independent request
      slot: the current chunk (decode: S=1, chunked prefill: S=C) is
      scattered into the stacked buffers at ``[*layer, b, pos[b] +
      arange(S)]``, the layer's entry is read back, and the
      mask attends cache indices ``start[b] <= j <= pos[b] + i`` only, so
      left-pad rows (``j < start``) and unwritten rows are never attended.
      All index math is static-shape (gather/scatter), keeping the decode
      scan jittable with requests at heterogeneous positions.
    * paged slot mode (``"kp"`` present): the block-paged pool layout —
      ``{"kp", "vp": [P, bs, KV, hd], "tbl", "wtbl": [B, NB], "pos",
      "start": [B]}`` (+ ``"ks"``/``"vs"`` [P, bs, KV] scales when the
      pool is int8). Logical cache index ``j`` lives at physical block
      ``tbl[b, j//bs]``, offset ``j % bs``; the scheduler's refcounting
      allocator (``serve.kv_pool``) hands each slot the blocks its
      request needs — possibly *shared* with other slots via prefix
      caching. Reads always go through ``tbl``; writes go through the
      **write table** ``wtbl``, which equals ``tbl`` for private blocks
      and redirects prefix-hit (shared, immutable) blocks to the
      reserved sink block — a chunk re-scoring a cached region can never
      corrupt it (the write-protection contract the prefix cache relies
      on, mirroring the fully-masked-row sink redirect). The decode read
      routes through the paged flash-decode op (``kernels.dispatch``),
      which only visits each row's live blocks — decode cost and bytes
      scale with actual fill, not ``max_len``. Chunked prefill scores the
      chunk against the pool in place via the paged flash-prefill op.
    """
    hd = cfg.head_dim
    if "qkv" in p:
        qkv, st_qkv = analog_linear(p["qkv"], x, acfg, ctx)
        q, k, v = _split_qkv(qkv, cfg)
        stats_in = {"qkv": st_qkv}
    else:   # de-fused q/k/v sites (§Perf: avoids split-reshard permutes)
        q, st_q = analog_linear(p["q"], x, acfg, ctx)
        k, st_k = analog_linear(p["k"], x, acfg, ctx)
        v, st_v = analog_linear(p["v"], x, acfg, ctx)
        q = q.reshape(*q.shape[:-1], cfg.num_heads, hd)
        k = k.reshape(*k.shape[:-1], cfg.num_kv_heads, hd)
        v = v.reshape(*v.shape[:-1], cfg.num_kv_heads, hd)
        stats_in = {"q": st_q, "k": st_k, "v": st_v}
    q = shard_hint(rope(q, positions, cfg.rope_theta),
                   "batch", "seq", "heads", None)
    k = shard_hint(rope(k, positions, cfg.rope_theta),
                   "batch", "seq", "heads", None)
    v = shard_hint(v, "batch", "seq", "heads", None)
    scale = cfg.head_dim ** -0.5

    if cache is not None and "kp" in cache:          # paged slot mode
        out, new_cache = _paged_slot_attention(cache, layer, q, k, v, x,
                                               scale, acfg.kv_splits,
                                               seq_mask)
    elif cache is not None:
        pos = layer_slice(cache["pos"], layer)
        bsz, s = x.shape[0], x.shape[1]
        t = cache["k"].shape[len(layer) + 1]
        if pos.ndim == 1:                            # slot mode
            start = layer_slice(cache["start"], layer)
            row_on = _row_active(seq_mask, bsz)              # [B] 0/1
            idx = pos[:, None] + jnp.arange(s)[None, :]      # [B, S] writes
            idx_w = jnp.where(row_on[:, None] > 0, idx, t)   # drop if inactive
            at = layer + (jnp.arange(bsz)[:, None], idx_w)
            write = lambda buf, val: buf.at[at].set(val.astype(buf.dtype),
                                                    mode="drop")
            j = jnp.arange(t)[None, None, :]
            mask = (j >= start[:, None, None]) & (j <= idx[:, :, None])
            new_pos = pos + s * row_on
        else:               # legacy: decode writes at pos, prefill at 0
            at = layer + (0, pos if s == 1 else 0, 0, 0)
            write = lambda buf, val: jax.lax.dynamic_update_slice(
                buf, _lead(val.astype(buf.dtype), layer), at)
            mask = (jnp.broadcast_to((jnp.arange(t) <= pos)[None, None, :],
                                     (bsz, 1, t)) if s == 1 else None)
            new_pos = pos + s
        k_buf, v_buf = write(cache["k"], k), write(cache["v"], v)
        if mask is None:    # prefill attends over its own chunk
            out = _causal_attention(q, k, v, positions, scale)
        else:
            out = _gqa_scores_softmax_v(q, layer_slice(k_buf, layer),
                                        layer_slice(v_buf, layer), mask,
                                        scale)
        new_cache = {**cache, "k": k_buf, "v": v_buf,
                     "pos": layer_update(cache["pos"], layer, new_pos)}
    else:                                            # train
        out = _causal_attention(q, k, v, positions, scale)
        new_cache = None

    out = out.reshape(*x.shape[:-1], cfg.num_heads * cfg.head_dim)
    # "attn_out" == "heads" under training rules; serve rules replicate it
    # here so the o-projection contracts locally on every shard (bitwise TP)
    out = shard_hint(out, "batch", "seq", "attn_out")
    y, st_o = analog_linear(p["o"], out, acfg, ctx)
    return y, {**stats_in, "o": st_o}, new_cache


def _causal_attention(q, k, v, positions, scale):
    """Causal self-attention of a whole sequence (train / lockstep
    prefill): the dense mask up to 1024 tokens, flash-style beyond."""
    if q.shape[1] <= 1024:
        t = k.shape[1]
        mask = positions[:, :, None] >= jnp.arange(t)[None, None, :]
        return _gqa_scores_softmax_v(q, k, v, mask, scale)
    return _chunked_causal_attention(q, k, v, scale)


def layer_slice(leaf: jax.Array, layer: tuple) -> jax.Array:
    """One layer's entry of a layer-stacked cache leaf: ``layer`` holds the
    (traced or static) indices of the leaf's leading stack axes — ``(i,)``
    for a uniform stack, ``(sb, m)`` for a hybrid super-block's mamba
    leaves, empty for an unstacked leaf."""
    n = len(layer)
    sl = jax.lax.dynamic_slice(leaf, tuple(layer) + (0,) * (leaf.ndim - n),
                               (1,) * n + leaf.shape[n:])
    return sl.reshape(leaf.shape[n:])


def layer_update(leaf: jax.Array, layer: tuple, val: jax.Array) -> jax.Array:
    """Write one layer's entry ``val`` back into a layer-stacked cache leaf
    (the inverse of :func:`layer_slice`)."""
    return jax.lax.dynamic_update_slice(
        leaf, _lead(val.astype(leaf.dtype), layer),
        tuple(layer) + (0,) * val.ndim)


def _lead(val: jax.Array, layer: tuple) -> jax.Array:
    """``val`` with one unit axis per stack index in ``layer``."""
    return val.reshape((1,) * len(layer) + val.shape)


def _row_active(seq_mask, bsz):
    """Per-row activity flag for the slot-cache branches: 1 when the row's
    chunk carries at least one real token (left-padded prefill, decode),
    0 when the whole row is masked (a slot the current fused substep must
    leave untouched). No mask ⇒ every row active."""
    if seq_mask is None:
        return jnp.ones((bsz,), jnp.int32)
    return (jnp.max(seq_mask, axis=1) > 0).astype(jnp.int32)


def _paged_slot_attention(cache, layer, q, k, v, x, scale, kv_splits=1,
                          seq_mask=None):
    """Paged-pool branch of :func:`attention`: scatter-write the current
    chunk into the layer's entry of the stacked block pool, at
    ``[*layer, blk, off]``, then score against the live range only. The
    attention ops get the whole stacked pool, viewed as one pool of blocks,
    and the read table offset to the layer's entry (``_flat_pool``).

    Decode (S=1) routes through the paged flash-decode op; chunked prefill
    routes through the paged flash-prefill op — the chunk's queries score
    against the pool *in place* (online softmax over each row's live
    blocks, causal window ``start[b] <= j <= pos[b] + i``), so no logical
    view is ever gathered out of the pool. Writes resolve physical blocks
    through the *write table* ``wtbl`` (reads use ``tbl``): the scheduler
    points prefix-hit shared blocks at the reserved sink block, so a
    chunk re-scoring a cached region drops its (bitwise-identical)
    rewrites instead of touching blocks other slots read. Fully-masked
    rows (``seq_mask`` all zero) write to the sink and keep their
    cursor."""
    at_layer = lambda name: layer_slice(cache[name], layer)
    pos, start, tbl = at_layer("pos"), at_layer("start"), at_layer("tbl")
    wtbl = at_layer("wtbl") if "wtbl" in cache else tbl
    bsz, s = x.shape[0], x.shape[1]
    bs = cache["kp"].shape[len(layer) + 1]
    row_on = _row_active(seq_mask, bsz)                      # [B] 0/1
    idx = pos[:, None] + jnp.arange(s)[None, :]              # [B, S] logical
    blk = jnp.take_along_axis(wtbl, idx // bs, axis=1)       # [B, S] physical
    blk = jnp.where(row_on[:, None] > 0, blk, 0)             # sink if inactive
    at = layer + (blk, idx % bs)
    if "ks" in cache:                                        # int8 pool
        kq, ks = quant.kv_quantize(k, 8)
        vq, vs = quant.kv_quantize(v, 8)
        rows = {"kp": kq, "vp": vq, "ks": ks, "vs": vs}
    else:
        rows = {"kp": k, "vp": v}
    new_cache = dict(cache)
    for name, val in rows.items():
        new_cache[name] = cache[name].at[at].set(
            val.astype(cache[name].dtype), mode="drop")
    new_cache["pos"] = layer_update(cache["pos"], layer, pos + s * row_on)
    # the ops read the stacked pool in place, through this layer's tables
    pool = {name: _flat_pool(new_cache[name], layer)[0] for name in rows}
    tbl = tbl + _flat_pool(cache["kp"], layer)[1]

    if s == 1:                                    # decode: flash over blocks
        out = dispatch.paged_decode_attention(
            q[:, 0], pool["kp"], pool["vp"], tbl, pos, start, scale,
            k_scale=pool.get("ks"), v_scale=pool.get("vs"),
            num_splits=kv_splits)
        return out[:, None].astype(q.dtype), new_cache

    # chunked prefill: flash over blocks, in place on the pool
    out = dispatch.paged_prefill_attention(
        q, pool["kp"], pool["vp"], tbl, pos, start, scale,
        k_scale=pool.get("ks"), v_scale=pool.get("vs"))
    return out.astype(q.dtype), new_cache


def _flat_pool(leaf: jax.Array, layer: tuple):
    """A stacked pool leaf ``[*stack, P, ...]`` as one pool of blocks
    ``[N * P, ...]`` (a free reshape), and the index of the first block of
    the entry at ``layer`` in it: a block table offset by that index
    addresses the layer's blocks, so no layer's pool is sliced out (a
    kernel's operand would have to be a copy)."""
    n = len(layer)
    first = 0
    for i, d in zip(layer, leaf.shape[:n]):
        first = first * d + i
    return leaf.reshape((-1,) + leaf.shape[n + 1:]), first * leaf.shape[n]


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.float32,
               per_slot: bool = False, paged: bool = False,
               kv_block_size: int = 16, kv_blocks: int | None = None,
               kv_bits: int = 0) -> dict:
    """Attention KV cache. ``per_slot=True`` selects the continuous-batching
    slot layout: per-row write cursors (``pos`` [B]) and first-valid-index
    markers (``start`` [B], the number of left-pad rows) instead of one
    shared scalar position.

    ``paged=True`` (implies per-slot) replaces the per-slot ``max_len``
    buffers with a block-paged pool: ``kv_blocks`` usable physical blocks
    of ``kv_block_size`` tokens (default: enough for every slot at
    ``max_len`` — size it smaller to oversubscribe; the scheduler's
    allocator backpressures admission) plus one reserved write-sink block
    at physical index 0 (``serve.kv_pool.SINK_BLOCK`` — where retired
    slots' dead writes and write-protected shared-block writes land), a
    per-slot read block table ``tbl`` and write block table ``wtbl``
    (identical for private blocks; ``wtbl`` points prefix-hit shared
    blocks at the sink). ``kv_bits=8`` stores the pool as int8 with
    per-token/head scales (``core.quant.kv_quantize``)."""
    hd = cfg.head_dim
    if paged:
        nb = -(-max_len // kv_block_size)
        npool = 1 + (kv_blocks if kv_blocks else batch * nb)
        kv_dtype = jnp.int8 if kv_bits == 8 else dtype
        c = {"kp": jnp.zeros((npool, kv_block_size, cfg.num_kv_heads, hd),
                             kv_dtype),
             "vp": jnp.zeros((npool, kv_block_size, cfg.num_kv_heads, hd),
                             kv_dtype),
             "tbl": jnp.zeros((batch, nb), jnp.int32),
             "wtbl": jnp.zeros((batch, nb), jnp.int32),
             "pos": jnp.zeros((batch,), jnp.int32),
             "start": jnp.zeros((batch,), jnp.int32)}
        if kv_bits == 8:
            c["ks"] = jnp.zeros((npool, kv_block_size, cfg.num_kv_heads),
                                jnp.float32)
            c["vs"] = jnp.zeros((npool, kv_block_size, cfg.num_kv_heads),
                                jnp.float32)
        return c
    c = {"k": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype),
         "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype)}
    if per_slot:
        c["pos"] = jnp.zeros((batch,), jnp.int32)
        c["start"] = jnp.zeros((batch,), jnp.int32)
    else:
        c["pos"] = jnp.zeros((), jnp.int32)
    return c


# ---------------------------------------------------------------------------
# MLP (SwiGLU / plain-GELU), analog projections
# ---------------------------------------------------------------------------


def init_mlp(key, cfg, dtype=jnp.float32) -> dict:
    """Init MLP params: SwiGLU (fused gate_up) for silu, plain GELU else."""
    k1, k2 = jax.random.split(key)
    if cfg.act == "silu":             # SwiGLU: fused gate+up, then down
        return {"gate_up": init_linear(k1, cfg.d_model, 2 * cfg.d_ff,
                                       use_bias=False, dtype=dtype),
                "down": init_linear(k2, cfg.d_ff, cfg.d_model,
                                    use_bias=False, dtype=dtype)}
    return {"up": init_linear(k1, cfg.d_model, cfg.d_ff, use_bias=True,
                              dtype=dtype),
            "down": init_linear(k2, cfg.d_ff, cfg.d_model, use_bias=True,
                                dtype=dtype)}


def mlp_labels(p: dict) -> dict:
    """Labels for MLP params: one linear-site label set per projection."""
    return {k: linear_labels(v) for k, v in p.items()}


def mlp(p: dict, x: jax.Array, cfg, acfg: AnalogConfig, ctx: AnalogCtx):
    """MLP block over [B, S, d] (analog projections). Returns (y, stats)."""
    if "gate_up" in p:
        gu, st1 = analog_linear(p["gate_up"], x, acfg, ctx)
        gate, up = jnp.split(gu, 2, axis=-1)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
        h = shard_hint(h, "batch", "seq", "mlp_act")
        y, st2 = analog_linear(p["down"], h, acfg, ctx)
        return y, {"gate_up": st1, "down": st2}
    h, st1 = analog_linear(p["up"], x, acfg, ctx)
    h = shard_hint(jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype),
                   "batch", "seq", "mlp_act")
    y, st2 = analog_linear(p["down"], h, acfg, ctx)
    return y, {"up": st1, "down": st2}
