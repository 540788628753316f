"""Mamba-2 (SSD) mixer block.

Projections (in_proj / out_proj) are analog sites; the causal depthwise conv,
the SSD recurrence and the gated RMSNorm are digital (they are stateful /
elementwise ops, not static-weight MVMs — DESIGN.md §4). Used by the
``mamba2-130m`` arch and Jamba's mamba layers (Jamba-v0.1 ships Mamba-1; we
realize it with the SSD formulation — hardware-adaptation note in DESIGN.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.analog import (AnalogConfig, AnalogCtx, analog_linear,
                               init_linear, linear_labels)
from repro.distributed.sharding import shard_hint
from repro.kernels import ops as kops
from repro.models import layers as L


def _dims(cfg):
    """Derived mamba dims: (d_inner, heads, groups*state, conv_ch, in_proj)."""
    d_inner = cfg.d_inner
    heads = cfg.ssm_heads
    gn = cfg.ssm_groups * cfg.ssm_state
    conv_ch = d_inner + 2 * gn
    d_in_proj = 2 * d_inner + 2 * gn + heads
    return d_inner, heads, gn, conv_ch, d_in_proj


def init_mamba(key, cfg, dtype=jnp.float32) -> dict:
    """Init one SSD mixer: analog in/out projections + digital scan params."""
    d_inner, heads, gn, conv_ch, d_in_proj = _dims(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "in_proj": init_linear(k1, cfg.d_model, d_in_proj, use_bias=False,
                               dtype=dtype),
        "conv_w": (jax.random.normal(k2, (cfg.conv_width, conv_ch), jnp.float32)
                   * cfg.conv_width ** -0.5).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, heads, dtype=jnp.float32)),
        "d_skip": jnp.ones((heads,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.full((heads,), 0.01, jnp.float32))),  # softplus^-1(0.01)
        "gate_norm": jnp.ones((d_inner,), dtype),
        "out_proj": init_linear(k3, d_inner, cfg.d_model, use_bias=False,
                                dtype=dtype),
    }


def mamba_labels(p: dict) -> dict:
    """Labels for mamba params: analog projections, digital scan/conv."""
    lab = {k: "digital" for k in p
           if k not in ("in_proj", "out_proj")}
    lab["in_proj"] = linear_labels(p["in_proj"])
    lab["out_proj"] = linear_labels(p["out_proj"])
    return lab


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array | None = None):
    """Depthwise causal conv along seq. x [B, S, C], w [W, C].

    Returns (y, new_state) where state holds the trailing W-1 inputs.
    """
    width = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i][None, None, :]
            for i in range(width))
    y = y + b[None, None, :]
    new_state = xp[:, -(width - 1):] if width > 1 else None
    return jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype), new_state


def _gated_rmsnorm(y, z, scale, eps=1e-5):
    """Mamba-2 gated RMSNorm: normalize y * silu(z), then scale."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g * scale.astype(jnp.float32)).astype(y.dtype)


def mamba(p: dict, x: jax.Array, cfg, acfg: AnalogConfig, ctx: AnalogCtx,
          cache: dict | None = None, seq_mask: jax.Array | None = None,
          layer: tuple = ()):
    """SSD mixer over x [B, S, d]. Returns (y, stats, new_cache).

    cache: the layer-stacked decoding cache, whose entry at the leading
    indices ``layer`` (see ``layers.layer_slice``) is this mixer's
    {"conv": [B, W-1, conv_ch], "ssm": [B, H, N, P]}; decode (S = 1)
    advances it, prefill (S > 1) continues it, and the new state is
    written back at ``layer`` into the returned stacked cache (other
    leaves pass through). Train (cache None) skips state.

    ``seq_mask`` [B, S] (1 = real token) makes padded/inactive positions
    state-transparent, which is what the continuous-batching scheduler's
    left-padded chunked prefill relies on: masked positions get ``dt = 0``
    (state decay ``exp(dt·a) = 1`` and input contribution ``dt·B·x = 0``,
    so the recurrence passes through unchanged) and zeroed conv inputs
    (left-pads then match the zero-padding a fresh ``_causal_conv`` start
    applies). The state after a masked chunk is bit-equal to running the
    unpadded tokens alone.
    """
    bsz, s, _ = x.shape
    d_inner, heads, gn, conv_ch, _ = _dims(cfg)
    pdim = cfg.ssm_headdim
    g, n = cfg.ssm_groups, cfg.ssm_state

    zxbcdt, st_in = analog_linear(p["in_proj"], x, acfg, ctx)
    # serve-only gather ("skip" in training): under tensor parallelism the
    # in_proj output is collected here and every mamba internal (conv, SSD
    # recurrence, gated norm — all digital, reduction-heavy, and tiny next
    # to the projections) computes replicated, keeping TP bitwise
    zxbcdt = shard_hint(zxbcdt, "batch", "seq", "serve_act")
    z, xbc, dt_raw = jnp.split(zxbcdt, [d_inner, d_inner + conv_ch], axis=-1)

    if seq_mask is not None:
        xbc = xbc * seq_mask[..., None].astype(xbc.dtype)

    if cache is not None:
        conv_state = L.layer_slice(cache["conv"], layer)
        ssm_state = L.layer_slice(cache["ssm"], layer)
    else:
        conv_state = ssm_state = None
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    xs, b, c = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])           # [B,S,H]
    if seq_mask is not None:
        dt = dt * seq_mask[..., None].astype(dt.dtype)
    a = -jnp.exp(p["a_log"])                                      # [H]
    xh = shard_hint(xs.reshape(bsz, s, heads, pdim),
                    "batch", "seq", "ssm_heads", None)
    bg = b.reshape(bsz, s, g, n)
    cg = c.reshape(bsz, s, g, n)

    # the conv tail is stored at the cache's dtype (bf16 caches hand the
    # model a bf16 state and must get one back — scatter requires it).
    # width == 1 carries no tail (new_conv is None): the zero-length
    # [B, 0, C] cache leaf passes through unchanged so every gather/
    # scatter keeps a consistent tree. Fully-masked rows keep their old
    # tail exactly: the trailing-window update would otherwise shift
    # zeros into a row the current fused substep must leave untouched
    # (the SSM state is already transparent through dt = 0; the conv
    # state needs this explicit freeze).
    conv_cast = (None if cache is None
                 else conv_state if new_conv is None
                 else new_conv.astype(conv_state.dtype))
    if cache is not None and seq_mask is not None and new_conv is not None:
        row_on = jnp.max(seq_mask, axis=1) > 0                # [B]
        conv_cast = jnp.where(row_on[:, None, None], conv_cast, conv_state)
    if cache is not None and s == 1:                              # decode
        rep = heads // g
        to_bh = lambda t: t[:, 0].repeat(rep, axis=1).reshape(bsz * heads, -1)
        h, y_t = kops.ssd_decode_step(
            ssm_state.reshape(bsz * heads, n, pdim),
            xh[:, 0].reshape(bsz * heads, pdim),
            dt[:, 0].reshape(bsz * heads), jnp.tile(a, bsz),
            to_bh(bg), to_bh(cg))
        y = y_t.reshape(bsz, 1, heads, pdim)
    else:
        h0 = (ssm_state.reshape(bsz * heads, n, pdim)
              if cache is not None else None)
        y, h = _ssd_with_state(xh, dt, a, bg, cg, h0)
    # {**cache, ...} passes extra leaves (the scheduler's *_snap snapshot
    # pools) through untouched
    new_cache = (None if cache is None else
                 {**cache,
                  "conv": L.layer_update(cache["conv"], layer, conv_cast),
                  "ssm": L.layer_update(cache["ssm"], layer,
                                        h.reshape(bsz, heads, n, pdim))})

    y = y + p["d_skip"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(bsz, s, d_inner).astype(x.dtype)
    y = shard_hint(_gated_rmsnorm(y, z, p["gate_norm"]),
                   "batch", "seq", "mlp_act")
    out, st_out = analog_linear(p["out_proj"], y, acfg, ctx)
    return out, {"in_proj": st_in, "out_proj": st_out}, new_cache


def _ssd_with_state(xh, dt, a, bg, cg, h0=None):
    """Chunked SSD returning (y [B,S,H,P] f32, final state [B*H, N, P]).

    ``h0`` [B*H, N, P] is an optional incoming recurrence state (continuous
    batching's chunked prefill: chunk k continues from chunk k-1's state).
    The carried state contributes ``C_t · exp(Σ_{i≤t} dt_i·a) · h0`` to each
    output and decays by ``exp(Σ dt·a)`` into the final state; with the
    all-zero state a fresh cache holds, both terms vanish exactly.
    """
    y = kops.ssd(xh, dt, a, bg, cg).astype(jnp.float32)
    # final state via one extra recurrence over chunk summaries (cheap):
    bsz, s, heads, pdim = xh.shape
    g, n = bg.shape[2], bg.shape[3]
    rep = heads // g
    to_bh = lambda t: jnp.moveaxis(jnp.repeat(t, rep, axis=2), 2, 1
                                   ).reshape(bsz * heads, s, -1)
    xf = jnp.moveaxis(xh, 2, 1).reshape(bsz * heads, s, pdim).astype(jnp.float32)
    dtf = jnp.moveaxis(dt, 2, 1).reshape(bsz * heads, s)
    af = jnp.tile(a, bsz)
    bf = to_bh(bg).astype(jnp.float32)
    la = dtf * af[:, None]
    cums = jnp.cumsum(la, axis=-1)
    total = cums[:, -1]
    w_r = jnp.exp(total[:, None] - cums) * dtf                    # [BH, S]
    h = jnp.einsum("zs,zsn,zsp->znp", w_r, bf, xf)
    if h0 is not None:
        cf = to_bh(cg).astype(jnp.float32)
        h0 = h0.astype(jnp.float32)
        y_carry = jnp.einsum("zs,zsn,znp->zsp", jnp.exp(cums), cf, h0)
        y = y + jnp.moveaxis(
            y_carry.reshape(bsz, heads, s, pdim), 1, 2)
        h = h + jnp.exp(total)[:, None, None] * h0
    return y, h


def init_mamba_cache(cfg, batch: int, dtype=jnp.float32,
                     state_snaps: int = 0) -> dict:
    """Decode-time SSM state. Slot-major: every leaf has the batch/slot
    dimension leading (``conv`` [B, W-1, C], ``ssm`` [B, H, N, P]) so the
    continuous-batching scheduler can gather/scatter one request's state
    with a single dynamic slice per leaf, uniformly with the KV cache.

    ``state_snaps > 0`` adds the prefix-cache snapshot pools ``conv_snap``
    [NS, W-1, C] / ``ssm_snap`` [NS, H, N, P]: NS content-addressed copies
    of the per-slot state, captured at KV-block boundaries during prefill
    and restored at admission (``serve.kv_pool.StateSnapshotPool`` owns
    the NS-axis slot ids). The model threads them through unchanged.
    """
    d_inner, heads, gn, conv_ch, _ = _dims(cfg)
    cache = {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_ch), dtype),
        "ssm": jnp.zeros((batch, heads, cfg.ssm_state, cfg.ssm_headdim),
                         jnp.float32)}
    if state_snaps:
        cache["conv_snap"] = jnp.zeros(
            (state_snaps, cfg.conv_width - 1, conv_ch), dtype)
        cache["ssm_snap"] = jnp.zeros(
            (state_snaps, heads, cfg.ssm_state, cfg.ssm_headdim),
            jnp.float32)
    return cache
