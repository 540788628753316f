"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are swept against (interpret=True on
CPU; the TPU kernel must match them bit-for-bit up to f32 accumulation order).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _qmax(bits: int) -> float:
    """Largest positive level of a symmetric ``bits``-bit quantizer."""
    return float(2 ** (bits - 1) - 1)


# Deterministic ADC tie-break, shared by every implementation of eq. (2)
# (``core.quant.output_quantize``, this oracle, the fused kernel's ADC
# stage). RTN-lattice arithmetic places accumulator values *exactly* on
# round-half boundaries, where a 1-ulp accumulation-order difference
# (K-padding, blocked K loops, XLA reassociation) flips a full ADC level.
# Scaling the rounding operand by (1 - 2^-16) moves the decision boundary
# strictly between lattice points (lattice spacing ≥ 1/(qi*qo) ≫ 2^-16), so
# all implementations agree as long as their accumulations differ by much
# less than 2^-16 relative — true for any reassociation of an f32 dot.
ADC_TIE_BREAK = 1.0 - 2.0 ** -16

# Columns per packing group of the int4 layout (see :func:`pack_int4`):
# two 128-lane tiles, one per nibble.
PACK_GROUP = 256


def round_up(v: int, mult: int) -> int:
    """Round ``v`` up to a multiple of ``mult`` (block/tile padding helper)."""
    return ((v + mult - 1) // mult) * mult


def adc_bound(w_eff: jax.Array, beta: jax.Array, lam: float) -> jax.Array:
    """Per-column ADC bound of eq. (2): ``lam * beta * max|W[:, i]|``.

    Shared between the unfused path (``core.analog``), the fused dispatch
    layer and the oracles — one definition so the parity suite compares the
    same quantizer. ``w_eff`` is the effective weight matrix the MVM actually
    executes (noise-free for the analog training bound, RTN-dequantized for
    digital deployment). Reduces over ``axis=0`` (per output column / ADC).
    """
    col_max = jnp.max(jnp.abs(w_eff.astype(jnp.float32)), axis=0)
    return lam * beta.astype(jnp.float32) * col_max


def analog_matmul_ref(x: jax.Array, w_eff: jax.Array, beta: jax.Array,
                      bound: jax.Array, col_off: jax.Array | None = None, *,
                      in_bits: int = 8, out_bits: int = 8) -> jax.Array:
    """Oracle for the fused analog MVM.

    x       [M, K]   activations (any float dtype; computed in f32)
    w_eff   [K, N]   effective (already noise-perturbed) weights
    beta    scalar   static input range (eq. 1)
    bound   [N]      per-column ADC bound = lambda_adc * beta * max|W[:,i]| (eq. 2)
    col_off [N]      optional per-column absolute offset added to the f32
                     accumulator before ADC quant (the drifted periphery
                     offset of ``core.devices``; ``None`` = no offset)

    Quantizers are formulated reciprocal-free — ``round(v * (q/range))``
    rather than ``round(v / scale)`` — matching ``core.quant`` and the fused
    kernel bit-for-bit (see the note in ``quant.input_quantize``).
    """
    xf = x.astype(jnp.float32)
    qi = _qmax(in_bits)
    beta = jnp.maximum(beta.astype(jnp.float32), 1e-8)
    x_q = (beta / qi) * jnp.round(jnp.clip(xf, -beta, beta) * (qi / beta))

    y = jnp.matmul(x_q, w_eff.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    if col_off is not None:
        y = y + col_off.astype(jnp.float32)[None, :]

    qo = _qmax(out_bits)
    b = jnp.maximum(bound.astype(jnp.float32), 1e-8)[None, :]
    inv = (qo / b) * ADC_TIE_BREAK
    y_q = jnp.clip((b / qo) * jnp.round(y * inv), -b, b)
    return y_q.astype(x.dtype)


def int4_matmul_ref(x: jax.Array, w_packed: jax.Array, scale: jax.Array
                    ) -> jax.Array:
    """Oracle for the packed-int4 digital deployment matmul.

    w_packed [K, ceil(N/256)*128] uint8 in the :func:`pack_int4` layout.
    scale    [N] per-output-channel dequant scales (N sets the width).
    """
    w = unpack_int4(w_packed)[:, :scale.shape[-1]]
    w = w.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]
    y = jnp.matmul(x.astype(jnp.float32), w, preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def pack_int4(w_int: jax.Array) -> jax.Array:
    """Pack int8-carrier int4 values ([-7,7], [K, N]) to [K, ceil(N/256)*128].

    Columns go in groups of :data:`PACK_GROUP` = 256 (N is zero-padded to
    a whole group). In group ``g``, byte ``128 g + j`` holds column
    ``256 g + j`` in its low nibble and column ``256 g + 128 + j`` in its
    high nibble, each stored as ``int4 + 8``: the low nibbles of a group
    unpack to one whole 128-lane tile and the high nibbles to the next,
    so the kernel unpacks without interleaving lanes.
    """
    k, n = w_int.shape
    npad = round_up(n, PACK_GROUP) - n
    u = (jnp.pad(w_int.astype(jnp.int32), ((0, 0), (0, npad))) + 8
         ).astype(jnp.uint8).reshape(k, -1, 2, PACK_GROUP // 2)
    return (u[:, :, 0] | (u[:, :, 1] << 4)).reshape(k, -1)


def unpack_int4(w_packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`: [K, G*128] uint8 → [K, G*256] int32
    (padded columns included; callers slice to N)."""
    k = w_packed.shape[0]
    u = w_packed.astype(jnp.int32).reshape(k, -1, 1, PACK_GROUP // 2)
    return (jnp.concatenate([u & 0x0F, u >> 4], axis=2) - 8).reshape(k, -1)


def paged_decode_ref(q: jax.Array, kp: jax.Array, vp: jax.Array,
                     tbl: jax.Array, pos: jax.Array, start: jax.Array,
                     scale: float, k_scale: jax.Array | None = None,
                     v_scale: jax.Array | None = None) -> jax.Array:
    """Oracle for the paged flash-decode attention kernel.

    One single-token GQA attention step per batch row against a block-paged
    KV pool, with the online-softmax block loop the Pallas kernel uses:

    q        [B, H, hd]       current-token queries (H = KV * group)
    kp, vp   [P, bs, KV, hd]  physical KV block pool (fp, or int8 + scales)
    tbl      [B, NB]          per-slot block table (logical → physical)
    pos      [B]              logical index of the current token (inclusive)
    start    [B]              first valid logical index (left-pad count)
    k_scale, v_scale [P, bs, KV]  per-token/head dequant scales (int8 pool)

    Row ``b`` attends logical positions ``start[b] <= j <= pos[b]`` only.
    The block loop is a ``lax.scan`` whose step body sits behind a
    ``lax.cond`` on block liveness, so dead blocks (before ``start`` or
    after ``pos``) are *skipped at runtime*, not just masked — decode cost
    scales with live tokens, which is the whole point of the paged layout
    (and what ``benchmarks/attn_bench.py`` measures). Rows are processed
    with ``lax.map`` (scan, not vmap) to keep the conds real branches.
    """
    bsz, nq, hd = q.shape
    nb = tbl.shape[1]
    bs, nkv = kp.shape[1], kp.shape[2]
    group = nq // nkv

    def one_row(args):
        qb, tb, pb, sb = args                         # [H,hd], [NB], (), ()
        qg = qb.reshape(nkv, group, hd).astype(jnp.float32)
        first, last = sb // bs, pb // bs

        def blk_step(carry, j):
            def compute(c):
                m, l, acc = c
                phys = tb[j]
                k_blk = kp[phys].astype(jnp.float32)  # [bs, KV, hd]
                v_blk = vp[phys].astype(jnp.float32)
                if k_scale is not None:
                    k_blk = k_blk * k_scale[phys][..., None]
                    v_blk = v_blk * v_scale[phys][..., None]
                jpos = j * bs + jnp.arange(bs)
                valid = (jpos >= sb) & (jpos <= pb)   # [bs]
                logits = jnp.einsum("ngh,snh->ngs", qg,
                                    k_blk) * scale    # [KV, group, bs]
                logits = jnp.where(valid[None, None, :], logits, -1e30)
                m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
                p = jnp.exp(logits - m_new[..., None])
                p = jnp.where(valid[None, None, :], p, 0.0)
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "ngs,snh->ngh", p, v_blk)
                return m_new, l_new, acc_new

            live = (j >= first) & (j <= last)
            return jax.lax.cond(live, compute, lambda c: c, carry), None

        m0 = jnp.full((nkv, group), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((nkv, group), jnp.float32)
        a0 = jnp.zeros((nkv, group, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(blk_step, (m0, l0, a0), jnp.arange(nb))
        return (acc / jnp.maximum(l, 1e-30)[..., None]).reshape(nq, hd)

    out = jax.lax.map(one_row, (q, tbl, pos, start))
    return out.astype(q.dtype)


def paged_prefill_ref(q: jax.Array, kp: jax.Array, vp: jax.Array,
                      tbl: jax.Array, pos: jax.Array, start: jax.Array,
                      scale: float, k_scale: jax.Array | None = None,
                      v_scale: jax.Array | None = None) -> jax.Array:
    """Oracle for the paged flash-prefill attention kernel.

    One query *chunk* of GQA attention per batch row against the block-paged
    KV pool, with the same online-softmax block loop the Pallas kernel uses:

    q        [B, S, H, hd]     chunk queries (H = KV * group); column ``i``
                               of row ``b`` sits at logical position
                               ``pos[b] + i``
    kp, vp   [P, bs, KV, hd]   physical KV block pool (fp, or int8 + scales)
    tbl      [B, NB]           per-slot block table (logical → physical)
    pos      [B]               logical position of the chunk's first column
                               (the slot's pre-chunk write cursor; the
                               chunk's own K/V are already in the pool)
    start    [B]               first valid logical index (left-pad count)
    k_scale, v_scale [P, bs, KV]  per-token/head dequant scales (int8 pool)

    Row ``b``'s column ``i`` attends ``start[b] <= j <= pos[b] + i`` only —
    the causal window against per-row cursors. The block loop is a
    ``lax.scan`` whose step body sits behind a ``lax.cond`` on block
    liveness, so blocks before ``start`` or after the chunk's last column
    are *skipped at runtime*: prefill attention cost scales with live
    tokens on CPU too (the win ``benchmarks/attn_bench.py`` measures
    against the gathered-logical-view dense path). Rows go through
    ``lax.map`` to keep the conds real branches.
    """
    bsz, s, nq, hd = q.shape
    nb = tbl.shape[1]
    bs, nkv = kp.shape[1], kp.shape[2]
    group = nq // nkv

    def one_row(args):
        qb, tb, pb, sb = args                     # [S,H,hd], [NB], (), ()
        qg = jnp.swapaxes(qb.reshape(s, nkv, group, hd), 0, 1
                          ).astype(jnp.float32)   # [KV, S, group, hd]
        first, last = sb // bs, (pb + s - 1) // bs

        def blk_step(carry, j):
            def compute(c):
                m, l, acc = c
                phys = tb[j]
                k_blk = kp[phys].astype(jnp.float32)  # [bs, KV, hd]
                v_blk = vp[phys].astype(jnp.float32)
                if k_scale is not None:
                    k_blk = k_blk * k_scale[phys][..., None]
                    v_blk = v_blk * v_scale[phys][..., None]
                jpos = j * bs + jnp.arange(bs)
                qpos = pb + jnp.arange(s)
                valid = ((jpos[None, :] >= sb)
                         & (jpos[None, :] <= qpos[:, None]))      # [S, bs]
                logits = jnp.einsum("nsgh,tnh->nsgt", qg,
                                    k_blk) * scale  # [KV, S, group, bs]
                logits = jnp.where(valid[None, :, None, :], logits, -1e30)
                m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
                p = jnp.exp(logits - m_new[..., None])
                p = jnp.where(valid[None, :, None, :], p, 0.0)
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "nsgt,tnh->nsgh", p, v_blk)
                return m_new, l_new, acc_new

            live = (j >= first) & (j <= last)
            return jax.lax.cond(live, compute, lambda c: c, carry), None

        m0 = jnp.full((nkv, s, group), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((nkv, s, group), jnp.float32)
        a0 = jnp.zeros((nkv, s, group, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(blk_step, (m0, l0, a0), jnp.arange(nb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.swapaxes(out, 0, 1).reshape(s, nq, hd)

    out = jax.lax.map(one_row, (q, tbl, pos, start))
    return out.astype(q.dtype)


def ssd_ref(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
            c: jax.Array, h0: jax.Array | None = None) -> jax.Array:
    """Naive sequential Mamba-2 SSD recurrence (the slow-but-sure oracle).

    x  [BH, S, P]  inputs (head-split)
    dt [BH, S]     positive timestep
    a  [BH]        negative per-head decay rate (A)
    b  [BH, S, N]  input gate (already broadcast from groups to heads)
    c  [BH, S, N]  output gate
    h0 [BH, N, P]  optional initial state
    returns y [BH, S, P] (and matches the chunked kernel exactly in f32)
    """
    bh, s, p = x.shape
    n = b.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((bh, n, p), jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp          # [BH,P], [BH], [BH,N], [BH,N]
        decay = jnp.exp(dtt * a)       # [BH]
        h = decay[:, None, None] * h + (dtt[:, None] * bt)[:, :, None] * xt[:, None, :]
        yt = jnp.einsum("zn,znp->zp", ct, h)
        return h, yt

    xs = (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
          jnp.moveaxis(b.astype(jnp.float32), 1, 0),
          jnp.moveaxis(c.astype(jnp.float32), 1, 0))
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)
