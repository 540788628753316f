"""Paged flash-decode attention Pallas TPU kernel.

One decode step of GQA attention per batch row against a **block-paged KV
pool**: physical blocks of ``block_size`` tokens live in one pool tensor
(``[P, bs, KV, hd]``), a per-slot block table maps logical cache positions
to physical blocks, and per-slot ``pos``/``start`` cursors bound the live
range. The kernel visits KV blocks with an online softmax (flash-decode),
so nothing of size ``[B, T]`` is ever materialized, and — the actual perf
point — each row only *reads* its ``ceil((pos - start)/bs)`` live blocks:

* the block-table lookup happens in the BlockSpec index map (scalar
  prefetch), so Pallas's pipeline fetches physical blocks straight from the
  pool — no host-side gather of the logical view;
* dead grid steps (blocks before ``start`` or after ``pos``) clamp their
  index map to the nearest live block — consecutive identical indices make
  the pipeline skip the re-fetch — and skip all compute via ``pl.when``;
* a split-K grid dimension (``num_splits``) partitions long contexts into
  independent partial reductions (unnormalized acc + m/l statistics per
  split) merged by one tiny jnp pass — the classic 2-pass flash-decode
  shape for decode batches too small to fill the chip with rows alone.

The int8-quantized pool (``k_scale``/``v_scale`` per token/head row,
``core.quant.kv_quantize``) dequantizes in VMEM right after the block load,
halving-to-quartering the HBM bytes the decode step actually moves — on the
digital-side memory wall this is the dominant term (Rasch et al. 2023).

``kernels.ref.paged_decode_ref`` is the ground-truth ``lax.scan`` oracle;
``kernels.dispatch.paged_decode_attention`` routes between the two (kernel
on TPU, interpret-mode/oracle elsewhere). Grid iterates (rows, splits,
blocks-per-split) with the block dim innermost so the m/l/acc scratch
carries across exactly one split's blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _paged_decode_kernel(tbl_ref, pos_ref, start_ref, q_ref, k_ref, v_ref,
                         ks_ref, vs_ref, o_ref, m_ref, l_ref,
                         acc_scr, m_scr, l_scr, *, bs: int, nkv: int,
                         group: int, scale: float, nbs: int,
                         quantized: bool):
    """Tile body: online-softmax update for one (row, split, block) step.

    The block arrives as ``[bs * KV, hd]`` rows ordered (token, kv head),
    so every query head scores all of them in one ``[H, hd] x [hd, bs*KV]``
    MXU dot; columns of other kv heads are masked out of the softmax like
    dead positions. That keeps every operand a full 2-D tile — no
    lane-splitting reshape, which the TPU compiler refuses at hd < 128.
    """
    b, sidx, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq = nkv * group

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    blk = sidx * nbs + j
    p_b, s_b = pos_ref[b], start_ref[b]
    live = (blk >= s_b // bs) & (blk <= p_b // bs)

    @pl.when(live)
    def _compute():
        k_blk = k_ref[0].astype(jnp.float32)            # [bs*KV, hd]
        v_blk = v_ref[0].astype(jnp.float32)
        if quantized:
            k_blk = k_blk * ks_ref[0]                   # [bs*KV, 1] scales
            v_blk = v_blk * vs_ref[0]
        q = q_ref[0].astype(jnp.float32)                # [H, hd]

        logits = jax.lax.dot_general(
            q, k_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, bs*KV]

        col = jax.lax.broadcasted_iota(jnp.int32, (nq, bs * nkv), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (nq, bs * nkv), 0)
        jpos = blk * bs + col // nkv
        valid = ((col % nkv == head // group)
                 & (jpos >= s_b) & (jpos <= p_b))
        logits = jnp.where(valid, logits, -1e30)

        m_prev = m_scr[...]                              # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v_blk, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [H, hd]
        m_scr[...] = m_new

    @pl.when(j == nbs - 1)
    def _store():
        o_ref[0, 0] = acc_scr[...]
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


@functools.partial(
    jax.jit, static_argnames=("scale", "num_splits", "interpret"))
def paged_flash_decode(q: jax.Array, kp: jax.Array, vp: jax.Array,
                       tbl: jax.Array, pos: jax.Array, start: jax.Array, *,
                       scale: float, k_scale: jax.Array | None = None,
                       v_scale: jax.Array | None = None, num_splits: int = 1,
                       interpret: bool = False) -> jax.Array:
    """Paged flash-decode attention (see module docstring).

    q [B, H, hd], kp/vp [P, bs, KV, hd] (+ optional [P, bs, KV] scales for
    the int8 pool), tbl [B, NB], pos/start [B]. Returns [B, H, hd] in
    q.dtype. ``num_splits`` > 1 partitions the block loop into independent
    split-K partials merged in a second jnp pass.
    """
    bsz, nq, hd = q.shape
    npool, bs, nkv = kp.shape[:3]
    nb = tbl.shape[1]
    group = nq // nkv
    quantized = k_scale is not None
    nbs = -(-nb // num_splits)                   # blocks per split

    # free HBM views: one block = bs*KV rows of hd (token-major, then head)
    kp2 = kp.reshape(npool, bs * nkv, hd)
    vp2 = vp.reshape(npool, bs * nkv, hd)
    if quantized:
        ks2 = k_scale.reshape(npool, bs * nkv, 1).astype(jnp.float32)
        vs2 = v_scale.reshape(npool, bs * nkv, 1).astype(jnp.float32)
    else:  # dummy 1-block operands so the kernel signature is static
        ks2 = jnp.zeros((1, bs * nkv, 1), jnp.float32)
        vs2 = jnp.zeros((1, bs * nkv, 1), jnp.float32)

    def _phys(b, s, j, tbl_ref, pos_ref, start_ref):
        # Dead steps clamp to the nearest live block: consecutive identical
        # block indices let the pipeline skip the redundant fetch.
        blk = s * nbs + j
        jj = jnp.clip(blk, start_ref[b] // bs, pos_ref[b] // bs)
        return tbl_ref[b, jj]

    # every block's last two dims are the array's own (TPU tiling rule)
    kv_spec = pl.BlockSpec(
        (1, bs * nkv, hd), lambda b, s, j, *pf: (_phys(b, s, j, *pf), 0, 0))
    sc_spec = pl.BlockSpec(
        (1, bs * nkv, 1),
        (lambda b, s, j, *pf: (_phys(b, s, j, *pf), 0, 0)) if quantized
        else (lambda b, s, j, *pf: (0, 0, 0)))

    def part(last):      # one (row, split) partial: [H, last]
        return pl.BlockSpec((1, 1, nq, last),
                            lambda b, s, j, *pf: (b, s, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, num_splits, nbs),
        in_specs=[
            pl.BlockSpec((1, nq, hd), lambda b, s, j, *pf: (b, 0, 0)),  # q
            kv_spec, kv_spec, sc_spec, sc_spec,
        ],
        out_specs=[part(hd), part(1), part(1)],
        scratch_shapes=[
            pltpu.VMEM((nq, hd), jnp.float32),       # acc
            pltpu.VMEM((nq, 1), jnp.float32),        # m
            pltpu.VMEM((nq, 1), jnp.float32),        # l
        ],
    )

    o_part, m_part, l_part = pl.pallas_call(
        functools.partial(_paged_decode_kernel, bs=bs, nkv=nkv, group=group,
                          scale=scale, nbs=nbs, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, num_splits, nq, hd), jnp.float32),
            jax.ShapeDtypeStruct((bsz, num_splits, nq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bsz, num_splits, nq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(tbl, pos, start, q, kp2, vp2, ks2, vs2)

    return merge_splits(o_part, m_part[..., 0],
                        l_part[..., 0]).astype(q.dtype)


def merge_splits(o_part: jax.Array, m_part: jax.Array,
                 l_part: jax.Array) -> jax.Array:
    """2nd pass of the split-K reduction: combine per-split flash partials.

    o_part [B, NS, H, hd] unnormalized accumulators, m_part/l_part
    [B, NS, H] running max / sum-of-exponentials. Dead splits carry
    ``m = -inf, l = 0, acc = 0`` and drop out via ``exp(-inf - M) = 0``
    (at least one split is always live — the current token attends itself).
    """
    m_tot = jnp.max(m_part, axis=1)                        # [B, H]
    w = jnp.exp(m_part - m_tot[:, None])                   # [B, NS, H]
    l_tot = jnp.sum(l_part * w, axis=1)                    # [B, H]
    o = jnp.sum(o_part * w[..., None], axis=1)             # [B, H, hd]
    return o / jnp.maximum(l_tot, 1e-30)[..., None]
