"""Paged flash-prefill attention Pallas TPU kernel.

One *query chunk* of GQA attention per batch row against the same
block-paged KV pool the flash-decode kernel reads (physical blocks of
``block_size`` tokens in one ``[P, bs, KV, hd]`` pool tensor, per-slot block
table, per-slot ``pos``/``start`` cursors). This is the prefill half of the
paged attention story: the serving engine's chunked prefill scatter-writes a
``[B, S]`` token chunk into the pool and then scores it here — **in place**
— instead of gathering each slot's logical view back out of the pool on the
host (the per-chunk ``pool[tbl]`` gather + dense ``[S, max_len]`` softmax
that made paged prefill slower than the contiguous layout in PR 3's
``BENCH_serve.json``).

Shape/masking contract (mirrors ``layers._paged_slot_attention``):

* ``q [B, S, H, hd]`` — the current chunk's queries; query column ``i`` of
  row ``b`` sits at logical cache position ``pos[b] + i`` (``pos`` is the
  slot's write cursor *before* the chunk — the chunk's own K/V have already
  been scattered into the pool when the kernel runs);
* row ``b``'s column ``i`` attends logical positions
  ``start[b] <= j <= pos[b] + i`` only — the causal window against per-row
  cursors, so left-pad positions (``j < start``) and future in-chunk tokens
  are never read;
* the grid visits KV blocks with an online softmax (flash forward): blocks
  before ``start[b] // bs`` or after ``(pos[b] + S - 1) // bs`` clamp their
  scalar-prefetch index map to the nearest live block (consecutive identical
  physical indices make the pipeline skip the re-fetch) and skip all compute
  via ``pl.when`` — prefill attention cost scales with the slot's *live*
  tokens, not ``max_len``;
* the int8 pool (``k_scale``/``v_scale`` per token/head row) dequantizes in
  VMEM right after the block load, exactly like the decode kernel.

``kernels.ref.paged_prefill_ref`` is the ground-truth ``lax.scan`` oracle
(same block-loop accumulation order, so interpret-mode parity is tight);
``kernels.dispatch.paged_prefill_attention`` routes between the two. No
split-K dimension: a chunk already gives each row ``S * H`` independent
softmax lanes, so rows alone fill the chip at serving batch sizes.

Write-protection contract (prefix caching, PR 5): this kernel only ever
*reads* the pool — the chunk's K/V were scattered by the caller
(``layers._paged_slot_attention``) before it runs, and that scatter
resolves physical blocks through the per-slot *write* table ``wtbl``,
not the read table ``tbl`` this kernel consumes. When the scheduler maps
a slot onto shared prefix-hit blocks it points their ``wtbl`` entries at
the reserved sink block, so a chunk re-scoring a cached region (its
per-row ``pos`` cursor starts past the hit; the re-run region's rewrites
are bitwise-identical and safely dropped) can never corrupt blocks other
slots read — mirroring the PR 4 fully-masked-row sink-redirect contract.
The kernel needs no change for prefix caching precisely because its
``pos``/``start`` cursors already score chunks at arbitrary offsets
against arbitrary block mappings.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _paged_prefill_kernel(tbl_ref, pos_ref, start_ref, q_ref, k_ref, v_ref,
                          ks_ref, vs_ref, o_ref, acc_scr, m_scr, l_scr, *,
                          bs: int, nkv: int, group: int, s: int,
                          scale: float, nb: int, quantized: bool):
    """Tile body: online-softmax update for one (row, block) step.

    The chunk's queries arrive as ``[S * H, hd]`` rows ordered (column,
    head) and the block as ``[bs * KV, hd]`` rows ordered (token, kv
    head): one MXU dot scores every query row against every block row,
    and the mask drops pairs whose kv head does not serve the query head,
    beside the causal window. Every operand stays a full 2-D tile — no
    lane-splitting reshape, which the TPU compiler refuses at hd < 128.
    """
    b, j = pl.program_id(0), pl.program_id(1)
    nq = nkv * group

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    p_b, s_b = pos_ref[b], start_ref[b]
    live = (j >= s_b // bs) & (j <= (p_b + s - 1) // bs)

    @pl.when(live)
    def _compute():
        k_blk = k_ref[0].astype(jnp.float32)            # [bs*KV, hd]
        v_blk = v_ref[0].astype(jnp.float32)
        if quantized:
            k_blk = k_blk * ks_ref[0]                   # [bs*KV, 1] scales
            v_blk = v_blk * vs_ref[0]
        q = q_ref[0].astype(jnp.float32)                # [S*H, hd]

        logits = jax.lax.dot_general(
            q, k_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [S*H, bs*KV]

        # causal window against the per-row cursors: query column i (at
        # logical pos p_b + i) sees KV positions start <= jpos <= p_b + i
        col = jax.lax.broadcasted_iota(jnp.int32, (s * nq, bs * nkv), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (s * nq, bs * nkv), 0)
        jpos = j * bs + col // nkv
        qpos = p_b + row // nq
        valid = ((col % nkv == (row % nq) // group)
                 & (jpos >= s_b) & (jpos <= qpos))
        logits = jnp.where(valid, logits, -1e30)

        m_prev = m_scr[...]                              # [S*H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v_blk, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [S*H, hd]
        m_scr[...] = m_new

    @pl.when(j == nb - 1)
    def _store():
        o_ref[0] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_flash_prefill(q: jax.Array, kp: jax.Array, vp: jax.Array,
                        tbl: jax.Array, pos: jax.Array, start: jax.Array, *,
                        scale: float, k_scale: jax.Array | None = None,
                        v_scale: jax.Array | None = None,
                        interpret: bool = False) -> jax.Array:
    """Paged flash-prefill attention (see module docstring).

    q [B, S, H, hd], kp/vp [P, bs, KV, hd] (+ optional [P, bs, KV] scales
    for the int8 pool), tbl [B, NB], pos/start [B]. ``pos[b]`` is the
    logical position of row ``b``'s *first* query column (the pre-chunk
    write cursor). Returns [B, S, H, hd] in q.dtype.
    """
    bsz, s, nq, hd = q.shape
    npool, bs, nkv = kp.shape[:3]
    nb = tbl.shape[1]
    group = nq // nkv
    quantized = k_scale is not None

    # free HBM views: rows (column, head) of q, (token, kv head) of a block
    q2 = q.reshape(bsz, s * nq, hd)
    kp2 = kp.reshape(npool, bs * nkv, hd)
    vp2 = vp.reshape(npool, bs * nkv, hd)
    if quantized:
        ks2 = k_scale.reshape(npool, bs * nkv, 1).astype(jnp.float32)
        vs2 = v_scale.reshape(npool, bs * nkv, 1).astype(jnp.float32)
    else:  # dummy 1-block operands so the kernel signature is static
        ks2 = jnp.zeros((1, bs * nkv, 1), jnp.float32)
        vs2 = jnp.zeros((1, bs * nkv, 1), jnp.float32)

    def _phys(b, j, tbl_ref, pos_ref, start_ref):
        # Dead steps clamp to the nearest live block: consecutive identical
        # block indices let the pipeline skip the redundant fetch.
        jj = jnp.clip(j, start_ref[b] // bs, (pos_ref[b] + s - 1) // bs)
        return tbl_ref[b, jj]

    # every block's last two dims are the array's own (TPU tiling rule)
    kv_spec = pl.BlockSpec(
        (1, bs * nkv, hd), lambda b, j, *pf: (_phys(b, j, *pf), 0, 0))
    sc_spec = pl.BlockSpec(
        (1, bs * nkv, 1),
        (lambda b, j, *pf: (_phys(b, j, *pf), 0, 0)) if quantized
        else (lambda b, j, *pf: (0, 0, 0)))
    row_spec = pl.BlockSpec((1, s * nq, hd), lambda b, j, *pf: (b, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, nb),
        in_specs=[row_spec, kv_spec, kv_spec, sc_spec, sc_spec],
        out_specs=[row_spec],
        scratch_shapes=[
            pltpu.VMEM((s * nq, hd), jnp.float32),       # acc
            pltpu.VMEM((s * nq, 1), jnp.float32),        # m
            pltpu.VMEM((s * nq, 1), jnp.float32),        # l
        ],
    )

    (o,) = pl.pallas_call(
        functools.partial(_paged_prefill_kernel, bs=bs, nkv=nkv, group=group,
                          s=s, scale=scale, nb=nb, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bsz, s * nq, hd), jnp.float32)],
        interpret=interpret,
    )(tbl, pos, start, q2, kp2, vp2, ks2, vs2)

    return o.reshape(bsz, s, nq, hd).astype(q.dtype)
