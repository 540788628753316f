"""Packed-int4 weight × float activation matmul (digital deployment path).

Table 3 of the paper deploys analog foundation models on 4-bit digital
hardware via per-channel RTN. This kernel keeps the weights packed two-per-
byte in HBM (halving weight bandwidth — the dominant term for decode shapes)
and dequantizes in VMEM right before the MXU: unpack nibbles → subtract the
+8 offset → scale by the per-column f32 scale.

Packing layout (``kernels.ref.pack_int4``): columns are packed in groups
of ``PACK_GROUP`` = 256; within group ``g``, byte ``[k, 128 g + j]`` holds
column ``256 g + j`` in its low nibble and column ``256 g + 128 + j`` in
its high nibble. A group's low nibbles thus unpack to one full 128-lane
tile and its high nibbles to the next, so the kernel never interleaves
lanes (an interleave needs a relayout the TPU compiler runs out of VMEM
on). N is zero-padded up to a whole group. Nibbles store ``int4 + 8``
with int4 ∈ [-7, 7] (symmetric RTN never produces -8); the nibble
arithmetic runs in int32, since the TPU has no 8-bit vector shifts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import PACK_GROUP
from repro.kernels.ref import round_up as _rup


def _int4_matmul_kernel(x_ref, wp_ref, scale_ref, o_ref, acc_ref,
                        *, k_steps: int):
    """Pallas tile body: unpack int4 nibbles in VMEM, dequant, accumulate."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # unpack [bk, bn//2] uint8 -> [bk, bn] f32, one 128-lane tile per half
    # of each packing group (see the module docstring)
    wp = wp_ref[...].astype(jnp.int32)
    half = PACK_GROUP // 2
    tiles = []
    for g in range(wp.shape[1] // half):
        byte = wp[:, g * half:(g + 1) * half]
        tiles += [(byte & 0x0F) - 8, (byte >> 4) - 8]
    w = jnp.concatenate(tiles, axis=1).astype(jnp.float32)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] *
                      scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def int4_matmul(x: jax.Array, w_packed: jax.Array, scale: jax.Array, *,
                bm: int = 256, bn: int = 256, bk: int = 512,
                interpret: bool = False) -> jax.Array:
    """``y = x @ dequant(w_packed, scale)`` with in-VMEM int4 unpacking.

    x [M, K], w_packed [K, ceil(N/256)*128] uint8 (``ref.pack_int4``),
    scale [N]. Returns [M, N] in x.dtype. ``bn`` must be a multiple of
    ``PACK_GROUP``.
    """
    assert bn % PACK_GROUP == 0, (bn, PACK_GROUP)
    m, kdim = x.shape
    k2, nh = w_packed.shape
    n = scale.shape[-1]
    assert kdim == k2 and nh == _rup(n, PACK_GROUP) // 2, (
        x.shape, w_packed.shape, scale.shape)
    bm_ = min(bm, _rup(m, 8))
    bn_ = min(bn, _rup(n, PACK_GROUP))
    bk_ = min(bk, _rup(kdim, 128))

    mp, np_, kp = _rup(m, bm_), _rup(n, bn_), _rup(kdim, bk_)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - kdim)))
    # 0x88 packs two zero int4s (0 + 8 = 0x8 per nibble)
    wp = jnp.pad(w_packed, ((0, kp - kdim), (0, np_ // 2 - nh)),
                 constant_values=0x88)
    sp = jnp.pad(scale.reshape(1, -1), ((0, 0), (0, np_ - n)))

    k_steps = kp // bk_
    out = pl.pallas_call(
        functools.partial(_int4_matmul_kernel, k_steps=k_steps),
        grid=(mp // bm_, np_ // bn_, k_steps),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk_, bn_ // 2), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        interpret=interpret,
    )(xp, wp, sp)
    return out[:m, :n]
