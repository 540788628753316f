"""Kernel-dispatch layer: routes ``analog_linear``'s MVM onto the fused
Pallas kernels when :attr:`AnalogConfig.use_pallas` is set.

Dispatch rules
--------------
* ``analog`` / ``rtn`` modes with output quantization → :func:`analog_mvm`
  (one AIMC tile op: DAC-quant → MVM → per-column ADC-quant, fused in
  ``analog_matmul``). The weight matrix handed in is the *effective* one —
  training-noise-perturbed for the analog training forward, RTN-dequantized
  for digital deployment — so the kernel stays deterministic and
  oracle-checkable.
* ``rtn`` serving with 4-bit weights (``AnalogConfig.int4_serve``) →
  :func:`int4_mvm`: weights packed two-per-byte, dequantized in VMEM right
  before the MXU (input/output quantization stay in the digital periphery).
* On CPU the kernels run in ``interpret=True`` mode, so the fused path is
  differentially testable everywhere; on TPU they compile to Mosaic.

Shape reconciliation: the kernels are 2-D ``[M, K] @ [K, N]``, while the
model paths hand ``[B, S, K]`` activations (flattened here), per-layer
slices of stacked ``[L, K, N]`` scan weights (already 2-D inside the scan
body) and decode-shape single-token steps. :func:`select_blocks` drops to
``bm = 8`` for ``M ≤ 8`` decode steps so the M grid stays dense instead of
padding a 256-row block for one token.

Autodiff: :func:`fused_analog_mvm` is a ``jax.custom_vjp`` — the *forward*
(eval, serve and the training forward pass) runs the fused kernel; the
*backward* replays the unfused STE chain of ``repro.core.analog`` /
``repro.core.quant`` exactly: ADC output-quant is pure pass-through,
the matmul differentiates against the noise-free weights
(``noisy_matmul``'s rule), and the DAC input-quant applies the
clamp-STE/LSQ range rules (``input_quantize``'s rule).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.analog_matmul import analog_matmul
from repro.kernels.int4_matmul import int4_matmul
from repro.kernels.paged_attention import paged_flash_decode
from repro.kernels.paged_prefill import paged_flash_prefill

# Default tile sizes (see analog_matmul.py for the VMEM budget math) and the
# decode-shape M block: single-token serving steps have M = batch ∈ [1, 8],
# and an 8-row block is the f32 sublane minimum — no wasted padding rows.
PREFILL_BLOCKS = (256, 256, 512)
DECODE_BM = 8

# Query rows (chunk columns x query heads) one paged flash-prefill call
# scores: the kernel holds their [rows, hd] queries, f32 accumulator and
# [rows, bs*KV] scores in VMEM at once. 4096 rows (a 128-token chunk at
# 32 heads, blocks of 16 x 8 kv heads) fit a v5e's 16 MiB scoped VMEM and
# 6144 do not (tests/test_chip_compile.py); longer chunks run in pieces.
PREFILL_MAX_ROWS = 4096


def on_tpu() -> bool:
    """True when the default JAX backend is TPU (compiled kernels)."""
    return jax.default_backend() == "tpu"


def use_fused(cfg) -> bool:
    """True when ``analog_linear`` should route through the fused tile op.

    The fused kernel *is* the DAC→MVM→ADC pipeline, so it only applies to
    the modes that quantize both ends (``analog``, ``rtn``) with
    ``output_quant`` on; other modes keep the unfused path regardless of
    ``use_pallas``.
    """
    return bool(cfg.use_pallas and cfg.output_quant
                and cfg.mode in ("analog", "rtn"))


def select_blocks(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Pick (bm, bn, bk) for an [M, K] @ [K, N] call.

    Decode steps (M ≤ 8) get ``bm = 8``; everything else uses the prefill
    tiles (the kernels themselves clamp blocks down to the padded problem
    size, so small K/N never over-allocate VMEM).
    """
    bm, bn, bk = PREFILL_BLOCKS
    if m <= DECODE_BM:
        bm = DECODE_BM
    return bm, bn, bk


def flatten_batch(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    """[..., K] → ([M, K], leading shape): the kernels are strictly 2-D."""
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


# ---------------------------------------------------------------------------
# fused analog MVM (eq. 1 → MVM → eq. 2)
# ---------------------------------------------------------------------------

def analog_mvm(x: jax.Array, w_eff: jax.Array, beta: jax.Array,
               bound: jax.Array, *, in_bits: int = 8, out_bits: int = 8,
               col_off: jax.Array | None = None,
               block_shape: tuple[int, int, int] | None = None) -> jax.Array:
    """Fused DAC-quant → MVM → ADC-quant over arbitrary leading batch dims.

    Always executes the Pallas kernel — compiled on TPU, ``interpret=True``
    elsewhere. ``col_off`` [N] is the optional per-column pre-ADC offset of
    the per-tile device path (``core.devices.corrupt_weights``). No
    autodiff rule; use :func:`fused_analog_mvm` on paths that can be
    differentiated.
    """
    x2, lead = flatten_batch(x)
    m, kdim = x2.shape
    n = w_eff.shape[-1]
    bm, bn, bk = block_shape or select_blocks(m, kdim, n)
    y = analog_matmul(x2, w_eff, beta, bound, col_off, in_bits=in_bits,
                      out_bits=out_bits, bm=bm, bn=bn, bk=bk,
                      interpret=not on_tpu())
    return y.reshape(*lead, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_analog_mvm(in_bits, out_bits, x, w, w_noise, beta, bound):
    """custom_vjp core: fused forward on the effective (noisy) weights."""
    return analog_mvm(x, w + w_noise, beta, bound,
                      in_bits=in_bits, out_bits=out_bits)


def _fused_fwd(in_bits, out_bits, x, w, w_noise, beta, bound):
    """Forward rule: run the fused kernel, save STE residuals."""
    y = _fused_analog_mvm(in_bits, out_bits, x, w, w_noise, beta, bound)
    return y, (x, w, beta, bound)


def _fused_bwd(in_bits, out_bits, res, g):
    """Backward rule: replay the unfused STE chain (see module doc)."""
    # Replays the unfused VJP chain through the *canonical* custom rules in
    # core (single source of truth: quant.input_quantize's clamp-STE/LSQ
    # gradients and analog.noisy_matmul's noise-free weight rule compose
    # here exactly as in the unfused path; output_quantize is pure STE so g
    # enters untouched). Imported lazily — core.analog imports this module.
    from repro.core import quant
    from repro.core.analog import noisy_matmul

    x, w, beta, bound = res
    wf = w.astype(jnp.float32)

    def unfused_pre_adc(x_, w_, beta_):
        x_q = quant.input_quantize(x_, beta_, in_bits)
        return noisy_matmul(x_q, w_, jnp.zeros_like(w_))

    _, vjp = jax.vjp(unfused_pre_adc, x.astype(jnp.float32), wf,
                     beta.astype(jnp.float32))
    dx, dw, dbeta = vjp(g.astype(jnp.float32))
    return (dx.astype(x.dtype), dw.astype(w.dtype), jnp.zeros_like(w),
            dbeta.astype(beta.dtype).reshape(beta.shape),
            jnp.zeros_like(bound))


_fused_analog_mvm.defvjp(_fused_fwd, _fused_bwd)


def fused_analog_mvm(x: jax.Array, w: jax.Array, w_noise: jax.Array,
                     beta: jax.Array, bound: jax.Array, *,
                     in_bits: int = 8, out_bits: int = 8) -> jax.Array:
    """Differentiable fused analog MVM: Pallas forward, unfused backward.

    ``w_noise`` is the training-noise sample (zeros at eval); the forward
    executes ``w + w_noise``, the backward sees noise-free ``w`` — the same
    contract as ``core.analog.noisy_matmul``.
    """
    return _fused_analog_mvm(int(in_bits), int(out_bits),
                             x, w, w_noise, beta, bound)


# ---------------------------------------------------------------------------
# packed-int4 digital serving MVM
# ---------------------------------------------------------------------------

def can_use_int4(out_dim: int, weight_bits: int) -> bool:
    """Packing is two nibbles per byte: needs 4-bit weights and even N."""
    return weight_bits == 4 and out_dim % 2 == 0


def int4_mvm_packed(x_q: jax.Array, w_packed: jax.Array, scale: jax.Array, *,
                    block_shape: tuple[int, int, int] | None = None
                    ) -> jax.Array:
    """``x_q @ dequant(unpack(w_packed), scale)`` via the packed-int4 kernel.

    ``x_q`` is already DAC-quantized (the digital periphery's job on this
    path); ``w_packed`` holds two int4 nibbles per byte in the
    ``ref.pack_int4`` layout [K, ceil(N/256)*128] — the format
    ``core.analog.pack_int4_weights`` precomputes once per deployment so
    decode reads weights at int4 bandwidth; ``scale`` the per-column
    dequant scales [N], which also fix the output width. Output
    quantization is applied by the caller. Eval/serve-only — no autodiff
    rule.
    """
    x2, lead = flatten_batch(x_q)
    m, kdim = x2.shape
    n = scale.shape[-1]
    bm, bn, bk = block_shape or select_blocks(m, kdim, n)
    y = int4_matmul(x2, w_packed, scale.reshape(-1), bm=bm, bn=bn, bk=bk,
                    interpret=not on_tpu())
    return y.reshape(*lead, n)


# ---------------------------------------------------------------------------
# paged flash-decode attention (serving decode hot path)
# ---------------------------------------------------------------------------

def paged_decode_attention(q: jax.Array, kp: jax.Array, vp: jax.Array,
                           tbl: jax.Array, pos: jax.Array, start: jax.Array,
                           scale: float, *, k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           num_splits: int = 1,
                           impl: str | None = None) -> jax.Array:
    """One paged GQA decode step: q [B, H, hd] vs a block-paged KV pool.

    Routing mirrors the MVM ops: on TPU the Pallas flash-decode kernel
    (``kernels.paged_attention``) compiles to Mosaic; elsewhere the
    ``lax.scan`` oracle (``ref.paged_decode_ref``) runs — its per-block
    ``lax.cond`` skips dead blocks at runtime, so active-length scaling
    holds on CPU too. ``impl`` overrides: ``"kernel"`` forces the Pallas
    kernel (interpret-mode off-TPU — how the parity suite exercises it),
    ``"ref"`` forces the oracle. ``num_splits`` > 1 enables the 2-pass
    split-K reduction for long contexts (kernel path only).
    """
    if impl is None:
        impl = "kernel" if on_tpu() else "ref"
    if impl == "kernel":
        return paged_flash_decode(q, kp, vp, tbl, pos, start, scale=scale,
                                  k_scale=k_scale, v_scale=v_scale,
                                  num_splits=num_splits,
                                  interpret=not on_tpu())
    return ref.paged_decode_ref(q, kp, vp, tbl, pos, start, scale,
                                k_scale=k_scale, v_scale=v_scale)


def paged_prefill_attention(q: jax.Array, kp: jax.Array, vp: jax.Array,
                            tbl: jax.Array, pos: jax.Array,
                            start: jax.Array, scale: float, *,
                            k_scale: jax.Array | None = None,
                            v_scale: jax.Array | None = None,
                            impl: str | None = None) -> jax.Array:
    """One paged GQA prefill chunk: q [B, S, H, hd] vs the block-paged pool.

    The prefill counterpart of :func:`paged_decode_attention`: the chunk's
    K/V have already been scattered into the pool; this scores the chunk's
    queries against each row's live blocks *in place* (no host-side gather
    of the logical view). Column ``i`` of row ``b`` attends logical
    positions ``start[b] <= j <= pos[b] + i``. Routing is identical to the
    decode op: Pallas kernel on TPU, ``lax.scan`` oracle elsewhere (its
    per-block ``lax.cond`` skips dead blocks at runtime, so active-length
    scaling holds on CPU too); ``impl`` = ``"kernel"`` / ``"ref"``
    overrides (interpret-mode off-TPU for the parity suite). The kernel
    scores a chunk of more than ``PREFILL_MAX_ROWS`` query rows as
    consecutive column pieces, each at its own ``pos`` — the same result
    bit for bit, since every query row's softmax is independent.
    """
    if impl is None:
        impl = "kernel" if on_tpu() else "ref"
    if impl == "kernel":
        s, step = q.shape[1], max(1, PREFILL_MAX_ROWS // q.shape[2])
        pieces = [(q, pos)] if s <= step else [
            (q[:, i:i + step], pos + i) for i in range(0, s, step)]
        out = [paged_flash_prefill(qi, kp, vp, tbl, pi, start, scale=scale,
                                   k_scale=k_scale, v_scale=v_scale,
                                   interpret=not on_tpu())
               for qi, pi in pieces]
        return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)
    return ref.paged_prefill_ref(q, kp, vp, tbl, pos, start, scale,
                                 k_scale=k_scale, v_scale=v_scale)


def int4_mvm(x_q: jax.Array, w_int: jax.Array, scale: jax.Array, *,
             block_shape: tuple[int, int, int] | None = None) -> jax.Array:
    """:func:`int4_mvm_packed` with on-the-fly packing of the int8-carrier
    RTN output ``w_int`` [K, N] (N even) — the functional fallback when the
    caller hasn't precomputed packed weights."""
    return int4_mvm_packed(x_q, ref.pack_int4(w_int), scale,
                           block_shape=block_shape)
