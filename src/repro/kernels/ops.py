"""Public jit'd wrappers around the Pallas kernels.

These handle batch-dim flattening, dtype plumbing and the CPU/TPU switch:
on the CPU container the kernels run in ``interpret=True`` mode (functional
validation); on TPU (the target) they compile to Mosaic. Without
``force_kernel`` the CPU path is the pure-jnp oracle (``*_ref``) so XLA's
fusion and cost-analysis see ordinary HLO. The model forward itself routes
through ``repro.kernels.dispatch`` instead (via ``analog_linear`` when
``AnalogConfig.use_pallas`` is set), which always executes the kernels —
interpret-mode on CPU — so the deployed path is what gets tested.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch as _dispatch
from repro.kernels import ref as _ref
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan

_flatten_batch = _dispatch.flatten_batch

# SSD chunk length: the kernel's [L, L] intra-chunk matmul is one MXU tile
SSD_CHUNK = 128


def analog_matmul(x: jax.Array, w_eff: jax.Array, beta: jax.Array,
                  bound: jax.Array, *, in_bits: int = 8, out_bits: int = 8,
                  force_kernel: bool = False) -> jax.Array:
    """Fused DAC-quant → MVM → ADC-quant over arbitrary leading batch dims."""
    if _dispatch.on_tpu() or force_kernel:
        return _dispatch.analog_mvm(x, w_eff, beta, bound,
                                    in_bits=in_bits, out_bits=out_bits)
    x2, lead = _flatten_batch(x)
    y = _ref.analog_matmul_ref(x2, w_eff, beta, bound,
                               in_bits=in_bits, out_bits=out_bits)
    return y.reshape(*lead, w_eff.shape[-1])


def int4_matmul(x: jax.Array, w_packed: jax.Array, scale: jax.Array, *,
                force_kernel: bool = False) -> jax.Array:
    """Packed-int4 weight matmul over arbitrary leading batch dims."""
    x2, lead = _flatten_batch(x)
    if _dispatch.on_tpu() or force_kernel:
        from repro.kernels.int4_matmul import int4_matmul as _kernel
        m, kdim = x2.shape
        bm, bn, bk = _dispatch.select_blocks(m, kdim, scale.shape[-1])
        y = _kernel(x2, w_packed, scale, bm=bm, bn=bn, bk=bk,
                    interpret=not _dispatch.on_tpu())
    else:
        y = _ref.int4_matmul_ref(x2, w_packed, scale)
    return y.reshape(*lead, scale.shape[-1])


def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
        c: jax.Array, *, chunk: int = SSD_CHUNK,
        force_kernel: bool = False) -> jax.Array:
    """Mamba-2 SSD over [B, S, H, P] inputs with [B, S, G, N] gates.

    Broadcasts B/C groups to heads, flattens (B, H) and dispatches by
    :func:`ssd_uses_kernel` to the chunked Pallas kernel or to
    :func:`ssd_chunked_jnp`, a chunked jnp implementation of the same math
    — both are tested against the sequential oracle.
    """
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    b_h = jnp.repeat(b, rep, axis=2)
    c_h = jnp.repeat(c, rep, axis=2)

    def to_bh(t):
        return jnp.moveaxis(t, 2, 1).reshape(bsz * h, s, *t.shape[3:])

    x_f, b_f, c_f = to_bh(x), to_bh(b_h), to_bh(c_h)
    dt_f = jnp.moveaxis(dt, 2, 1).reshape(bsz * h, s)
    a_f = jnp.tile(a, bsz)

    if ssd_uses_kernel(s, chunk, force_kernel):
        y = _ssd_scan(x_f, dt_f, a_f, b_f, c_f, chunk=chunk,
                      interpret=not _dispatch.on_tpu())
    else:
        y = ssd_chunked_jnp(x_f, dt_f, a_f, b_f, c_f,
                            chunk=min(chunk, s) if s % chunk else chunk)
    y = y.reshape(bsz, h, s, p)
    return jnp.moveaxis(y, 1, 2)


def ssd_uses_kernel(s: int, chunk: int, force_kernel: bool = False) -> bool:
    """The shape rule of :func:`ssd`: the Pallas kernel runs (compiled on
    TPU, interpreted under ``force_kernel``) only when the sequence is
    whole chunks, ``s % chunk == 0`` — long prefills and training. Any
    other length runs :func:`ssd_chunked_jnp`, which pads to a chunk: on
    TPU that includes every serving prefill chunk (8-16 tokens against
    the 128-token SSD chunk), so SSM serving on the chip runs the jnp
    path by this rule, not by a fallback."""
    return (_dispatch.on_tpu() or force_kernel) and s % chunk == 0


def ssd_chunked_jnp(x, dt, a, b, c, *, chunk: int = 128):
    """Chunk-parallel SSD in pure jnp (same math as the Pallas kernel; used on
    CPU and as the lowering the dry-run sees — intra-chunk matmuls dominate
    its FLOPs exactly like the kernel's MXU work)."""
    bh, s, p = x.shape
    n = b.shape[-1]
    if s % chunk:
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    sc = x.shape[1] // chunk

    xf = x.reshape(bh, sc, chunk, p).astype(jnp.float32)
    dtf = dt.reshape(bh, sc, chunk).astype(jnp.float32)
    bf = b.reshape(bh, sc, chunk, n).astype(jnp.float32)
    cf = c.reshape(bh, sc, chunk, n).astype(jnp.float32)

    la = dtf * a[:, None, None]
    cums = jnp.cumsum(la, axis=-1)                        # [bh, sc, L]
    rel = cums[..., :, None] - cums[..., None, :]
    mask = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
    decay = jnp.exp(jnp.minimum(rel, 0.0)) * mask   # see ssd_scan.py: NaN guard
    gates = jnp.einsum("zctn,zcrn->zctr", cf, bf)
    y_intra = jnp.einsum("zctr,zcrp->zctp", gates * decay,
                         dtf[..., None] * xf)

    # inter-chunk state recurrence (scan over chunks)
    total = cums[..., -1]                                  # [bh, sc]
    w_r = jnp.exp(total[..., None] - cums) * dtf           # [bh, sc, L]
    states = jnp.einsum("zcrn,zcrp->zcnp", bf * w_r[..., None], xf)

    def chunk_step(h, inp):
        st, tot = inp
        h_new = jnp.exp(tot)[:, None, None] * h + st
        return h_new, h

    init = jnp.zeros((bh, n, p), jnp.float32)
    _, h_ins = jax.lax.scan(chunk_step,
                            init,
                            (jnp.moveaxis(states, 1, 0),
                             jnp.moveaxis(total, 1, 0)))
    h_ins = jnp.moveaxis(h_ins, 0, 1)                      # state entering chunk
    y_inter = jnp.exp(cums)[..., None] * jnp.einsum(
        "zctn,zcnp->zctp", cf, h_ins)

    y = (y_intra + y_inter).reshape(bh, sc * chunk, p)
    return y[:, :s].astype(x.dtype)


def ssd_decode_step(h: jax.Array, x_t: jax.Array, dt_t: jax.Array,
                    a: jax.Array, b_t: jax.Array, c_t: jax.Array):
    """Single-token SSD recurrence for serving. h [BH,N,P] → (h', y [BH,P])."""
    decay = jnp.exp(dt_t * a)
    h = decay[:, None, None] * h + (dt_t[:, None] * b_t)[:, :, None] * x_t[:, None, :]
    y = jnp.einsum("zn,znp->zp", c_t, h)
    return h, y.astype(x_t.dtype)
