"""Chunked Mamba-2 SSD (state-space duality) scan as a Pallas TPU kernel.

Used by the ``mamba2-130m`` and ``jamba-v0.1-52b`` architectures. The SSD
insight (arXiv:2405.21060) is that the selective-SSM recurrence

    h_t = exp(dt_t·A) · h_{t-1} + dt_t · B_t xᵀ_t          y_t = C_t · h_t

decomposes over chunks of length L into (a) an *intra-chunk* quadratic form
``(C Bᵀ ⊙ decay-mask) X`` — a dense L×L matmul that maps onto the MXU — and
(b) an *inter-chunk* rank-N state recurrence carried sequentially. The GPU
implementation uses warp-level scans for (b); on TPU we instead make the
chunk axis the innermost (sequential) grid dimension and carry the (N, P)
state in VMEM scratch across grid steps — grid-carried scratch is the
TPU-idiomatic substitute for persistent-CTA state.

Shapes (heads pre-flattened, B/C pre-broadcast from groups to heads):
    x [BH, S, P], dt [BH, S], a [BH] (negative), b/c [BH, S, N]
Grid: (BH, S/L); scratch state [N, P] f32, reset at chunk 0. The kernel
runs at ``S % L == 0`` (``kernels.ops.ssd`` chooses it by shape).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref,
                *, chunk: int):
    """Pallas body: chunked SSD recurrence for one (batch·head) block.

    ``dt`` arrives as a ``[1, L]`` row; its column form is a transpose.
    Mosaic has no cumsum, so the inclusive prefix sum is a dot against
    an upper-triangular ones matrix at full f32 precision.
    """
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _reset():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)           # [L, P]
    dt_row = dt_ref[0].astype(jnp.float32)     # [1, L]
    a = a_ref[pl.program_id(0)]                # scalar (SMEM)
    bmat = b_ref[0].astype(jnp.float32)        # [L, N]
    cmat = c_ref[0].astype(jnp.float32)        # [L, N]

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    la = dt_row * a                            # log-decay per step  [1, L]
    cums = jnp.dot(la, (row <= col).astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)  # inclusive [1, L]
    cums_col = cums.T                          # [L, 1]
    dt_col = dt_row.T                          # [L, 1]

    # --- intra-chunk: (C Bᵀ ⊙ M) (dt ⊙ X) on the MXU -----------------------
    # M[t, r] = exp(cums[t] - cums[r]) for r <= t: x_r enters the state at
    # step r *after* that step's decay a_r was applied to h_{r-1}, so its
    # decay to step t spans (r, t] only.
    rel = cums_col - cums                      # [L(t), L(r)]
    # clamp before exp: above-diagonal rel is positive and can overflow to
    # inf, and inf * mask(0) = NaN (valid entries always have rel <= 0)
    decay = jnp.where(col <= row, jnp.exp(jnp.minimum(rel, 0.0)), 0.0)
    gates = jax.lax.dot_general(cmat, bmat,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [L, L]
    y_intra = jax.lax.dot_general(gates * decay, dt_col * x,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # --- inter-chunk: contribution of the carried state --------------------
    h_in = state_ref[...]                      # [N, P]
    y_inter = jnp.exp(cums_col) * jax.lax.dot_general(
        cmat, h_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # --- state update -------------------------------------------------------
    total = jnp.sum(la, axis=1, keepdims=True)  # [1, 1]
    w_r = jnp.exp(total - cums_col) * dt_col   # decay from r to chunk end [L, 1]
    state_ref[...] = (jnp.exp(total) * h_in +
                      jax.lax.dot_general((bmat * w_r).T, x,
                                          (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 128,
             interpret: bool = False) -> jax.Array:
    """Chunked SSD forward. See module docstring for shapes/semantics."""
    bh, s, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0, f"seq {s} must be a multiple of chunk {chunk}"
    grid = (bh, s // chunk)

    # blocks keep their last two dims aligned or full (TPU tiling rule):
    # dt goes in as [BH, 1, S] rows, the per-head decay rate `a` in SMEM
    return pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # a
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),   # x
            pl.BlockSpec((1, 1, chunk), lambda i, j: (i, 0, j)),   # dt
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),   # b
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),   # c
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), x, dt.reshape(bh, 1, s), b, c)
