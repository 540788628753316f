"""Paged attention: kernel/oracle/dense differential suites.

Three-way parity at both serving seams: the Pallas flash-decode and
flash-prefill kernels (interpret-mode on CPU) vs their ``lax.scan`` oracles
(``kernels.ref.paged_decode_ref`` / ``paged_prefill_ref``) vs a dense
full-buffer softmax over the gathered logical view — across fill ratios,
GQA group sizes, chunk lengths, split-K factors, and the int8-quantized
pool. Plus the KV quantization helpers and the host-side free-list
allocator.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels import dispatch, ref
from repro.kernels.paged_attention import paged_flash_decode
from repro.kernels.paged_prefill import paged_flash_prefill
from repro.serve.kv_pool import SINK_BLOCK, KVPool, OutOfBlocksError


def _setup(seed, bsz, nq, nkv, hd, bs, nb, max_pos=None):
    """Random pool + block tables + ragged live ranges for ``bsz`` rows."""
    rng = np.random.default_rng(seed)
    npool = bsz * nb + 1
    q = jnp.asarray(rng.normal(size=(bsz, nq, hd)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(npool, bs, nkv, hd)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(npool, bs, nkv, hd)).astype(np.float32))
    # every row gets a disjoint shuffled set of physical blocks (sink at 0)
    tbl = jnp.asarray(
        (1 + rng.permutation(bsz * nb)).reshape(bsz, nb).astype(np.int32))
    hi = max_pos if max_pos is not None else nb * bs - 1
    pos = jnp.asarray(rng.integers(0, hi + 1, bsz).astype(np.int32))
    start = jnp.asarray((np.asarray(pos) * rng.random(bsz) * 0.7)
                        .astype(np.int32))
    return q, kp, vp, tbl, pos, start


def _dense_reference(q, kp, vp, tbl, pos, start, scale):
    """Full-buffer softmax over the gathered logical view (numpy)."""
    bsz, nq, hd = q.shape
    bs, nkv = kp.shape[1], kp.shape[2]
    out = np.zeros((bsz, nq, hd), np.float32)
    for b in range(bsz):
        kk = np.asarray(kp)[np.asarray(tbl)[b]].reshape(-1, nkv, hd)
        vv = np.asarray(vp)[np.asarray(tbl)[b]].reshape(-1, nkv, hd)
        j = np.arange(kk.shape[0])
        live = (j >= int(start[b])) & (j <= int(pos[b]))
        qg = np.asarray(q)[b].reshape(nkv, nq // nkv, hd)
        lo = np.einsum("ngh,tnh->ngt", qg, kk) * scale
        lo[:, :, ~live] = -1e30
        p = np.exp(lo - lo.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[b] = np.einsum("ngt,tnh->ngh", p, vv).reshape(nq, hd)
    return out


@pytest.mark.parametrize("shape", [
    # (B, H, KV, hd, block, num_blocks)
    (1, 4, 4, 8, 4, 3),        # MHA, single row
    (3, 8, 2, 16, 4, 6),       # GQA group 4
    (5, 6, 1, 32, 8, 5),       # MQA, wider head
    (2, 8, 8, 16, 16, 2),      # big blocks, few of them
])
def test_ref_matches_dense_full_buffer(shape):
    """The online-softmax block oracle must reproduce the dense softmax
    over the gathered logical view at every ragged (start, pos)."""
    bsz, nq, nkv, hd, bs, nb = shape
    q, kp, vp, tbl, pos, start = _setup(0, bsz, nq, nkv, hd, bs, nb)
    scale = hd ** -0.5
    got = ref.paged_decode_ref(q, kp, vp, tbl, pos, start, scale)
    want = _dense_reference(q, kp, vp, tbl, pos, start, scale)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("num_splits", [1, 2, 4])
@pytest.mark.parametrize("shape", [
    (3, 8, 2, 16, 4, 6),
    (2, 4, 4, 8, 4, 8),
])
def test_kernel_matches_ref(shape, num_splits):
    """Pallas kernel (interpret) ≡ scan oracle, incl. the 2-pass split-K
    reduction at several split factors."""
    bsz, nq, nkv, hd, bs, nb = shape
    q, kp, vp, tbl, pos, start = _setup(1, bsz, nq, nkv, hd, bs, nb)
    scale = hd ** -0.5
    want = ref.paged_decode_ref(q, kp, vp, tbl, pos, start, scale)
    got = paged_flash_decode(q, kp, vp, tbl, pos, start, scale=scale,
                             num_splits=num_splits, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)


def test_kernel_matches_ref_int8():
    """Quantized-pool parity: kernel and oracle dequantize identically, and
    the int8 result stays within quantization distance of the fp path."""
    bsz, nq, nkv, hd, bs, nb = 3, 8, 2, 16, 4, 6
    q, kp, vp, tbl, pos, start = _setup(2, bsz, nq, nkv, hd, bs, nb)
    scale = hd ** -0.5
    kq, ks = quant.kv_quantize(kp, 8)
    vq, vs = quant.kv_quantize(vp, 8)
    want = ref.paged_decode_ref(q, kq, vq, tbl, pos, start, scale,
                                k_scale=ks, v_scale=vs)
    got = paged_flash_decode(q, kq, vq, tbl, pos, start, scale=scale,
                             k_scale=ks, v_scale=vs, num_splits=2,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)
    fp = ref.paged_decode_ref(q, kp, vp, tbl, pos, start, scale)
    assert float(jnp.max(jnp.abs(want - fp))) < 0.1   # bounded divergence


def test_dispatch_routing():
    """impl overrides force either implementation; auto picks the oracle
    off-TPU. Results agree regardless of route."""
    q, kp, vp, tbl, pos, start = _setup(3, 2, 4, 2, 8, 4, 3)
    scale = 8 ** -0.5
    auto = dispatch.paged_decode_attention(q, kp, vp, tbl, pos, start, scale)
    forced_ref = dispatch.paged_decode_attention(
        q, kp, vp, tbl, pos, start, scale, impl="ref")
    forced_kernel = dispatch.paged_decode_attention(
        q, kp, vp, tbl, pos, start, scale, impl="kernel")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(forced_ref))
    np.testing.assert_allclose(np.asarray(forced_kernel), np.asarray(auto),
                               atol=2e-6, rtol=2e-6)


def _setup_prefill(seed, bsz, s, nq, nkv, hd, bs, nb):
    """Random pool + tables + ragged chunk cursors for the prefill seam.

    ``pos`` is the logical position of each row's *first* query column;
    the chunk's own K/V are assumed already in the pool (the engine
    scatter-writes before scoring), so ``pos + s - 1`` stays in range."""
    rng = np.random.default_rng(seed)
    npool = bsz * nb + 1
    q = jnp.asarray(rng.normal(size=(bsz, s, nq, hd)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(npool, bs, nkv, hd)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(npool, bs, nkv, hd)).astype(np.float32))
    tbl = jnp.asarray(
        (1 + rng.permutation(bsz * nb)).reshape(bsz, nb).astype(np.int32))
    pos = jnp.asarray(rng.integers(0, nb * bs - s + 1, bsz).astype(np.int32))
    start = jnp.asarray((np.asarray(pos) * rng.random(bsz) * 0.7)
                        .astype(np.int32))
    return q, kp, vp, tbl, pos, start


def _dense_prefill_reference(q, kp, vp, tbl, pos, start, scale):
    """Per-column dense softmax over the gathered logical view (numpy):
    column ``i`` of row ``b`` attends ``start[b] <= j <= pos[b] + i``."""
    bsz, s, nq, hd = q.shape
    bs, nkv = kp.shape[1], kp.shape[2]
    out = np.zeros((bsz, s, nq, hd), np.float32)
    for b in range(bsz):
        kk = np.asarray(kp)[np.asarray(tbl)[b]].reshape(-1, nkv, hd)
        vv = np.asarray(vp)[np.asarray(tbl)[b]].reshape(-1, nkv, hd)
        j = np.arange(kk.shape[0])
        for i in range(s):
            live = (j >= int(start[b])) & (j <= int(pos[b]) + i)
            qg = np.asarray(q)[b, i].reshape(nkv, nq // nkv, hd)
            lo = np.einsum("ngh,tnh->ngt", qg, kk) * scale
            lo[:, :, ~live] = -1e30
            p = np.exp(lo - lo.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, i] = np.einsum("ngt,tnh->ngh", p, vv).reshape(nq, hd)
    return out


@pytest.mark.parametrize("shape", [
    # (B, S, H, KV, hd, block, num_blocks)
    (1, 4, 4, 4, 8, 4, 3),      # MHA, single row
    (3, 5, 8, 2, 16, 4, 6),     # GQA group 4, odd chunk
    (2, 8, 6, 1, 32, 8, 4),     # MQA, chunk spanning 2 blocks
    (2, 16, 8, 4, 16, 16, 3),   # chunk == block
])
def test_prefill_ref_matches_dense(shape):
    """The online-softmax prefill oracle must reproduce the per-column
    dense softmax over the gathered view at every ragged (start, pos)."""
    bsz, s, nq, nkv, hd, bs, nb = shape
    q, kp, vp, tbl, pos, start = _setup_prefill(10, bsz, s, nq, nkv, hd,
                                                bs, nb)
    scale = hd ** -0.5
    got = ref.paged_prefill_ref(q, kp, vp, tbl, pos, start, scale)
    want = _dense_prefill_reference(q, kp, vp, tbl, pos, start, scale)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", [
    (3, 5, 8, 2, 16, 4, 6),
    (2, 4, 4, 4, 8, 4, 8),
    (1, 16, 8, 4, 16, 8, 4),
])
def test_prefill_kernel_matches_ref(shape):
    """Pallas flash-prefill kernel (interpret) ≡ scan oracle — identical
    block-loop accumulation order, so the comparison is bitwise."""
    bsz, s, nq, nkv, hd, bs, nb = shape
    q, kp, vp, tbl, pos, start = _setup_prefill(11, bsz, s, nq, nkv, hd,
                                                bs, nb)
    scale = hd ** -0.5
    want = ref.paged_prefill_ref(q, kp, vp, tbl, pos, start, scale)
    got = paged_flash_prefill(q, kp, vp, tbl, pos, start, scale=scale,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)


def test_prefill_kernel_matches_ref_int8():
    """Quantized-pool prefill parity: kernel and oracle dequantize
    identically; the int8 result stays near the fp path."""
    bsz, s, nq, nkv, hd, bs, nb = 3, 5, 8, 2, 16, 4, 6
    q, kp, vp, tbl, pos, start = _setup_prefill(12, bsz, s, nq, nkv, hd,
                                                bs, nb)
    scale = hd ** -0.5
    kq, ks = quant.kv_quantize(kp, 8)
    vq, vs = quant.kv_quantize(vp, 8)
    want = ref.paged_prefill_ref(q, kq, vq, tbl, pos, start, scale,
                                 k_scale=ks, v_scale=vs)
    got = paged_flash_prefill(q, kq, vq, tbl, pos, start, scale=scale,
                              k_scale=ks, v_scale=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)
    fp = ref.paged_prefill_ref(q, kp, vp, tbl, pos, start, scale)
    assert float(jnp.max(jnp.abs(want - fp))) < 0.1   # bounded divergence


def test_prefill_dispatch_routing():
    """impl overrides force either implementation; auto picks the oracle
    off-TPU. Results agree regardless of route."""
    q, kp, vp, tbl, pos, start = _setup_prefill(13, 2, 4, 4, 2, 8, 4, 3)
    scale = 8 ** -0.5
    auto = dispatch.paged_prefill_attention(q, kp, vp, tbl, pos, start,
                                            scale)
    forced_ref = dispatch.paged_prefill_attention(
        q, kp, vp, tbl, pos, start, scale, impl="ref")
    forced_kernel = dispatch.paged_prefill_attention(
        q, kp, vp, tbl, pos, start, scale, impl="kernel")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(forced_ref))
    np.testing.assert_allclose(np.asarray(forced_kernel), np.asarray(auto),
                               atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("max_rows", [8, 24])    # pieces of 1 and 3 columns
def test_prefill_dispatch_splits_long_chunks_bitwise(monkeypatch, max_rows):
    """A chunk of more query rows than one kernel call may hold in VMEM
    is scored in column pieces at shifted cursors — bitwise the unsplit
    kernel."""
    bsz, s, nq, nkv, hd, bs, nb = 3, 5, 8, 2, 16, 4, 6
    q, kp, vp, tbl, pos, start = _setup_prefill(17, bsz, s, nq, nkv, hd,
                                                bs, nb)
    scale = hd ** -0.5
    whole = paged_flash_prefill(q, kp, vp, tbl, pos, start, scale=scale,
                                interpret=True)
    monkeypatch.setattr(dispatch, "PREFILL_MAX_ROWS", max_rows)
    split = dispatch.paged_prefill_attention(q, kp, vp, tbl, pos, start,
                                             scale, impl="kernel")
    np.testing.assert_array_equal(np.asarray(split), np.asarray(whole))


def test_prefill_last_column_matches_decode():
    """Seam consistency: a chunk's last column must score exactly like a
    decode step at the same cursor (same pool, pos' = pos + S - 1)."""
    bsz, s, nq, nkv, hd, bs, nb = 2, 4, 8, 2, 16, 4, 6
    q, kp, vp, tbl, pos, start = _setup_prefill(14, bsz, s, nq, nkv, hd,
                                                bs, nb)
    scale = hd ** -0.5
    chunk = ref.paged_prefill_ref(q, kp, vp, tbl, pos, start, scale)
    dec = ref.paged_decode_ref(q[:, -1], kp, vp, tbl, pos + s - 1, start,
                               scale)
    np.testing.assert_allclose(np.asarray(chunk[:, -1]), np.asarray(dec),
                               atol=2e-6, rtol=2e-6)


def test_verify_window_chunk_equals_successive_decodes_bitwise():
    """Speculative-verification seam: a k-token verify window is a
    flash-prefill chunk scored at an arbitrary (non-chunk-aligned,
    non-block-aligned) offset, and exact-match verification relies on
    its columns being **bitwise** what k successive flash-decode steps
    would produce. Both oracles share the same block-loop online-softmax
    accumulation order, so the comparison is exact equality, not
    allclose — any reordering of the accumulation breaks spec≡non-spec
    parity and must fail this test."""
    bsz, s, nq, nkv, hd, bs, nb = 3, 5, 8, 2, 16, 4, 6
    q, kp, vp, tbl, pos, start = _setup_prefill(15, bsz, s, nq, nkv, hd,
                                                bs, nb)
    # force every cursor odd: mid-block, mid-chunk offsets — the shape a
    # rejected window leaves behind after a pos rewind
    pos = jnp.minimum(pos | 1, nb * bs - s)
    scale = hd ** -0.5
    chunk = ref.paged_prefill_ref(q, kp, vp, tbl, pos, start, scale)
    for i in range(s):
        dec = ref.paged_decode_ref(q[:, i], kp, vp, tbl, pos + i, start,
                                   scale)
        np.testing.assert_array_equal(np.asarray(chunk[:, i]),
                                      np.asarray(dec))


def test_verify_window_dispatch_matches_decode_dispatch():
    """Same seam through the dispatch layer the model actually calls:
    ``paged_prefill_attention`` at an odd offset ≡ per-column
    ``paged_decode_attention``, bitwise on the auto (oracle) route."""
    bsz, s, nq, nkv, hd, bs, nb = 2, 4, 4, 2, 8, 4, 5
    q, kp, vp, tbl, pos, start = _setup_prefill(16, bsz, s, nq, nkv, hd,
                                                bs, nb)
    pos = jnp.minimum(pos | 1, nb * bs - s)
    scale = hd ** -0.5
    chunk = dispatch.paged_prefill_attention(q, kp, vp, tbl, pos, start,
                                             scale)
    for i in range(s):
        dec = dispatch.paged_decode_attention(q[:, i], kp, vp, tbl,
                                              pos + i, start, scale)
        np.testing.assert_array_equal(np.asarray(chunk[:, i]),
                                      np.asarray(dec))


def test_kv_quantize_roundtrip():
    """Per-vector int8 KV quantization: bounded error, exact absmax scale."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(7, 3, 16)).astype(np.float32)) * 4.0
    xq, scale = quant.kv_quantize(x, 8)
    assert xq.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    back = quant.kv_dequantize(xq, scale)
    err = np.abs(np.asarray(back) - np.asarray(x))
    # max error is half a quantization step per vector
    step = np.asarray(scale)[..., None]
    assert np.all(err <= 0.5 * step + 1e-6)
    assert int(jnp.max(jnp.abs(xq))) <= 127


def test_kv_pool_alloc_release_churn():
    """Free-list invariants across admission/retirement churn: LIFO reuse,
    disjoint ownership, full recovery after release."""
    pool = KVPool(num_blocks=8, block_size=4)
    assert pool.num_free == 8 and pool.blocks_for(9, 4) == 4
    a = pool.alloc(0, 3)
    b = pool.alloc(1, 4)
    assert SINK_BLOCK not in a + b          # sink is never handed out
    assert set(a).isdisjoint(b) and pool.num_free == 1
    assert not pool.can_alloc(2)
    with pytest.raises(OutOfBlocksError):
        pool.alloc(2, 2)
    with pytest.raises(ValueError):
        pool.alloc(0, 1)                    # double-alloc same uid
    pool.release(0)
    assert pool.num_free == 4 and pool.can_alloc(4)
    c = pool.alloc(3, 4)
    assert set(c).isdisjoint(b)
    pool.release(1)
    pool.release(3)
    assert pool.num_free == 8 and pool.num_live == 0
