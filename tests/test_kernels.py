"""Pallas-kernel sweeps: shapes × dtypes against the pure-jnp oracles
(interpret=True on CPU; Mosaic on real TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_adc_parity

from repro.core.quant import rtn_quantize
from repro.kernels import ops
from repro.kernels.analog_matmul import analog_matmul
from repro.kernels.int4_matmul import int4_matmul
from repro.kernels.ref import (analog_matmul_ref, int4_matmul_ref, pack_int4,
                               ssd_ref)
from repro.kernels.ssd_scan import ssd_scan

SHAPES_MM = [(8, 32, 16), (64, 128, 96), (300, 515, 257), (128, 512, 256),
             (1, 128, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("m,k,n", SHAPES_MM)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_analog_matmul_vs_oracle(m, k, n, dtype):
    key = jax.random.PRNGKey(m * 7 + k)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, (k, n), jnp.float32) * 0.05)
    beta = jnp.float32(3.0)
    bound = 12.0 * beta * jnp.max(jnp.abs(w), axis=0)
    ref = analog_matmul_ref(x, w, beta, bound)
    ker = analog_matmul(x, w, beta, bound, bm=64, bn=128, bk=128,
                        interpret=True)
    if dtype == jnp.float32:
        # strict 1e-5, except exact one-ADC-level boundary ties (blocked K
        # accumulation vs one dot — see conftest.assert_adc_parity)
        assert_adc_parity(np.asarray(ker, np.float32),
                          np.asarray(ref, np.float32),
                          np.asarray(bound) / 127.0)
    else:
        np.testing.assert_allclose(np.asarray(ker, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("bits_sweep", [(8, 8), (8, 6), (4, 8)])
def test_analog_matmul_bit_widths(bits_sweep):
    in_bits, out_bits = bits_sweep
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (32, 64))
    w = jax.random.normal(key, (64, 32)) * 0.05
    beta = jnp.float32(2.5)
    bound = 12.0 * beta * jnp.max(jnp.abs(w), axis=0)
    ref = analog_matmul_ref(x, w, beta, bound, in_bits=in_bits,
                            out_bits=out_bits)
    ker = analog_matmul(x, w, beta, bound, in_bits=in_bits,
                        out_bits=out_bits, bm=32, bn=128, bk=128,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("m,k,n", [(16, 32, 64), (100, 257, 130),
                                   (64, 512, 256)])
def test_int4_matmul_vs_oracle(m, k, n):
    key = jax.random.PRNGKey(n)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (m, k))
    w = jax.random.normal(kw, (k, n)) * 0.05
    w_int, scale = rtn_quantize(w, 4)
    wp = pack_int4(w_int)
    ref = int4_matmul_ref(x, wp, scale[0])
    ker = int4_matmul(x, wp, scale[0], bm=64, bn=256, bk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)
    # and the packed path equals dense dequant matmul exactly
    dense = x @ (w_int.astype(jnp.float32) * scale)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bh,s,p,n,chunk", [
    (4, 256, 32, 16, 64), (2, 512, 64, 32, 128), (1, 128, 16, 8, 32),
    (8, 128, 64, 64, 128)])
def test_ssd_kernel_vs_sequential_oracle(bh, s, p, n, chunk):
    key = jax.random.PRNGKey(s + p)
    kk = jax.random.split(key, 5)
    x = jax.random.normal(kk[0], (bh, s, p))
    dt = jax.nn.softplus(jax.random.normal(kk[1], (bh, s)) * 0.5)
    a = -jnp.exp(jax.random.normal(kk[2], (bh,)) * 0.3)
    b = jax.random.normal(kk[3], (bh, s, n)) * 0.3
    c = jax.random.normal(kk[4], (bh, s, n)) * 0.3
    ref = ssd_ref(x, dt, a, b, c)
    ker = ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), rtol=5e-4,
                               atol=5e-4)


def test_ssd_chunked_jnp_matches_kernel_math():
    """The CPU jnp path and the Pallas kernel implement identical math."""
    key = jax.random.PRNGKey(9)
    kk = jax.random.split(key, 5)
    bh, s, p, n = 3, 256, 16, 8
    x = jax.random.normal(kk[0], (bh, s, p))
    dt = jax.nn.softplus(jax.random.normal(kk[1], (bh, s)) * 0.5)
    a = -jnp.exp(jax.random.normal(kk[2], (bh,)) * 0.3)
    b = jax.random.normal(kk[3], (bh, s, n)) * 0.3
    c = jax.random.normal(kk[4], (bh, s, n)) * 0.3
    jnp_path = ops.ssd_chunked_jnp(x, dt, a, b, c, chunk=64)
    ker = ssd_scan(x, dt, a, b, c, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp_path), np.asarray(ker),
                               rtol=2e-4, atol=2e-4)


def test_ssd_decode_step_matches_scan_tail():
    key = jax.random.PRNGKey(11)
    kk = jax.random.split(key, 5)
    bh, s, p, n = 2, 64, 8, 4
    x = jax.random.normal(kk[0], (bh, s, p))
    dt = jax.nn.softplus(jax.random.normal(kk[1], (bh, s)) * 0.5)
    a = -jnp.exp(jax.random.normal(kk[2], (bh,)) * 0.3)
    b = jax.random.normal(kk[3], (bh, s, n)) * 0.3
    c = jax.random.normal(kk[4], (bh, s, n)) * 0.3
    ref = ssd_ref(x, dt, a, b, c)
    h = jnp.zeros((bh, n, p))
    for t in range(s):
        h, y = ops.ssd_decode_step(h, x[:, t], dt[:, t], a, b[:, t], c[:, t])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref[:, -1]),
                               rtol=2e-4, atol=2e-4)


def test_ops_batch_dim_flattening():
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (2, 3, 32))
    w = jax.random.normal(key, (32, 16)) * 0.1
    beta = jnp.float32(3.0)
    bound = 12.0 * beta * jnp.max(jnp.abs(w), axis=0)
    y = ops.analog_matmul(x, w, beta, bound)
    assert y.shape == (2, 3, 16)
    y2 = analog_matmul_ref(x.reshape(-1, 32), w, beta, bound).reshape(2, 3, 16)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-5)
