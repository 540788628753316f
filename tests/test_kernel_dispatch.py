"""Differential suite for the kernel-dispatch layer (``use_pallas``).

Proves the fused Pallas path (interpret-mode on CPU, Mosaic on TPU) matches
the unfused digital oracle the models otherwise execute:

* fused kernel vs ``kernels/ref.py`` oracle over a grid of shapes —
  including non-multiple-of-block ragged M/K/N and decode shapes M = 1..8 —
  and ``in_bits``/``out_bits`` ∈ {4, 8};
* ``analog_linear(use_pallas=True)`` vs the unfused path, modes ``analog``
  and ``rtn``, at eval in f32 within 1e-5;
* training-mode gradient parity (the fused op's custom VJP must reproduce
  the unfused STE chain);
* the packed-int4 serving path vs the unfused RTN path;
* end-to-end: one transformer forward with ``use_pallas=True`` vs ``False``.

Accumulation-order caveat (the documented parity contract, also in the
README "Fused kernels" section): the fused kernel's blocked K loop and
XLA's shape-dependent GEMM blocking may reassociate the f32 accumulation,
so the two paths' pre-ADC values can differ by ~1 ulp. The deterministic
tie-break (``kernels.ref.ADC_TIE_BREAK``) removes the *systematic*
RTN-lattice rounding ties this would otherwise flip; what remains are
coincidental boundary landings at measure ~1e-6 per element, where the two
paths legitimately disagree by exactly one ADC level. ``assert_adc_parity``
therefore enforces: strict 1e-5 agreement for every element *except* ones
whose results are exactly one ADC LSB apart (the boundary-tie signature),
allowed at rate < 1e-4. On every small/decode shape this reduces to plain
1e-5 parity in practice.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_adc_parity

from repro.configs.base import ArchConfig
from repro.core.analog import (AnalogConfig, AnalogCtx, analog_linear,
                               init_linear)
from repro.core import quant
from repro.kernels import dispatch, ref
from repro.models import apply as model_apply
from repro.models import build

EVAL = AnalogCtx(key=None, training=False)

# Strict-parity grid: ragged, MXU-aligned and decode shapes, all K ≤ 512
# (single K block — see module docstring).
SHAPES_STRICT = [
    (1, 128, 128),     # single-token decode, aligned
    (2, 32, 48),       # decode, tiny ragged K/N
    (5, 64, 96),       # decode, ragged everything
    (8, 256, 130),     # decode upper block edge, ragged N (even: int4-able)
    (3, 300, 257),     # ragged K and odd N
    (300, 384, 257),   # prefill, M and N ragged vs blocks
    (64, 512, 512),    # aligned prefill at the K-block boundary
]
SHAPES_MULTI_K = [(300, 515, 257), (16, 1024, 128)]
BITS = [(8, 8), (4, 8), (8, 4), (4, 4)]


def _case(m, k, n, key, batch=2):
    kx, kp = jax.random.split(jax.random.PRNGKey(key))
    p = init_linear(kp, k, n, use_bias=True)
    x = jax.random.normal(kx, (batch, m, k), jnp.float32)
    return p, x


def _adc_lsb(p, out_bits, mode="analog"):
    """Per-column ADC step [N] — the unit of a boundary-tie flip."""
    beta = jnp.squeeze(p["input_range"])
    w = p["kernel"]
    if mode == "rtn":   # bound is computed from the dequantized weights
        w = quant.rtn_dequantize(*quant.rtn_quantize(w, 4))
    bound = ref.adc_bound(w, beta, 12.0)
    return np.asarray(bound) / (2 ** (out_bits - 1) - 1)




# ---------------------------------------------------------------------------
# kernel vs oracle (dispatch plumbing: flattening, blocks, padding)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", SHAPES_STRICT)
@pytest.mark.parametrize("bits", BITS, ids=lambda b: f"i{b[0]}o{b[1]}")
def test_dispatch_mvm_vs_oracle(m, k, n, bits):
    in_bits, out_bits = bits
    p, x = _case(m, k, n, key=m * 31 + k)
    beta = jnp.squeeze(p["input_range"])
    bound = ref.adc_bound(p["kernel"], beta, 12.0)
    y_ker = dispatch.analog_mvm(x, p["kernel"], beta, bound,
                                in_bits=in_bits, out_bits=out_bits)
    y_ref = ref.analog_matmul_ref(x.reshape(-1, k), p["kernel"], beta, bound,
                                  in_bits=in_bits, out_bits=out_bits)
    assert_adc_parity(np.asarray(y_ker).reshape(-1, n), y_ref,
                      _adc_lsb(p, out_bits))


def test_decode_block_selection():
    for m in range(1, 9):
        assert dispatch.select_blocks(m, 512, 512)[0] == dispatch.DECODE_BM
    assert dispatch.select_blocks(9, 512, 512)[0] == dispatch.PREFILL_BLOCKS[0]


# ---------------------------------------------------------------------------
# analog_linear fused vs unfused (the wiring the models actually run)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["analog", "rtn"])
@pytest.mark.parametrize("m,k,n", SHAPES_STRICT)
def test_analog_linear_parity_eval(mode, m, k, n):
    p, x = _case(m, k, n, key=m + k + n)
    y0, s0 = analog_linear(p, x, AnalogConfig(mode=mode), EVAL)
    y1, s1 = analog_linear(p, x, AnalogConfig(mode=mode, use_pallas=True),
                           EVAL)
    assert_adc_parity(y1, y0, _adc_lsb(p, 8, mode))
    # stats structure must be unchanged by the dispatch (scan-stackable)
    assert jax.tree.structure(s0) == jax.tree.structure(s1)


@pytest.mark.parametrize("mode", ["analog", "rtn"])
@pytest.mark.parametrize("bits", BITS, ids=lambda b: f"i{b[0]}o{b[1]}")
def test_analog_linear_parity_bit_widths(mode, bits):
    p, x = _case(8, 256, 130, key=77)
    cfg = dict(mode=mode, input_bits=bits[0], output_bits=bits[1])
    y0, _ = analog_linear(p, x, AnalogConfig(**cfg), EVAL)
    y1, _ = analog_linear(p, x, AnalogConfig(**cfg, use_pallas=True), EVAL)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", ["analog", "rtn"])
@pytest.mark.parametrize("m,k,n", SHAPES_MULTI_K)
def test_analog_linear_multi_k_block_lsb_bound(mode, m, k, n):
    """K > block: the kernel's blocked K loop reassociates the sum — same
    boundary-tie contract, exercised where it's most likely to trigger."""
    p, x = _case(m, k, n, key=k)
    y0, _ = analog_linear(p, x, AnalogConfig(mode=mode), EVAL)
    y1, _ = analog_linear(p, x, AnalogConfig(mode=mode, use_pallas=True),
                          EVAL)
    assert_adc_parity(y1, y0, _adc_lsb(p, 8, mode))


def test_analog_linear_parity_under_jit():
    """Same comparison inside jit — guards against XLA rewrites (reciprocal
    strength-reduction) diverging the quantizer decisions."""
    p, x = _case(7, 500, 96, key=3)
    for mode in ("analog", "rtn"):
        f0 = jax.jit(lambda p, x, _m=mode: analog_linear(
            p, x, AnalogConfig(mode=_m), EVAL)[0])
        f1 = jax.jit(lambda p, x, _m=mode: analog_linear(
            p, x, AnalogConfig(mode=_m, use_pallas=True), EVAL)[0])
        np.testing.assert_allclose(np.asarray(f1(p, x)), np.asarray(f0(p, x)),
                                   rtol=1e-5, atol=1e-5)


def test_training_forward_and_gradient_parity():
    """Fused custom-VJP: noisy forward matches, backward replays the unfused
    STE chain (noise-free weight grad, clamp-STE dx, LSQ dbeta)."""
    p, x = _case(5, 96, 64, key=11)
    noise_key = jax.random.PRNGKey(7)

    def loss(p, x, use_pallas):
        ctx = AnalogCtx(key=noise_key, training=True)
        y, _ = analog_linear(p, x, AnalogConfig(mode="analog",
                                                use_pallas=use_pallas), ctx)
        return jnp.sum(y * jnp.cos(y))

    l0, l1 = loss(p, x, False), loss(p, x, True)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    g0 = jax.grad(loss)(p, x, False)
    g1 = jax.grad(loss)(p, x, True)
    for name in g0:
        np.testing.assert_allclose(np.asarray(g1[name]), np.asarray(g0[name]),
                                   rtol=1e-4, atol=1e-4, err_msg=name)
    gx0 = jax.grad(loss, argnums=1)(p, x, False)
    gx1 = jax.grad(loss, argnums=1)(p, x, True)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx0),
                               rtol=1e-4, atol=1e-4)


def test_qat_di8_modes_unaffected_by_use_pallas():
    """Dispatch only covers analog/rtn; other modes must ignore the flag."""
    p, x = _case(4, 64, 32, key=5)
    for mode in ("qat", "di8", "off"):
        y0, _ = analog_linear(p, x, AnalogConfig(mode=mode), EVAL)
        y1, _ = analog_linear(p, x, AnalogConfig(mode=mode, use_pallas=True),
                              EVAL)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


# ---------------------------------------------------------------------------
# packed-int4 serving path
# ---------------------------------------------------------------------------

def test_int4_serving_parity():
    p, x = _case(6, 256, 130, key=13)
    y0, _ = analog_linear(p, x, AnalogConfig(mode="rtn"), EVAL)
    y1, _ = analog_linear(
        p, x, AnalogConfig(mode="rtn", use_pallas=True, int4_serve=True),
        EVAL)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-5, atol=1e-5)


def test_int4_serving_odd_n_falls_back():
    """Odd N can't pack two nibbles per byte — must fall back, not crash."""
    p, x = _case(4, 64, 33, key=17)
    y0, _ = analog_linear(p, x, AnalogConfig(mode="rtn"), EVAL)
    y1, _ = analog_linear(
        p, x, AnalogConfig(mode="rtn", use_pallas=True, int4_serve=True),
        EVAL)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-5, atol=1e-5)


def test_int4_serving_without_output_quant():
    """int4_serve must route through the packed kernel even when the ADC is
    disabled (output_quant=False): the ADC lives outside this kernel."""
    from repro.kernels import dispatch as dispatch_mod

    p, x = _case(4, 64, 32, key=23)
    calls = []
    orig = dispatch_mod.int4_mvm_packed

    def counting(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    dispatch_mod.int4_mvm_packed = counting
    try:
        y1, _ = analog_linear(
            p, x, AnalogConfig(mode="rtn", use_pallas=True, int4_serve=True,
                               output_quant=False), EVAL)
    finally:
        dispatch_mod.int4_mvm_packed = orig
    assert calls, "int4 kernel was not dispatched with output_quant=False"
    y0, _ = analog_linear(
        p, x, AnalogConfig(mode="rtn", output_quant=False), EVAL)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-5, atol=1e-5)


def test_pack_int4_weights_serving_parity():
    """Precomputed packed carriers: same outputs as on-the-fly packing and
    as the unfused RTN path; odd-N sites skipped; stacked dims preserved."""
    from repro.core.analog import pack_int4_weights

    key = jax.random.PRNGKey(3)
    cfg, params, labels = build(_toy_cfg(), key)
    packed = pack_int4_weights(params, labels)
    # stacked scan weights keep their leading layer dim
    site = packed["blocks"]["attn"]["o"]
    kshape = site["kernel"].shape
    assert site["int4"]["packed"].shape == (
        kshape[0], kshape[1], ref.round_up(kshape[2], ref.PACK_GROUP) // 2)
    assert site["int4"]["packed"].dtype == jnp.uint8
    toks = jax.random.randint(key, (2, 12), 0, cfg.vocab_size)
    acfg = AnalogConfig(mode="rtn", use_pallas=True, int4_serve=True)
    l_pre, _, _ = model_apply(packed, cfg, acfg, EVAL, {"tokens": toks})
    l_fly, _, _ = model_apply(params, cfg, acfg, EVAL, {"tokens": toks})
    l_ref, _, _ = model_apply(params, cfg, AnalogConfig(mode="rtn"), EVAL,
                              {"tokens": toks})
    np.testing.assert_allclose(np.asarray(l_pre), np.asarray(l_fly),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_pre), np.asarray(l_ref),
                               rtol=1e-5, atol=1e-5)
    # odd-N site: untouched (no "int4" entry), still serves via fallback
    p_odd, x_odd = _case(4, 64, 33, key=29)
    lab_odd = {"kernel": "analog_weight", "input_range": "input_range",
               "bias": "digital"}
    p_odd2 = pack_int4_weights(p_odd, lab_odd)
    assert "int4" not in p_odd2


def test_int4_mvm_matches_int4_oracle():
    key = jax.random.PRNGKey(19)
    x = jax.random.normal(key, (3, 9, 128))
    w = jax.random.normal(jax.random.fold_in(key, 1), (128, 64)) * 0.05
    w_int, scale = quant.rtn_quantize(w, 4)
    y_ker = dispatch.int4_mvm(x, w_int, scale)
    y_ref = ref.int4_matmul_ref(x.reshape(-1, 128), ref.pack_int4(w_int),
                                scale[0])
    np.testing.assert_allclose(np.asarray(y_ker).reshape(-1, 64),
                               np.asarray(y_ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# end-to-end: transformer forward / serve step
# ---------------------------------------------------------------------------

def _toy_cfg(**kw):
    base = dict(name="toy", family="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128,
                d_head=16, norm="rmsnorm", act="silu")
    base.update(kw)
    return ArchConfig(**base)


@pytest.mark.parametrize("mode", ["analog", "rtn"])
def test_transformer_forward_parity(mode):
    key = jax.random.PRNGKey(0)
    cfg, params, _ = build(_toy_cfg(), key)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    l0, s0, _ = model_apply(params, cfg, AnalogConfig(mode=mode), EVAL,
                            {"tokens": toks})
    l1, s1, _ = model_apply(params, cfg, AnalogConfig(mode=mode,
                                                      use_pallas=True),
                            EVAL, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               rtol=1e-5, atol=1e-5)
    assert jax.tree.structure(s0) == jax.tree.structure(s1)


def test_transformer_moe_forward_parity():
    """vmap over experts composes with the Pallas batching rule."""
    key = jax.random.PRNGKey(1)
    cfg, params, _ = build(
        _toy_cfg(name="toymoe", family="moe", num_experts=4, top_k=2), key)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    l0, _, _ = model_apply(params, cfg, AnalogConfig(mode="analog"), EVAL,
                           {"tokens": toks})
    l1, _, _ = model_apply(params, cfg,
                           AnalogConfig(mode="analog", use_pallas=True),
                           EVAL, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               rtol=1e-5, atol=1e-5)


def test_serve_decode_parity():
    from repro.serve.decode import digital_int4_config, prefill, serve_step

    key = jax.random.PRNGKey(2)
    cfg, params, _ = build(_toy_cfg(), key)
    toks = jax.random.randint(key, (2, 12), 0, cfg.vocab_size)
    for acfg in (AnalogConfig(mode="analog", use_pallas=True),
                 digital_int4_config(AnalogConfig(mode="analog"))):
        base = dataclasses.replace(acfg, use_pallas=False, int4_serve=False)
        logits1, caches1, pos1 = prefill(params, cfg, acfg, toks, 16)
        logits0, caches0, pos0 = prefill(params, cfg, base, toks, 16)
        np.testing.assert_allclose(np.asarray(logits1), np.asarray(logits0),
                                   rtol=1e-5, atol=1e-5)
        tok = jnp.argmax(logits0, -1)[:, None].astype(jnp.int32)
        step1, _ = serve_step(params, cfg, acfg, tok, caches1, pos1)
        step0, _ = serve_step(params, cfg, base, tok, caches0, pos0)
        np.testing.assert_allclose(np.asarray(step1), np.asarray(step0),
                                   rtol=1e-5, atol=1e-5)
