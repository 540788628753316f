import jax
import numpy as np
import pytest


def assert_adc_parity(y1, y0, lsb, *, max_flip_rate=1e-4):
    """Parity contract for ADC-quantized outputs across implementations.

    Strict 1e-5 agreement for every element, except ones whose accumulated
    pre-ADC value landed exactly on a rounding boundary: f32 accumulation-
    order reassociation (blocked K loops, XLA's shape-dependent GEMM
    blocking) can move such a value by ~1 ulp across the boundary, flipping
    the result by exactly one ADC level. ``lsb`` is the per-column ADC step
    [N] (broadcast over leading dims). Mismatches must equal exactly one
    level (within 1e-3 relative) and stay under ``max_flip_rate`` —
    anything else is a real defect. See the README "Fused kernels" section.
    """
    a, b = np.asarray(y1, np.float64), np.asarray(y0, np.float64)
    d = np.abs(a - b)
    flips = d > 1e-5
    if not flips.any():
        return
    rate = flips.mean()
    lsb_b = np.broadcast_to(np.asarray(lsb, np.float64), d.shape)
    level_err = np.abs(d[flips] - lsb_b[flips]) / lsb_b[flips]
    assert rate < max_flip_rate, (
        f"flip rate {rate:.2e} exceeds {max_flip_rate:.0e} — not boundary "
        f"ties but a real mismatch (max err {d.max():.3e})")
    assert level_err.max() < 1e-3, (
        f"mismatches are not exactly one ADC level (rel dev "
        f"{level_err.max():.3e}) — real defect, not a rounding tie")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration tests (subprocess pjit)")
