"""The plain reference of a served configuration, and its control.

The forward pass of the configuration, written from the published
description of the model (pre-norm decoder, RMSNorm, rotary embeddings in
rotate-half form, causal GQA, SwiGLU) and of the deployment (the paper's
eq. 1 DAC and eq. 2 ADC around every projection; RTN int4 weights per
output column for the Table-3 deployment) in straightforward
``jax.numpy`` and float32 at ``"highest"`` matmul precision. It imports
nothing of the program and takes only the benchmark's own weights. Keys
and values pass through the configuration's KV dtype, as they are stored.

``precision="fp8"`` is the control: every matmul operand (projections,
attention scores and values, the head) rounded to float8 e4m3 with a
scale per row of the left operand and per column of the right one, the
step below the bfloat16 the program's matmuls compute in.

``gaps`` is what ``correct`` is built on: the amount by which a chosen
token's reference logit lies below the reference's best logit at its
position. The number compared is ``gap_ratio``, the served tokens' mean
gap over the control's on the same tokens: 8-bit DAC and ADC at every
projection turn any difference in summation order into whole
quantization levels, so even a sound program's gaps are about a quarter
of the control's, and the share of near ties in a seed's sample moves
both alike (PERF.md, Findings).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Spec

E4M3_MAX = 448.0
#: Query rows scored at once: at 32 heads and 4096 keys a block's float32
#: scores take 256 MiB, where the whole [H, S, S] would take 2 GiB.
Q_BLOCK = 512


def _fp8(x, axis):
    """``x`` rounded to e4m3 at a scale per slice along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, precision, b_axis=-2):
    """``a @ b`` in float32; under the control the operands are rounded
    first, ``a`` per row and ``b`` per slice along ``b_axis`` (a column
    of weights; a key position of values, so that no position's rounding
    depends on a later one)."""
    if precision == "fp8":
        a, b = _fp8(a, -1), _fp8(b, b_axis)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    """RMSNorm over the last axis."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotary embedding, rotate-half form; x [S, H, hd], pos [S]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _levels(bits):
    """Largest level of a symmetric ``bits``-bit quantizer."""
    return float(2 ** (bits - 1) - 1)


def _project(site, x, spec: Spec, precision):
    """One deployed projection: DAC, the weights' matmul, ADC (eq. 1-2)."""
    w = site["kernel"]
    beta = site["input_range"][0]
    if spec.mode == "off":            # no deployment: the float model
        return _mm(x, w, precision)
    if spec.mode == "rtn":            # Table 3: RTN weights per column
        q = _levels(spec.weight_bits)
        wmax = jnp.maximum(jnp.max(jnp.abs(w), 0, keepdims=True), 1e-12)
        w = jnp.clip(jnp.round(w * (q / wmax)), -q, q) * (wmax / q)
    qi = _levels(spec.input_bits)
    xq = beta / qi * jnp.round(jnp.clip(x, -beta, beta) * (qi / beta))
    y = _mm(xq, w, precision)
    bound = spec.out_bound * beta * jnp.max(jnp.abs(w), 0)
    qo = _levels(spec.output_bits)
    return jnp.clip(bound / qo * jnp.round(y * (qo / bound)), -bound, bound)


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def logits_at(params, tokens, length, rows, *, spec: Spec, precision):
    """Logits [len(rows), vocab] of the sequence ``tokens[:length]``
    (padded to a fixed size) at positions ``rows``."""
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = params["embed"]["tokens"][tokens]
    causal = (pos[None, :] <= pos[:, None]) & (pos[None, :] < length)
    hd, nq, nkv = spec.head_dim, spec.heads, spec.kv_heads
    group = nq // nkv

    def layer(x, p):
        h = _rms(x, p["ln1"]["scale"], spec.eps)
        qkv = _project(p["attn"]["qkv"], h, spec, precision)
        q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], -1)
        q = _rope(q.reshape(s, nq, hd), pos, spec.rope_theta)
        k = _rope(k.reshape(s, nkv, hd), pos, spec.rope_theta)
        k = k.astype(jnp.bfloat16).astype(jnp.float32)   # stored KV dtype
        v = v.reshape(s, nkv, hd).astype(jnp.bfloat16).astype(jnp.float32)
        k = jnp.repeat(k, group, axis=1).transpose(1, 2, 0)   # [H, hd, S]
        v = jnp.repeat(v, group, axis=1).transpose(1, 0, 2)   # [H, S, hd]
        q = q.transpose(1, 0, 2)                              # [H, S, hd]
        parts = []
        for i in range(0, s, Q_BLOCK):       # query rows in blocks: fits
            sc = _mm(q[:, i:i + Q_BLOCK], k, precision) * hd ** -0.5
            sc = jnp.where(causal[None, i:i + Q_BLOCK], sc, -jnp.inf)
            parts.append(_mm(jax.nn.softmax(sc, -1), v, precision,
                             b_axis=-1))
        att = jnp.concatenate(parts, 1).transpose(1, 0, 2).reshape(
            s, nq * hd)
        x = x + _project(p["attn"]["o"], att, spec, precision)
        h = _rms(x, p["ln2"]["scale"], spec.eps)
        gate, up = jnp.split(_project(p["ffn"]["gate_up"], h, spec,
                                      precision), 2, -1)
        x = x + _project(p["ffn"]["down"], jax.nn.silu(gate) * up, spec,
                         precision)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    h = _rms(x[rows], params["final_norm"]["scale"], spec.eps)
    if spec.tied:
        logits = _mm(h, params["embed"]["tokens"].T, precision)
    else:
        logits = _project(params["lm_head"], h, spec, precision)
    return logits[:, :spec.vocab]


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """``max(ref) - ref[chosen]`` of each row."""
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, chosen[:, None], -1)[:, 0]
    return best - got


def summary(g: np.ndarray, prefix: str) -> dict:
    """The widest and the mean gap of the checked tokens, and the share
    of them that are not the reference's first choice."""
    return {f"{prefix}_gap_max": float(g.max()),
            f"{prefix}_gap_mean": float(g.mean()),
            f"{prefix}_mismatch_share": float(np.mean(g > 0))}


def check_requests(params, spec: Spec, served, *, pad_to: int,
                   rows_to: int) -> dict:
    """Compare served greedy tokens with the reference and the control.

    ``served`` is a list of ``(prompt, tokens)`` pairs. For each, the
    reference runs once over ``prompt + tokens[:-1]``, padded to
    ``pad_to`` (the positions scored padded to ``rows_to``, so one
    executable serves every request), and scores the positions that chose
    each served token; the fp8 control runs over the same tokens and its
    first choice at each position is scored the same way. Returns the
    ``served_*`` and ``control_*`` summaries, the number of tokens, and
    ``gap_ratio``: the served tokens' mean gap over the control's.
    """
    got, low = [], []
    for prompt, toks in served:
        seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
        n, m = len(seq), len(toks)
        padded = np.zeros(pad_to, np.int32)
        padded[:n] = seq
        rows = np.full(rows_to, len(prompt) - 1, np.int32)
        rows[:m] = np.arange(len(prompt) - 1, len(prompt) - 1 + m)
        args = (params, jnp.asarray(padded), jnp.int32(n), jnp.asarray(rows))
        ref = np.asarray(logits_at(*args, spec=spec,
                                   precision="highest"))[:m]
        lp = np.asarray(logits_at(*args, spec=spec, precision="fp8"))[:m]
        got.append(gaps(ref, np.asarray(toks)))
        low.append(gaps(ref, lp.argmax(-1)))
    out = {**summary(np.concatenate(got), "served"),
           **summary(np.concatenate(low), "control"),
           "tokens": int(sum(len(g) for g in got))}
    c = out["control_gap_mean"]
    out["gap_ratio"] = (out["served_gap_mean"] / c if c > 0
                        else float(out["served_gap_mean"] > 0) * np.inf)
    return out
