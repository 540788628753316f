"""Operations and bytes of the served model, computed from its shapes.

The parameter arithmetic is the benchmark's copy of
``benchmarks/flops.py::param_counts`` for a dense SwiGLU decoder (2
operations per matmul parameter per token). Attention adds, per layer and
per token, ``4 * keys * heads * head_dim``: scores and values against the
keys the token attends.
"""

from __future__ import annotations

from bench.model import Spec


def matmul_params(spec: Spec) -> int:
    """Matmul parameters one token passes through (layers and head; the
    embedding is a lookup). The head counts the real vocabulary."""
    d, hd = spec.d, spec.head_dim
    attn = d * spec.qkv_out + spec.heads * hd * d
    mlp = 3 * d * spec.ff
    return spec.layers * (attn + mlp) + d * spec.vocab


def attention_flops(spec: Spec, keys: int) -> float:
    """Attention operations of one token that attends ``keys`` keys."""
    return 4.0 * keys * spec.heads * spec.head_dim * spec.layers


def prompt_flops(spec: Spec, prompt_len: int) -> float:
    """Operations of a whole prompt: position ``p`` attends ``p + 1``."""
    n = prompt_len
    return (2.0 * matmul_params(spec) * n
            + 4.0 * spec.heads * spec.head_dim * spec.layers
            * n * (n + 1) / 2)


def decode_flops(spec: Spec, prompt_len: int, j: int) -> float:
    """Operations that produced output token ``j >= 1`` (the forward at
    position ``prompt_len + j - 1``; token 0 comes out of the prompt)."""
    return 2.0 * matmul_params(spec) + attention_flops(spec, prompt_len + j)


def int4_matmul_work(m: int, k: int, n: int) -> tuple[float, float]:
    """``(operations, bytes)`` of one packed-int4 matmul ``[m, k] @ [k, n]``:
    2 m k n products; packed weights ``k n / 2`` bytes, per-column float32
    scales ``4 n``, float32 activations in ``4 m k`` and out ``4 m n``."""
    return 2.0 * m * k * n, k * n / 2 + 4.0 * n + 4.0 * m * k + 4.0 * m * n


def roofline_seconds(ops: float, nbytes: float, ops_per_s: float,
                     bytes_per_s: float) -> float:
    """Least time for the work: the larger of its compute and memory
    bounds."""
    return max(ops / ops_per_s, nbytes / bytes_per_s)
