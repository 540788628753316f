"""Compile rehearsal: the serving kernels, compiled for a described TPU v5e.

Interpret mode cannot see what the chip's compiler refuses — block shapes
off the (8, 128) tiling, more VMEM than a kernel may use, vector ops
Mosaic cannot legalize. These tests compile each Pallas kernel of the
serving path for one chip of a described ``v5e:2x2`` topology at real
widths: llama-3.2-1b for the attention and matmul kernels (d_model 2048,
d_ff 8192, 32 query / 8 kv heads of 64, KV blocks of 16), mamba2-130m
for ``ssd_scan`` (24 heads of 64, state 128). The serving engine's
mixed step is compiled whole at a cut of the chat cell's phi-3-mini.
Nothing runs; a compile that passes is not a chip run.

The topology is described in a module fixture, never at import: only
one process may load the TPU library, and the test workers all import
this file. The persistent compilation cache is off around these
compiles (an entry compiled for a described chip cannot be read back
without one).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.analog import AnalogConfig
from repro.kernels.analog_matmul import analog_matmul
from repro.kernels.int4_matmul import int4_matmul
from repro.kernels.paged_attention import paged_flash_decode
from repro.kernels.paged_prefill import paged_flash_prefill
from repro.kernels.ref import PACK_GROUP, round_up
from repro.kernels.ssd_scan import ssd_scan
from repro.models import transformer as T
from repro.serve import scheduler as S

D, D_FF, NQ, NKV, HD, BS = 2048, 8192, 32, 8, 64, 16   # llama-3.2-1b
POOL, NB = 64, 8                                      # pool blocks, per slot


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the compile cache off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    """Lower and compile ``fn`` for the described chip; returns the
    executable (raises what the chip's compiler would raise)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("m", [8, 512])          # decode rows, prefill rows
@pytest.mark.parametrize("col_off", [False, True])
def test_analog_matmul_compiles(one_chip, m, col_off):
    bm = 8 if m <= 8 else 256
    shapes = [((m, D), jnp.float32), ((D, D_FF), jnp.float32),
              ((), jnp.float32), ((D_FF,), jnp.float32)]
    if col_off:
        shapes.append(((D_FF,), jnp.float32))
    _compile(one_chip, lambda *a: analog_matmul(*a, bm=bm), *shapes)


@pytest.mark.parametrize("m", [8, 512])
def test_int4_matmul_compiles(one_chip, m):
    bm = 8 if m <= 8 else 256
    packed = round_up(D_FF, PACK_GROUP) // 2
    _compile(one_chip, lambda x, w, s: int4_matmul(x, w, s, bm=bm),
             ((m, D), jnp.float32), ((D, packed), jnp.uint8),
             ((D_FF,), jnp.float32))


@pytest.mark.parametrize("num_splits", [1, 2])
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_flash_decode_compiles(one_chip, num_splits, kv_dtype):
    b = 8                                         # decode batch
    shapes = [((b, NQ, HD), jnp.float32), ((POOL, BS, NKV, HD), kv_dtype),
              ((POOL, BS, NKV, HD), kv_dtype), ((b, NB), jnp.int32),
              ((b,), jnp.int32), ((b,), jnp.int32)]
    if kv_dtype == jnp.int8:
        shapes += [((POOL, BS, NKV), jnp.float32)] * 2
    _compile(one_chip, lambda q, k, v, t, p, s, *sc: paged_flash_decode(
        q, k, v, t, p, s, scale=HD ** -0.5, k_scale=sc[0] if sc else None,
        v_scale=sc[1] if sc else None, num_splits=num_splits), *shapes)


# 16 is the scheduler's default prefill chunk; 128 is the largest chunk
# whose (S * heads, hd) query rows, scratch and logits fit the 16 MiB
# scoped VMEM at 32 heads (192 does not).
@pytest.mark.parametrize("b,s", [(4, 16), (1, 128)])
def test_paged_flash_prefill_compiles(one_chip, b, s):
    _compile(one_chip, lambda q, k, v, t, p, st: paged_flash_prefill(
        q, k, v, t, p, st, scale=HD ** -0.5),
        ((b, s, NQ, HD), jnp.float32), ((POOL, BS, NKV, HD), jnp.bfloat16),
        ((POOL, BS, NKV, HD), jnp.bfloat16), ((b, NB), jnp.int32),
        ((b,), jnp.int32), ((b,), jnp.int32))


def test_paged_prefill_dispatch_splits_long_chunk(one_chip, monkeypatch):
    """A 256-token chunk (8192 query rows) overflows one kernel call's
    VMEM; the dispatch scores it in pieces that each compile."""
    from repro.kernels import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    _compile(one_chip, lambda q, k, v, t, p, st:
             dispatch.paged_prefill_attention(q, k, v, t, p, st, HD ** -0.5),
             ((1, 256, NQ, HD), jnp.float32),
             ((POOL, BS, NKV, HD), jnp.bfloat16),
             ((POOL, BS, NKV, HD), jnp.bfloat16), ((1, 24), jnp.int32),
             ((1,), jnp.int32), ((1,), jnp.int32))


def test_ssd_scan_compiles(one_chip):
    bh, s, p, n = 24, 256, 64, 128                # mamba2-130m, batch 1
    _compile(one_chip, lambda *a: ssd_scan(*a, chunk=128),
             ((bh, s, p), jnp.float32), ((bh, s), jnp.float32),
             ((bh,), jnp.float32), ((bh, s, n), jnp.float32),
             ((bh, s, n), jnp.float32))


def test_mixed_step_moves_rows_not_the_cache(one_chip):
    """The engine's fused mixed step for phi-3-mini's cache (32 heads of
    96, bf16 KV) cut to 2 layers, 16 slots x 256 and a ``prefill_batch``
    of 2: its slot gather and scatter hold no slot-major copy of the
    cache, and its temporaries exceed the decode step's by less than one
    whole K+V. The decode step, which gathers and scatters nothing, is
    the base: where head dim is under 128 the chip's default layout of
    the cache argument puts the positions minor, so every step holds
    layout copies of K and V. The feed-forward and vocab widths are cut
    so that the weights' temporaries do not hide the cache's."""
    slots, max_len, pb, chunk = 16, 256, 2, 16
    cfg = dataclasses.replace(get_config("phi-3-mini-4k"), num_layers=2,
                              d_ff=256, vocab_size=512)
    acfg = AnalogConfig(mode="analog", train_noise=False)
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda k: T.init_model(k, cfg)[0], jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(lambda: T.init_caches(
        cfg, slots, max_len, jnp.bfloat16, per_slot=True)))
    f32, i32 = jnp.float32, jnp.int32
    decode = on_chip([jax.ShapeDtypeStruct(s, d) for s, d in [
        ((slots,), i32), ((slots,), i32), ((slots,), f32),
        ((slots, 2), jnp.uint32), ((slots,), i32), ((slots,), f32),
        ((slots,), i32), ((slots,), f32), ((slots,), i32)]])
    prefill = on_chip(dict(
        pf_idx=jax.ShapeDtypeStruct((pb,), i32),
        pf_toks=jax.ShapeDtypeStruct((pb, chunk), i32),
        pf_mask=jax.ShapeDtypeStruct((pb, chunk), f32),
        pf_off=jax.ShapeDtypeStruct((pb,), i32)))
    flags = dict(cfg=cfg, acfg=acfg, use_top_k=False, use_top_p=False, k=1)
    step = S._step_jit.lower(params, caches, *decode, **flags).compile()
    mixed = S._mixed_step_jit.lower(params, caches, *decode, **prefill,
                                    **flags, paged=False).compile()
    k = caches["k"]
    slot_major = (k.shape[1], k.shape[0]) + k.shape[2:]
    assert "bf16[%s]" % ",".join(map(str, slot_major)) not in mixed.as_text()
    kv = 2 * k.size * k.dtype.itemsize
    temp = lambda c: c.memory_analysis().temp_size_in_bytes
    assert temp(mixed) - temp(step) < kv
