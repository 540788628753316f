"""Tensor-parallel serving: bitwise tp>=2 == tp=1 parity + honest gating.

The TP contract (docs/distributed.md): weights are column-parallel, every
activation is explicitly gathered back to replicated before the next
contraction, so the partitioned computation contains no cross-shard
floating-point reduction — greedy decode under tp>=2 must be **bitwise
identical** to single-device decode, across all four model families, with
the prefix cache warm-hitting and the speculative path engaged. jax locks
the device count at init, so the multi-device tests fork a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (same idiom as
test_sharding).

The in-process tests pin the honest-gating seam: a tp the runtime cannot
satisfy must surface ``gating_reasons["tensor_parallel"]`` and fall back
to a correct tp=1 engine — never a silent downgrade, never a wrong answer.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core.analog import AnalogConfig
from repro.models import build
from repro.serve.scheduler import Request, SchedulerConfig, ServeEngine


def _env():
    return dict(os.environ,
                PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                        "src"))


def _run_prog(prog, timeout=900):
    out = subprocess.run([sys.executable, "-c", prog], env=_env(),
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# multi-device parity (subprocess: 8 host devices)
# ---------------------------------------------------------------------------

_PARITY_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import numpy as np
    import jax
    from repro.configs import get_config
    from repro.core.analog import AnalogConfig
    from repro.models import build
    from repro.serve.scheduler import Request, SchedulerConfig, ServeEngine

    def build_arch(arch):
        cfg = get_config(arch).reduce()
        if cfg.num_experts:   # no-drop capacity (see test_decode)
            cfg = dataclasses.replace(
                cfg, capacity_factor=float(cfg.num_experts))
        return build(cfg, jax.random.PRNGKey(0))

    def run(cfg, params, tp, **kw):
        scfg = SchedulerConfig(num_slots=2, max_len=32, prefill_chunk=4,
                               paged=True, tp=tp, **kw)
        eng = ServeEngine(params, cfg, AnalogConfig(mode="off"), scfg)
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
        out = eng.run([Request(uid=0, prompt=prompt, max_new=6,
                               temperature=0.0)])[0]
        return np.asarray(out), eng

    rec = {"devices": len(jax.devices()), "parity": {}, "gating": {}}
    for arch in ["granite-3-8b", "mamba2-130m", "jamba-v0.1-52b",
                 "dbrx-132b"]:
        cfg, params, labels = build_arch(arch)
        o1, _ = run(cfg, params, 1)
        o2, e2 = run(cfg, params, 2)
        rec["parity"][arch] = bool(np.array_equal(o1, o2))
        rec["gating"][arch] = dict(e2.gating_reasons)

    # speculative under tp=2 (dense): drafter gates to unfused RTN-W4,
    # verification contract still forces bitwise tp parity
    cfg, params, labels = build_arch("granite-3-8b")
    s1, e1 = run(cfg, params, 1, speculative=True, draft_k=2)
    s2, e2 = run(cfg, params, 2, speculative=True, draft_k=2)
    rec["spec_parity"] = bool(np.array_equal(s1, s2))
    rec["spec_tp2_gating"] = dict(e2.gating_reasons)
    rec["spec_acceptance"] = [float(e1.spec_acceptance),
                              float(e2.spec_acceptance)]

    # prefix-cache warm hit under tp=2: warm == cold == tp=1 reference
    scfg = SchedulerConfig(num_slots=2, max_len=48, prefill_chunk=4,
                           paged=True, tp=2)
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"), scfg)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, 17).astype(np.int32)
    cold = eng.run([Request(uid=0, prompt=prompt, max_new=5,
                            temperature=0.0)])[0]
    warm = eng.run([Request(uid=1, prompt=prompt, max_new=5,
                            temperature=0.0)])[1]
    ref_eng = ServeEngine(params, cfg, AnalogConfig(mode="off"),
                          dataclasses.replace(scfg, tp=1))
    ref = ref_eng.run([Request(uid=0, prompt=prompt, max_new=5,
                               temperature=0.0)])[0]
    rec["prefix_skipped"] = int(eng.prefix_skipped_tokens)
    rec["prefix_parity"] = bool(np.array_equal(cold, warm)
                                and np.array_equal(warm, ref))

    # honest gating with real devices: heads=4 not divisible by tp=3
    o3, e3 = run(cfg, params, 3)
    rec["tp3_reason"] = e3.gating_reasons.get("tensor_parallel", "")
    rec["tp3_parity"] = bool(np.array_equal(run(cfg, params, 1)[0], o3))
    print(json.dumps(rec))
""")


@pytest.mark.slow
def test_tp_parity_all_families_subprocess():
    """tp=2 greedy decode is bitwise identical to tp=1 for dense / ssm /
    hybrid / moe, including speculative and prefix-warm-hit runs, and a
    non-divisible tp surfaces an honest gating reason while still
    serving bitwise-correct tp=1 output."""
    rec = _run_prog(_PARITY_PROG)
    assert rec["devices"] == 8
    for arch, ok in rec["parity"].items():
        assert ok, (arch, rec["gating"][arch])
    # tp itself never gated for the divisible families
    for arch in ("granite-3-8b", "jamba-v0.1-52b", "dbrx-132b"):
        assert "tensor_parallel" not in rec["gating"][arch]
    assert rec["spec_parity"]
    # under a mesh the packed-int4 drafter honestly gates to unfused W4
    assert "draft_packed_int4" in rec["spec_tp2_gating"]
    assert rec["spec_acceptance"][0] == rec["spec_acceptance"][1]
    assert rec["prefix_skipped"] > 0
    assert rec["prefix_parity"]
    assert "divisible" in rec["tp3_reason"]
    assert rec["tp3_parity"]


_BIG_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import numpy as np
    import jax
    from repro.configs import get_config
    from repro.core.analog import AnalogConfig
    from repro.models import build
    from repro.serve.scheduler import Request, SchedulerConfig, ServeEngine

    # qwen2.5-32b at FULL width (d_model 5120, 40 heads / 8 KV,
    # d_ff 27648): the per-layer shapes the tp=4 bytes-per-device table
    # proves fit. Depth and vocab are truncated so the smoke finishes on
    # CPU — width, not depth, is what sharding must handle.
    full = get_config("qwen2.5-32b")
    cfg = dataclasses.replace(full, name=full.name + "-tpsmoke",
                              num_layers=2, vocab_size=2048)
    cfg, params, labels = build(cfg, jax.random.PRNGKey(0))
    scfg = SchedulerConfig(num_slots=2, max_len=16, prefill_chunk=4,
                           paged=True, tp=2)
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"), scfg)
    prompt = (np.arange(3) % cfg.vocab_size).astype(np.int32)
    out = eng.run([Request(uid=0, prompt=prompt, max_new=2,
                           temperature=0.0)])[0]
    toks = [int(t) for t in np.asarray(out)]
    print(json.dumps({"mesh": eng.mesh is not None,
                      "gating": dict(eng.gating_reasons),
                      "d_model": cfg.d_model, "heads": cfg.num_heads,
                      "d_ff": cfg.d_ff, "tokens": toks}))
""")


@pytest.mark.slow
def test_big_config_serves_under_tp_subprocess():
    """Full-width qwen2.5-32b (depth/vocab truncated for CPU) constructs
    and serves a greedy request under tp=2 with the mesh actually
    active — the 'previously unservable config now fits' smoke."""
    rec = _run_prog(_BIG_PROG)
    assert rec["mesh"], rec["gating"]
    assert "tensor_parallel" not in rec["gating"]
    assert rec["d_model"] == 5120 and rec["heads"] == 40
    assert rec["d_ff"] == 27648
    assert len(rec["tokens"]) > 0


# ---------------------------------------------------------------------------
# honest gating (in-process: single host device)
# ---------------------------------------------------------------------------

def test_tp_gating_insufficient_devices_falls_back():
    """tp=2 on a 1-device runtime: honest reason, engine serves at tp=1
    and produces exactly the tp=1 output."""
    cfg = get_config("granite-3-8b").reduce()
    cfg, params, labels = build(cfg, jax.random.PRNGKey(0))
    acfg = AnalogConfig(mode="off")
    prompt = np.arange(5, dtype=np.int32)

    def run(tp):
        scfg = SchedulerConfig(num_slots=2, max_len=32, prefill_chunk=4,
                               tp=tp)
        eng = ServeEngine(params, cfg, acfg, scfg)
        out = eng.run([Request(uid=0, prompt=prompt, max_new=4,
                               temperature=0.0)])[0]
        return np.asarray(out), eng

    if len(jax.devices()) >= 2:
        pytest.skip("runtime has >=2 devices; the fallback cannot fire")
    o2, eng = run(2)
    assert eng.mesh is None
    assert "devices" in eng.gating_reasons["tensor_parallel"]
    o1, _ = run(1)
    assert np.array_equal(o1, o2)


def test_tp_gating_pallas_refused():
    """use_pallas engines refuse tensor parallelism with a reason (the
    kernels are single-device) instead of silently partitioning them."""
    reason = None
    import repro.distributed.sharding as shd
    cfg = get_config("granite-3-8b").reduce()
    acfg = AnalogConfig(mode="off", use_pallas=True)
    devs = jax.devices()
    if len(devs) < 2:
        # reason check only needs the API, not real devices
        import unittest.mock as mock
        with mock.patch.object(jax, "devices", lambda *a: [devs[0]] * 8):
            reason = shd.serve_tp_unsupported(cfg, acfg, 2, paged=True,
                                             chunk=8)
    else:
        reason = shd.serve_tp_unsupported(cfg, acfg, 2, paged=True, chunk=8)
    assert reason is not None and "allas" in reason


@pytest.mark.parametrize("arch,paged,chunk,refused", [
    ("granite-3-8b", True, 8, True),      # paged flash kernels
    ("granite-3-8b", False, 8, False),    # contiguous cache: jnp attention
    ("mamba2-130m", False, 128, True),    # whole SSD chunks: ssd_scan
    ("mamba2-130m", False, 8, False),     # ragged chunks: jnp SSD
])
def test_tp_gate_on_tpu_refuses_only_pallas_routes(arch, paged, chunk,
                                                   refused):
    """On TPU the gate refuses exactly the routes that reach a
    pallas_call (paged attention, whole-chunk SSD prefill) and lets the
    contiguous-cache jnp routes shard."""
    import unittest.mock as mock

    import repro.distributed.sharding as shd
    from repro.kernels import dispatch
    cfg = get_config(arch).reduce()
    dev = jax.devices()[0]
    with mock.patch.object(jax, "devices", lambda *a: [dev] * 8), \
            mock.patch.object(dispatch, "on_tpu", lambda: True):
        reason = shd.serve_tp_unsupported(cfg, AnalogConfig(mode="off"), 2,
                                          paged=paged, chunk=chunk)
    assert (reason is not None) == refused, reason
    if refused:
        assert "Pallas" in reason
