"""Trace reduction: busy time, idle share, per-operation time, idle gaps
and kernel calls, on a hand-built trace with known answers and on a small
trace recorded on a TPU v5e."""

import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402
from bench.run import load_reader  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000


def _ev(name, start_ms, dur_ms):
    return types.SimpleNamespace(name=name, start_ns=start_ms * MS,
                                 duration_ns=dur_ms * MS)


def _plane(name, **lines):
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=k, events=v)
                          for k, v in lines.items()])


INT4 = ("%int4_matmul.{} = f32[16,6144]{{1,0:T(8,128)}} custom-call("
        "f32[16,4096]{{1,0}} %x, u8[4096,3072]{{1,0}} %w, f32[6144]{{0}} %s)")
FUSION = "%fusion.{} = f32[16,8]{{0,1}} fusion(f32[16,6144]{{1,0}} %p), kind=kLoop"


def _planes():
    host = _plane("/host:CPU", main=[
        _ev("bench.window", 10, 100),
        _ev("PjitFunction(_step_jit)", 30, 25),
        _ev("sampling", 82, 5),
    ])
    dev = _plane("/device:TPU:0", **{
        "XLA Ops": [
            _ev(FUSION.format(3), 0, 20),           # clipped to 10-20
            _ev(INT4.format(7), 20, 10),
            _ev(FUSION.format(4), 25, 10),          # overlaps: union
            _ev(INT4.format(9), 60, 20),
            _ev(FUSION.format(5), 100, 30),         # clipped to 100-110
        ],
        "XLA Modules": [_ev("jit_step", 0, 200)],  # not an op line
    })
    return [host, dev]


def test_reduce_by_hand():
    r = trace.reduce(_planes())
    assert r["window_s"] == pytest.approx(0.100)
    # busy: 10-35, 60-80, 100-110 -> 55 ms of 100
    assert r["busy_s"] == pytest.approx(0.055)
    assert trace.idle_share(r) == pytest.approx(45.0)
    assert r["op_time"] == pytest.approx({"fusion f32[16,8]": 0.030,
                                          "int4_matmul f32[16,6144]": 0.030})
    gaps = dict(r["breakdown"]["idle_gaps"])
    # 35-60 overlaps the step dispatch (30-55) most; 80-100 the sampling
    assert gaps == pytest.approx({"PjitFunction(_step_jit)": 0.025,
                                  "sampling": 0.020})
    calls = trace.kernel_calls(r, "int4_matmul")
    assert [c[0] for c in calls] == pytest.approx([0.010, 0.020])
    assert calls[0][1][:2] == [("f32", (16, 6144)), ("f32", (16, 4096))]


def test_roofline_reader_by_hand():
    r = trace.reduce(_planes())
    peaks = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    ctx = types.SimpleNamespace(trace=r, peaks=peaks)
    m, k, n = 16, 4096, 6144
    nbytes = k * n / 2 + 4 * n + 4 * m * k + 4 * m * n
    least = max(2 * m * k * n / 393e12, nbytes / 819e9)
    want = 100 * 2 * least / 0.030
    assert load_reader("int4_matmul_roofline")(ctx) == pytest.approx(want)
    for name in ("device.idle_share.chat", "device.idle_share.offline"):
        assert load_reader(name)(ctx) == pytest.approx(45.0)


def test_no_trace_reads_nothing():
    ctx = types.SimpleNamespace(trace=None, peaks={})
    for name in ("int4_matmul_roofline", "device.idle_share.chat"):
        assert load_reader(name)(ctx) is None
    with pytest.raises(ValueError):
        trace.reduce([_plane("/host:CPU", main=[])])


def test_union_and_clip():
    assert trace.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert trace.clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]


RECORDED = sorted(DATA.glob("*.xplane.pb"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_v5e_trace(path):
    from jax.profiler import ProfileData
    r = trace.reduce(ProfileData.from_file(str(path)).planes)
    # two int4_matmul calls [32, 4096] @ [4096, 6144] and two small
    # fusions in the span; the device ran 158 us of its 12.86 ms
    assert r["devices"] == 1 and r["ops"] == 4
    assert r["window_s"] == pytest.approx(0.012860097)
    assert r["busy_s"] == pytest.approx(0.000158016)
    calls = trace.kernel_calls(r, "int4_matmul")
    assert [secs for secs, _ in calls] == pytest.approx([78.522e-6,
                                                         78.553e-6])
    assert calls[0][1][:2] == [("f32", (32, 6144)), ("f32", (32, 4096))]
