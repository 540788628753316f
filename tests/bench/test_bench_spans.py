"""Program spans and lifecycle stamps: the span reduction and the idle
split on a hand-built trace with known answers, the readers of the
spans, stamps and KV counters, and a traced run of the serving path at a
small size on the CPU."""

import asyncio
import os
import pathlib
import re
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import model, run, spans, trace, traffic  # noqa: E402
from bench.stats import Record  # noqa: E402

from test_bench_harness import small_chat  # noqa: E402

MS = 1_000_000
SERVE_SRC = ROOT / "src" / "repro" / "serve"
NEW_READERS = ("frontend.queue_wait_p90_ms.chat",
               "sched.prefill_wait_p90_ms.chat",
               "sched.host_ms_per_step.chat",
               "device.idle_between_steps_share.chat",
               "kv.used_share.chat")
KV_COUNTERS = ("kv_used_tokens", "kv_capacity_tokens")


def _ev(name, start_ms, end_ms):
    return types.SimpleNamespace(name=name, start_ns=start_ms * MS,
                                 duration_ns=(end_ms - start_ms) * MS)


def _line(name, *events):
    return types.SimpleNamespace(name=name, events=list(events))


def _planes():
    """Window 0-100 ms. The engine's thread: a step_begin holding admit,
    upload and dispatch, a step_commit holding a readback, and a second
    step_commit that the window's close cuts. The event loop's thread:
    three command drains (one under the step_commit) and an event pump.
    The device: busy 20-40 and 45-58 ms."""
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        _line("main", _ev("bench.window", 0, 100)),
        _line("engine",
              _ev("$thread.py:54 run", 0, 100),
              _ev("serve.step_begin", 10, 30), _ev("serve.admit", 10, 14),
              _ev("serve.upload", 15, 18), _ev("serve.dispatch", 18, 22),
              _ev("serve.step_commit", 40, 70),
              _ev("serve.readback", 40, 60),
              _ev("serve.step_commit", 95, 110)),
        _line("loop",
              _ev("frontend.commands", 5, 8),
              _ev("frontend.commands", 32, 36),
              _ev("frontend.commands", 62, 66),
              _ev("frontend.events", 72, 76)),
    ])
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        _line("XLA Ops", _ev("%fusion.1 = f32[8]{0} fusion()", 20, 40),
              _ev("%fusion.2 = f32[8]{0} fusion()", 45, 58)),
    ])
    return [host, dev]


def test_idle_split_sums_to_the_idle_time():
    planes = _planes()
    r = trace.reduce(planes)
    got = spans.reduce_spans(planes)
    assert got["window_s"] == pytest.approx(r["window_s"])
    assert sum(got["idle_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert sum(got["idle_s"].values()) == pytest.approx(0.067)


def test_idle_goes_to_the_innermost_span():
    idle = spans.reduce_spans(_planes())["idle_s"]
    # idle 0-20: between 0-5 and 8-10, the command drain 5-8, then the
    # step_begin's children (admit shares its start and ends first);
    # idle 40-45 and 58-60: the readback; 60-70: the step_commit, over
    # the command drain at 62-66; 70-72 and 76-95: between; 72-76: the
    # event pump; 95-100: the cut step_commit
    assert idle == pytest.approx({
        spans.BETWEEN: 0.028, "frontend.commands": 0.003,
        "frontend.events": 0.004, "serve.admit": 0.004,
        "serve.step_begin": 0.001, "serve.upload": 0.003,
        "serve.dispatch": 0.002, "serve.readback": 0.007,
        "serve.step_commit": 0.015})


def test_self_time_leaves_out_child_spans():
    got = spans.reduce_spans(_planes())["spans"]
    assert set(got) == {"serve.step_begin", "serve.admit", "serve.upload",
                        "serve.dispatch", "serve.step_commit",
                        "serve.readback", "frontend.commands",
                        "frontend.events"}
    begin, commit = got["serve.step_begin"], got["serve.step_commit"]
    assert begin["count"] == 1 and begin["total_s"] == pytest.approx(0.020)
    assert begin["self_s"] == pytest.approx(0.020 - 0.004 - 0.003 - 0.004)
    assert commit["count"] == 2 and commit["total_s"] == pytest.approx(0.035)
    assert commit["self_s"] == pytest.approx(0.035 - 0.020)
    assert got["serve.readback"]["self_s"] == pytest.approx(0.020)
    # the event loop's spans are on another thread: not children
    assert got["frontend.commands"]["self_s"] == pytest.approx(0.011)
    ctx = types.SimpleNamespace(spans=spans.reduce_spans(_planes()))
    # (20 + 35 - 20) ms of engine-thread host time over 2 steps
    assert run.load_reader("sched.host_ms_per_step.chat")(ctx) == (
        pytest.approx(17.5))
    # idle with no serve.* span open: 28 + 3 + 4 ms of 100
    assert run.load_reader("device.idle_between_steps_share.chat")(ctx) == (
        pytest.approx(35.0))


def test_stamp_waits_censor_at_the_end_of_the_follow_on():
    records = [Record(uid=u, due=d, prompt_len=8, max_new=4, greedy=True)
               for u, d in ((0, 1.0), (1, 2.0), (2, 3.0), (3, 12.0))]
    ctx = types.SimpleNamespace(
        records=records, t0=0.0, t1=10.0, t_end=20.0,
        submit_time={0: 1.0, 1: 2.0, 2: 3.0, 3: 12.0},
        admit_time={0: 1.5, 1: 25.0, 3: 12.5},
        first_token_at={0: 4.0})
    # uid 1 admitted after the end and uid 2 never: waits so far; uid 3
    # was due after the close
    assert spans.stamp_waits(ctx, "submit_time", "admit_time") == (
        pytest.approx([0.5, 18.0, 17.0]))
    assert spans.stamp_waits(ctx, "admit_time", "first_token_at") == (
        pytest.approx([2.5]))


def test_readers_read_nothing_without_the_programs_telemetry():
    """A program that opens no spans, stamps no admission and keeps no
    KV counters (as before they were added) gives every new reader
    nothing to read, and none raises."""
    ctx = types.SimpleNamespace(
        records=[Record(uid=0, due=1.0, prompt_len=8, max_new=4,
                        greedy=True)],
        t0=0.0, t1=10.0, t_end=12.0, trace=None,
        counters0={"decode_steps": 0}, counters1={"decode_steps": 5})
    for name in NEW_READERS:
        assert run.load_reader(name)(ctx) is None, name


def program_span_names() -> set:
    """The literal names of the spans the serving path opens."""
    return {n for f in ("scheduler.py", "frontend.py")
            for n in re.findall(r'TraceAnnotation\("([^"]+)"\)',
                                (SERVE_SRC / f).read_text())}


def test_readers_look_for_the_spans_the_program_opens():
    names = program_span_names()
    assert names == {"serve.step_begin", "serve.admit", "serve.upload",
                     "serve.dispatch", "serve.step_commit",
                     "serve.readback", "frontend.commands",
                     "frontend.events"}
    assert {spans.STEP_BEGIN, spans.STEP_COMMIT, spans.READBACK} <= names
    assert all(n.startswith((spans.SERVE, spans.FRONTEND)) for n in names)


def test_small_chat_traced_on_cpu(monkeypatch, tmp_path):
    """The serving path traced at a small size on the CPU, read as the
    harness's ``--trace 1`` run reads a window: every span opens, the
    stamp, span and KV readers read, and the device metrics read
    nothing, since the CPU trace has no device plane."""
    from jax.profiler import ProfileData
    monkeypatch.setattr(run, "COUNTERS", run.COUNTERS + KV_COUNTERS)
    conf, mix = small_chat()
    spec = model.spec_of(conf)
    seed, seconds = 2 ** 33 + 5, 3.0
    eng = run.build_engine(spec, mix, seed)
    run.warm_up(eng, mix, spec.vocab)
    items = traffic.schedule(mix, spec.vocab, seed, seconds)
    after = traffic.follow_on(mix, spec.vocab, seed, seconds, run.FOLLOW_S,
                              len(items))
    trace_dir = os.path.join(tmp_path, "trace")
    win = asyncio.run(run.drive(eng, items + after, seconds, trace_dir,
                                bool(after)))
    planes = list(ProfileData.from_file(trace.find_xplane(trace_dir)).planes)
    ctx = types.SimpleNamespace(
        records=win["records"], t0=win["t0"], t1=win["t1"],
        t_end=win["t_end"], counters0=win["counters0"],
        counters1=win["counters1"], trace=trace.reduce(planes),
        spans=spans.reduce_spans(planes),
        submit_time=dict(eng.submit_time), admit_time=dict(eng.admit_time),
        first_token_at=dict(eng.first_token_at))
    assert set(ctx.spans["spans"]) == program_span_names()
    got = {n: run.load_reader(n)(ctx)
           for n in NEW_READERS + ("device.idle_share.chat",)}
    assert got["frontend.queue_wait_p90_ms.chat"] >= 0.0
    assert got["sched.prefill_wait_p90_ms.chat"] > 0.0
    assert got["sched.host_ms_per_step.chat"] > 0.0
    assert 0.0 < got["kv.used_share.chat"] <= 100.0
    assert got["device.idle_share.chat"] is None
    assert got["device.idle_between_steps_share.chat"] is None
