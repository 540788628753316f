"""The benchmark's arithmetic: tails and rates from client records, the
program's counters, FLOP and byte counts, against hand-computed values."""

import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import counters, flops, model, stats  # noqa: E402
from bench.run import load_reader  # noqa: E402


def _records():
    R = stats.Record
    return [
        # due 1.0, first token at 1.5, then every 0.1 s
        R(uid=0, due=1.0, prompt_len=10, max_new=4,
          greedy=True, token_times=[1.5, 1.6, 1.7, 1.8], status="finished"),
        # shed: no token ever; counts at its wait so far
        R(uid=1, due=2.0, prompt_len=10, max_new=4, greedy=False,
          status="shed"),
        # first token after the close: counts at its wait so far
        R(uid=2, due=8.0, prompt_len=10, max_new=4, greedy=False,
          token_times=[10.5, 10.6]),
        # still in flight at the close, two tokens before it
        R(uid=3, due=9.0, prompt_len=10, max_new=9, greedy=False,
          token_times=[9.2, 9.8, 10.4]),
        # due before the window: not counted, its tokens in the window are
        R(uid=4, due=-1.0, prompt_len=10, max_new=3, greedy=False,
          token_times=[0.5, 0.9], status="finished"),
    ]


@pytest.mark.parametrize("t_end,want", [(10.0, [0.5, 8.0, 2.0, 0.2]),
                                         (11.0, [0.5, 9.0, 2.5, 0.2])])
def test_ttft_counts_shed_and_unfinished_at_their_wait(t_end, want):
    # followed past the close to t_end: a first token by then counts
    vals = stats.ttft_values(_records(), 0.0, 10.0, t_end)
    assert vals == pytest.approx(want)


def test_tpot_over_tokens_delivered_by_the_close():
    vals = stats.tpot_values(_records(), 0.0, 10.0)
    assert vals == pytest.approx([0.1, 0.6])


def test_tokens_in_window_and_failures():
    recs = _records()
    assert stats.tokens_in(recs, 0.0, 10.0) == 4 + 2 + 2
    assert [r.failed for r in recs] == [False, True, False, False, False]


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (90, 4.6),
                                    (100, 5.0)])
def test_percentile_interpolates_like_numpy(q, want):
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.percentile(vals, q) == pytest.approx(want)
    assert stats.percentile(vals, q) == pytest.approx(np.percentile(vals, q))


def _ctx(**kw):
    base = dict(records=_records(), t0=0.0, t1=10.0, t_end=10.0,
                setup_s=12.5)
    base.update(kw)
    return types.SimpleNamespace(**base)


@pytest.mark.parametrize("name,want", [
    ("client.ttft_p90_ms.chat",
     np.percentile([0.5, 8.0, 2.0, 0.2], 90) * 1e3),
    ("tpot_p90_ms", np.percentile([0.1, 0.6], 90) * 1e3),
    ("tokens_per_s", 0.8),
    ("setup_s", 12.5),
])
def test_end_to_end_readers(name, want):
    assert load_reader(name)(_ctx()) == pytest.approx(want)


C0 = {"decode_steps": 100, "mixed_steps": 40,
      "phase_time": {"decode": 1.0, "mixed": 2.0, "prefill": 0.5}}
C1 = {"decode_steps": 260, "mixed_steps": 100,
      "phase_time": {"decode": 3.0, "mixed": 5.0, "prefill": 1.0}}


def test_counter_deltas():
    # 160 decode positions, 60 of them in fused steps: 2 s over 100
    assert counters.decode_step_seconds(C0, C1) == pytest.approx(0.02)
    assert counters.prefill_share(C0, C1) == pytest.approx(100 * 3.5 / 5.5)
    assert counters.decode_step_seconds(C0, C0) is None
    assert counters.prefill_share(C0, C0) is None
    ctx = types.SimpleNamespace(counters0=C0, counters1=C1)
    assert load_reader("sched.decode_step_ms.chat")(ctx) == \
        pytest.approx(20.0)
    for name in ("sched.prefill_share.chat", "sched.prefill_share.offline"):
        assert load_reader(name)(ctx) == pytest.approx(100 * 3.5 / 5.5)


def _spec(**kw):
    base = dict(name="t", layers=2, d=8, heads=4, kv_heads=2, head_dim=2,
                ff=16, vocab=10, rope_theta=1e4, eps=1e-5, tied=False,
                mode="analog", noise="none", weight_bits=16, input_bits=8,
                output_bits=8, out_bound=12.0, input_range=3.0, arch="a",
                deploy="analog")
    base.update(kw)
    return model.Spec(**base)


def test_flop_counts_by_hand():
    s = _spec()
    # qkv 8*(4+4)*2=128, o 8*8=64, mlp 3*8*16=384 per layer; head 80
    assert s.qkv_out == 16
    assert flops.matmul_params(s) == 2 * (128 + 64 + 384) + 80
    assert flops.attention_flops(s, 5) == 4 * 5 * 4 * 2 * 2
    # prompt of 3: 3 tokens of matmuls, attention over 1 + 2 + 3 keys
    assert flops.prompt_flops(s, 3) == 2 * 1232 * 3 + 4 * 4 * 2 * 2 * 6
    assert flops.decode_flops(s, 3, 2) == 2 * 1232 + 4 * 5 * 16


def test_int4_work_and_roofline_by_hand():
    ops, nbytes = flops.int4_matmul_work(16, 256, 512)
    assert ops == 2 * 16 * 256 * 512
    assert nbytes == 256 * 512 / 2 + 4 * 512 + 4 * 16 * 256 + 4 * 16 * 512
    t = flops.roofline_seconds(ops, nbytes, 393e12, 819e9)
    assert t == pytest.approx(nbytes / 819e9)      # memory-bound here
    assert flops.roofline_seconds(1e12, 1.0, 1e12, 1.0) == 1.0


def test_mfu_reader_by_hand():
    s = _spec()
    recs = [stats.Record(uid=0, due=0.0, prompt_len=3, max_new=3,
                         greedy=True, token_times=[1.0, 2.0, 12.0])]
    ctx = types.SimpleNamespace(records=recs, t0=0.0, t1=10.0, spec=s,
                                peaks={"bf16_flops_per_s": 1e4}, chips=1)
    want = flops.prompt_flops(s, 3) + flops.decode_flops(s, 3, 1)
    assert load_reader("step.mfu.offline")(ctx) == \
        pytest.approx(100 * want / 1e5)


def test_padded_vocab_and_key_of_large_seeds():
    assert _spec(vocab=32064).padded_vocab == 32256
    assert _spec(vocab=49155).padded_vocab == 49408
    a = np.asarray(model.key_of(2 ** 33 + 1))
    assert a.tolist() == np.asarray(model.key_of(2 ** 33 + 1)).tolist()
    assert a.tolist() != np.asarray(model.key_of(1)).tolist()
