"""Open-loop request lifecycle: cancellation, deadlines, shedding, chaos.

PR 9's contracts over the continuous-batching engine:

* **cancellation at any stage** — queued, mid-prefill, mid-decode, and
  *mid-speculative-window* (deferred to the commit boundary by the
  cancel-vs-rewind ordering contract, ``serve.kv_pool``) — always
  releases every KV block, COW tail and state-snapshot ref: pool
  conservation ``free + cached + live == pool`` holds after every event;
* **deadlines** (TTFT and end-to-end) retire requests as ``timed_out``
  with their partial output at step boundaries;
* **load shedding** — a bounded admission queue rejects overflow with an
  explicit reason and the books always balance (no silent drop:
  every submitted uid reaches exactly one terminal status);
* **chaos-tested recovery** — injected faults at the dispatch, admission
  allocator and health-read points leave the engine serving: in-flight
  requests surface explicit ``errored`` terminals, fresh requests after
  the fault still complete bitwise-identically to a healthy engine;
* **churn** — randomized admit/cancel/timeout/finish interleavings
  across all four engine families hold the conservation invariants after
  every event, and the *survivors* finish bitwise identical to a
  closed-loop run of the same workload (admission parity extended to
  arbitrary lifecycle interleavings). Property-based when ``hypothesis``
  is installed (``strategies`` guard), seeded-random always;
* the **async frontend** (``serve.frontend``): token streaming matches
  terminal results, cancel mid-stream, deterministic ``ShedError``;
* **lifecycle telemetry** — submit, admission and first-token stamps in
  order on one clock, and the KV occupancy counters' hand count.
"""

import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import devices
from repro.core.analog import AnalogConfig
from repro.models import build
from repro.serve.frontend import AsyncServeFrontend, ShedError
from repro.serve.kv_pool import KVPool
from repro.serve.scheduler import Request, SchedulerConfig, ServeEngine

from strategies import HAVE_HYPOTHESIS, given, settings, st

FAMILIES = ["granite-3-8b", "mamba2-130m", "jamba-v0.1-52b", "dbrx-132b"]

_BUILT: dict = {}


def _build(arch, seed=0):
    """Reduced family config + params (memoized: the suite churns many
    engines over the same weights)."""
    key = (arch, seed)
    if key not in _BUILT:
        cfg = get_config(arch).reduce()
        if cfg.num_experts:   # no-drop capacity: deterministic greedy
            cfg = dataclasses.replace(
                cfg, capacity_factor=float(cfg.num_experts))
        _BUILT[key] = build(cfg, jax.random.PRNGKey(seed))
    return _BUILT[key]


def _prompt(cfg, n, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, n).astype(np.int32)


def _scfg(**kw):
    base = dict(num_slots=2, max_len=32, prefill_chunk=4, paged=True,
                kv_block_size=4)
    base.update(kw)
    return SchedulerConfig(**base)


def _assert_conserved(eng):
    """Pool conservation + refcount bookkeeping, both pools."""
    for pool in (eng.pool, eng.state_pool):
        if pool is None:
            continue
        assert (pool.num_free + pool.num_cached + pool.num_live
                == pool.num_blocks), "block conservation broken"
        assert (sum(pool._ref.values())
                == sum(len(v) for v in pool._owned.values())), \
            "sum of refcounts != sum of owned blocks"


def _reqs(cfg, n, seed=0, max_new=5, temperature=0.8):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(3, 9))
                                        ).astype(np.int32),
                    max_new=int(rng.integers(2, max_new + 1)),
                    temperature=temperature, top_k=50, seed=seed + i)
            for i in range(n)]


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------

def test_cancel_at_every_stage_releases_everything():
    """Cancel a queued, a mid-prefill and a mid-decode request; every
    stage must release its blocks (conservation after each event) and
    the surviving request must still finish with its full budget."""
    cfg, params, _ = _build("granite-3-8b")
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"),
                      _scfg(num_slots=2, max_len=48, prefill_chunk=4))
    long_prompt = _prompt(cfg, 12)    # 3 chunks -> spans several steps
    eng.submit(Request(uid=0, prompt=long_prompt, max_new=6,
                       temperature=0.0))
    eng.submit(Request(uid=1, prompt=_prompt(cfg, 4, seed=5), max_new=8,
                       temperature=0.0))
    eng.submit(Request(uid=2, prompt=_prompt(cfg, 4, seed=6), max_new=4,
                       temperature=0.0))
    # queued cancel: uid 2 waits behind the two slots
    assert eng.status[2] == "queued"
    assert eng.cancel(2)
    assert eng.status[2] == "cancelled" and len(eng.results[2]) == 0
    _assert_conserved(eng)
    eng.step()                         # first prefill chunks
    assert eng.status[0] == "prefill"  # 12-token prompt still chunking
    assert eng.cancel(0)               # mid-prefill cancel
    assert eng.status[0] == "cancelled"
    _assert_conserved(eng)
    while eng.status[1] != "decode":
        eng.step()
    assert eng.cancel(1)               # mid-decode cancel
    assert eng.status[1] == "cancelled"
    assert 0 < len(eng.results[1]) < 8    # partial output preserved
    _assert_conserved(eng)
    assert eng.pool.num_live == 0
    assert eng.cancel_count == 3
    assert not eng.cancel(1)           # already terminal: not an error
    # engine still serves: a fresh request completes bitwise vs solo
    solo = ServeEngine(params, cfg, AnalogConfig(mode="off"),
                       _scfg(num_slots=2, max_len=48, prefill_chunk=4)
                       ).run([Request(uid=7, prompt=_prompt(cfg, 5),
                                      max_new=5, temperature=0.0)])[7]
    after = eng.run([Request(uid=7, prompt=_prompt(cfg, 5), max_new=5,
                             temperature=0.0)])[7]
    np.testing.assert_array_equal(solo, after)


def test_deferred_cancel_mid_speculative_window():
    """A cancel landing between ``step_begin`` and ``step_commit`` of a
    speculative verify window must be deferred to the commit boundary —
    the slot stays live through the in-flight step, the retirement
    happens at commit, and conservation holds throughout."""
    cfg, params, _ = _build("granite-3-8b")
    scfg = _scfg(num_slots=2, max_len=48, prefill_chunk=4,
                 speculative=True, draft="self", draft_k=3)
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"), scfg)
    assert eng.spec_enabled
    for r in _reqs(cfg, 2, max_new=12, temperature=0.0):
        eng.submit(r)
    pending = None
    for _ in range(30):                # drive until a spec window opens
        pending = eng.step_begin()
        if pending is not None and pending["op"] == "spec":
            break
        if pending is not None:
            eng.step_commit(pending)
        pending = None
    assert pending is not None and pending["op"] == "spec"
    uid = eng.slots[pending["decode_rows"][0]].req.uid
    assert eng.pool.in_window(uid)
    # mid-window: release refuses, cancel defers
    with pytest.raises(ValueError, match="rewind window"):
        eng.pool.release(uid)
    assert eng.cancel(uid)
    assert eng.status[uid] == "decode"       # still live: deferred
    assert eng.slots[pending["decode_rows"][0]] is not None
    eng.step_commit(pending)                 # drain applies the cancel
    assert eng.status[uid] in ("cancelled", "finished")
    assert not eng.pool.in_window(uid)
    _assert_conserved(eng)
    eng.run()                                # remaining request finishes
    assert eng.pool.num_live == 0
    _assert_conserved(eng)


def test_kv_pool_release_in_window_raises():
    """Unit contract: ``release`` of a uid inside an open rewind window
    is a ``ValueError`` naming the fix (commit first); after
    ``end_window`` the same release succeeds."""
    pool = KVPool(num_blocks=8, block_size=4)
    pool.alloc(1, 2)
    pool.alloc(2, 1)
    pool.begin_window([1])
    with pytest.raises(ValueError, match="step_commit"):
        pool.release(1)
    pool.release(2)                    # uids outside the window: fine
    with pytest.raises(ValueError, match="window already open"):
        pool.begin_window([2])
    pool.end_window()
    pool.release(1)
    assert pool.num_live == 0


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------

def test_ttft_deadline_times_out_queued_and_prefilling():
    """Requests past their TTFT deadline are retired ``timed_out`` at
    the next step boundary — both while queued and during prefill."""
    cfg, params, _ = _build("granite-3-8b")
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"),
                      _scfg(num_slots=1, max_len=48))
    eng.submit(Request(uid=0, prompt=_prompt(cfg, 12), max_new=4,
                       temperature=0.0, ttft_deadline=60.0))
    eng.submit(Request(uid=1, prompt=_prompt(cfg, 4, seed=5), max_new=4,
                       temperature=0.0, ttft_deadline=60.0))
    eng.step()                               # uid 0 prefilling, uid 1 queued
    assert eng.status[0] == "prefill" and eng.status[1] == "queued"
    # age both past their deadline deterministically (no sleeps in CI)
    eng.submit_time[0] -= 120.0
    eng.submit_time[1] -= 120.0
    eng.step()
    assert eng.status[0] == "timed_out" and eng.status[1] == "timed_out"
    assert "TTFT" in eng.errors[0] and "queued" in eng.errors[1]
    assert len(eng.results[0]) == 0
    assert eng.timeout_count == 2
    assert eng.pool.num_live == 0
    _assert_conserved(eng)


def test_e2e_deadline_preserves_partial_output():
    """An end-to-end deadline tripping mid-decode keeps the tokens
    decoded so far and reports the reason."""
    cfg, params, _ = _build("granite-3-8b")
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"),
                      _scfg(max_len=48))
    eng.submit(Request(uid=0, prompt=_prompt(cfg, 4), max_new=24,
                       temperature=0.0, deadline=60.0))
    while eng.status[0] != "decode":
        eng.step()
    eng.step()
    n = len(eng.results.get(0, ()))          # partial so far
    eng.submit_time[0] -= 120.0
    eng.step()
    assert eng.status[0] == "timed_out"
    assert len(eng.results[0]) >= max(1, n)
    assert "end-to-end deadline" in eng.errors[0]
    assert eng.num_active == 0 and eng.pool.num_live == 0
    _assert_conserved(eng)


# ---------------------------------------------------------------------------
# shedding
# ---------------------------------------------------------------------------

def test_bounded_queue_sheds_with_reason_and_books_balance():
    """`try_submit` past ``max_queue`` sheds with an explicit reason;
    accepted + shed == submitted and every uid reaches a terminal —
    the no-silent-drop ledger."""
    cfg, params, _ = _build("granite-3-8b")
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"),
                      _scfg(num_slots=1, max_queue=2))
    # a request that can never fit is shed (distinct reason), not raised
    big = Request(uid=99, prompt=_prompt(cfg, 4), max_new=999,
                  temperature=0.0)
    assert "max_len" in eng.try_submit(big)
    reqs = _reqs(cfg, 6, max_new=3, temperature=0.0)
    reasons = [eng.try_submit(r) for r in reqs]
    accepted = sum(r is None for r in reasons)
    shed = [r for r in reasons if r is not None]
    assert accepted == 2 and len(shed) == 4   # slots empty: queue bounds
    assert all("queue full" in r for r in shed)
    assert eng.submitted == 7 and eng.shed_count == 5
    eng.run()
    statuses = [eng.status[r.uid] for r in reqs]
    assert sorted(statuses) == ["finished", "finished"] + ["shed"] * 4
    assert eng.submitted == 7 == (
        sum(s == "finished" for s in eng.status.values())
        + eng.shed_count)
    _assert_conserved(eng)


# ---------------------------------------------------------------------------
# chaos
# ---------------------------------------------------------------------------

class _Chaos:
    """Scripted fault injector: raise on the n-th visit to one point."""

    def __init__(self, point, at=1, exc=RuntimeError):
        self.point, self.at, self.exc = point, at, exc
        self.seen = 0

    def __call__(self, point):
        if point == self.point:
            self.seen += 1
            if self.seen == self.at:
                raise self.exc(f"chaos: injected {point} fault")


def test_chaos_dispatch_fault_errored_then_keeps_serving():
    """A raising dispatch mid-run: in-flight requests surface explicit
    ``errored`` terminals (reason recorded), pools and caches are
    rebuilt, and the engine serves fresh requests bitwise-identically
    to a healthy engine."""
    cfg, params, _ = _build("granite-3-8b")
    hook = _Chaos("dispatch", at=3)
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"), _scfg(),
                      chaos_hook=hook)
    reqs = _reqs(cfg, 2, max_new=8, temperature=0.0)
    res = eng.run(reqs)
    assert hook.seen >= 3
    assert eng.fault_count == 1
    errored = [u for u in (0, 1) if eng.status[u] == "errored"]
    assert errored, "the in-flight step's requests must surface errors"
    for u in errored:
        assert "chaos: injected dispatch fault" in eng.errors[u]
        assert u in res                     # partial output, not a hang
    _assert_conserved(eng)
    # recovery: same engine serves a fresh request == healthy engine
    probe = Request(uid=50, prompt=_prompt(cfg, 5), max_new=5,
                    temperature=0.0)
    healthy = ServeEngine(params, cfg, AnalogConfig(mode="off"),
                          _scfg()).run([dataclasses.replace(probe)])[50]
    np.testing.assert_array_equal(
        eng.run([dataclasses.replace(probe)])[50], healthy)
    _assert_conserved(eng)


def test_chaos_without_tolerance_flag_still_degrades():
    """Installing a chaos hook implies fault tolerance; a bare engine
    (no hook, ``fault_tolerant=False``) re-raises — opt-in, not a
    behavior change for existing callers."""
    cfg, params, _ = _build("granite-3-8b")
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"), _scfg())
    assert not eng._tolerant
    hooked = ServeEngine(params, cfg, AnalogConfig(mode="off"), _scfg(),
                         chaos_hook=_Chaos("dispatch", at=10 ** 9))
    assert hooked._tolerant


def test_chaos_allocator_fault_sheds_head_only():
    """An allocator exhaustion fault at admission sheds the request at
    the queue head with an explicit reason; everything else completes."""
    cfg, params, _ = _build("granite-3-8b")
    hook = _Chaos("alloc", at=2, exc=MemoryError)
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"),
                      _scfg(num_slots=1), chaos_hook=hook)
    reqs = _reqs(cfg, 3, max_new=3, temperature=0.0)
    eng.run(reqs)
    statuses = sorted(eng.status[r.uid] for r in reqs)
    assert statuses == ["finished", "finished", "shed"]
    shed_uid = next(r.uid for r in reqs if eng.status[r.uid] == "shed")
    assert "allocator fault at admission" in eng.errors[shed_uid]
    assert eng.shed_count == 1 and eng.fault_count == 0
    _assert_conserved(eng)


def test_chaos_corrupted_health_read_skips_watchdog():
    """A corrupted health read (raise, then NaN) must skip that watchdog
    round — counted in ``health_faults``, never a recalibration decision
    on garbage — while serving completes normally."""
    cfg, params, labels = _build("granite-3-8b")
    dp = devices.attach_device_state(
        params, labels, jax.random.PRNGKey(7),
        devices.DeviceConfig(sigma_gain=0.02, nu_median=0.1, nu_sigma=0.3))
    hook = _Chaos("health", at=1)
    eng = ServeEngine(dp, cfg, AnalogConfig(mode="analog"),
                      _scfg(paged=False, max_len=48, drift_dt=4.0,
                            recalibrate=True, recal_interval=1,
                            recal_threshold=0.05),
                      chaos_hook=hook)
    assert eng.drift_enabled
    res = eng.run(_reqs(cfg, 2, max_new=6, temperature=0.0))
    assert all(len(v) > 0 for v in res.values())
    assert eng.health_faults >= 1
    assert all(eng.status[u] == "finished" for u in res)
    # every non-faulted round still health-checked
    assert eng.watchdog_checks == hook.seen - 1


# ---------------------------------------------------------------------------
# churn: randomized lifecycle interleavings, every family
# ---------------------------------------------------------------------------

def _churn(arch: str, seed: int) -> None:
    """Drive a randomized admit/cancel/timeout/finish interleaving and
    assert conservation after every event plus survivor bitwise parity
    vs a closed-loop run of the same workload."""
    cfg, params, _ = _build(arch)
    acfg = AnalogConfig(mode="off")
    scfg = _scfg(num_slots=2, max_len=32, max_queue=4)
    # every third request carries deadlines the churn loop can age past
    reqs = [dataclasses.replace(r, deadline=60.0, ttft_deadline=60.0)
            if r.uid % 3 == 0 else r
            for r in _reqs(cfg, 6, seed=seed, max_new=4)]
    ref = ServeEngine(params, cfg, acfg, scfg).run(
        [dataclasses.replace(r) for r in reqs])

    rng = np.random.default_rng(seed)
    eng = ServeEngine(params, cfg, acfg, scfg)
    pending_reqs = [dataclasses.replace(r) for r in reqs]
    disturbed: set = set()
    while pending_reqs or eng.num_active or eng.queue_depth:
        ev = rng.integers(0, 4)
        if ev == 0 and pending_reqs:
            eng.try_submit(pending_reqs.pop(0))
        elif ev == 1:
            live = [u for u, s in eng.status.items()
                    if s in ("queued", "prefill", "decode")]
            if live:
                u = int(rng.choice(live))
                eng.cancel(u)
                disturbed.add(u)
        elif ev == 2:
            # deterministic timeout: age a deadline-carrying request
            live = [u for u, s in eng.status.items()
                    if s in ("queued", "prefill", "decode") and u % 3 == 0]
            if live:
                u = int(rng.choice(live))
                eng.submit_time[u] -= 120.0
                disturbed.add(u)
        eng.step()
        _assert_conserved(eng)
        assert eng.queue_high_water <= scfg.max_queue
    for pool in (eng.pool, eng.state_pool):
        if pool is not None:
            assert pool.num_live == 0
    # ledger: every submitted uid has exactly one terminal status
    terminals = ("finished", "cancelled", "timed_out", "shed", "errored")
    assert all(eng.status[r.uid] in terminals for r in reqs)
    assert eng.fault_count == 0
    # survivors decoded bitwise what the closed-loop run decoded
    for r in reqs:
        if eng.status[r.uid] == "finished" and r.uid not in disturbed:
            np.testing.assert_array_equal(eng.results[r.uid], ref[r.uid])


@pytest.mark.parametrize("arch", FAMILIES)
def test_churn_conservation_and_survivor_parity(arch):
    """Seeded churn (always runs, hypothesis or not) per family."""
    _churn(arch, seed=2)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 1000))
def test_churn_conservation_property(seed):
    """Property-based churn on the dense family (skips without
    hypothesis — the seeded test above still covers every family)."""
    _churn("granite-3-8b", seed)


# ---------------------------------------------------------------------------
# async frontend
# ---------------------------------------------------------------------------

def test_frontend_streams_cancels_and_sheds():
    """End-to-end asyncio frontend: streamed tokens equal the terminal
    result (which equals the engine's record), a cancel mid-stream
    terminates with partial output, and overflow submits raise
    ``ShedError`` deterministically."""
    cfg, params, _ = _build("granite-3-8b")
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"),
                      _scfg(num_slots=1, max_queue=1))
    fe = AsyncServeFrontend(eng)

    async def scenario():
        await fe.start()
        a = await fe.submit(Request(uid=0, prompt=_prompt(cfg, 4),
                                    max_new=6, temperature=0.0))
        b = await fe.submit(Request(uid=1, prompt=_prompt(cfg, 5, seed=4),
                                    max_new=24, temperature=0.0))
        # queue is now full (uid 1 queued behind uid 0's slot)
        with pytest.raises(ShedError, match="queue full"):
            await fe.submit(Request(uid=2, prompt=_prompt(cfg, 3, seed=5),
                                    max_new=2, temperature=0.0))
        streamed = [t async for t in a.stream()]
        res_a = await a.result()
        # cancel b after its first streamed token
        async for _ in b.stream():
            assert await fe.cancel(1)
            break
        res_b = await b.result()
        await fe.stop()
        return streamed, res_a, res_b

    streamed, res_a, res_b = asyncio.run(scenario())
    assert res_a.status == "finished" and res_a.ttft is not None
    np.testing.assert_array_equal(streamed, res_a.tokens)
    np.testing.assert_array_equal(res_a.tokens, eng.results[0])
    assert res_b.status == "cancelled"
    assert 0 < len(res_b.tokens) < 24        # partial output surfaced
    assert eng.shed_count == 1 and eng.status[2] == "shed"
    assert eng.pool.num_live == 0
    _assert_conserved(eng)


def test_frontend_closed_loop_parity():
    """The overlapped begin/commit split must not change tokens: the
    frontend's outputs are bitwise the closed-loop ``run()`` outputs."""
    cfg, params, _ = _build("granite-3-8b")
    reqs = _reqs(cfg, 4, seed=1, max_new=6)
    ref = ServeEngine(params, cfg, AnalogConfig(mode="off"),
                      _scfg()).run([dataclasses.replace(r) for r in reqs])
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"), _scfg())
    fe = AsyncServeFrontend(eng)

    async def scenario():
        await fe.start()
        handles = [await fe.submit(dataclasses.replace(r)) for r in reqs]
        out = [await h.result() for h in handles]
        await fe.stop()
        return out

    for res in asyncio.run(scenario()):
        assert res.status == "finished"
        np.testing.assert_array_equal(res.tokens, ref[res.uid])


def test_lifecycle_stamps_are_ordered():
    """Every finished request is stamped at submit, at admission and at
    its first token, in that order; the frontend's TTFT and latency both
    run from the engine's submit stamp. Four requests on two slots, so
    two of them wait in the queue."""
    cfg, params, _ = _build("granite-3-8b")
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"), _scfg())
    fe = AsyncServeFrontend(eng)

    async def scenario():
        await fe.start()
        handles = [await fe.submit(r) for r in _reqs(cfg, 4, seed=2)]
        out = [await h.result() for h in handles]
        await fe.stop()
        return out

    for res in asyncio.run(scenario()):
        uid = res.uid
        assert res.status == "finished"
        sub, admit = eng.submit_time[uid], eng.admit_time[uid]
        first, done = eng.first_token_at[uid], eng.finished_at[uid]
        assert sub <= admit <= first <= done
        assert res.ttft == first - sub and res.latency == done - sub
    waits = sorted(eng.admit_time[u] - eng.submit_time[u] for u in range(4))
    assert waits[-1] > waits[0]         # the queued pair waited


@pytest.mark.parametrize("paged,capacity", [(False, 2 * 32),
                                            (True, 10 * 4)])
def test_kv_counters_hand_count(paged, capacity):
    """The KV occupancy counters on a scripted two-request run. Chunk 4,
    budget 10 (two chunks a step). A: 6 prompt tokens (2 left pads),
    3 new; B: 3 prompt tokens (1 pad), 2 new. At each dispatch the
    counter adds, per live slot, ``pos - start`` floored at 0:
    step 1, both prefill their first chunk: A 0-2 -> 0, B 0-1 -> 0;
    step 2, A's second chunk fused with B's decode: A 4-2, B 4-1 -> 5
    (B retires with its second token); step 3, A decodes a block of
    2: A 8-2 -> 6. Capacity: 3 steps of the layout's positions (2 slots
    of 32, or 10 blocks of 4)."""
    cfg, params, _ = _build("granite-3-8b")
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"),
                      _scfg(paged=paged, kv_blocks=10 if paged else 0))
    eng.run([Request(uid=0, prompt=_prompt(cfg, 6), max_new=3,
                     temperature=0.0),
             Request(uid=1, prompt=_prompt(cfg, 3, seed=4), max_new=2,
                     temperature=0.0)])
    assert [len(eng.results[u]) for u in (0, 1)] == [3, 2]
    assert len(eng.step_token_log) == 3
    assert eng.kv_used_tokens == 0 + 5 + 6
    assert eng.kv_capacity_tokens == 3 * capacity


@pytest.mark.parametrize("fault", [False, True])
def test_finite_qps_replay_exits_nonzero_on_step_fault(monkeypatch, fault):
    """``launch.serve --qps`` survives step faults while it serves (the
    frontend's contract) but a finite replay with any fault or errored
    request fails the process; a clean replay returns normally."""
    import sys

    from repro.launch import serve as serve_mod

    if fault:
        def boom(self):
            raise RuntimeError("injected dispatch fault")
        monkeypatch.setattr(ServeEngine, "_dispatch", boom)
    monkeypatch.setattr(serve_mod, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "llama-3.2-1b", "--reduced", "--paged",
        "--qps", "1000", "--num-requests", "2", "--new-tokens", "2",
        "--prompt-len", "4"])
    if fault:
        with pytest.raises(SystemExit) as e:
            serve_mod.main()
        assert e.value.code not in (0, None)
        assert "injected dispatch fault" in str(e.value.code)
    else:
        serve_mod.main()
