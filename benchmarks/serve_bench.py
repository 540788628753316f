"""Serving throughput benchmark: static pad-to-max vs continuous batching.

Drives the same mixed-length synthetic workload (ragged prompt lengths and
per-request token budgets) through

* **static** — the legacy ``serve.decode.generate`` loop: prompts padded to
  the workload max, requests batched in fixed groups of ``num_slots``, every
  group decoding until its *largest* budget is exhausted (the pre-scheduler
  serving path), and
* **continuous** — the request-level ``serve.scheduler.ServeEngine``: slots
  recycle the moment a request finishes, waiting prompts stream in as
  prefill chunks piggybacked on the decode batch (fused mixed steps), and
* **paged** — the same engine on the block-paged KV pool
  (``SchedulerConfig(paged=True)``): paged flash-decode reads plus paged
  flash-prefill chunk scoring, both in place on the pool. CPU caveat: both
  paged reads are the sequential ``lax.scan`` oracles (rows via ``lax.map``
  so dead-block skipping is a real branch), so end-to-end tokens/s on CPU
  understate the TPU kernels, which parallelize rows across the Pallas
  grid; the isolated active-length wins are what
  ``benchmarks/attn_bench.py`` measures.

Every continuous engine row also reports a **prefill/decode phase-time
split** (wall-clock attribution over the engine's step kinds: pure-decode
blocks, fused mixed steps, prefill-only steps) and the fused-admission
telemetry (``mixed_steps``, ``prefill_chunks``,
``decode_tokens_during_admission`` — the last must be nonzero: decode no
longer stalls while prompts stream in). Regressions like PR 3's
paged-prefill tax show up directly in the phase split instead of hiding in
totals.

Also emits the ``kv_cache`` section: attention-KV bytes per slot measured
from the engines' actual device buffers (contiguous fp32 vs paged int8,
reduction must be >= 2x) and the int8 bounded-divergence eval (greedy
first-token match + prefix agreement vs the fp32 paged engine).

And the ``prefix_cache`` section: a **shared-prefix workload** (bimodal
prompt lengths, groups of requests sharing a 64-token header — the
system-prompt / few-shot-eval traffic shape) served **cold** (prefix
cache off) and **warm** (prefix cache on, index populated by a priming
pass) on the paged engine. Reports both rows, the warm/cold speedup
(CI gates >= 1.3x via ``tools/check_perf_regression.py --prefix-floor``),
the hit/skipped-token telemetry, retained-block and eviction counts, and
the cold==warm greedy-parity flag (bitwise, a hard invariant).

The ``prefix_cache_hybrid`` section repeats the shared-prefix cold/warm
comparison on the reduced Jamba stack (``hybrid_bench_arch``): warm
admissions there restore a (KV blocks, SSM state snapshot) pair from the
content-addressed snapshot pool, so the row also reports
``state_snaps_captured`` / ``state_snap_restores``. And
``prefix_family_parity`` runs a tiny warm≡cold bitwise greedy check on
all four engine families (dense/moe/ssm/hybrid) — every entry must be
True (CI gates it via ``check_perf_regression.py``).

The ``speculative`` section serves a **decode-heavy greedy workload**
(short prompts, long budgets — the per-candidate decode cost best-of-n
scaling pays for) non-speculatively and with each drafter (replay /
ngram / self, plus int4 on the full run), reporting per-row acceptance
rate, verify-window count, tokens/s-per-candidate and
``speedup_vs_nonspec``; the best row's speedup is CI-gated >= 1.0x
(``--spec-floor``) with nonzero acceptance, and every row must be
bitwise identical to the non-speculative reference (``spec_parity``).

The ``open_loop`` section replays the mixed workload as *arriving
traffic* through the async frontend (``serve.frontend``) at 0.5x / 1x /
2x the engine's calibrated capacity: per-row TTFT/TPOT percentiles,
goodput under the calibrated SLO, shed counts against a bounded
admission queue (the overload row must shed — explicitly, never
silently: ``no_silent_drop`` asserts every arrival reached a terminal),
and the ``max_sustainable_qps`` saturation summary. CI gates the
no-silent-drop invariant, nonzero shedding under overload, saturation
row presence, and the base-rate goodput ratio (``--slo-floor``).

The ``tensor_parallel`` section (docs/distributed.md) serves a greedy
workload at tp=1 vs tp=2 over 8 forced host devices (subprocess): both
tokens/s rows, the ratio (CI-gated >= ``--tp-floor`` — host devices are
threads, so this is a no-pathology floor, not a speedup claim), the
bitwise tp parity flag (hard invariant), and ``bytes_per_device`` rows
showing the big configs (dbrx-132b / jamba-v0.1-52b / qwen2.5-32b) going
from does-not-fit at tp=1 to fitting per device under sharding.

Both paths run once untimed (to compile every executable) and once timed.
Emits ``BENCH_serve.json`` with useful-token throughput and p50/p99 request
latency for both engines, the speedup, and the result of the scheduler's
admission-parity check (solo request ≡ request admitted mid-batch) — the
start of the serving perf trajectory (ROADMAP: serve heavy mixed traffic).

    PYTHONPATH=src:. python benchmarks/serve_bench.py [--quick] [--out PATH]
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.core import devices as devices_lib
from repro.core.analog import AnalogConfig
from repro.launch.serve import arrival_offsets, open_loop_run
from repro.models import build
from repro.serve.decode import generate
from repro.serve.frontend import AsyncServeFrontend
from repro.serve.scheduler import (Request, SchedulerConfig, ServeEngine,
                                   padded_prompt_len, required_max_len)

from benchmarks import common

# attention KV leaves by cache layout (cache-bytes accounting)
_KV_LEAVES = {False: ("k", "v"),
              True: ("kp", "vp", "ks", "vs", "tbl", "wtbl")}


def bench_arch(d_model: int = 320, num_layers: int = 6) -> ArchConfig:
    """A serving-shaped toy config: big enough that one decode step's
    compute dominates the per-step host dispatch, small enough for CI."""
    return ArchConfig(name="serve-bench", family="dense",
                      num_layers=num_layers, d_model=d_model, num_heads=8,
                      num_kv_heads=4, d_ff=4 * d_model, vocab_size=2048,
                      d_head=40, norm="rmsnorm", act="silu")


def hybrid_bench_arch() -> ArchConfig:
    """The hybrid shape for the prefix-cache row: the reduced Jamba stack
    (attention/mamba mix, MoE every other layer) with no-drop MoE
    capacity so greedy decode is deterministic and the warm/cold passes
    are bitwise comparable. Exercises the (KV blocks, state snapshot)
    restore pair end to end."""
    cfg = get_config("jamba-v0.1-52b").reduce()
    return dataclasses.replace(cfg,
                               capacity_factor=float(cfg.num_experts))


def make_workload(num_requests: int, max_prompt: int, max_new: int,
                  seed: int = 0) -> list[Request]:
    """Mixed-length requests: ragged prompts, bimodal decode budgets.

    Budgets follow serving reality — most requests are short, a heavy tail
    runs to the full ``max_new``. Under pad-to-max batching one long
    request pins its whole group at the long budget; slot recycling is
    exactly what continuous batching monetizes here.
    """
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(num_requests):
        plen = int(rng.integers(4, max_prompt + 1))
        budget = (max_new if rng.random() < 0.25
                  else int(rng.integers(2, max(3, max_new // 4))))
        reqs.append(Request(
            uid=i, prompt=rng.integers(0, 2048, plen).astype(np.int32),
            max_new=budget, temperature=0.8, seed=seed + i))
    return reqs


def make_shared_prefix_workload(num_groups: int = 2, per_group: int = 8,
                                header: int = 64, seed: int = 11,
                                vocab: int = 2048) -> list[Request]:
    """Shared-prefix requests: ``per_group`` prompts per shared 64-token
    header, bimodal total lengths and decode budgets.

    Groups alternate between short and long prompts (every prompt in a
    group has the same length, so left-pad geometry — and therefore RoPE
    positions — line up and the header blocks are genuinely shareable);
    budgets are bimodal the same way serving traffic is. Greedy
    (temperature 0) so the cold and warm passes are bitwise comparable.
    """
    rng = np.random.default_rng(seed)
    reqs = []
    for g in range(num_groups):
        hdr = rng.integers(0, vocab, header)
        plen = 72 if g % 2 == 0 else 88
        for i in range(per_group):
            prompt = np.concatenate(
                [hdr, rng.integers(0, vocab, plen - header)]
            ).astype(np.int32)
            uid = g * per_group + i
            reqs.append(Request(
                uid=uid, prompt=prompt, max_new=12 if i % 4 == 0 else 4,
                temperature=0.0, seed=seed + uid))
    return reqs


_STATIC_JIT: dict = {}


def run_static(params, cfg, acfg, reqs, num_slots):
    """Pad-to-max batched serving: groups of ``num_slots``, each decoding
    to the group's largest budget. Returns (wall_s, latencies_s, tokens).

    The per-group ``generate`` call is jit-wrapped and cached per
    ``(batch, num_new)`` shape, so the baseline pays zero re-tracing —
    the comparison isolates scheduling, not dispatch overhead.
    """
    max_prompt = max(len(r.prompt) for r in reqs)
    lats, useful = [], 0
    t0 = time.perf_counter()
    for g in range(0, len(reqs), num_slots):
        group = reqs[g:g + num_slots]
        batch = np.zeros((len(group), max_prompt), np.int32)
        for i, r in enumerate(group):         # left-pad to the workload max
            batch[i, max_prompt - len(r.prompt):] = r.prompt
        new = max(r.max_new for r in group)
        sig = (id(cfg), id(acfg), len(group), max_prompt, new)
        if sig not in _STATIC_JIT:
            _STATIC_JIT[sig] = jax.jit(
                lambda p, k, b, n=new: generate(p, cfg, acfg, k, b, n,
                                                temperature=0.8))
        toks = _STATIC_JIT[sig](params, jax.random.PRNGKey(g),
                                jax.numpy.asarray(batch))
        toks.block_until_ready()
        done = time.perf_counter() - t0
        lats += [done] * len(group)
        useful += sum(r.max_new for r in group)
    return time.perf_counter() - t0, lats, useful


def run_continuous(params, cfg, acfg, reqs, num_slots, prefill_chunk,
                   paged=False, kv_block_size=16, prefix_cache=False,
                   kv_blocks=0, state_snapshots=0, engine=None):
    """Continuous batching. Returns (wall_s, latencies_s, tokens, engine).

    Pass ``engine`` to time a workload on an existing engine (the warm
    prefix-cache pass reuses the primed engine so its block index
    survives between passes)."""
    eng = engine
    if eng is None:
        max_len = max(required_max_len(len(r.prompt), r.max_new,
                                       prefill_chunk) for r in reqs)
        eng = ServeEngine(params, cfg, acfg, SchedulerConfig(
            num_slots=num_slots, max_len=max_len,
            prefill_chunk=prefill_chunk, paged=paged,
            kv_block_size=kv_block_size, prefix_cache=prefix_cache,
            kv_blocks=kv_blocks, state_snapshots=state_snapshots))
    t0 = time.perf_counter()
    results = eng.run(reqs)
    wall = time.perf_counter() - t0
    lats = [eng.finished_at[r.uid] - t0 for r in reqs]
    return wall, lats, sum(len(results[r.uid]) for r in reqs), eng


def engine_phase_stats(eng) -> dict:
    """Wall-clock phase attribution + fused-admission telemetry of one
    finished engine run (the per-row split the CI guard inspects)."""
    return {
        "decode_steps": eng.decode_steps,
        "phase_s": {k: round(v, 3) for k, v in eng.phase_time.items()},
        "mixed_steps": eng.mixed_steps,
        "prefill_chunks": eng.prefill_chunks,
        "decode_tokens_during_admission":
            eng.decode_tokens_during_admission,
    }


def kv_bytes_per_slot(params, cfg, acfg, scfg) -> int:
    """Attention-KV cache bytes one slot costs under ``scfg``'s layout,
    measured from the engine's actual device buffers (block tables and
    int8 scale planes included for the paged pool)."""
    eng = ServeEngine(params, cfg, acfg, scfg)
    names = _KV_LEAVES[scfg.paged]
    total = sum(int(eng.caches[n].nbytes) for n in names
                if n in eng.caches)
    return total // scfg.num_slots


def int8_divergence_check(params, cfg, reqs, num_slots, prefill_chunk):
    """Bounded-divergence eval for the int8 KV pool: greedy tokens of the
    int8-paged engine vs the fp32-paged engine on the same requests.
    Returns (first_token_match_rate, mean_prefix_agreement)."""
    greedy = [dataclasses.replace(r, temperature=0.0) for r in reqs]
    max_len = max(required_max_len(len(r.prompt), r.max_new, prefill_chunk)
                  for r in greedy)
    scfg = SchedulerConfig(num_slots=num_slots, max_len=max_len,
                           prefill_chunk=prefill_chunk, paged=True)
    fp = ServeEngine(params, cfg, AnalogConfig(mode="off"), scfg).run(
        list(greedy))
    q8 = ServeEngine(params, cfg, AnalogConfig(mode="off", kv_bits=8),
                     scfg).run(list(greedy))
    first, prefix = [], []
    for r in greedy:
        a, b = np.asarray(fp[r.uid]), np.asarray(q8[r.uid])
        n = min(len(a), len(b))
        agree = np.flatnonzero(a[:n] != b[:n])
        lcp = int(agree[0]) if len(agree) else n
        first.append(lcp >= 1)
        prefix.append(lcp / n)
    return float(np.mean(first)), float(np.mean(prefix))


def prefix_cache_bench(params, cfg, acfg, num_slots, prefill_chunk,
                       per_group: int = 8) -> dict:
    """Cold-vs-warm shared-prefix rows on the paged engine.

    *cold* — prefix cache disabled, every request prefills its whole
    prompt. *warm* — prefix cache enabled and the index populated by an
    untimed priming pass of the same workload (which doubles as the
    compile warm-up for the warm pool geometry), then the workload is
    re-served: every prompt's blocks are LRU-retained, so prefill
    collapses to the mandatory final chunk. Cold and warm are greedy and
    must match bitwise (``cold_warm_greedy_parity`` — a CI invariant
    alongside the >= 1.3x ``warm_speedup_vs_cold`` floor).

    Works for any family: attention-only stacks share KV blocks; the
    ssm/hybrid stacks additionally capture and restore SSM state
    snapshots (reported when the engine carries a snapshot pool).
    """
    reqs = make_shared_prefix_workload(num_groups=2, per_group=per_group,
                                       vocab=cfg.vocab_size)
    bs = 16
    max_len = max(required_max_len(len(r.prompt), r.max_new, prefill_chunk)
                  for r in reqs)
    # pool headroom: slot capacity + every distinct prompt's blocks, so
    # the warm pass never evicts what the priming pass cached; the
    # ssm/hybrid snapshot pool gets the same headroom
    nb = -(-max_len // bs)
    kv_blocks = (num_slots + len(reqs)) * nb
    snaps = (num_slots + len(reqs)) * nb

    # cold: compile warm-up pass, then best-of-2 timed runs (single
    # samples on shared CI runners are noisy enough to flip the gate)
    run_continuous(params, cfg, acfg, list(reqs), num_slots, prefill_chunk,
                   paged=True, kv_block_size=bs)
    c_wall, c_lats, c_tok, c_eng = min(
        (run_continuous(params, cfg, acfg, list(reqs), num_slots,
                        prefill_chunk, paged=True, kv_block_size=bs)
         for _ in range(2)), key=lambda r: r[0])

    # warm: prime (untimed — populates index + compiles the geometry),
    # then best-of-2 re-serves of the same prompts on the same engine
    _, _, _, w_eng = run_continuous(
        params, cfg, acfg, list(reqs), num_slots, prefill_chunk,
        paged=True, kv_block_size=bs, prefix_cache=True,
        kv_blocks=kv_blocks, state_snapshots=snaps)
    prime_hits = w_eng.prefix_hit_tokens
    prime_skipped = w_eng.prefix_skipped_tokens
    runs = []
    for rep in range(1, 3):
        warm_reqs = [dataclasses.replace(r, uid=r.uid + 1000 * rep)
                     for r in reqs]
        runs.append(run_continuous(params, cfg, acfg, warm_reqs,
                                   num_slots, prefill_chunk, engine=w_eng))
    w_wall, w_lats, w_tok, w_eng = min(runs, key=lambda r: r[0])

    parity = all(
        np.array_equal(c_eng.results[r.uid],
                       w_eng.results[r.uid + 1000 * rep])
        for r in reqs for rep in (1, 2))
    # hit/skip accounting is over padded prompt positions (the cache's
    # unit of work); telemetry accumulated over both warm reps -> per pass
    prompt_tokens = sum(padded_prompt_len(len(r.prompt), prefill_chunk)
                        for r in reqs)
    warm_hits = (w_eng.prefix_hit_tokens - prime_hits) // len(runs)
    warm_skipped = ((w_eng.prefix_skipped_tokens - prime_skipped)
                    // len(runs))
    out = {
        "workload": {"num_requests": len(reqs), "shared_header": 64,
                     "per_group": per_group,
                     "prompt_tokens": prompt_tokens,
                     "family": cfg.family},
        "cold": summarize(c_wall, c_lats, c_tok),
        "warm": summarize(w_wall, w_lats, w_tok),
        "warm_speedup_vs_cold": round((w_tok / w_wall) / (c_tok / c_wall),
                                      3),
        "prime_hit_tokens": int(prime_hits),
        "warm_hit_tokens": int(warm_hits),
        "warm_skipped_prefill_tokens": int(warm_skipped),
        "warm_hit_rate": round(warm_hits / prompt_tokens, 3),
        "cached_blocks": int(w_eng.pool.num_cached),
        "evictions": int(w_eng.pool.evictions),
        "cold_warm_greedy_parity": bool(parity),
    }
    if w_eng.state_pool is not None:
        out["state_snaps_captured"] = int(w_eng.state_snaps_captured)
        out["state_snap_restores"] = int(w_eng.state_snap_restores)
        out["cached_snapshots"] = int(w_eng.state_pool.num_cached)
    return out


def make_decode_heavy_workload(num_requests: int = 8, prompt_len: int = 14,
                               max_new: int = 96, seed: int = 9,
                               vocab: int = 2048) -> list[Request]:
    """Short greedy prompts with long decode budgets — the regime
    speculative decoding targets (prefill is negligible, every slot sits
    in pure decode for most of the run). Greedy so every drafter row is
    bitwise comparable to the non-speculative reference."""
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, vocab, prompt_len
                                        ).astype(np.int32),
                    max_new=max_new, temperature=0.0, seed=seed + i)
            for i in range(num_requests)]


def speculative_bench(params, cfg, acfg, num_slots, prefill_chunk,
                      include_int4: bool = True) -> dict:
    """Draft-and-verify rows on the decode-heavy workload.

    One non-speculative reference row, then one row per drafter
    (best-of-2 timed passes on fresh engines after an untimed compile
    pass, like every other section):

    * ``replay`` — a host ``draft_fn`` replaying the reference run's own
      completions (the regression-replay / repeated-greedy-serving
      shape: the completion is known, the engine must still verify it).
      Acceptance ~1.0 at zero proposal cost, so this row isolates the
      *verification* cost of the fused k+1-position window — the
      headline ``speedup_vs_nonspec`` the CI floor gates.
    * ``ngram`` — host prompt-lookup proposals; free but weak on the
      random-token workload (real text is far more self-similar).
    * ``self`` — the target drafting for itself; acceptance is exactly
      1.0 by the shared-PRNG-stream argument, and the row prices a
      maximally accurate model drafter at full proposal cost.
    * ``int4`` (full bench only) — the paper pairing: the RTN-int4
      digital deployment of the same weights drafts for the fp target.
      On CPU the unfused fake-quant drafter forward is slow, so this
      row is reported for its *acceptance rate*, not its speedup.

    Every row must be bitwise identical to the reference
    (``parity`` — a CI invariant); ``tokens_per_s_per_candidate``
    divides by the in-flight candidate count (= ``num_slots``: the
    best-of-n decode-phase fan-out this workload models).
    """
    reqs = make_decode_heavy_workload(vocab=cfg.vocab_size)
    prompts = {r.uid: np.asarray(r.prompt) for r in reqs}
    max_len = max(required_max_len(len(r.prompt), r.max_new, prefill_chunk)
                  for r in reqs)

    def serve(scfg, **ekw):
        # fresh engine per pass; the compile cache is shared module-wide
        eng = ServeEngine(params, cfg, acfg, scfg, **ekw)
        t0 = time.perf_counter()
        res = eng.run([dataclasses.replace(r) for r in reqs])
        wall = time.perf_counter() - t0
        return wall, sum(len(v) for v in res.values()), res, eng

    def best_of_2(scfg, **ekw):
        serve(scfg, **ekw)                                 # compile pass
        return min((serve(scfg, **ekw) for _ in range(2)),
                   key=lambda r: r[0])

    base_scfg = SchedulerConfig(num_slots=num_slots, max_len=max_len,
                                prefill_chunk=prefill_chunk, paged=True)
    b_wall, b_tok, b_res, _ = best_of_2(base_scfg)
    b_tps = b_tok / b_wall
    outs = {u: np.asarray(v) for u, v in b_res.items()}

    def replay(ctx, k):
        # ctx = prompt + tokens so far; draft the known continuation
        uid = next(u for u, p in prompts.items()
                   if len(ctx) >= len(p)
                   and np.array_equal(ctx[:len(p)], p))
        n = len(ctx) - len(prompts[uid])
        return outs[uid][n:n + k].astype(np.int32)

    rows = [("replay", 8, dict(draft="ngram"), dict(draft_fn=replay)),
            ("ngram", 4, dict(draft="ngram"), {}),
            ("self", 4, dict(draft="self"), {})]
    if include_int4:
        rows.append(("int4", 4, dict(draft="int4"), {}))
    drafters = {}
    for name, k, skw, ekw in rows:
        scfg = dataclasses.replace(base_scfg, speculative=True,
                                   draft_k=k, **skw)
        wall, tok, res, eng = best_of_2(scfg, **ekw)
        tps = tok / wall
        drafters[name] = {
            "draft_k": k,
            "tokens_per_s": round(tps, 1),
            "tokens_per_s_per_candidate": round(tps / num_slots, 2),
            "acceptance_rate": round(eng.spec_acceptance, 3),
            "verify_windows": int(eng.spec_steps),
            "speedup_vs_nonspec": round(tps / b_tps, 3),
            "parity": bool(all(np.array_equal(res[u], b_res[u])
                               for u in b_res)),
        }
    best = max(drafters, key=lambda d: drafters[d]["speedup_vs_nonspec"])
    return {
        "workload": {"num_requests": len(reqs), "max_new": 96,
                     "num_slots": num_slots, "temperature": 0.0},
        "nonspec": {"wall_s": round(b_wall, 3),
                    "tokens_per_s": round(b_tps, 1),
                    "tokens_per_s_per_candidate": round(b_tps / num_slots,
                                                        2)},
        "drafters": drafters,
        "best_drafter": best,
        "best_speedup_vs_nonspec": drafters[best]["speedup_vs_nonspec"],
        "best_acceptance_rate": drafters[best]["acceptance_rate"],
        "spec_parity": bool(all(d["parity"] for d in drafters.values())),
    }


def drift_bench(cfg, params, labels, num_slots, prefill_chunk,
                quick=False) -> dict:
    """Drift-aware long-running-serve eval on the analog engine.

    Serves one greedy workload from an analog deployment whose per-tile
    device state has been **pre-aged** to each point of an
    hours-deployed curve (``core.devices.advance``), with a small
    per-step drift ``dt`` ticking during the run, and scores each arm
    against a pristine (no device state) engine on the identical
    requests by greedy **first-token match rate** and mean
    **prefix agreement** (fraction of each completion before its first
    divergence) — the ``int8_divergence_check`` metrics: cascade-free,
    so they track weight corruption rather than the greedy butterfly
    effect of chaotic toy-model continuations. Each hours point runs
    twice:

    * *no_recal* — the chip keeps serving as-programmed; tiles decay on
      their lognormal-``nu`` trajectories and agreement falls with
      hours deployed.
    * *recal* — the engine's drift watchdog (tight cadence/threshold so
      the CI-sized run trips it immediately) reprograms the tiles in
      place mid-serve; agreement must recover to >= the no_recal arm
      at the worst-aged point (``recal_recovers``, CI-gated together
      with an absolute floor via ``--drift-floor``).

    Also asserts the legacy path is untouched: an engine whose params
    carry an all-zero device state (null sigmas/faults, drift clock off)
    must emit **token-bitwise identical** outputs to the device-free
    engine (``no_drift_parity`` — a hard CI invariant).

    Faults are left at zero here: stuck columns and dead tiles are
    permanent, so they would cap both arms identically and only blur the
    recovery signal this section gates (the launcher's ``--fault-prob``
    exercises fault telemetry end to end).
    """
    acfg = AnalogConfig(mode="analog", train_noise=False)
    hours = (6.0, 168.0) if quick else (6.0, 48.0, 168.0)
    rng = np.random.default_rng(13)
    # burn-in batch (served first, unscored: the window the watchdog
    # reprograms in) + scoring batch (the post-watchdog serving quality
    # both arms are judged on — a recal mid-deployment only helps the
    # traffic that arrives after it)
    burn = [Request(uid=100 + i,
                    prompt=rng.integers(0, cfg.vocab_size, 10
                                        ).astype(np.int32),
                    max_new=8, temperature=0.0) for i in range(4)]
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 10
                                        ).astype(np.int32),
                    max_new=8, temperature=0.0, seed=13 + i)
            for i in range(12)]
    max_len = max(required_max_len(len(r.prompt), r.max_new, prefill_chunk)
                  for r in reqs + burn)
    base = SchedulerConfig(num_slots=num_slots, max_len=max_len,
                           prefill_chunk=prefill_chunk)

    def serve(p, drift_dt=0.0, recal=False):
        eng = ServeEngine(p, cfg, acfg, dataclasses.replace(
            base, drift_dt=drift_dt, recalibrate=recal,
            recal_interval=1, recal_threshold=0.05))
        eng.run([dataclasses.replace(r) for r in burn])
        res = eng.run([dataclasses.replace(r) for r in reqs])
        return res, eng

    ref, _ = serve(params)                    # pristine analog reference

    def agreement(res):
        # greedy + fixed budgets + no stop tokens -> equal lengths
        first, lcp = [], []
        for r in reqs:
            a, b = np.asarray(ref[r.uid]), np.asarray(res[r.uid])
            d = np.flatnonzero(a != b)
            k = int(d[0]) if len(d) else len(a)
            first.append(k >= 1)
            lcp.append(k / len(a))
        return float(np.mean(first)), float(np.mean(lcp))

    # null device state (zero sigmas/faults, clock off) must be a no-op
    null_params = devices_lib.attach_device_state(
        params, labels, jax.random.PRNGKey(21),
        devices_lib.DeviceConfig(sigma_gain=0.0, nu_median=0.0,
                                 nu_sigma=0.0, sigma_offset=0.0))
    null_res, _ = serve(null_params)
    no_drift_parity = bool(all(
        np.array_equal(null_res[r.uid], ref[r.uid]) for r in reqs))

    dcfg = devices_lib.DeviceConfig(sigma_gain=0.02, nu_median=0.1,
                                    nu_sigma=0.3)
    dparams = devices_lib.attach_device_state(
        params, labels, jax.random.PRNGKey(42), dcfg)
    curve = []
    for h in hours:
        aged = devices_lib.advance(dparams, h)
        nr_res, nr_eng = serve(aged, drift_dt=0.02)
        rc_res, rc_eng = serve(aged, drift_dt=0.02, recal=True)
        nr_first, nr_lcp = agreement(nr_res)
        rc_first, rc_lcp = agreement(rc_res)
        curve.append({
            "hours_deployed": h,
            "first_match_no_recal": round(nr_first, 3),
            "first_match_recal": round(rc_first, 3),
            "prefix_agree_no_recal": round(nr_lcp, 3),
            "prefix_agree_recal": round(rc_lcp, 3),
            "tile_scale_err_no_recal": round(nr_eng.tile_scale_err, 4),
            "tile_scale_err_recal": round(rc_eng.tile_scale_err, 4),
            "recal_count": int(rc_eng.recal_count),
        })
    worst = curve[-1]
    return {
        "workload": {"num_requests": len(reqs), "max_new": 8,
                     "num_slots": num_slots, "temperature": 0.0,
                     "drift_dt_per_step": 0.02},
        "device": {"sigma_gain": dcfg.sigma_gain,
                   "nu_median": dcfg.nu_median,
                   "nu_sigma": dcfg.nu_sigma},
        "no_drift_parity": no_drift_parity,
        "hours": curve,
        "recal_fired": bool(all(r["recal_count"] >= 1 for r in curve)),
        "final_first_match_no_recal": worst["first_match_no_recal"],
        "final_first_match_recal": worst["first_match_recal"],
        "recal_recovers": bool(
            worst["first_match_recal"] >= worst["first_match_no_recal"]
            and worst["prefix_agree_recal"]
            >= worst["prefix_agree_no_recal"]),
    }


def family_parity_check() -> dict:
    """warm≡cold bitwise greedy parity across all four engine families
    (dense KV sharing, moe no-drop, ssm snapshot-only, hybrid
    KV+snapshot) on tiny reduced archs. Every entry must be True — the
    CI guard fails the build otherwise."""
    archs = [("dense", "granite-3-8b"), ("moe", "dbrx-132b"),
             ("ssm", "mamba2-130m"), ("hybrid", "jamba-v0.1-52b")]
    out = {}
    for fam, arch in archs:
        cfg = get_config(arch).reduce()
        if cfg.num_experts:       # no-drop capacity: deterministic greedy
            cfg = dataclasses.replace(
                cfg, capacity_factor=float(cfg.num_experts))
        cfg, params, _ = build(cfg, jax.random.PRNGKey(0))
        acfg = AnalogConfig(mode="off")
        rng = np.random.default_rng(5)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab_size, 9
                                            ).astype(np.int32),
                        max_new=4, temperature=0.0) for i in range(2)]
        base = SchedulerConfig(
            num_slots=2, max_len=required_max_len(9, 4, 4),
            prefill_chunk=4, paged=True, kv_block_size=4,
            prefix_cache=False)
        cold = ServeEngine(params, cfg, acfg, base).run(list(reqs))
        eng = ServeEngine(params, cfg, acfg,
                          dataclasses.replace(base, prefix_cache=True))
        eng.run(list(reqs))       # priming pass populates the index
        warm = eng.run([dataclasses.replace(r, uid=r.uid + 10)
                        for r in reqs])
        out[fam] = bool(all(np.array_equal(cold[r.uid], warm[r.uid + 10])
                            for r in reqs)
                        and eng.prefix_hit_tokens > 0)
    return out


def open_loop_bench(params, cfg, acfg, reqs, num_slots,
                    prefill_chunk) -> dict:
    """Open-loop QPS sweep through the async frontend (PR 9).

    A closed-loop pass on the same paged geometry calibrates the
    engine's **capacity** (requests/s it sustains with the queue always
    full) and the **SLO** (that pass's p99 request latency — a latency
    every request provably meets under full batch pressure). The same
    workload is then replayed as *arriving traffic* at 0.5x / 1x / 2x
    capacity via :class:`AsyncServeFrontend`; the overload row arrives
    in bursts against a deliberately small admission queue, so shedding
    is structural, not a race. Per row: TTFT/TPOT p50/p99, goodput
    under the SLO (fraction of arrivals finishing inside it, and their
    tokens/s), shed/timeout counts, and the **no-silent-drop** check —
    ``finished + shed + timed_out + cancelled + errored == submitted``,
    every arrival reaches an explicit terminal. The summary carries the
    **saturation row**: ``max_sustainable_qps`` is the highest swept
    rate served with zero shedding and goodput ratio >= 0.8. CI gates
    (``check_perf_regression.py``): saturation row present, every row
    no-silent-drop, the overload row sheds (nonzero), and the base-rate
    goodput ratio clears ``--slo-floor``.
    """
    # capacity + SLO calibration (geometry matches the main paged rows,
    # so every executable is already compiled)
    c_wall, c_lats, c_tok, _ = run_continuous(
        params, cfg, acfg, list(reqs), num_slots, prefill_chunk,
        paged=True)
    capacity_qps = len(reqs) / c_wall
    slo_s = float(np.percentile(np.asarray(c_lats), 99))

    max_len = max(required_max_len(len(r.prompt), r.max_new, prefill_chunk)
                  for r in reqs)
    rows = []
    for mult in (0.5, 1.0, 2.0):
        overload = mult >= 2.0
        qps = capacity_qps * mult
        # overload: burst arrivals against a small queue -> guaranteed
        # overflow; sustainable rates get comfortable queue headroom
        arrival = "burst" if overload else "poisson"
        max_queue = max(2, num_slots // 4) if overload else 2 * num_slots
        eng = ServeEngine(params, cfg, acfg, SchedulerConfig(
            num_slots=num_slots, max_len=max_len,
            prefill_chunk=prefill_chunk, paged=True,
            max_queue=max_queue))
        row_reqs = [dataclasses.replace(r) for r in reqs]
        offsets = arrival_offsets(len(row_reqs), qps, arrival,
                                  np.random.default_rng(17))
        fe = AsyncServeFrontend(eng)

        async def drive():
            await fe.start()
            try:
                return await open_loop_run(fe, row_reqs, offsets)
            finally:
                await fe.stop()

        records, wall = asyncio.run(drive())
        ttfts = [r["ttft"] for r in records if r["ttft"] is not None]
        tpots = [(r["latency"] - r["ttft"]) / (r["tokens"] - 1)
                 for r in records
                 if r["ttft"] is not None and r["tokens"] > 1]
        good = [r for r in records
                if r["status"] == "finished" and r["latency"] <= slo_s]
        counts = {}
        for r in records:
            counts[r["status"]] = counts.get(r["status"], 0) + 1
        accounted = sum(counts.get(s, 0) for s in
                        ("finished", "shed", "timed_out", "cancelled",
                         "errored"))

        def pct(xs, q):
            return (round(float(np.percentile(xs, q)) * 1e3, 1)
                    if xs else None)

        rows.append({
            "offered_x_capacity": mult,
            "qps": round(qps, 2),
            "arrival": arrival,
            "max_queue": max_queue,
            "submitted": int(eng.submitted),
            "outcomes": counts,
            "shed": int(eng.shed_count),
            "timed_out": int(eng.timeout_count),
            "wall_s": round(wall, 3),
            "ttft_p50_ms": pct(ttfts, 50), "ttft_p99_ms": pct(ttfts, 99),
            "tpot_p50_ms": pct(tpots, 50), "tpot_p99_ms": pct(tpots, 99),
            "goodput_ratio": round(len(good) / len(records), 3),
            "goodput_tokens_per_s": round(
                sum(r["tokens"] for r in good) / wall, 1),
            "queue_high_water": int(eng.queue_high_water),
            "overload": overload,
            "no_silent_drop": bool(accounted == len(records)
                                   == eng.submitted),
        })
    sustainable = [r["qps"] for r in rows
                   if r["shed"] == 0 and r["goodput_ratio"] >= 0.8]
    return {
        "capacity_qps": round(capacity_qps, 2),
        "slo_s": round(slo_s, 3),
        "slo_source": "closed-loop p99 request latency",
        "rows": rows,
        "max_sustainable_qps": round(max(sustainable), 2) if sustainable
        else 0.0,
    }


_TP_PROG = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import numpy as np
import jax
from repro.configs.base import ArchConfig
from repro.core.analog import AnalogConfig
from repro.models import build
from repro.serve.scheduler import Request, SchedulerConfig, ServeEngine

d_model, num_layers = {d_model}, {num_layers}
cfg = ArchConfig(name="serve-bench", family="dense", num_layers=num_layers,
                 d_model=d_model, num_heads=8, num_kv_heads=4,
                 d_ff=4 * d_model, vocab_size=2048, d_head=40,
                 norm="rmsnorm", act="silu")
cfg, params, labels = build(cfg, jax.random.PRNGKey(0))

def mk(base):
    rng = np.random.default_rng(0)
    reqs = []
    for i in range({nreq}):
        plen = int(rng.integers(4, 17))
        reqs.append(Request(
            uid=base + i,
            prompt=rng.integers(0, 2048, plen).astype(np.int32),
            max_new=16, temperature=0.0, seed=i))
    return reqs

def serve(tp):
    scfg = SchedulerConfig(num_slots=8, max_len=48, prefill_chunk=16,
                           paged=True, tp=tp)
    eng = ServeEngine(params, cfg, AnalogConfig(mode="off"), scfg)
    eng.run(mk(1000))                        # untimed: compiles the mesh
    t0 = time.perf_counter()
    out = eng.run(mk(0))
    wall = time.perf_counter() - t0
    toks = sum(len(v) for v in out.values())
    outs = {{str(k): [int(x) for x in np.asarray(v)]
             for k, v in out.items()}}
    return toks / wall, outs, dict(eng.gating_reasons), eng.mesh is not None

r1, o1, g1, m1 = serve(1)
r2, o2, g2, m2 = serve(2)
print(json.dumps({{
    "platform": jax.default_backend(), "devices": len(jax.devices()),
    "mesh_active": m2,
    "tp1_tokens_per_s": round(r1, 2), "tp2_tokens_per_s": round(r2, 2),
    "tp2_vs_tp1": round(r2 / r1, 3), "tp_parity": o1 == o2,
    "tp2_gating": g2}}))
"""


def _tp_bytes_rows(tp: int = 4) -> list:
    """Bytes-per-device rows for the big configs, priced by
    ``tools/kv_memory_table`` (exact ``eval_shape`` weights under the real
    serve spec table + paged-int8 KV + SSM recurrent state; the table
    ``docs/distributed.md`` embeds)."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "kv_memory_table.py")
    spec = importlib.util.spec_from_file_location("kv_memory_table", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rows = []
    for name in mod.TP_ARCHS:
        cfg = get_config(name)
        total, wdev = mod.weight_bytes(cfg, tp)
        _, _, int8 = mod.bytes_per_slot(cfg, 4096, 16)
        ssm = mod.ssm_state_bytes(cfg)
        kv = cfg.num_kv_heads or 1
        slot1 = int8 + ssm
        slot_dev = (int8 // tp if kv % tp == 0 else int8) + (
            ssm // tp if (not ssm or cfg.ssm_heads % tp == 0) else ssm)
        budget = 80 * 2**30
        rows.append({
            "arch": cfg.name, "tp": tp,
            "weights_gib_tp1": round(total / 2**30, 1),
            "weights_gib_per_dev": round(wdev / 2**30, 1),
            "slot_mib_tp1": round(slot1 / 2**20, 1),
            "slot_mib_per_dev": round(slot_dev / 2**20, 1),
            "fits_80gib_tp1": bool(total + 8 * slot1 <= budget),
            "fits_80gib": bool(wdev + 8 * slot_dev <= budget),
        })
    return rows


def tp_bench(quick=False) -> dict:
    """Tensor-parallel scaling row: tp=1 vs tp=2 closed-loop tokens/s on
    8 forced host devices (subprocess — jax locks the device count at
    init), bitwise tp parity, and the bytes-per-device fit rows.

    The child is pinned to the CPU (``JAX_PLATFORMS=cpu``): this parent
    has already run engines and holds any accelerator, and a chip
    belongs to one process. Tensor parallelism over real chips is
    ``chip_smoke.py --chips 4``.

    CPU caveat: host "devices" are threads on the same cores, so tp=2
    adds collective overhead without adding FLOPs — the gate is a
    not-pathologically-slower floor (``--tp-floor``), not a speedup
    claim; the fit rows carry the capacity win."""
    import os
    import subprocess
    import sys
    d_model, num_layers, nreq = (192, 4, 10) if quick else (320, 6, 16)
    prog = _TP_PROG.format(d_model=d_model, num_layers=num_layers,
                           nreq=nreq)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (env.get("PYTHONPATH"), "src") if p)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        rec = {"error": out.stderr[-2000:]}
    else:
        rec = json.loads(out.stdout.strip().splitlines()[-1])
    rec["bytes_per_device"] = _tp_bytes_rows()
    return rec


def parity_check(params, cfg, acfg, num_slots, prefill_chunk) -> bool:
    """Acceptance check: a request admitted mid-batch at step k produces
    exactly the tokens it produces running solo."""
    scfg = SchedulerConfig(num_slots=num_slots, max_len=96,
                           prefill_chunk=prefill_chunk)
    rng = np.random.default_rng(7)
    target = Request(uid=99, prompt=rng.integers(0, 2048, 9).astype(np.int32),
                     max_new=10, temperature=0.9, top_k=64, seed=123)
    solo = ServeEngine(params, cfg, acfg, scfg).run([target])[99]
    eng = ServeEngine(params, cfg, acfg, scfg)
    for i in range(num_slots):
        eng.submit(Request(
            uid=i, prompt=rng.integers(0, 2048, 5 + i).astype(np.int32),
            max_new=3 + i, temperature=1.0, seed=i))
    for _ in range(2):
        eng.step()                         # slots busy, decode under way
    eng.submit(target)                     # admitted mid-decode
    mixed = eng.run()[99]
    return bool(np.array_equal(solo, mixed))


def summarize(wall, lats, tokens):
    lats_ms = np.asarray(lats) * 1e3
    return {"wall_s": round(wall, 3), "tokens": int(tokens),
            "tokens_per_s": round(tokens / wall, 1),
            "p50_ms": round(float(np.percentile(lats_ms, 50)), 1),
            "p99_ms": round(float(np.percentile(lats_ms, 99)), 1)}


def run(num_requests=24, max_prompt=32, max_new=48, num_slots=8,
        prefill_chunk=16, quick=False, out="BENCH_serve.json"):
    if quick:
        num_requests, max_prompt, max_new, num_slots = 20, 16, 48, 8
    cfg = bench_arch() if not quick else bench_arch(192, 4)
    cfg, params, labels = build(cfg, jax.random.PRNGKey(0))
    acfg = AnalogConfig(mode="off")
    reqs = make_workload(num_requests, max_prompt, max_new)

    # untimed warm-up pass compiles every executable all three paths use;
    # the timed rows are best-of-2 (single samples on shared runners are
    # noisy enough to flip the ratio gates)
    run_static(params, cfg, acfg, reqs, num_slots)
    run_continuous(params, cfg, acfg, reqs, num_slots, prefill_chunk)
    run_continuous(params, cfg, acfg, reqs, num_slots, prefill_chunk,
                   paged=True)

    s_wall, s_lats, s_tok = min(
        (run_static(params, cfg, acfg, reqs, num_slots) for _ in range(2)),
        key=lambda r: r[0])
    c_wall, c_lats, c_tok, c_eng = min(
        (run_continuous(params, cfg, acfg, reqs, num_slots, prefill_chunk)
         for _ in range(2)), key=lambda r: r[0])
    p_wall, p_lats, p_tok, p_eng = min(
        (run_continuous(params, cfg, acfg, reqs, num_slots, prefill_chunk,
                        paged=True) for _ in range(2)),
        key=lambda r: r[0])
    parity = parity_check(params, cfg, acfg, num_slots, prefill_chunk)

    # cache-bytes accounting + int8 bounded-divergence eval
    max_len = max(required_max_len(len(r.prompt), r.max_new, prefill_chunk)
                  for r in reqs)
    geo = dict(num_slots=num_slots, max_len=max_len,
               prefill_chunk=prefill_chunk)
    fp32_bytes = kv_bytes_per_slot(params, cfg, acfg,
                                   SchedulerConfig(**geo))
    int8_bytes = kv_bytes_per_slot(params, cfg,
                                   AnalogConfig(mode="off", kv_bits=8),
                                   SchedulerConfig(paged=True, **geo))
    first_match, prefix_agree = int8_divergence_check(
        params, cfg, reqs[:6], num_slots, prefill_chunk)
    prefix = prefix_cache_bench(params, cfg, acfg, num_slots,
                                prefill_chunk)

    # the same shared-prefix shape on the hybrid (Jamba) stack: warm
    # admissions restore a (KV blocks, state snapshot) pair instead of
    # KV blocks alone — small per_group keeps the row CI-cheap
    h_cfg, h_params, _ = build(hybrid_bench_arch(), jax.random.PRNGKey(1))
    prefix_hybrid = prefix_cache_bench(h_params, h_cfg, acfg,
                                       num_slots=4, prefill_chunk=16,
                                       per_group=4)
    family_parity = family_parity_check()
    spec = speculative_bench(params, cfg, acfg, num_slots, prefill_chunk,
                             include_int4=not quick)
    drift = drift_bench(cfg, params, labels, num_slots, prefill_chunk,
                        quick=quick)
    open_loop = open_loop_bench(params, cfg, acfg, reqs, num_slots,
                                prefill_chunk)
    tp = tp_bench(quick=quick)

    result = {
        "workload": {"num_requests": num_requests, "max_prompt": max_prompt,
                     "max_new": max_new, "num_slots": num_slots,
                     "prefill_chunk": prefill_chunk,
                     "arch": f"d{cfg.d_model}xL{cfg.num_layers}"},
        "static": {**summarize(s_wall, s_lats, s_tok),
                   # prefill+decode fused in one jitted generate() call per
                   # group — not separable without instrumenting the jit
                   "phase_s": None},
        "continuous": {**summarize(c_wall, c_lats, c_tok),
                       **engine_phase_stats(c_eng)},
        "paged": {**summarize(p_wall, p_lats, p_tok),
                  **engine_phase_stats(p_eng)},
        "speedup_tokens_per_s": round((c_tok / c_wall) / (s_tok / s_wall), 3),
        "paged_speedup_vs_static": round(
            (p_tok / p_wall) / (s_tok / s_wall), 3),
        "admission_parity": parity,
        "kv_cache": {
            "contiguous_fp32_bytes_per_slot": fp32_bytes,
            "paged_int8_bytes_per_slot": int8_bytes,
            "bytes_reduction": round(fp32_bytes / int8_bytes, 2),
            "int8_first_token_match": first_match,
            "int8_prefix_agreement": round(prefix_agree, 3),
            "int8_divergence_ok": bool(first_match >= 0.99
                                       and prefix_agree >= 0.5),
        },
        "prefix_cache": prefix,
        "prefix_cache_hybrid": prefix_hybrid,
        "prefix_family_parity": family_parity,
        "speculative": spec,
        "drift": drift,
        "open_loop": open_loop,
        "tensor_parallel": tp,
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=2)
    common.bench_row("serve.static", s_wall * 1e6,
                     f"tok_s={result['static']['tokens_per_s']}")
    common.bench_row("serve.continuous", c_wall * 1e6,
                     f"tok_s={result['continuous']['tokens_per_s']} "
                     f"steps={c_eng.decode_steps} "
                     f"phase={result['continuous']['phase_s']}")
    common.bench_row("serve.paged", p_wall * 1e6,
                     f"tok_s={result['paged']['tokens_per_s']} "
                     f"steps={p_eng.decode_steps} "
                     f"phase={result['paged']['phase_s']}")
    common.bench_row(
        "serve.prefix", 0.0,
        f"cold_tok_s={prefix['cold']['tokens_per_s']} "
        f"warm_tok_s={prefix['warm']['tokens_per_s']} "
        f"warm_speedup={prefix['warm_speedup_vs_cold']} "
        f"hit_tokens={prefix['warm_hit_tokens']} "
        f"cached_blocks={prefix['cached_blocks']} "
        f"evictions={prefix['evictions']} "
        f"parity={prefix['cold_warm_greedy_parity']}")
    common.bench_row(
        "serve.prefix_hybrid", 0.0,
        f"cold_tok_s={prefix_hybrid['cold']['tokens_per_s']} "
        f"warm_tok_s={prefix_hybrid['warm']['tokens_per_s']} "
        f"warm_speedup={prefix_hybrid['warm_speedup_vs_cold']} "
        f"hit_tokens={prefix_hybrid['warm_hit_tokens']} "
        f"snaps={prefix_hybrid['state_snaps_captured']} "
        f"restores={prefix_hybrid['state_snap_restores']} "
        f"parity={prefix_hybrid['cold_warm_greedy_parity']} "
        f"family_parity={family_parity}")
    common.bench_row(
        "serve.speculative", 0.0,
        f"nonspec_tok_s={spec['nonspec']['tokens_per_s']} " + " ".join(
            f"{name}=[{d['speedup_vs_nonspec']}x acc="
            f"{d['acceptance_rate']} win={d['verify_windows']}]"
            for name, d in spec["drafters"].items()) +
        f" best={spec['best_drafter']} parity={spec['spec_parity']}")
    common.bench_row(
        "serve.drift", 0.0,
        f"no_drift_parity={drift['no_drift_parity']} " + " ".join(
            f"h{r['hours_deployed']:g}=[no_recal="
            f"{r['first_match_no_recal']} recal={r['first_match_recal']} "
            f"recals={r['recal_count']}]" for r in drift["hours"]) +
        f" recal_recovers={drift['recal_recovers']}")
    common.bench_row(
        "serve.open_loop", 0.0,
        f"capacity={open_loop['capacity_qps']}qps "
        f"slo={open_loop['slo_s']}s " + " ".join(
            f"{r['offered_x_capacity']}x=[goodput={r['goodput_ratio']} "
            f"ttft_p50={r['ttft_p50_ms']}ms shed={r['shed']}]"
            for r in open_loop["rows"]) +
        f" max_sustainable={open_loop['max_sustainable_qps']}qps "
        f"no_silent_drop="
        f"{all(r['no_silent_drop'] for r in open_loop['rows'])}")
    if "error" not in tp:
        common.bench_row(
            "serve.tensor_parallel", 0.0,
            f"tp1_tok_s={tp['tp1_tokens_per_s']} "
            f"tp2_tok_s={tp['tp2_tokens_per_s']} "
            f"ratio={tp['tp2_vs_tp1']} parity={tp['tp_parity']} "
            f"mesh={tp['mesh_active']} " + " ".join(
                f"{r['arch']}=[{r['weights_gib_tp1']}GiB→"
                f"{r['weights_gib_per_dev']}GiB/dev "
                f"fits={r['fits_80gib_tp1']}→{r['fits_80gib']}]"
                for r in tp["bytes_per_device"]))
    kv = result["kv_cache"]
    common.bench_row(
        "serve.claims", 0.0,
        f"speedup={result['speedup_tokens_per_s']} parity={parity} "
        f"continuous_wins={result['speedup_tokens_per_s'] > 1.0} "
        f"paged_wins={result['paged_speedup_vs_static'] > 1.0} "
        f"decode_during_admission="
        f"{result['paged']['decode_tokens_during_admission']} "
        f"kv_bytes_reduction={kv['bytes_reduction']} "
        f"int8_ok={kv['int8_divergence_ok']} "
        f"prefix_warm_wins={prefix['warm_speedup_vs_cold'] >= 1.3}")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized workload (~tens of seconds)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    run(quick=args.quick, out=args.out)
