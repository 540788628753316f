"""Serving launcher: continuous-batching generation from a deployed model.

Demonstrates the deployment stage of the paper's pipeline (Fig. 2c):
restore/construct a model, optionally apply one simulated chip programming
(hw noise) or RTN-quantize for digital hardware (unfused, fused, or
packed-int4), and serve a mixed-length request workload through the
continuous-batching scheduler (``--engine static`` falls back to the
legacy pad-to-max ``generate`` loop for comparison). Paged engines run
with the radix prefix cache by default (``--no-prefix-cache`` to
disable; ``--cache-salt`` segregates index entries per deployment) and
report hit rate, skipped prefill tokens, retained blocks and evictions
in the per-run line.

    PYTHONPATH=src python -m repro.launch.serve --arch llama-3.2-1b \
        --reduced --deploy analog_hw --num-requests 8

    # Table-3 digital deployment on the packed-int4 serving kernel:
    PYTHONPATH=src python -m repro.launch.serve --arch phi-3-mini-4k \
        --reduced --deploy digital_int4 --num-requests 8

Open-loop modes (PR 9, ``serve.frontend``): ``--qps`` replays the same
synthetic workload as *arriving traffic* (``--arrival poisson|burst``)
through the async frontend with per-request deadlines
(``--request-timeout``/``--ttft-timeout``) and a bounded admission queue
(``--max-queue`` — overflow is shed with an explicit reason, never
dropped silently); ``--serve`` opens a minimal HTTP/1.1 front door
(``POST /generate`` with a JSON body, ``GET /health`` for live engine
counters) on ``--port`` until interrupted:

    PYTHONPATH=src python -m repro.launch.serve --arch llama-3.2-1b \
        --reduced --paged --qps 4 --arrival poisson --max-queue 8 \
        --request-timeout 30
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import devices as devices_lib
from repro.core.analog import (AnalogConfig, pack_int4_weights,
                               perturb_analog_weights)
from repro.core.noise import validate_noise_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.serve.decode import digital_int4_config, generate
from repro.serve.frontend import AsyncServeFrontend, ShedError
from repro.serve.scheduler import (Request, SchedulerConfig, ServeEngine,
                                   required_max_len)


def deploy_model(args, cfg, params, labels, key):
    """Apply the selected deployment transform. Returns (params, acfg)."""
    if args.deploy == "fp":
        return params, AnalogConfig(mode="off")
    if args.deploy == "analog":
        return params, AnalogConfig(mode="analog", train_noise=False)
    if args.deploy == "analog_hw":
        params = perturb_analog_weights(params, labels, key, "hw")
        print("[serve] applied one simulated PCM chip programming")
        return params, AnalogConfig(mode="analog", train_noise=False)
    if args.deploy == "digital_rtn4":
        print("[serve] RTN-int4 digital deployment (unfused)")
        return params, AnalogConfig(mode="rtn", weight_bits=4)
    # digital_int4: RTN weights served from the packed-int4 Pallas kernel
    params = pack_int4_weights(params, labels)
    print("[serve] RTN-int4 digital deployment (packed-int4 kernel)")
    return params, digital_int4_config(AnalogConfig(weight_bits=4))


def mixed_requests(args, cfg) -> list[Request]:
    """A mixed-length synthetic workload (ragged prompts and budgets)."""
    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.num_requests):
        plen = int(rng.integers(3, args.prompt_len + 1))
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        max_new = int(rng.integers(max(1, args.new_tokens // 4),
                                   args.new_tokens + 1))
        reqs.append(Request(uid=i, prompt=prompt, max_new=max_new,
                            temperature=0.8, top_k=50, seed=args.seed + i))
    return reqs


def arrival_offsets(n: int, qps: float, arrival: str,
                    rng: np.random.Generator) -> np.ndarray:
    """Arrival times (seconds from start) for ``n`` open-loop requests.

    ``poisson``: i.i.d. exponential inter-arrival gaps at rate ``qps``.
    ``burst``: groups of 4 arriving together, groups spaced so the
    long-run rate is still ``qps`` — the adversarial shape for a bounded
    queue (transient overload even when the mean rate is sustainable).
    """
    if arrival == "poisson":
        return np.cumsum(rng.exponential(1.0 / qps, size=n))
    group = 4
    starts = np.arange(n) // group * (group / qps)
    return starts + rng.uniform(0, 1e-3, size=n)


def lat_stats(vals) -> str:
    """``p50/p99`` milliseconds, or ``-/-`` when nothing completed."""
    xs = [v for v in vals if v is not None]
    if not xs:
        return "-/-"
    return (f"{np.percentile(xs, 50) * 1e3:.0f}/"
            f"{np.percentile(xs, 99) * 1e3:.0f}ms")


async def open_loop_run(frontend: AsyncServeFrontend, reqs, offsets):
    """Replay ``reqs`` as open-loop traffic: submit each at its arrival
    offset, collect every terminal result (shed ones included). Returns
    ``(records, wall_seconds)`` where each record is a dict with status,
    ttft, latency and token count."""
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    records: list[dict] = []

    async def one(req, at):
        await asyncio.sleep(max(0.0, at - (loop.time() - t0)))
        try:
            h = await frontend.submit(req)
        except ShedError as e:
            records.append(dict(uid=req.uid, status="shed", ttft=None,
                                latency=0.0, tokens=0, reason=str(e)))
            return
        res = await h.result()
        records.append(dict(uid=req.uid, status=res.status, ttft=res.ttft,
                            latency=res.latency, tokens=len(res.tokens),
                            reason=res.reason))

    await asyncio.gather(*(one(r, a) for r, a in zip(reqs, offsets)))
    return records, loop.time() - t0


def lifecycle_report(eng: ServeEngine, records=None) -> str:
    """The lifecycle tail of the serve report line: TTFT/TPOT and queue
    wait (submit to admission) percentiles, shed/timeout/cancel counts,
    queue high-water mark."""
    waits = [admit - eng.submit_time[uid]
             for uid, admit in eng.admit_time.items()]
    ttfts, tpots = [], []
    for uid, first in eng.first_token_at.items():
        sub = eng.submit_time.get(uid)
        if sub is not None:
            ttfts.append(first - sub)
        done = eng.finished_at.get(uid)
        n = len(eng.results.get(uid, ()))
        if done is not None and n > 1:
            tpots.append((done - first) / (n - 1))
    return (f"TTFT p50/p99 {lat_stats(ttfts)}, "
            f"TPOT p50/p99 {lat_stats(tpots)}, "
            f"queue wait p50/p99 {lat_stats(waits)}, "
            f"{eng.shed_count} shed, {eng.timeout_count} timed out, "
            f"{eng.cancel_count} cancelled, {eng.fault_count} step faults, "
            f"queue high-water {eng.queue_high_water}")


async def http_serve(frontend: AsyncServeFrontend, args, vocab: int):
    """Minimal hand-rolled HTTP/1.1 front door (stdlib only).

    ``POST /generate`` with JSON ``{"prompt": [ids], "max_new": n,
    "temperature": t, "ttft_deadline": s, "deadline": s}`` answers
    ``{"uid", "status", "tokens", "reason", "ttft", "latency"}`` —
    shed requests answer 503 with the engine's explicit reason.
    ``GET /health`` reports live lifecycle counters. Serves until
    cancelled (Ctrl-C)."""
    uid_counter = iter(range(1 << 30))

    async def handle(reader, writer):
        try:
            head = await reader.readuntil(b"\r\n\r\n")
        except (asyncio.IncompleteReadError, asyncio.LimitOverrunError):
            writer.close()
            return
        line = head.split(b"\r\n", 1)[0].decode("latin-1")
        parts = line.split()
        method, path = (parts + ["", ""])[:2]
        clen = 0
        for h in head.split(b"\r\n")[1:]:
            if h.lower().startswith(b"content-length:"):
                clen = int(h.split(b":", 1)[1])
        body = await reader.readexactly(clen) if clen else b""

        def respond(code, obj):
            payload = json.dumps(obj).encode()
            writer.write(
                f"HTTP/1.1 {code}\r\nContent-Type: application/json\r\n"
                f"Content-Length: {len(payload)}\r\n"
                f"Connection: close\r\n\r\n".encode() + payload)

        eng = frontend.engine
        if method == "GET" and path == "/health":
            respond("200 OK", dict(
                active=eng.num_active, queued=eng.queue_depth,
                submitted=eng.submitted, shed=eng.shed_count,
                timed_out=eng.timeout_count, cancelled=eng.cancel_count,
                step_faults=eng.fault_count,
                queue_high_water=eng.queue_high_water))
        elif method == "POST" and path == "/generate":
            try:
                spec = json.loads(body or b"{}")
                prompt = np.asarray(spec["prompt"], np.int32) % vocab
                req = Request(
                    uid=next(uid_counter), prompt=prompt,
                    max_new=int(spec.get("max_new", args.new_tokens)),
                    temperature=float(spec.get("temperature", 0.8)),
                    top_k=int(spec.get("top_k", 50)),
                    seed=int(spec.get("seed", args.seed)),
                    ttft_deadline=float(spec.get("ttft_deadline",
                                                 args.ttft_timeout)),
                    deadline=float(spec.get("deadline",
                                            args.request_timeout)))
            except (KeyError, TypeError, ValueError) as e:
                respond("400 Bad Request", dict(error=str(e)))
            else:
                try:
                    h = await frontend.submit(req)
                except ShedError as e:
                    respond("503 Service Unavailable",
                            dict(uid=req.uid, status="shed",
                                 reason=str(e)))
                else:
                    res = await h.result()
                    respond("200 OK", dict(
                        uid=res.uid, status=res.status,
                        tokens=[int(t) for t in res.tokens],
                        reason=res.reason, ttft=res.ttft,
                        latency=res.latency))
        else:
            respond("404 Not Found", dict(error=f"no route {path}"))
        await writer.drain()
        writer.close()

    server = await asyncio.start_server(handle, "127.0.0.1", args.port)
    print(f"[serve] HTTP front door on http://127.0.0.1:{args.port} "
          f"(POST /generate, GET /health); Ctrl-C to stop")
    async with server:
        await server.serve_forever()


def main():
    """CLI entry point (see module docstring for usage)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--deploy", default="fp",
                    choices=["fp", "analog", "analog_hw", "digital_rtn4",
                             "digital_int4"])
    ap.add_argument("--engine", default="continuous",
                    choices=["continuous", "static"])
    ap.add_argument("--num-requests", type=int, default=8)
    ap.add_argument("--num-slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--step-tokens", type=int, default=0,
                    help="token budget of the fused mixed prefill/decode "
                         "step: one token per decode slot + prefill chunks "
                         "of admitting slots up to the budget (0 = auto: "
                         "num_slots + 2 * prefill_chunk)")
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache-dtype", default="bf16",
                    choices=["bf16", "f32"],
                    help="KV-cache storage precision (bf16 halves cache "
                         "bytes; scores/softmax stay fp32)")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache: free-list block allocation "
                         "+ paged flash-decode attention")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="tokens per physical KV block (paged mode)")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="pool size in blocks (0 = full slot capacity; "
                         "smaller oversubscribes with admission "
                         "backpressure)")
    ap.add_argument("--kv-bits", type=int, default=0, choices=[0, 8],
                    help="8 = int8 KV pool with per-token/head scales "
                         "(paged mode; 2-4x fewer cache bytes)")
    ap.add_argument("--prefix-cache", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="radix prefix caching on the paged pool: "
                         "admissions reuse content-matching KV blocks, "
                         "retired prompts stay LRU-cached "
                         "(--no-prefix-cache frees blocks eagerly)")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-and-verify speculative decoding: the "
                         "drafter proposes --draft-k tokens per slot per "
                         "step, the target verifies the whole window in "
                         "one fused dispatch; exact-match verification "
                         "keeps outputs bitwise identical to the "
                         "non-speculative path (attention families only)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="speculative window length (tokens proposed per "
                         "slot per step)")
    ap.add_argument("--draft", default="int4",
                    choices=["int4", "self", "ngram"],
                    help="drafter: int4 = RTN-int4 digital deployment of "
                         "the target weights (Table 3 pairing), self = "
                         "target drafts for itself (acceptance 1.0), "
                         "ngram = host prompt-lookup (no draft forward)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="truncate the model drafter to its first N "
                         "transformer blocks (0 = full depth; layer-skip "
                         "self-speculation)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards: serve over a (1, N) "
                         "device mesh with column-parallel weights and a "
                         "per-shard KV-head split of the paged pool; "
                         "greedy decode stays bitwise identical to tp=1 "
                         "(docs/distributed.md; on CPU export "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N to provide the devices)")
    ap.add_argument("--cache-salt", type=int, default=0,
                    help="salt folded into every prefix-cache block key "
                         "— segregates entries whose KV would differ for "
                         "reasons outside the token ids (deployment "
                         "config, tenancy)")
    ap.add_argument("--noise-model", default="none",
                    choices=["none", "hw", "gaussian"],
                    help="extra eval-time weight perturbation on analog "
                         "deployments: hw = PCM Hermes programming noise, "
                         "gaussian = per-channel-max additive (set "
                         "--noise-gamma > 0; gaussian at gamma 0 is a "
                         "placebo and errors out)")
    ap.add_argument("--noise-gamma", type=float, default=0.0,
                    help="gaussian magnitude as a fraction of the "
                         "per-channel max weight (--noise-model gaussian)")
    ap.add_argument("--drift-hours", type=float, default=0.0,
                    help="total deployment-hours of conductance drift "
                         "spread (approximately) across the serve run: "
                         "attaches per-tile device state to analog "
                         "weights and ticks the engine's drift clock "
                         "each worked step")
    ap.add_argument("--recalibrate", action="store_true",
                    help="let the drift watchdog reprogram analog tiles "
                         "in place when per-tile scale error trips its "
                         "threshold (needs --drift-hours > 0)")
    ap.add_argument("--fault-prob", type=float, default=0.0,
                    help="per-column stuck-fault / per-tile dead-tile "
                         "probability of the attached device state "
                         "(--drift-hours mode; faults are permanent — "
                         "recalibration never clears them)")
    ap.add_argument("--qps", type=float, default=0.0,
                    help="open-loop mode: replay the synthetic workload "
                         "as arriving traffic at this rate through the "
                         "async frontend (0 = closed-loop eng.run)")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "burst"],
                    help="open-loop arrival process: poisson = "
                         "exponential gaps at --qps, burst = groups of 4 "
                         "arriving together at the same long-run rate")
    ap.add_argument("--request-timeout", type=float, default=0.0,
                    help="end-to-end deadline per request in seconds "
                         "(0 = none); overdue requests are retired as "
                         "timed_out with their partial output")
    ap.add_argument("--ttft-timeout", type=float, default=0.0,
                    help="first-token deadline per request in seconds "
                         "(0 = none); enforced while queued and during "
                         "prefill")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue for open-loop modes "
                         "(0 = unbounded); arrivals past the bound are "
                         "shed with an explicit reason, never silently "
                         "dropped")
    ap.add_argument("--serve", action="store_true",
                    help="open a minimal HTTP/1.1 front door on --port "
                         "(POST /generate, GET /health) and serve until "
                         "interrupted instead of replaying the synthetic "
                         "workload")
    ap.add_argument("--port", type=int, default=8321,
                    help="TCP port for --serve")
    args = ap.parse_args()
    # honest config: reject meaningless noise settings before any work
    validate_noise_config(args.noise_model, args.noise_gamma)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduce()
    key = jax.random.PRNGKey(args.seed)
    cfg, params, labels = build(cfg, key)
    params, acfg = deploy_model(args, cfg, params, labels, key)
    if args.noise_model != "none":
        if acfg.mode == "analog":
            params = perturb_analog_weights(
                params, labels, jax.random.fold_in(key, 1),
                args.noise_model, args.noise_gamma)
            print(f"[serve] applied {args.noise_model} eval noise"
                  + (f" (gamma={args.noise_gamma:g})"
                     if args.noise_model == "gaussian" else ""))
        else:
            print(f"[serve] WARNING: --noise-model {args.noise_model} "
                  "perturbs analog weights; inert for deploy="
                  f"{args.deploy!r}")
    cache_dtype = jnp.bfloat16 if args.cache_dtype == "bf16" else jnp.float32
    if args.kv_bits:
        acfg = dataclasses.replace(acfg, kv_bits=args.kv_bits)
        if not args.paged:
            print("[serve] --kv-bits implies the paged pool: enabling "
                  "--paged")
            args.paged = True

    if cfg.family in ("audio", "vlm") and args.engine == "continuous":
        # the scheduler does not serve multi-codebook / patch-embed
        # families yet — keep these archs on the lockstep path
        print(f"[serve] family={cfg.family!r}: falling back to the static "
              "engine (continuous batching not wired for it)")
        args.engine = "static"

    if args.engine == "static":
        if args.paged or args.kv_bits:
            print("[serve] --paged/--kv-bits are continuous-engine "
                  "options: ignored on the static path")
        if args.drift_hours or args.recalibrate:
            print("[serve] --drift-hours/--recalibrate are "
                  "continuous-engine options: ignored on the static path")
        prompts = jax.random.randint(key, (args.num_requests, 4), 0,
                                     cfg.vocab_size)
        if cfg.family == "audio":
            prompts = prompts[..., None].repeat(cfg.num_codebooks, -1)
        t0 = time.perf_counter()
        toks = generate(params, cfg, acfg, key, prompts, args.new_tokens,
                        temperature=0.8, top_k=50, cache_dtype=cache_dtype)
        toks.block_until_ready()
        dt = time.perf_counter() - t0
        total = args.num_requests * args.new_tokens
        print(f"[serve] static: {total} tokens in {dt:.2f}s "
              f"({total / dt:.1f} tok/s); sample: "
              f"{jax.device_get(toks[0])[:8]}")
        return

    open_loop = args.serve or args.qps > 0
    reqs = mixed_requests(args, cfg)
    if open_loop and (args.request_timeout or args.ttft_timeout):
        reqs = [dataclasses.replace(r, deadline=args.request_timeout,
                                    ttft_deadline=args.ttft_timeout)
                for r in reqs]
    chunk = args.prefill_chunk
    max_len = max(required_max_len(len(r.prompt), r.max_new, chunk)
                  for r in reqs)
    drift_dt = 0.0
    # step count is only estimable (admission interleaves with decode) —
    # served hours are approximate; the engine reports the exact total
    est_steps = max(1, sum(r.max_new for r in reqs) // args.num_slots
                    + args.num_requests)
    if args.drift_hours > 0:
        if acfg.mode == "analog":
            dcfg = devices_lib.DeviceConfig(p_stuck_col=args.fault_prob,
                                            p_dead_tile=args.fault_prob)
            params = devices_lib.attach_device_state(
                params, labels, jax.random.fold_in(key, 2), dcfg)
            drift_dt = args.drift_hours / est_steps
            print(f"[serve] per-tile device state attached "
                  f"(~{args.drift_hours:g}h drift over ~{est_steps} steps)")
        else:
            print("[serve] WARNING: --drift-hours needs an analog "
                  f"deployment (deploy={args.deploy!r} has no crossbar "
                  "tiles to age): drift clock inert")
    eng = ServeEngine(params, cfg, acfg, SchedulerConfig(
        num_slots=args.num_slots, max_len=max_len, prefill_chunk=chunk,
        step_tokens=args.step_tokens, cache_dtype=cache_dtype,
        paged=args.paged, kv_block_size=args.kv_block_size,
        kv_blocks=args.kv_blocks, prefix_cache=args.prefix_cache,
        cache_salt=args.cache_salt, speculative=args.speculative,
        draft_k=args.draft_k, draft=args.draft,
        draft_layers=args.draft_layers,
        drift_dt=drift_dt, recalibrate=args.recalibrate,
        # watchdog cadence scaled to the workload so short demo runs
        # still health-check a handful of times
        recal_interval=max(1, est_steps // 8) if drift_dt else 25,
        # open-loop modes bound the queue and survive step faults — a
        # public front door must degrade, not die (a finite --qps replay
        # still exits non-zero on any fault, below)
        max_queue=args.max_queue if open_loop else 0,
        fault_tolerant=open_loop, tp=args.tp))
    # honest feature reporting: a requested-but-inert feature warns
    # loudly with the engine's recorded reason — never a silent placebo.
    # --prefix-cache defaults on, so its warning fires only when the
    # flag was explicitly requested on the command line.
    requested = {"paged": args.paged,
                 "prefix_cache": "--prefix-cache" in sys.argv,
                 "speculative": args.speculative,
                 "drift": args.drift_hours > 0,
                 "recalibrate": args.recalibrate,
                 "tensor_parallel": args.tp > 1}
    for feat, why in eng.gating_reasons.items():
        if requested.get(feat):
            flag = {"drift": "--drift-hours",
                    "tensor_parallel": "--tp"}.get(
                feat, "--" + feat.replace("_", "-"))
            print(f"[serve] WARNING: {flag} requested but inactive: {why}")
    if eng.mesh is not None:
        print(f"[serve] tensor parallel: tp={args.tp} over "
              f"{[d.id for d in eng.mesh.devices.flat]} "
              f"(column-parallel weights, kv_heads/{args.tp} per shard)")
    if args.serve:
        fe = AsyncServeFrontend(eng)

        async def door():
            await fe.start()
            try:
                await http_serve(fe, args, cfg.vocab_size)
            finally:
                await fe.stop()

        try:
            asyncio.run(door())
        except KeyboardInterrupt:
            print(f"[serve] shutting down; {lifecycle_report(eng)}")
        return

    if args.qps > 0:
        rng = np.random.default_rng(args.seed + 1)
        offsets = arrival_offsets(len(reqs), args.qps, args.arrival, rng)
        fe = AsyncServeFrontend(eng)

        async def drive():
            await fe.start()
            try:
                return await open_loop_run(fe, reqs, offsets)
            finally:
                await fe.stop()

        records, wall = asyncio.run(drive())
        by = {}
        for r in records:
            by[r["status"]] = by.get(r["status"], 0) + 1
        total = sum(r["tokens"] for r in records)
        # no-silent-drop accounting: every arrival reaches a terminal
        assert len(records) == len(reqs) == eng.submitted
        print(f"[serve] open-loop ({args.arrival} @ {args.qps:g} qps, "
              f"{len(reqs)} arrivals): {total} tokens in {wall:.2f}s "
              f"({total / wall:.1f} tok/s), outcomes {by}, "
              f"{fe.steps} engine steps; {lifecycle_report(eng)}")
        # a finite replay is a run to completion, not a front door: any
        # step fault or errored request fails the process
        if eng.fault_count or by.get("errored"):
            first = next(iter(eng.errors.values()), "")
            sys.exit(f"[serve] open-loop replay failed: {eng.fault_count} "
                     f"step faults, {by.get('errored', 0)} errored "
                     f"requests; first error: {first}")
        return

    t0 = time.perf_counter()
    results = eng.run(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(v) for v in results.values())
    lats = sorted(eng.finished_at[r.uid] - t0 for r in reqs)
    # report what the engine actually runs (SSM stacks serve from the
    # contiguous state cache; their prefix cache is the snapshot pool)
    mode = ("paged" + ("-int8" if acfg.kv_bits == 8 else "")
            if eng.pool is not None else "contiguous")
    if eng.prefix_enabled:
        hit_rate = (eng.prefix_hits / eng.prefix_lookups
                    if eng.prefix_lookups else 0.0)
        idx_pool = eng.pool if eng.pool is not None else eng.state_pool
        snaps = (f", {eng.state_snaps_captured} state snapshots "
                 f"({eng.state_snap_restores} restored)"
                 if eng.state_pool is not None else "")
        prefix = (f", prefix cache: {hit_rate:.0%} hit rate, "
                  f"{eng.prefix_skipped_tokens} prefill tokens skipped, "
                  f"{idx_pool.num_cached} blocks retained, "
                  f"{idx_pool.evictions} evictions{snaps}")
    else:
        prefix = ""
    if eng.spec_enabled:
        prefix += (f", speculative ({eng.scfg.draft} drafter, k="
                   f"{eng.scfg.draft_k}): {eng.spec_steps} verify windows, "
                   f"{eng.spec_acceptance:.0%} draft acceptance")
    if eng.drift_enabled:
        prefix += (f", drift: {eng.drift_hours:.1f}h deployed, "
                   f"tile_err={eng.tile_scale_err:.3f}, "
                   f"{eng.dead_tiles} dead tiles, {eng.stuck_cols} stuck "
                   f"cols, {eng.recal_count} recals "
                   f"({eng.watchdog_checks} watchdog checks)")
    print(f"[serve] continuous ({mode} kv, {args.cache_dtype}): {total} "
          f"tokens across {len(reqs)} "
          f"mixed-length requests in {dt:.2f}s ({total / dt:.1f} tok/s, "
          f"{eng.decode_steps} decode steps, {eng.mixed_steps} fused "
          f"mixed steps, {eng.decode_tokens_during_admission} decode "
          f"tokens emitted during admission, "
          f"p50 latency {lats[len(lats) // 2] * 1e3:.0f}ms{prefix}; "
          f"{lifecycle_report(eng)}); sample: {results[0][:8]}")


if __name__ == "__main__":
    main()
