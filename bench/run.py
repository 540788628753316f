#!/usr/bin/env python3
"""Benchmark harness: run one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload phi3.analog.chat --seed 7 \\
        --seconds 40 --trace 0

A cell names a configuration (``bench/configs/<config>.json``: sizes,
deployment) and a traffic mix (``bench/traffic/<traffic>.json``: lengths,
arrivals, sampling, slots). The harness

1. makes the weights from ``--seed`` on the device (``bench/model.py``),
   hands them to the program's deployment (``launch/serve.py``'s
   ``deploy_model``) and builds a ``ServeEngine`` with the mix's slots,
   ``max_len`` and KV dtype, every other setting at the program's default;
2. warms up every executable the window will run, with requests outside
   it, and opens the window: ``setup_s`` is process start to here;
3. drives ``AsyncServeFrontend.submit`` as clients do for ``--seconds``:
   open-loop arrivals or a backlog from ``bench/traffic.py``, each token
   timestamped as the client's stream receives it; open-loop arrivals go
   on past the close, uncounted, until every request due in the window
   has its first token (``FOLLOW_S`` at most);
4. closes the window, cancels what is still in flight, reads the device's
   peak memory, frees the program's state, and checks a seeded sample of
   the finished greedy requests, the longest among them, against the
   plain reference (``bench/reference.py``): ``correct`` holds when the
   logit gaps of the served tokens stay within the cell's limits
   (``bench/limits/<cell>.json``);
5. prints one JSON line last on stdout. ``--trace 0`` reports the cell's
   end-to-end metrics, ``--trace 1`` its per-layer metrics, read from a
   profiler trace of the window and from the program's counters. Each
   metric is computed by ``bench/metrics/<name>.py``.

The run fails, and prints no result, on any platform but a TPU with
enough chips, or outside a checkout that holds the program (``src/``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# run as a script, sys.path[0] is bench/: put the checkout there instead,
# so the benchmark's modules are only reachable as ``bench.*``
if sys.path and pathlib.Path(sys.path[0]).resolve() == BENCH:
    sys.path[0] = str(ROOT)

#: The harness's host span around the window, in a ``--trace 1`` run.
WINDOW_SPAN = "bench.window"
#: Longest wait past the close for the first tokens of the window's
#: requests, while open-loop arrivals go on (``traffic.follow_on``).
FOLLOW_S = 60.0


class RunError(RuntimeError):
    """A run that cannot produce a result (no chip, no program, bad cell)."""


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    """``BENCHMARK.json`` of the checkout."""
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    """The ``workloads`` entry called ``name``."""
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise RunError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, section: str, cell: str) -> list[dict]:
    """The metrics of ``section`` that ``cell`` reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str, root: pathlib.Path = BENCH / "metrics"):
    """The ``read(ctx)`` function of metric ``name``."""
    path = root / f"{name}.py"
    if not path.is_file():
        raise RunError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_limits(cell: str, root: pathlib.Path = BENCH / "limits") -> dict:
    """The comparison limits of ``cell``."""
    path = root / f"{cell}.json"
    if not path.is_file():
        raise RunError(f"no limits for cell {cell!r} at {path}")
    return json.loads(path.read_text())


def require_program(root: pathlib.Path = ROOT) -> None:
    """Put the checkout's program on the path, or fail."""
    src = root / "src"
    if not (src / "repro").is_dir():
        raise RunError(f"no program at {src}: run from a checkout of the "
                       "repository")
    if str(src) not in sys.path:
        sys.path.insert(1, str(src))


def enable_cache(root: pathlib.Path = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``JAX_COMPILATION_CACHE_DIR`` when the environment sets it)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chip(chips: int, peaks: dict) -> dict:
    """The device record, or fail: a TPU, enough chips, known peaks."""
    import jax
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if platform != "tpu":
        raise RunError(f"JAX found no TPU (platform {platform!r}); the "
                       "benchmark runs on the chip only")
    if len(devs) < chips:
        raise RunError(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    if kind not in peaks:
        raise RunError(f"no peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    return {"platform": platform, "kind": kind, "count": len(devs)}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def program_config(spec):
    """The program's ``ArchConfig``: its own file, with the published
    sizes of the benchmark's configuration put in."""
    from repro.configs import get_config
    base = get_config(spec.arch)
    cfg = dataclasses.replace(
        base, num_layers=spec.layers, d_model=spec.d, num_heads=spec.heads,
        num_kv_heads=spec.kv_heads, d_ff=spec.ff, vocab_size=spec.vocab,
        rope_theta=spec.rope_theta, tie_embeddings=spec.tied,
        d_head=spec.head_dim)
    if (cfg.family, cfg.norm, cfg.act, cfg.qkv_bias) != (
            "dense", "rmsnorm", "silu", False):
        raise RunError(f"{spec.arch}: the program's config is not a dense "
                       "RMSNorm SwiGLU decoder without qkv bias")
    return cfg


def build_engine(spec, mix: dict, seed: int):
    """Weights from the seed, the program's deployment, the engine."""
    import jax
    import jax.numpy as jnp

    from bench import model
    from repro.launch.serve import deploy_model
    from repro.serve.scheduler import SchedulerConfig, ServeEngine

    cfg = program_config(spec)
    key = model.key_of(seed)
    params = jax.block_until_ready(model.make_params(key, spec))
    params, acfg = deploy_model(argparse.Namespace(deploy=spec.deploy), cfg,
                                params, None, key)
    eng_cfg = mix["engine"]
    scfg = SchedulerConfig(num_slots=eng_cfg["num_slots"],
                           max_len=eng_cfg["max_len"],
                           cache_dtype=jnp.dtype(eng_cfg["kv_dtype"]))
    return ServeEngine(params, cfg, acfg, scfg)


def to_request(item, uid: int):
    """The program's ``Request`` for a traffic item."""
    from repro.serve.scheduler import Request
    return Request(uid=uid, prompt=item.prompt, max_new=item.max_new,
                   temperature=item.temperature, top_k=item.top_k,
                   greedy_first=item.max_new if item.greedy else 0,
                   seed=item.seed)


def warm_up(eng, mix: dict, vocab: int) -> None:
    """Run every executable the window uses, outside it: admission, a
    prefill-only step, a prefill step fused with decode, and decode
    blocks of 8, 4, 2 and 1 tokens, with the mix's sampling."""
    import numpy as np

    from bench.traffic import Item
    smp = mix["sampling"]
    rng = np.random.default_rng(0)

    def item(i, plen, new):
        return Item(index=i, due=0.0,
                    prompt=rng.integers(0, vocab, plen).astype(np.int32),
                    max_new=new, temperature=float(smp["temperature"]),
                    top_k=int(smp["top_k"]), greedy=False, seed=i)

    base = -1_000_000
    eng.run([to_request(item(0, 32, 16), base)])
    eng.run([to_request(item(1, 16, 40), base - 1),
             to_request(item(2, 64, 4), base - 2)])


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

COUNTERS = ("decode_steps", "mixed_steps")


def counters(eng) -> dict:
    """The program's own counters and phase clocks."""
    out = {k: getattr(eng, k) for k in COUNTERS}
    out["phase_time"] = dict(eng.phase_time)
    return out


def first_tokens_due(records, t1: float) -> bool:
    """Whether every request due before ``t1`` has its first token or
    has ended."""
    return all(r.token_times or r.status is not None
               for r in records if r.due < t1)


async def drive(eng, items, seconds: float, trace_dir, follow: bool):
    """Serve ``items`` through the async frontend for one window; with
    ``follow``, go on serving (``items`` holds the load that follows the
    window) until the window's requests have their first tokens."""
    from repro.serve.frontend import AsyncServeFrontend, ShedError

    from bench.stats import Record
    loop = asyncio.get_running_loop()
    fe = AsyncServeFrontend(eng)
    span = None
    if trace_dir is not None:
        # before the window: the profiler's start stalls the host for
        # seconds on a TPU
        import jax
        jax.profiler.start_trace(trace_dir)
        span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
    await fe.start()
    out = {"setup_s": time.perf_counter() - T_START,
           "counters0": counters(eng)}
    if span is not None:
        span.__enter__()
    t0 = loop.time()
    t1 = t0 + seconds
    records = [Record(uid=it.index, due=t0 + it.due,
                      prompt_len=len(it.prompt), max_new=it.max_new,
                      greedy=it.greedy) for it in items]

    async def client(it, rec):
        delay = rec.due - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        rec.sent = loop.time()
        try:
            handle = await fe.submit(to_request(it, it.index))
        except ShedError:
            rec.status = "shed"
            return
        async for tok in handle.stream():
            rec.token_times.append(loop.time())
            rec.tokens.append(tok)
        rec.status = (await handle.result()).status

    tasks = [loop.create_task(client(it, rec))
             for it, rec in zip(items, records)]
    await asyncio.sleep(max(0.0, t1 - loop.time()))
    out["counters1"] = counters(eng)
    if span is not None:
        span.__exit__(None, None, None)
    while (follow and not first_tokens_due(records, t1)
           and loop.time() < t1 + FOLLOW_S):
        await asyncio.sleep(0.05)
    out.update(t0=t0, t1=t1, t_end=max(t1, loop.time()), records=records)
    if span is not None:
        # after the follow-on: stopping the profiler stalls the host for
        # seconds, which the last requests' first tokens would wait out
        jax.profiler.stop_trace()
    # the check samples requests finished by now: cancel the rest
    await asyncio.gather(*(fe.cancel(r.uid) for r in records
                           if r.status is None and r.sent is not None))
    for task, rec in zip(tasks, records):
        if rec.sent is None:
            task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    await fe.stop()
    out["lateness"] = sorted(r.sent - r.due for r in records
                             if r.sent is not None)
    return out


def sample_checked(records, t1: float, n: int, seed: int) -> list:
    """A seeded sample of ``n`` finished greedy requests due in the
    window, the longest (prompt plus output) always among them."""
    import numpy as np
    done = [r for r in records if r.greedy and r.due < t1
            and r.status == "finished"]
    if not done:
        return []
    done.sort(key=lambda r: (r.prompt_len + len(r.tokens), r.uid))
    rest = done[:-1]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [done[-1]] + [rest[i] for i in sorted(pick)]


def backlog_note(records, t0: float, t1: float, t_end: float) -> str:
    """Whether the queue grew over the window: requests due with no first
    token at its close, and the median time to first token of the
    requests due in each half of it."""
    from bench.stats import percentile, ttft_values
    mid = (t0 + t1) / 2
    halves = [ttft_values(records, a, b, t_end)
              for a, b in ((t0, mid), (mid, t1))]
    waiting = sum(not r.token_times or r.token_times[0] > t1
                  for r in records if t0 <= r.due < t1)
    med = [percentile(h, 50) if h else None for h in halves]
    return (f"backlog: {waiting} requests due and waiting at the close; "
            f"median TTFT {med[0]!r} s in the first half, {med[1]!r} s in "
            "the second")


def compare(cmp: dict, limits: dict) -> tuple[bool, dict]:
    """Hold the check's numbers to the cell's limits: at least
    ``min_checked_tokens`` tokens checked, and every other statistic
    named in ``limits`` (a key of ``reference.check_requests``' result,
    such as ``gap_ratio``) at or under its limit. A statistic that is
    missing or not finite fails. Every statistic of the check is
    reported, those not held with the limit None, the held ones last."""
    compared = {k: {"value": v, "limit": None} for k, v in cmp.items()
                if k != "tokens" and k not in limits}
    compared["checked_tokens"] = {"value": cmp["tokens"],
                                  "limit": limits["min_checked_tokens"]}
    ok = cmp["tokens"] >= limits["min_checked_tokens"]
    for key, limit in limits.items():
        if key == "min_checked_tokens":
            continue
        value = cmp.get(key, math.inf)
        compared[key] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and value <= limit
    return ok, compared


def run_cell(bench: dict, cell: dict, conf: dict, mix: dict, limits: dict,
             seed: int, seconds: float, trace: bool,
             device: dict | None = None,
             peaks: dict | None = None) -> tuple[dict, list[str]]:
    """One run of ``cell`` with its configuration ``conf``, traffic mix
    ``mix`` and comparison ``limits``, on ``device`` (whose ``peaks``
    the metrics use). Returns the result line's object and the lines that
    state each compared number with its limit."""
    import jax
    import numpy as np

    from bench import model, reference, traffic
    spec = model.spec_of(conf)

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **_: compiles.append(time.perf_counter())
        if ev == "/jax/core/compile/backend_compile_duration" else None)

    eng = build_engine(spec, mix, seed)
    gated = eng.gating_reasons
    warm_up(eng, mix, spec.vocab)
    items = traffic.schedule(mix, spec.vocab, seed, seconds)
    after = traffic.follow_on(mix, spec.vocab, seed, seconds, FOLLOW_S,
                              len(items))
    with tempfile.TemporaryDirectory() as tmp:
        trace_dir = os.path.join(tmp, "trace") if trace else None
        t_open = time.perf_counter()
        win = asyncio.run(drive(eng, items + after, seconds, trace_dir,
                                bool(after)))
        t_shut = time.perf_counter()
        reduced = None
        if trace:
            from bench import trace as trace_lib
            reduced = trace_lib.reduce_dir(trace_dir, WINDOW_SPAN)
    window_compiles = sum(t_open <= t <= t_open + seconds for t in compiles)
    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")

    records = win["records"]
    t0, t1, t_end = win["t0"], win["t1"], win["t_end"]
    attempted = [r for r in records if t0 <= r.due < t1]
    failed = sum(r.failed for r in attempted)
    checked = sample_checked(records, t1, int(mix["check"]["requests"]),
                             seed)
    served = [(items[r.uid].prompt, np.asarray(r.tokens, np.int32))
              for r in checked]
    del eng
    gc.collect()

    cmp = {"tokens": 0}
    if served:
        ref_params = model.make_params(model.key_of(seed), spec)
        cmp = reference.check_requests(
            ref_params, spec, served, pad_to=mix["engine"]["max_len"],
            rows_to=mix["output_tokens"]["max"])
        del ref_params
    correct, compared = compare(cmp, limits)

    ctx = types.SimpleNamespace(
        spec=spec, records=records, t0=t0, t1=t1, t_end=t_end,
        setup_s=win["setup_s"],
        counters0=win["counters0"], counters1=win["counters1"],
        trace=reduced, peaks=peaks, chips=cell["chips"])
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, section, cell["name"]):
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device or {})
    dev["memory_peak_bytes"] = mem_peak
    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": int(failed), "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["compared"] = compared
    late = win["lateness"]
    notes = [f"engine settings the program gated off: "
             f"{sorted(gated)}",
             f"window {seconds} s, {len(attempted)} requests due, "
             f"{failed} failed, {window_compiles} compiles in the window, "
             f"drain and check {time.perf_counter() - t_shut:.1f} s",
             f"load generator lateness: median "
             f"{late[len(late) // 2] if late else 0.0!r} s, max "
             f"{late[-1] if late else 0.0!r} s",
             f"followed {t_end - t1:.1f} s past the close for first tokens",
             backlog_note(records, t0, t1, t_end)]
    if reduced is not None:
        notes.append(f"trace: {reduced['devices']} device plane(s), "
                     f"{reduced['ops']} operations in the window")
    notes += [f"compared {k} {v['value']!r} limit {v['limit']!r}"
              for k, v in compared.items()]
    return result, notes


def main(argv=None) -> int:
    """CLI entry point (see module docstring)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        from bench import model, traffic
        bench = load_benchmark()
        cell = find_cell(bench, args.workload)
        conf = model.load_config(cell["config"])
        mix = traffic.load_mix(cell["traffic"])
        limits = load_limits(cell["name"])
        require_program()
        enable_cache()
        peaks = json.loads((BENCH / "peaks.json").read_text())
        device = require_chip(cell["chips"], peaks)
        result, notes = run_cell(bench, cell, conf, mix, limits, args.seed,
                                 args.seconds, bool(args.trace), device,
                                 peaks[device["kind"]])
    except (RunError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for line in notes:
        print(f"bench: {line}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
