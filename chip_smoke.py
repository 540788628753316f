#!/usr/bin/env python3
"""Chip smoke test: serve llama-3.2-1b at full width on one TPU chip.

Builds llama-3.2-1b at its published widths (16 layers, d_model 2048,
32 query / 8 kv heads, d_ff 8192, vocab 128256; random weights from
``--seed``) and serves a few mixed-length requests through
``ServeEngine`` on the paged KV pool, under three deployments in turn:

* ``analog_hw`` — one simulated PCM chip programming, unfused analog MVM;
* ``analog_hw_fused`` — the same weights on the fused analog MVM kernel
  (``AnalogConfig.use_pallas=True``);
* ``digital_int4`` — the paper's Table-3 RTN-int4 deployment on the
  packed-int4 kernel.

Every serving kernel but ``ssd_scan`` (an SSM kernel) then runs compiled
on the chip: ``paged_flash_prefill``, ``paged_flash_decode``,
``analog_matmul`` and ``int4_matmul``. Each deployment must finish every
request with its full token budget, and must pass a logits check: the
last-position logits of a prefill and of one decode step on the serving
path (paged cache, the deployment's kernels) against ``models.apply``
with no cache and no Pallas kernel, both under
``jax.default_matmul_precision("highest")``. The same check on the
float model (no quantizer) holds the paged kernels to a tight tolerance
first.

    python3 chip_smoke.py              # one chip, the three deployments
    python3 chip_smoke.py --chips 4    # tensor parallel, four chips

``--chips 4`` serves the unfused analog deployment on the contiguous
cache at tp=4 and at tp=1 in one process, checks that the engine's mesh
spans four devices, and compares the two runs' greedy tokens and
prefill/decode logits. It runs no other phase.

The script refuses to run anywhere but on a TPU, and outside a checkout
of the repository. Any failure exits non-zero without a result line;
on success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed on the way are set-up-inclusive smoke timings (compilation
included), not metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# Logits-check tolerances: relative L2 error ||got - ref|| / ||ref|| of a
# last-position logits vector, both sides at "highest" matmul precision
# (the Pallas dots included: Mosaic then contracts f32 at f32).
#
# FLOAT_RTOL holds the float model (no quantizer) on the paged attention
# kernels. What differs from the reference there is f32 summation order
# alone (online softmax over 16-token blocks, tiled dots), of order 1e-6
# relative; a kernel that drops or misplaces one head's or one block's
# keys moves the logits by 1e-2 or more.
FLOAT_RTOL = 1e-3
# LOGITS_RTOL holds the quantized deployments. Their 8-bit DAC/ADC
# quantizers at every projection turn a summation-order difference into
# whole quantization levels: a value within rounding error of a level
# boundary moves one full level (~1% of its range), and 16 layers carry
# such flips forward. On a v5e the three deployments measured 0.025 to
# 0.036, and tp=4 against tp=1 (no kernel at all) 0.034. A wrong layout,
# scale or unpacking gives an error of order 1.
LOGITS_RTOL = 0.1


def _say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _require_checkout() -> None:
    """Put the checkout's ``src`` on the path, or fail: the script is
    not the program."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chip_smoke: no repro package at {SRC} — run this "
                 "script from a checkout of the repository")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def _require_tpu(chips: int):
    """The platform JAX found, or exit: a smoke run never falls back."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {platform!r}, "
                 f"{len(devs)} device(s)); this script runs on the chip "
                 "only")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# logits check
# ---------------------------------------------------------------------------

@functools.cache
def _jits():
    """The check's jitted forwards (built once, after ``repro`` is
    importable)."""
    import jax
    import jax.numpy as jnp

    from repro.core.analog import AnalogCtx
    from repro.distributed import sharding
    from repro.models import apply as model_apply

    ctx = AnalogCtx(key=None, training=False)

    @functools.partial(jax.jit, static_argnames=("cfg", "acfg"))
    def reference(params, tokens, *, cfg, acfg):
        logits, _, _ = model_apply(params, cfg, acfg, ctx,
                                   {"tokens": tokens})
        return logits[0, -2:].astype(jnp.float32)

    @functools.partial(jax.jit, static_argnames=("cfg", "acfg", "mesh"))
    def cached(params, caches, tokens, pos, *, cfg, acfg, mesh):
        with sharding.serve_ctx(mesh):
            logits, _, caches = model_apply(
                params, cfg, acfg, ctx, {"tokens": tokens}, caches=caches,
                pos_offset=pos, seq_mask=jnp.ones(tokens.shape, jnp.float32),
                last_only=True)
        return logits[0, -1].astype(jnp.float32), caches

    return reference, cached


def logits_check(params, cfg, acfg, prompt, *, paged: bool, mesh=None,
                 max_len: int, block_size: int = 16):
    """Serving-path logits vs the plain reference, for one prompt.

    The serving side prefills ``prompt`` into a fresh one-slot engine
    cache (paged pool or contiguous, f32, sharded over ``mesh`` when one
    is given) with the deployment's ``acfg``, then decodes one step fed
    the prompt's last token. The reference runs ``models.apply`` over the
    prompt plus that token with no cache and ``use_pallas=False``.
    Returns ``(got [2, V], ref [2, V])`` for (prefill, decode).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed import sharding
    from repro.models import transformer as T

    reference, cached = _jits()
    toks = jnp.asarray(np.asarray(prompt, np.int32))[None]
    step = toks[:, -1:]
    caches = T.init_caches(cfg, 1, max_len, jnp.float32, per_slot=True,
                           paged=paged, kv_block_size=block_size)
    if paged:   # the slot owns physical blocks 1..NB (0 is the sink)
        nb = caches["tbl"].shape[-1]
        row = jnp.broadcast_to(jnp.arange(1, nb + 1, dtype=jnp.int32),
                               caches["tbl"].shape)
        caches = {**caches, "tbl": row, "wtbl": row}
    if mesh is not None:
        caches = sharding.shard_caches_for_serve(mesh, caches)
    s = toks.shape[1]
    with jax.default_matmul_precision("highest"):
        lp, caches = cached(params, caches, toks, jnp.zeros((1, 1), jnp.int32),
                            cfg=cfg, acfg=acfg, mesh=mesh)
        ld, _ = cached(params, caches, step, jnp.full((1, 1), s, jnp.int32),
                       cfg=cfg, acfg=acfg, mesh=mesh)
        ref = reference(params, jnp.concatenate([toks, step], axis=1),
                        cfg=cfg, acfg=dataclasses.replace(acfg,
                                                          use_pallas=False))
    got = np.stack([np.asarray(lp), np.asarray(ld)])
    return got, np.asarray(ref)


def rel_err(got, ref) -> list[float]:
    """Per-row relative L2 error ``||got - ref|| / ||ref||``."""
    import numpy as np
    return [float(np.linalg.norm(g - r) / max(np.linalg.norm(r), 1e-30))
            for g, r in zip(got, ref)]


def _check_logits(name, got, ref, tol, failures):
    """Finite, and within ``tol`` of the reference; records failures."""
    import numpy as np
    errs = rel_err(got, ref)
    finite = bool(np.isfinite(got).all())
    ok = finite and max(errs) <= tol
    _say(f"{name}: logits check prefill rel_err={errs[0]!r} decode "
         f"rel_err={errs[1]!r} (tol {tol}), finite={finite} -> "
         f"{'ok' if ok else 'FAIL'}")
    if not ok:
        failures.append(f"{name}: logits check")
    return errs


def _check_served(name, eng, reqs, results, failures):
    """Every request finished with its whole token budget."""
    bad = [r.uid for r in reqs
           if eng.status.get(r.uid) != "finished"
           or len(results.get(r.uid, ())) != r.max_new]
    if bad or eng.fault_count:
        failures.append(f"{name}: requests {bad} not finished with their "
                        f"budget ({eng.fault_count} step faults; "
                        f"{dict(eng.errors)})")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def _workload(cfg, seed, num_requests, prompt_len, new_tokens):
    """``launch.serve``'s own mixed-length workload for these sizes."""
    from repro.launch.serve import mixed_requests
    return mixed_requests(argparse.Namespace(
        seed=seed, num_requests=num_requests, prompt_len=prompt_len,
        new_tokens=new_tokens), cfg)


def _max_len(reqs, chunk):
    """Slot length that fits every request of the workload."""
    from repro.serve.scheduler import required_max_len
    return max(required_max_len(len(r.prompt), r.max_new, chunk)
               for r in reqs)


def smoke(cfg, *, seed: int = 0, num_requests: int = 8,
          prompt_len: int = 40, new_tokens: int = 24, num_slots: int = 4,
          prefill_chunk: int = 8) -> dict:
    """Serve ``cfg`` under the three deployments and check each one.

    The body of ``chip_smoke.py``, callable at any config and on any
    backend (tests run it at a ``reduce()``d config on the CPU).
    First the float model's logits through the paged kernels are held
    to ``FLOAT_RTOL``. Returns a dict with per-deployment tokens, wall
    seconds and logits errors, the float check's errors
    (``float_rel_err``), and ``failures``: the list of checks that
    failed.
    """
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import deploy_model
    from repro.models import build
    from repro.serve.scheduler import SchedulerConfig, ServeEngine

    reqs = _workload(cfg, seed, num_requests, prompt_len, new_tokens)
    max_len = _max_len(reqs, prefill_chunk)
    scfg = SchedulerConfig(num_slots=num_slots, max_len=max_len,
                           prefill_chunk=prefill_chunk,
                           cache_dtype=jnp.bfloat16, paged=True)
    out: dict = {"deployments": {}, "failures": []}

    def serve(name, params, acfg):
        got, ref = logits_check(params, cfg, acfg, reqs[0].prompt,
                                paged=True, max_len=max_len)
        errs = _check_logits(name, got, ref, LOGITS_RTOL, out["failures"])
        eng = ServeEngine(params, cfg, acfg, scfg)
        t0 = time.perf_counter()
        results = eng.run(reqs)
        wall = time.perf_counter() - t0
        _check_served(name, eng, reqs, results, out["failures"])
        toks = sum(len(v) for v in results.values())
        done = sum(eng.status.get(r.uid) == "finished" for r in reqs)
        _say(f"{name}: {done}/{len(reqs)} requests finished, {toks} tokens "
             f"in {wall!r} s wall (set-up-inclusive smoke timing, "
             "not a metric)")
        out["deployments"][name] = dict(tokens=toks, wall_s=wall,
                                        finished=done, logits_rel_err=errs)

    key = jax.random.PRNGKey(seed)
    for deploy in ("analog_hw", "digital_int4"):
        _, params, labels = build(cfg, key)
        if deploy == "analog_hw":   # the kernels alone, on the float model
            _, fp = deploy_model(argparse.Namespace(deploy="fp"), cfg,
                                 params, labels, key)
            got, ref = logits_check(params, cfg, fp, reqs[0].prompt,
                                    paged=True, max_len=max_len)
            out["float_rel_err"] = _check_logits(
                "float (paged kernels)", got, ref, FLOAT_RTOL,
                out["failures"])
        params, acfg = deploy_model(argparse.Namespace(deploy=deploy), cfg,
                                    params, labels, key)
        if deploy == "analog_hw":
            serve("analog_hw", params, acfg)
            serve("analog_hw_fused", params,
                  dataclasses.replace(acfg, use_pallas=True))
        else:
            serve(deploy, params, acfg)
        del params, labels       # free this deployment before the next
        gc.collect()
    return out


def smoke_tp(cfg, *, tp: int = 4, seed: int = 0, num_requests: int = 8,
             prompt_len: int = 40, new_tokens: int = 24, num_slots: int = 4,
             prefill_chunk: int = 8) -> dict:
    """Tensor-parallel phase: the unfused analog deployment on the
    contiguous cache at ``tp`` and at tp=1 in one process.

    Fails (in ``failures``) when the tp engine did not build a mesh over
    ``tp`` devices — it never falls back to tp=1 — when a request does
    not finish, or when the tp logits leave the tp=1 logits by more than
    the check's tolerance. Greedy token agreement and bitwise logits
    equality are reported.
    """
    import jax
    import numpy as np

    from repro.launch.serve import deploy_model
    from repro.models import build
    from repro.serve.scheduler import SchedulerConfig, ServeEngine

    reqs = [dataclasses.replace(r, temperature=0.0, top_k=0)
            for r in _workload(cfg, seed, num_requests, prompt_len,
                               new_tokens)]
    max_len = _max_len(reqs, prefill_chunk)
    key = jax.random.PRNGKey(seed)
    _, params, labels = build(cfg, key)
    params, acfg = deploy_model(argparse.Namespace(deploy="analog_hw"), cfg,
                                params, labels, key)
    del labels
    out: dict = {"failures": [], "runs": {}}
    for n in (tp, 1):
        eng = ServeEngine(params, cfg, acfg, SchedulerConfig(
            num_slots=num_slots, max_len=max_len,
            prefill_chunk=prefill_chunk, paged=False, tp=n))
        if n > 1 and (eng.mesh is None or eng.mesh.devices.size != n):
            out["failures"].append(
                f"tp={n} engine has no {n}-device mesh: "
                f"{eng.gating_reasons.get('tensor_parallel')}")
            return out
        t0 = time.perf_counter()
        results = eng.run(reqs)
        wall = time.perf_counter() - t0
        _check_served(f"tp={n}", eng, reqs, results, out["failures"])
        got, _ = logits_check(eng.params, cfg, acfg, reqs[0].prompt,
                              paged=False, mesh=eng.mesh, max_len=max_len)
        toks = sum(len(v) for v in results.values())
        mesh = ([d.id for d in eng.mesh.devices.flat]
                if eng.mesh is not None else None)
        _say(f"tp={n}: mesh devices {mesh}, {toks} tokens in {wall!r} s "
             "wall (set-up-inclusive smoke timing, not a metric)")
        out["runs"][n] = dict(results=results, logits=got, wall_s=wall,
                              tokens=toks, mesh=mesh)
        del eng
    a, b = out["runs"][tp], out["runs"][1]
    same = [bool(np.array_equal(a["results"][r.uid], b["results"][r.uid]))
            for r in reqs]
    errs = rel_err(a["logits"], b["logits"])
    bitwise = bool(np.array_equal(a["logits"], b["logits"]))
    _say(f"tp={tp} vs tp=1: greedy tokens identical for {sum(same)}/"
         f"{len(same)} requests; prefill/decode logits bitwise equal: "
         f"{bitwise}; rel_err prefill={errs[0]!r} decode={errs[1]!r} "
         f"(tol {LOGITS_RTOL})")
    out.update(tokens_identical=sum(same), bitwise_logits=bitwise,
               logits_rel_err=errs)
    if not np.isfinite(a["logits"]).all() or max(errs) > LOGITS_RTOL:
        out["failures"].append(f"tp={tp} vs tp=1 logits")
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    """CLI entry point (see module docstring)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: the three one-chip deployments; 4: the "
                         "tensor-parallel phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _require_checkout()
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devs = _require_tpu(args.chips)

    import jax
    from repro.configs import get_config

    compile_s = [0.0]
    hits = [0]

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _say(f"device: {device['kind']} x{device['count']} "
         f"(platform {device['platform']}); compile cache {cache_dir}")
    cfg = get_config("llama-3.2-1b")     # published widths, never reduced
    _say(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
         f"heads {cfg.num_heads}/{cfg.num_kv_heads}, d_ff {cfg.d_ff}, "
         f"vocab {cfg.vocab_size}, seed {args.seed}")
    t0 = time.perf_counter()
    if args.chips == 1:
        res = smoke(cfg, seed=args.seed)
    else:
        res = smoke_tp(cfg, tp=args.chips, seed=args.seed)
    wall = time.perf_counter() - t0
    stats = devs[0].memory_stats() or {}
    _say(f"backend compile {compile_s[0]!r} s, {hits[0]} persistent-cache "
         f"hits; whole run {wall!r} s wall (set-up included)")
    _say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
         f"{stats.get('bytes_limit')} on device 0")
    if res["failures"]:
        for f in res["failures"]:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
