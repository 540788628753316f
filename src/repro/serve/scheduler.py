"""Request-level continuous-batching serving engine (in-flight batching).

The static ``serve.decode.generate`` loop pads every prompt to the batch
max and decodes until the *slowest* request finishes — fine for the
lockstep data-generation pipelines, but it strands decode throughput on
the mixed-length traffic the ROADMAP targets (and that hardware-aware
deployments must serve efficiently — Rasch et al. 2023). This module
replaces it for serving:

* **Slot-based in-flight batching** — the engine owns ``num_slots`` cache
  slots (one row of the per-slot KV/SSM cache layout,
  ``models.transformer.init_caches(per_slot=True)``). A finished sequence
  releases its slot immediately and a waiting request is admitted
  mid-decode; the decode step itself stays one jitted static-shape call
  regardless of which subset of slots is live.
* **Fused chunked-prefill scheduling** (Sarathi/vLLM-style) — an admitted
  prompt is left-padded to a multiple of ``prefill_chunk`` and its chunks
  *piggyback on the decode batch*: each engine step carries a token
  budget (``SchedulerConfig.step_tokens``) split between one decode token
  per decode-phase slot and the prefill chunks of admitting slots, and a
  single jitted dispatch (``_mixed_step_jit``) advances both — decode
  throughput never drops to zero while a prompt streams in, and prefill
  chunks batch *across* admitting slots in one ``[num_slots, chunk]``
  forward instead of running B=1 per request. The chunk rows of
  non-admitting slots are fully masked, which the model layers treat as
  cache-transparent (attention drops their writes and freezes their
  cursors; the SSM state passes through via ``dt = 0`` and the conv tail
  is frozen — see ``layers.attention`` / ``mamba2.mamba``). The first
  generated token of a finishing prompt is sampled *inside* the fused
  step, batched across rows — admission makes no per-request host round
  trip. With no admissions pending, the engine falls back to the
  multi-step decode block (up to ``decode_block`` decode+sample steps in
  one ``lax.scan`` dispatch).
* **Device-resident step state** — the per-slot sampling parameters,
  PRNG keys, cursors and token counters live on device between steps and
  are re-uploaded only when the slot set changes (admission, phase flip,
  retirement); steady-state decode blocks dispatch with zero host→device
  transfers.
* **Block-paged KV cache** (``SchedulerConfig.paged``) — the per-slot
  ``max_len`` KV buffers become a pool of fixed-size physical blocks
  (``serve.kv_pool``: refcounted alloc at admission, decref at
  retirement, FIFO backpressure when undersized). The decode read routes
  through the paged flash-decode op and the prefill chunk through the
  paged flash-prefill op (``kernels.dispatch``), both scoring the pool
  *in place* — no logical view is ever gathered back to the host, and
  cost scales with each slot's live tokens. ``AnalogConfig.kv_bits = 8``
  stores the pool as int8 with per-token/head scales.
* **Radix prefix caching** (``SchedulerConfig.prefix_cache``, every
  paged-mode family) — admission matches the padded prompt against the
  pool's content-addressed block index (``KVPool.match_prefix``) and
  maps the slot's block-table row onto the shared physical blocks: the
  slot starts with its ``pos`` cursor advanced past the hit (rounded
  down to a chunk boundary; at least one chunk always runs so the
  first-token logits exist) and plans prefill chunks only for the tail.
  Chunks overlapping the hit re-score cached content but never rewrite
  it — the per-slot *write table* redirects shared-block writes to the
  sink block (``models.layers._paged_slot_attention``). A matched
  partial tail block is copy-on-written: a fresh block is device-copied
  from the frozen donor inside the admission jit, then appended to
  privately. A request's full prompt blocks are registered in the index
  when its prefill completes, and retirement *retains* zero-ref indexed
  blocks in an LRU (evicted only under allocation pressure) — a shared
  system prompt stays warm across the whole workload. Because serving is
  deterministic (``AnalogCtx(key=None)``), cached KV is bitwise
  identical to recomputed KV: warm-vs-cold greedy decode parity is exact
  (verified in ``tests/test_scheduler.py``).
* **State snapshots for the ssm/hybrid families** — SSM recurrence
  state summarizes its whole prefix in O(1), so skipping prompt chunks
  needs more than shared KV blocks: prefill captures the slot's
  ``ssm``/``conv`` rows into a content-addressed snapshot pool
  (``serve.kv_pool.StateSnapshotPool`` + the ``*_snap`` cache leaves) at
  every chunk boundary that lands on a KV-block boundary, indexed under
  the *same* hash-chain keys as the KV blocks and registered at the
  prefill→decode flip. A warm admission restores the deepest matching
  snapshot inside ``_admit_jit`` (instead of zeroing the state rows) and
  starts its ``pos`` cursor exactly there — ``_ssd_with_state``'s
  carried-state term makes the restored state an exact continuation
  point, so warm≡cold bitwise parity extends to ssm and hybrid. Hybrid
  stacks restore the ``(KV blocks, state snapshot)`` pair: the skip is
  bounded by both the KV hit and the deepest snapshot, chunks between
  snapshot and prompt end re-run against the shared (write-protected)
  blocks. Pure-ssm stacks run the snapshot pool without any KV pool.
* **Speculative decoding** (``SchedulerConfig.speculative``, attention
  families) — pure-decode steps become draft-and-verify windows: a
  drafter (the digital int4 deployment of the same weights, the target
  itself, or host-side prompt lookup — ``SchedulerConfig.draft``)
  proposes ``draft_k`` tokens per slot and one fused chunk forward
  scores all ``draft_k + 1`` positions through the existing paged
  flash-prefill path. Exact-match verification
  (``sampling.speculative_verify``) re-draws each position from the
  target's own per-row PRNG stream, so speculative output is bitwise
  identical to non-speculative output; rejected positions roll back as
  a pure ``pos``-cursor rewind, checked against the pool's
  rewind-safety contract (``KVPool.rewind_floor`` — never into
  refcount-shared or index-frozen content). SSM/hybrid auto-gate off
  (``gating_reasons``): their recurrent state has no positional cursor
  to rewind.
* **Per-request sampling and stop conditions** — temperature / top-k /
  top-p / ``greedy_first`` ride along each request as traced per-row
  arrays (``sampling.sample_logits_batched``), and every request carries
  its own PRNG key folded per generated token. Sampling and the model
  math are row-independent, which yields the engine's *admission-parity
  contract*: a request produces bit-identical tokens whether it runs solo
  or its prefill chunks piggyback on a half-full decoding batch (verified
  in ``tests/test_scheduler.py``; MoE capacity dropping is the one
  documented exception — token dropping is chunk-shape dependent).

* **Request lifecycle for open-loop serving** (PR 9) — every request
  moves through an explicit state machine ``queued → prefill → decode →
  {finished, cancelled, timed_out, shed, errored}`` (``ServeEngine.status``),
  with per-request TTFT and end-to-end deadlines enforced at step
  boundaries, ``cancel(uid)`` retiring a slot at any stage (every KV
  block, COW tail and snapshot ref released — pool conservation holds
  under arbitrary interleavings), admission control that *sheds* with an
  explicit reason when the bounded queue overflows (``try_submit`` — the
  ``gating_reasons`` honesty idiom applied to load: never a silent drop
  or hang), and a chaos hook + fault-tolerant step that turns an injected
  or real step fault into per-request ``errored`` results plus a clean
  device-state reset, so the engine keeps serving. The step itself splits
  into ``step_begin`` (admission + async device dispatch) and
  ``step_commit`` (readback + host bookkeeping) so the async frontend
  (``serve.frontend``) can overlap host scheduling with the in-flight
  device step; cancels arriving between the two are deferred to the
  commit boundary (the cancel-vs-rewind ordering contract —
  ``serve.kv_pool``).

Works in every serving mode of ``AnalogConfig`` — ``off``, ``analog``
(optionally after ``perturb_analog_weights``), ``rtn``, and packed-int4
(``decode.digital_int4_config`` + ``core.analog.pack_int4_weights``).
Families: dense / moe / ssm / hybrid (audio's multi-codebook tokens and
vlm's patch-embed prefill are not wired into the scheduler yet).

See ``docs/serving.md`` for the full design and ``benchmarks/serve_bench.py``
for the static-vs-continuous throughput comparison (with per-phase
wall-clock attribution).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import devices as devices_lib
from repro.core.analog import AnalogConfig, AnalogCtx, pack_int4_weights
from repro.distributed import sharding
from repro.models import apply as model_apply
from repro.models import transformer as T
from repro.serve.decode import digital_int4_config, serve_step
from repro.serve.kv_pool import SINK_BLOCK, KVPool, StateSnapshotPool
from repro.serve.sampling import sample_logits_batched, speculative_verify


def padded_prompt_len(plen: int, chunk: int) -> int:
    """Prompt length after left-padding to a multiple of ``chunk``.

    The single source of truth for admission geometry — capacity
    validation (``ServeEngine.submit``), the admission prefill itself,
    and every caller sizing ``SchedulerConfig.max_len`` must agree.
    """
    return max(chunk, -(-plen // chunk) * chunk)


def required_max_len(plen: int, max_new: int, chunk: int) -> int:
    """Minimum ``SchedulerConfig.max_len`` for a (prompt, budget) pair."""
    return padded_prompt_len(plen, chunk) + max_new


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request.

    ``stop_tokens``: sampling any of these ends the request (the stop token
    is kept in the output). ``greedy_first``: number of initial tokens
    decoded greedily before temperature sampling (RGS/SGS strategies of
    paper App. B.1). ``seed`` derives the request's private PRNG key —
    generation is deterministic per request, independent of batch-mates.

    ``ttft_deadline`` / ``deadline`` (seconds since submission, 0 = none)
    are the request's SLOs, enforced at step boundaries: a request whose
    first token has not been sampled within ``ttft_deadline``, or that
    has not finished within ``deadline``, is retired as ``timed_out``
    (partial output preserved) and its blocks/snapshots released — a
    stuck or oversized request can no longer degrade everyone behind it.
    """

    uid: int
    prompt: np.ndarray                 # [len] int32 token ids
    max_new: int = 16
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    greedy_first: int = 0
    stop_tokens: tuple = ()
    seed: int = 0
    ttft_deadline: float = 0.0
    deadline: float = 0.0


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Static engine geometry (determines the compiled executables).

    ``num_slots``: in-flight request capacity (decode batch rows).
    ``max_len``: per-slot cache length; a request needs
    ``padded_prompt + max_new <= max_len``. ``prefill_chunk``: admission
    prefill granularity — prompts are left-padded up to a multiple of this,
    so one ``[num_slots, chunk]`` executable serves every prompt length.
    ``decode_block``: multi-step decode horizon — with no admissions in
    flight, up to this many decode+sample steps run inside one ``lax.scan``
    dispatch (the block length is clipped to the smallest remaining budget
    in flight and quantized to powers of two, so per-step host overhead is
    amortized without ever overshooting a request's ``max_new``).

    ``step_tokens``: the per-step token budget of the fused mixed
    prefill/decode step (0 = auto: ``num_slots + 2 * prefill_chunk``).
    While any slot is mid-prefill, each engine step spends one token per
    decode-phase slot and fills the remainder with prefill chunks of
    admitting slots, oldest admission first:
    ``n_chunks = clip((step_tokens - n_decode) // prefill_chunk, 1,
    min(n_admitting, prefill_batch))``
    — the floor of one chunk per step means prefill can never starve, and
    the one-decode-token-per-slot term means decode can't either. The
    budget also fixes the *compact prefill width* the fused executable
    compiles at (``ServeEngine.prefill_batch`` =
    ``max(1, (budget - num_slots) // prefill_chunk)`` capped at
    ``num_slots``): only that many cache rows are gathered into the chunk
    forward, so masked filler rows never burn a full batch of compute.

    ``paged=True`` swaps the per-slot ``max_len`` KV buffers for the
    block-paged pool (``serve.kv_pool``): ``kv_blocks`` physical blocks of
    ``kv_block_size`` tokens, allocated per request at admission and
    released at retirement. ``kv_blocks=0`` sizes the pool for every slot
    at ``max_len`` (no oversubscription); smaller values trade worst-case
    headroom for more slots per byte of HBM, with allocator backpressure
    gating admission. The pool dtype follows ``cache_dtype`` unless
    ``AnalogConfig.kv_bits == 8`` selects the int8 pool.

    ``prefix_cache`` (default on; effective with ``paged=True``, for
    every family) enables the radix prefix cache: admission reuses
    content-matching blocks, retirement retains released prompt blocks
    in an LRU. The attention-only families (dense/moe) share KV blocks;
    ssm/hybrid stacks additionally (ssm: exclusively) run the
    content-addressed state-snapshot pool — ``state_snapshots`` sizes it
    (snapshot slots; 0 = auto, ``num_slots * ceil(max_len /
    kv_block_size)``). Bitwise-transparent for greedy decode — disable
    it only to reclaim retained blocks eagerly or to benchmark the cold
    path. ``cache_salt`` segregates index entries whose KV/state would
    differ for reasons outside the token ids (deployment config,
    tenancy); engines only ever share a pool with themselves today, but
    the salt keeps persisted/benchmark runs honest.

    ``speculative=True`` turns pure-decode steps into draft-and-verify
    windows: a drafter proposes ``draft_k`` tokens per slot and the
    target model scores all ``draft_k + 1`` positions in one fused
    dispatch (the same chunked forward the mixed step uses — the paged
    flash-prefill kernel already scores chunks at arbitrary per-row
    offsets). Verification is exact-match against the target's own
    per-position draw (``sampling.speculative_verify``), so speculative
    output is **bitwise identical** to non-speculative output for greedy
    and sampled requests alike; rejected positions roll back as a pure
    ``pos``-cursor rewind under the pool's rewind-safety contract
    (``KVPool.rewind_floor``). ``draft`` picks the drafter: ``"int4"``
    (default — the Table-3 digital int4 deployment of the *same*
    weights, ``decode.digital_int4_config``'s RTN-W4 numerics, run
    unfused so no packed carriers are required), ``"self"`` (the target
    itself — acceptance 1.0, a machinery-overhead reference), or
    ``"ngram"`` (host-side prompt-lookup drafting — free proposals, no
    draft model or cache at all). ``draft_layers > 0`` truncates the
    model drafter to its first n scan-stacked blocks (layer-skip
    self-drafting). Speculation is attention-only — a ``pos`` rewind
    fully rolls back KV state, while SSM/hybrid recurrences are
    cumulative — so those families auto-gate off with a
    ``gating_reasons["speculative"]`` entry; mixed admission steps stay
    non-speculative (windows resume once prefill drains).

    ``drift_dt > 0`` activates the deployment clock for analog serving
    with per-tile device state (``core.devices.attach_device_state``):
    every engine step advances conductance drift by ``drift_dt``
    deployment-hours — a pure update of the tiny device-state leaves, so
    no step executable recompiles as the chip ages. ``recalibrate=True``
    adds the drift watchdog: every ``recal_interval`` steps the engine
    reads per-tile health host-side, and when the mean ``|tile scale -
    1|`` over live tiles exceeds ``recal_threshold`` it reprograms in
    place (``core.devices.recalibrate`` — fresh gain/offset instances,
    drift clock restarted) *without* evicting the KV pool, the prefix
    index, or any in-flight request: the step degrades gracefully
    (slower) instead of serving silently-wrong logits. Telemetry:
    ``drift_hours``, ``recal_count``, ``tile_scale_err``,
    ``dead_tiles`` / ``stuck_cols``.

    ``tp > 1`` serves tensor-parallel over a ``(1, tp)`` device mesh
    (``distributed.sharding.serve_mesh``): every weight shards
    column-parallel on its output dim, the paged KV pool splits its
    ``kv_heads`` across shards (each shard holds ``kv_heads/tp`` heads
    of *every* physical block, so the host-side allocator, block tables,
    prefix index and snapshot pools stay shard-agnostic), and the step
    jits trace under :func:`distributed.sharding.serve_ctx` — activation
    gathers at every reduction boundary keep each contraction local to
    one shard, making tensor-parallel greedy decode **bitwise
    identical** to single-device decode (the TP parity contract,
    ``docs/distributed.md``). Configs that cannot shard (heads not
    divisible by ``tp``, Pallas-fused serving, too few devices) fall
    back to tp=1 with ``gating_reasons["tensor_parallel"]``.

    ``max_queue`` bounds the admission queue (0 = unbounded, the
    closed-loop default): ``try_submit`` *sheds* a request arriving at a
    full queue with an explicit reason instead of queueing it into a
    deadline it can never meet — open-loop admission control with
    backpressure the caller can see. ``fault_tolerant=True`` (implied by
    installing a chaos hook) wraps every step in fault recovery: an
    exception raised mid-step retires all in-flight requests as
    ``errored`` (partial outputs + the fault message in
    ``ServeEngine.errors``), rebuilds the device-side caches and pools
    (their contents are suspect after a mid-step fault), and keeps
    serving the queue — a single bad step can no longer wedge the
    engine. Off by default so programming errors in tests still raise.

    When a requested feature cannot run on the engine's family/config
    combination, ``ServeEngine`` records why in ``gating_reasons`` —
    never a silent downgrade (``launch.serve`` surfaces the reasons).
    """

    num_slots: int = 4
    max_len: int = 96
    prefill_chunk: int = 16
    decode_block: int = 8
    step_tokens: int = 0
    cache_dtype: jnp.dtype = jnp.float32
    paged: bool = False
    kv_block_size: int = 16
    kv_blocks: int = 0
    prefix_cache: bool = True
    cache_salt: int = 0
    state_snapshots: int = 0
    speculative: bool = False
    draft_k: int = 4
    draft: str = "int4"
    draft_layers: int = 0
    drift_dt: float = 0.0
    recalibrate: bool = False
    recal_interval: int = 25
    recal_threshold: float = 0.1
    max_queue: int = 0
    fault_tolerant: bool = False
    tp: int = 1


class _Slot:
    """Host-side bookkeeping for one in-flight request."""

    def __init__(self, req: Request, toks: np.ndarray, mask: np.ndarray,
                 npad: int, chunk: int, seq: int, skip: int = 0):
        """Fresh bookkeeping for ``req``: the left-padded prompt split into
        ``prefill_chunk``-sized pieces, the first ``skip // chunk`` of
        which a prefix-cache hit already covers."""
        self.req = req
        self.out: list[int] = []
        self.count = 0                 # tokens sampled so far
        self.toks = toks               # [padded] left-padded prompt
        self.mask = mask               # [padded] 1 = real token
        self.npad = npad               # left-pad count
        self.nchunks = len(toks) // chunk
        self.chunk = skip // chunk     # next prefill chunk to run
        self.seq = seq                 # admission order (prefill FIFO)
        # prefix-cache bookkeeping (paged engines): the slot's physical
        # block row, its hash-chain keys, and how many leading blocks
        # came from the index (those are shared — never re-registered)
        self.blocks: list[int] = []
        self.keys: list = []
        self.hit_full = 0
        # state-snapshot bookkeeping (ssm/hybrid): (key, snap slot) pairs
        # captured during this prefill, and the depth (in KV blocks) of
        # the restored snapshot the admission skipped to
        self.snaps: list[tuple] = []
        self.hit_snap = 0

    @property
    def prefilling(self) -> bool:
        """True while prompt chunks remain to be streamed in."""
        return self.chunk < self.nchunks


# ---------------------------------------------------------------------------
# jitted engine steps — module level (static on the hashable cfg/acfg
# dataclasses) so the compilation cache is shared across ServeEngine
# instances: constructing an engine is free once its shapes have been seen.
# The cache pytree is donated (the engine rebinds self.caches with the
# result immediately, so the input buffers are dead): the slot caches are
# updated in place instead of copied every decode block / mixed step.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("cfg", "paged", "kv_bits", "cow",
                                    "snaps", "restore"),
                   donate_argnums=(0,))
def _admit_jit(caches, slot, start, pos0, tbl_row, wtbl_row, cow_src,
               cow_dst, snap_src, *, cfg, paged=False, kv_bits=0,
               cow=False, snaps=False, restore=False):
    """Reset slot ``slot``: zero its state rows, set its ``start`` marker
    and initial ``pos`` cursor (``pos0`` > 0 = prefix-cache skip), and
    (paged) write its read/write block-table rows from the allocator's
    admission result. Pool leaves are untouched — stale blocks are
    masked, never attended — except the optional copy-on-write step
    (``cow=True``): physical block ``cow_src`` (a frozen shared partial
    tail) is copied whole into the slot's private block ``cow_dst``
    across every layer, so the slot can append to the tail without
    touching the shared original.

    ``restore=True`` (ssm/hybrid prefix hit, requires ``snaps=True``
    caches): instead of zeroing, each SSM/conv state row is loaded from
    snapshot slot ``snap_src`` of its ``*_snap`` sibling leaf — the
    recurrent state captured after exactly ``pos0`` prompt tokens, so
    the slot continues bitwise-identically to a cold prefill reaching
    ``pos0`` (``_ssd_with_state``'s carried-state term). Walked as a
    nested dict (not ``tree.map``) so a ``"state"`` leaf can see its
    ``"spool"`` sibling."""
    axes, kinds = T.cache_slot_spec(cfg, paged=paged, kv_bits=kv_bits,
                                    state_snaps=snaps)

    def upd(c, ax, kind, snap_leaf):
        if kind == "spool":
            return c                   # snapshot pools: admission-inert
        if kind == "pool":
            if not cow:
                return c
            # every pool leaf keeps its block axis at position 1, right
            # after the stacked layer axis (see cache_slot_spec)
            src = jax.lax.dynamic_index_in_dim(c, cow_src, 1,
                                               keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(c, src, cow_dst, 1)
        shape = c.shape[:ax] + c.shape[ax + 1:]
        if kind == "table":
            val = jnp.broadcast_to(tbl_row, shape).astype(c.dtype)
        elif kind == "wtable":
            val = jnp.broadcast_to(wtbl_row, shape).astype(c.dtype)
        elif kind == "start":
            val = jnp.full(shape, start, c.dtype)
        elif kind == "pos":
            val = jnp.full(shape, pos0, c.dtype)
        elif kind == "state" and restore and snap_leaf is not None:
            # the snapshot-slot axis of a *_snap leaf sits where the
            # state leaf keeps its slot axis (same layer stacking)
            val = jax.lax.dynamic_index_in_dim(
                snap_leaf, snap_src, ax, keepdims=False).astype(c.dtype)
        else:
            val = jnp.zeros(shape, c.dtype)
        return jax.lax.dynamic_update_index_in_dim(c, val, slot, ax)

    def rec(c, ax, kind):
        out = {}
        for name in c:
            if isinstance(c[name], dict):
                out[name] = rec(c[name], ax[name], kind[name])
            else:
                out[name] = upd(c[name], ax[name], kind[name],
                                c.get(name + "_snap"))
        return out

    return rec(caches, axes, kinds)


@functools.partial(jax.jit, static_argnames=("cfg", "paged", "kv_bits"),
                   donate_argnums=(0,))
def _snap_jit(caches, slot, snap_dst, *, cfg, paged=False, kv_bits=0):
    """Capture slot ``slot``'s SSM/conv state rows into snapshot slot
    ``snap_dst`` — one device copy per mamba state leaf, taken at a
    chunk boundary that lands on a KV-block boundary during prefill, so
    the captured state summarizes exactly the padded prompt blocks the
    chain key addresses (``StateSnapshotPool`` owns the indexing)."""
    axes, kinds = T.cache_slot_spec(cfg, paged=paged, kv_bits=kv_bits,
                                    state_snaps=True)

    def rec(c, ax, kind):
        out = {}
        for name in c:
            if isinstance(c[name], dict):
                out[name] = rec(c[name], ax[name], kind[name])
            elif kind[name] == "spool":
                src = name[:-len("_snap")]
                row = jax.lax.dynamic_index_in_dim(
                    c[src], slot, ax[src], keepdims=False)
                out[name] = jax.lax.dynamic_update_index_in_dim(
                    c[name], row.astype(c[name].dtype), snap_dst, ax[src])
            else:
                out[name] = c[name]
        return out

    return rec(caches, axes, kinds)


def _sample_tokens(logits, keys, counts, temp, topk, topp, gfirst,
                   use_top_k, use_top_p):
    """Fold each request key at its token count, then batched sampling."""
    ks = jax.vmap(jax.random.fold_in)(keys, counts)
    return sample_logits_batched(ks, logits, temp, topk, topp,
                                 greedy=counts < gfirst,
                                 use_top_k=use_top_k, use_top_p=use_top_p)


def _decode_scan(params, caches, toks, off, active, keys, counts, temp,
                 topk, topp, gfirst, cfg, acfg, use_top_k, use_top_p, k):
    """``k`` decode + per-request-sampling steps in one ``lax.scan``.

    Each scan step is row-independent and folds each request's own key at
    its own token count, so the produced tokens are invariant to how the
    host partitions decoding into blocks — the admission-parity contract
    extends to multi-step decode and to the fused mixed step's single
    decode substep alike. Rows with ``active = 0`` are cache-transparent
    (the attention/SSM layers drop their writes and freeze their cursors).
    Returns (tokens [k, B], last toks, off, counts, caches).
    """
    def body(carry, _):
        toks, off, counts, caches = carry
        logits, caches = serve_step(params, cfg, acfg, toks[:, None], caches,
                                    off[:, None], seq_mask=active[:, None])
        new = _sample_tokens(logits, keys, counts, temp, topk, topp, gfirst,
                             use_top_k, use_top_p)
        return (new, off + 1, counts + 1, caches), new

    (toks, off, counts, caches), out = jax.lax.scan(
        body, (toks, off, counts, caches), None, length=k)
    return out, toks, off, counts, caches


@functools.partial(jax.jit, static_argnames=("cfg", "acfg", "use_top_k",
                                             "use_top_p", "k", "mesh"),
                   donate_argnums=(1,))
def _step_jit(params, caches, toks, off, active, keys, counts, temp, topk,
              topp, gfirst, *, cfg, acfg, use_top_k, use_top_p, k,
              mesh=None):
    """Pure-decode engine step: one dispatch per ``k``-step decode block,
    amortizing dispatch exactly like the static ``generate`` scan does —
    while slots still recycle at block boundaries. Specialized per
    (use_top_k, use_top_p) so the full-vocab sorts drop out of the step
    when no in-flight request filters (see ``sampling`` module), and per
    block length ``k`` (powers of two). ``mesh`` (static, hashable) is
    the engine's tensor-parallel serve mesh: the body traces under
    ``sharding.serve_ctx`` so every model ``shard_hint`` resolves to the
    bitwise-parity serve rules — one executable per mesh, and tp=1
    engines (``mesh=None``) keep their unconstrained jaxprs. Returns the
    updated device-resident step state alongside the sampled tokens:
    (tokens [k, B], last toks, off, counts, caches).
    """
    with sharding.serve_ctx(mesh):
        return _decode_scan(params, caches, toks, off, active, keys,
                            counts, temp, topk, topp, gfirst, cfg, acfg,
                            use_top_k, use_top_p, k)


def _gather_rows(caches, idx, axes):
    """Gather the cache rows of slots ``idx`` into a compact batch
    (``-1``-axis pool leaves pass through whole)."""
    return jax.tree.map(
        lambda c, ax: c if ax < 0 else jnp.take(c, idx, axis=ax),
        caches, axes)


def _scatter_rows(caches, sub, idx, axes):
    """Write a compact gathered batch back to its slots (``idx`` rows are
    distinct by construction, so the scatter is order-independent; pool
    leaves replace the old leaf — the prefill updated them in place)."""
    def scat(c, s, ax):
        if ax < 0:
            return s
        cm = jnp.moveaxis(c, ax, 0).at[idx].set(jnp.moveaxis(s, ax, 0))
        return jnp.moveaxis(cm, 0, ax)

    return jax.tree.map(scat, caches, sub, axes)


@functools.partial(jax.jit, static_argnames=("cfg", "acfg", "use_top_k",
                                             "use_top_p", "k", "paged",
                                             "snaps", "mesh"),
                   donate_argnums=(1,))
def _mixed_step_jit(params, caches, toks, off, active, keys, counts, temp,
                    topk, topp, gfirst, pf_idx, pf_toks, pf_mask, pf_off, *,
                    cfg, acfg, use_top_k, use_top_p, k, paged,
                    snaps=False, mesh=None):
    """Fused mixed prefill/decode step: one dispatch advances the decode
    slots *and* a compact batched prefill chunk of the admitting slots.

    Substep 1 — ``k`` decode steps (``k = 0`` when no slot is in decode
    phase, e.g. cold start) over the rows flagged ``active``; admitting
    rows are fully masked and stay untouched. Substep 2 — the cache rows
    of the ``pf_idx`` slots are gathered into a compact
    ``[prefill_batch, chunk]`` forward of ``pf_toks`` with per-row
    position offsets ``pf_off`` and mask ``pf_mask``, then scattered
    back: each admitting row's chunk scatter-writes into its own
    cache/pool row and continues its recurrences exactly as a solo
    prefill would — row independence is what keeps piggybacked prefill
    bit-identical to solo prefill. ``pf_idx`` rows beyond the admitting
    count are distinct filler slots with all-zero masks: the model layers
    leave them untouched, so scattering them back is a no-op write of
    their own values. The last-position logits are sampled for every
    compact row at token count 0 (one batched sample across admitting
    slots); the host consumes row ``i``'s sample only when its slot
    finished the prompt this step — admission makes no per-request B=1
    dispatch or host round trip.

    Returns (decode tokens [k, B], first-token samples [prefill_batch],
    last toks, off, counts, caches).
    """
    with sharding.serve_ctx(mesh):
        dec_out, toks, off, counts, caches = _decode_scan(
            params, caches, toks, off, active, keys, counts, temp, topk,
            topp, gfirst, cfg, acfg, use_top_k, use_top_p, k)

        axes, _ = T.cache_slot_spec(cfg, paged=paged, kv_bits=acfg.kv_bits,
                                    state_snaps=snaps)
        sub = _gather_rows(caches, pf_idx, axes)
        ctx = AnalogCtx(key=None, training=False)
        logits, _, sub = model_apply(params, cfg, acfg, ctx,
                                     {"tokens": pf_toks}, caches=sub,
                                     pos_offset=pf_off[:, None],
                                     seq_mask=pf_mask, last_only=True)
        caches = _scatter_rows(caches, sub, pf_idx, axes)
        first = _sample_tokens(logits[:, -1], keys[pf_idx],
                               jnp.zeros_like(pf_idx), temp[pf_idx],
                               topk[pf_idx], topp[pf_idx], gfirst[pf_idx],
                               use_top_k, use_top_p)
        return dec_out, first, toks, off, counts, caches


def _rewind_pos(caches, delta, cfg, paged, kv_bits, snaps):
    """Roll back speculatively written positions: subtract ``delta[b]``
    from every ``pos`` cursor leaf of slot ``b``. Rollback is O(1) with
    zero data movement — stale KV past the cursor is never attended
    (every read is bounded by ``start <= j <= pos + i``) and the next
    window scatter-writes the same physical positions in place. Safe
    only above the pool's rewind floor (``KVPool.rewind_floor``), which
    the scheduler checks after every speculative step."""
    axes, kinds = T.cache_slot_spec(cfg, paged=paged, kv_bits=kv_bits,
                                    state_snaps=snaps)

    def rec(c, ax, kind):
        out = {}
        for name in c:
            if isinstance(c[name], dict):
                out[name] = rec(c[name], ax[name], kind[name])
            elif kind[name] == "pos":
                cm = jnp.moveaxis(c[name], ax[name], -1)
                cm = cm - delta.astype(cm.dtype)
                out[name] = jnp.moveaxis(cm, -1, ax[name])
            else:
                out[name] = c[name]
        return out

    return rec(caches, axes, kinds)


def _verify_and_commit(params, caches, toks, drafts, off, active, keys,
                       counts, temp, topk, topp, gfirst, cfg, acfg,
                       use_top_k, use_top_p, paged, snaps):
    """Shared verify core of both speculative step jits.

    Scores the ``[B, k+1]`` window ``[last_token, d_1 .. d_k]`` in one
    fused chunk forward at each row's own offset (exactly the mixed
    step's chunk path — inactive rows are fully masked and
    cache-transparent), runs exact-match accept/reject + bonus draw
    (``sampling.speculative_verify``), then commits: the ``pos``
    cursors — advanced by ``k+1`` by the forward — rewind to
    ``old + n_emit``, and the device-resident step state advances by
    each row's emitted count. Returns ``(target [k+1, B], n_emit [B],
    delta [B], toks, off, counts, caches)`` with ``delta`` the per-row
    rewind a model drafter must mirror on its own cache.
    """
    k = drafts.shape[0]
    window = jnp.concatenate([toks[:, None], drafts.T], axis=1)
    mask = jnp.broadcast_to(active[:, None],
                            window.shape).astype(jnp.float32)
    ctx = AnalogCtx(key=None, training=False)
    logits, _, caches = model_apply(params, cfg, acfg, ctx,
                                    {"tokens": window}, caches=caches,
                                    pos_offset=off[:, None], seq_mask=mask)
    target, n_acc = speculative_verify(keys, logits, drafts, counts, temp,
                                       topk, topp, gfirst, use_top_k,
                                       use_top_p)
    act = active > 0
    n_emit = jnp.where(act, n_acc + 1, 0).astype(jnp.int32)
    delta = jnp.where(act, (k + 1) - n_emit, 0).astype(jnp.int32)
    caches = _rewind_pos(caches, delta, cfg, paged, acfg.kv_bits, snaps)
    bonus = jnp.take_along_axis(target, n_acc[None, :], axis=0)[0]
    toks = jnp.where(act, bonus, toks)
    return (target, n_emit, delta, toks, off + n_emit, counts + n_emit,
            caches)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "acfg", "dcfg", "dacfg",
                                    "use_top_k", "use_top_p", "k", "paged",
                                    "snaps", "mesh"),
                   donate_argnums=(2, 3))
def _spec_step_jit(params, draft_params, caches, draft_caches, toks, off,
                   active, keys, counts, temp, topk, topp, gfirst, *, cfg,
                   acfg, dcfg, dacfg, use_top_k, use_top_p, k, paged,
                   snaps=False, mesh=None):
    """Model-drafter speculative step: ``k+1`` drafter decode steps in a
    ``lax.scan`` (on the drafter's private contiguous slot cache), then
    the fused verify window — one dispatch per engine step.

    The drafter samples with the *same* per-row key folds the verifier
    uses at each position, so a drafter equivalent to the target (the
    ``"self"`` mode, or ``"int4"`` under an int4-served target) proposes
    exactly the verifier's draws and every window fully accepts. The
    scan runs ``k+1`` steps but the window only consumes drafts
    ``1..k``: discarding the last draft makes the draft cache consume
    exactly the verify window's tokens, so both caches rewind by the
    same per-row ``delta`` and stay position-synchronized without any
    cross-cache bookkeeping. Returns ``(target [k+1, B], n_emit [B],
    toks, off, counts, caches, draft_caches)``.
    """
    def body(carry, i):
        dtoks, dcaches = carry
        logits, dcaches = serve_step(draft_params, dcfg, dacfg,
                                     dtoks[:, None], dcaches,
                                     (off + i)[:, None],
                                     seq_mask=active[:, None])
        new = _sample_tokens(logits, keys, counts + i, temp, topk, topp,
                             gfirst, use_top_k, use_top_p)
        return (new, dcaches), new

    with sharding.serve_ctx(mesh):
        (_, draft_caches), drafts = jax.lax.scan(
            body, (toks, draft_caches), jnp.arange(k + 1, dtype=jnp.int32))
        target, n_emit, delta, toks, off, counts, caches = (
            _verify_and_commit(
                params, caches, toks, drafts[:k], off, active, keys,
                counts, temp, topk, topp, gfirst, cfg, acfg, use_top_k,
                use_top_p, paged, snaps))
        draft_caches = _rewind_pos(draft_caches, delta, dcfg, False, 0,
                                   False)
        return target, n_emit, toks, off, counts, caches, draft_caches


@functools.partial(jax.jit, static_argnames=("cfg", "acfg", "use_top_k",
                                             "use_top_p", "paged", "snaps",
                                             "mesh"),
                   donate_argnums=(1,))
def _spec_verify_jit(params, caches, toks, off, active, keys, counts, temp,
                     topk, topp, gfirst, drafts, *, cfg, acfg, use_top_k,
                     use_top_p, paged, snaps=False, mesh=None):
    """Host-drafter speculative step: verify externally proposed drafts
    ``[k, B]`` (prompt-lookup n-grams, or a test-injected ``draft_fn``).
    No draft model, no draft cache — proposals cost nothing on device
    and the whole step is the one fused verify dispatch. Exact-match
    verification keeps the bitwise-parity guarantee for *any* proposal
    source: a draft either equals the token the non-speculative loop
    would have drawn or is rejected."""
    with sharding.serve_ctx(mesh):
        target, n_emit, _, toks, off, counts, caches = _verify_and_commit(
            params, caches, toks, drafts, off, active, keys, counts, temp,
            topk, topp, gfirst, cfg, acfg, use_top_k, use_top_p, paged,
            snaps)
        return target, n_emit, toks, off, counts, caches


@functools.partial(jax.jit, static_argnames=("dcfg", "dacfg", "mesh"),
                   donate_argnums=(1,))
def _draft_step_jit(draft_params, draft_caches, toks, off, active, *,
                    dcfg, dacfg, mesh=None):
    """Advance the model drafter's cache by the one decode token a mixed
    step consumed (logits discarded). Mixed admission steps decode
    non-speculatively, so without this catch-up the draft cache would
    silently fall behind the target across every admission window —
    drafts would still verify safely (exact-match), but acceptance would
    collapse for the rest of each affected request."""
    with sharding.serve_ctx(mesh):
        _, draft_caches = serve_step(draft_params, dcfg, dacfg,
                                     toks[:, None], draft_caches,
                                     off[:, None],
                                     seq_mask=active[:, None])
        return draft_caches


@functools.partial(jax.jit, static_argnames=("cfg", "acfg", "mesh"),
                   donate_argnums=(1,))
def _draft_prefill_jit(params, caches, slot, toks, mask, npad, *, cfg,
                       acfg, mesh=None):
    """Reset draft-cache slot ``slot`` and prefill the full padded prompt
    ``toks [1, padded]`` in one dispatch (at the prefill→decode flip).

    The drafter keeps a plain contiguous slot cache with no pool and no
    prefix index, so its prompt always runs whole — even when a prefix
    hit let the *target* skip chunks — one extra forward per admission.
    Compiles once per distinct padded prompt length (chunk multiples)."""
    axes, kinds = T.cache_slot_spec(cfg, paged=False, kv_bits=0)

    def reset(c, ax, kind):
        shape = c.shape[:ax] + c.shape[ax + 1:]
        val = (jnp.full(shape, npad, c.dtype) if kind == "start"
               else jnp.zeros(shape, c.dtype))
        return jax.lax.dynamic_update_index_in_dim(c, val, slot, ax)

    def rec(c, ax, kind):
        out = {}
        for name in c:
            if isinstance(c[name], dict):
                out[name] = rec(c[name], ax[name], kind[name])
            else:
                out[name] = reset(c[name], ax[name], kind[name])
        return out

    with sharding.serve_ctx(mesh):
        caches = rec(caches, axes, kinds)
        idx = slot[None]
        sub = _gather_rows(caches, idx, axes)
        ctx = AnalogCtx(key=None, training=False)
        _, _, sub = model_apply(params, cfg, acfg, ctx, {"tokens": toks},
                                caches=sub,
                                pos_offset=jnp.reshape(-npad, (1, 1)),
                                seq_mask=mask, last_only=True)
        return _scatter_rows(caches, sub, idx, axes)


def _ngram_propose(ctx: np.ndarray, k: int, max_n: int = 3) -> np.ndarray:
    """Prompt-lookup drafting: propose the continuation of the most
    recent earlier occurrence of the context's longest matching suffix
    n-gram (n = ``max_n`` down to 1), falling back to repeating the last
    token. Pure host-side numpy over a <= ``max_len`` context — the
    proposals are free, exact-match verification makes any quality
    level safe, and repetitive spans (the regime where lookup drafting
    shines) accept at high rates."""
    ctx = np.asarray(ctx, np.int32)
    n_ctx = len(ctx)
    if n_ctx == 0:
        return np.zeros(k, np.int32)
    out = np.full(k, int(ctx[-1]), np.int32)
    for n in range(min(max_n, n_ctx - 1), 0, -1):
        pat = ctx[n_ctx - n:]
        for j in range(n_ctx - n - 1, -1, -1):
            if np.array_equal(ctx[j:j + n], pat):
                cont = ctx[j + n:j + n + k]
                out[:len(cont)] = cont
                return out
    return out


class ServeEngine:
    """Continuous-batching engine over a slot cache.

    Usage::

        eng = ServeEngine(params, cfg, acfg, SchedulerConfig(num_slots=8))
        results = eng.run([Request(uid=0, prompt=np.array([1, 2, 3]))])
        results[0]                     # np.ndarray of generated ids

    ``submit``/``step`` expose the loop for finer control (e.g. injecting
    requests mid-decode, as the admission-parity tests do).
    """

    def __init__(self, params, cfg, acfg: AnalogConfig,
                 scfg: SchedulerConfig = SchedulerConfig(), *,
                 draft_params=None, draft_cfg=None, draft_acfg=None,
                 draft_fn=None, chaos_hook=None):
        """Allocate the slot caches and host-side request state.

        The ``draft_*`` keywords override ``scfg.draft``'s model drafter
        with an explicit (params, cfg, acfg) triple — e.g. a separately
        trained small draft model — while ``draft_fn(context, k) ->
        [<=k] int32`` replaces model drafting entirely with a host
        callable over the request's (prompt + generated) token context,
        the hook the forced-accept/forced-reject parity tests use.

        ``chaos_hook(point)`` is the fault-injection seam the chaos
        tests drive: it is called at the named checkpoints of every step
        — ``"alloc"`` (admission, before the allocator runs),
        ``"dispatch"`` (before each step's jit dispatch), ``"health"``
        (before the drift watchdog's health read) — and whatever it
        raises becomes the injected fault. Installing a hook implies
        ``fault_tolerant`` recovery (the point of chaos testing is
        proving the degraded path, not crashing it).
        """
        if cfg.family in ("audio", "vlm"):
            raise NotImplementedError(
                f"continuous batching not wired for family={cfg.family!r} "
                "(multi-codebook tokens / patch-embed prefill)")
        self.params = params
        self.cfg, self.acfg, self.scfg = cfg, acfg, scfg
        b = scfg.num_slots
        # paged mode: block-paged pool + host-side free-list allocator
        # (attention-free SSM stacks have no KV to page — pool stays None
        # and the cache layout is identical either way)
        self.pool: Optional[KVPool] = None
        paged = scfg.paged and cfg.family != "ssm"
        # honest feature gating: a requested feature that cannot run on
        # this family/config combination is recorded with its reason,
        # never silently downgraded (``launch.serve`` prints these)
        self.gating_reasons: dict[str, str] = {}
        # tensor-parallel serving: a (1, tp) mesh every step jit traces
        # against (static arg) with the bitwise-parity serve rules —
        # sharding.serve_ctx. Resolved before drafter construction so
        # the drafter can gate its packed-int4 path on it.
        self.mesh = None
        if scfg.tp > 1:
            reason = sharding.serve_tp_unsupported(
                cfg, acfg, scfg.tp, paged=paged, chunk=scfg.prefill_chunk)
            if reason is not None:
                self.gating_reasons["tensor_parallel"] = reason
            else:
                self.mesh = sharding.serve_mesh(scfg.tp)
        if scfg.paged and not paged:
            self.gating_reasons["paged"] = (
                "attention-free ssm stacks have no KV to page (per-slot "
                "state is O(1)); prefix caching still runs via the "
                "state-snapshot pool")
        if paged:
            nb_slot = -(-scfg.max_len // scfg.kv_block_size)
            n_pool = scfg.kv_blocks or b * nb_slot
            self.pool = KVPool(n_pool, scfg.kv_block_size,
                               salt=scfg.cache_salt)
        # radix prefix caching, every family: dense/moe/hybrid share KV
        # blocks; ssm/hybrid additionally snapshot SSM state at block
        # boundaries so a hit is a (KV blocks, state snapshot) pair
        self._prefix = scfg.prefix_cache and scfg.paged
        if scfg.prefix_cache and not self._prefix:
            self.gating_reasons["prefix_cache"] = (
                "prefix caching needs the paged engine "
                "(SchedulerConfig.paged=True): content-addressed reuse "
                "is keyed on KV-block-aligned prefixes")
        self.state_pool: Optional[StateSnapshotPool] = None
        state_snaps = 0
        if self._prefix and cfg.family in ("ssm", "hybrid"):
            nb_slot = -(-scfg.max_len // scfg.kv_block_size)
            state_snaps = scfg.state_snapshots or b * nb_slot
            self.state_pool = StateSnapshotPool(
                state_snaps, scfg.kv_block_size, salt=scfg.cache_salt)
        self.caches = T.init_caches(cfg, b, scfg.max_len, scfg.cache_dtype,
                                    per_slot=True, paged=paged,
                                    kv_block_size=scfg.kv_block_size,
                                    kv_blocks=scfg.kv_blocks or None,
                                    kv_bits=acfg.kv_bits if paged else 0,
                                    state_snaps=state_snaps)
        self._paged = paged
        self._snaps = state_snaps > 0
        # speculative decoding: attention-only — a pos-cursor rewind
        # fully rolls back KV state, while SSM/hybrid recurrences are
        # cumulative (snapshot-restore rollback is a possible follow-up)
        self._spec = bool(scfg.speculative) and cfg.family in ("dense",
                                                               "moe")
        if scfg.speculative and not self._spec:
            self.gating_reasons["speculative"] = (
                "speculative rollback is a pos-cursor rewind, which only "
                "rolls back attention KV; ssm/hybrid recurrent state is "
                "cumulative and has no per-position cursor — these "
                "families decode non-speculatively")
        if self._spec and scfg.draft_k < 1:
            raise ValueError("draft_k must be >= 1")
        self.draft_fn = draft_fn
        self._draft_host = self._spec and (draft_fn is not None
                                           or scfg.draft == "ngram")
        self.draft_params = self.draft_cfg = self.draft_acfg = None
        self.draft_caches = None
        if self._spec and not self._draft_host:
            if scfg.draft not in ("int4", "self"):
                raise ValueError(
                    f"unknown drafter {scfg.draft!r} "
                    "(expected 'int4', 'self' or 'ngram')")
            dcfg = draft_cfg if draft_cfg is not None else cfg
            if draft_cfg is None and scfg.draft_layers:
                dcfg = dataclasses.replace(
                    cfg, num_layers=min(scfg.draft_layers, cfg.num_layers))
            dacfg = draft_acfg
            pack_draft = False
            if dacfg is None:
                if scfg.draft == "self" or acfg.int4_serve:
                    dacfg = acfg      # int4-served target: drafter == it
                elif self.mesh is None:
                    # the digital int4 deployment of the same weights
                    # (decode.digital_int4_config numerics) served from
                    # the packed kernel: the carriers are precomputed
                    # once below, so the k-step draft scan reads weights
                    # at int4 bandwidth instead of quantizing+packing
                    # every projection every step
                    dacfg = digital_int4_config(
                        dataclasses.replace(acfg, weight_bits=4))
                    pack_draft = True
                else:
                    # the packed kernel is a pallas_call — single-device
                    # under GSPMD — so the tensor-parallel drafter keeps
                    # the unfused RTN-W4 path (identical numerics, the
                    # weights just read at full precision)
                    self.gating_reasons["draft_packed_int4"] = (
                        "the packed-int4 draft kernel does not partition "
                        "under tensor parallelism (pallas_call without "
                        "shard_map wiring) — drafting runs the unfused "
                        "rtn-w4 path instead")
                    dacfg = dataclasses.replace(acfg, mode="rtn",
                                                weight_bits=4)
            # the drafter cache is contiguous per-slot — never paged
            dacfg = dataclasses.replace(dacfg, kv_bits=0)
            dparams = draft_params
            if dparams is None:
                dparams = params
                if dcfg.num_layers < cfg.num_layers:
                    # layer-skip drafting: the first n scan-stacked blocks
                    dparams = dict(params)
                    dparams["blocks"] = jax.tree.map(
                        lambda t: t[:dcfg.num_layers], params["blocks"])
            if pack_draft:
                # precompute the packed-int4 carriers ONCE, after the
                # layer-skip slice (structural walk — the sliced tree has
                # no label pytree); tests gate this with a bitwise
                # packed-vs-unpacked drafter-parity assertion
                dparams = pack_int4_weights(dparams)
            self.draft_params, self.draft_cfg = dparams, dcfg
            self.draft_acfg = dacfg
            self.draft_caches = T.init_caches(dcfg, b, scfg.max_len,
                                              scfg.cache_dtype,
                                              per_slot=True)
        # commit params and caches to the serve mesh: column-parallel
        # weights, per-shard KV heads (every shard holds kv_heads/tp
        # heads of every pool block — the host-side allocator, block
        # tables and prefix index stay shard-agnostic). The drafter's
        # params/caches shard with the same rules.
        if self.mesh is not None:
            self.params = sharding.shard_params_for_serve(self.mesh,
                                                          self.params)
            self.caches = sharding.shard_caches_for_serve(self.mesh,
                                                          self.caches)
            if self.draft_params is not None:
                self.draft_params = sharding.shard_params_for_serve(
                    self.mesh, self.draft_params)
            if self.draft_caches is not None:
                self.draft_caches = sharding.shard_caches_for_serve(
                    self.mesh, self.draft_caches)
        # conductance-drift deployment clock + recalibration watchdog
        # (core.devices): both need per-tile device state on the params —
        # a drift clock over pristine digital weights would age nothing
        self._drift = scfg.drift_dt > 0 and devices_lib.has_device_state(
            params)
        if scfg.drift_dt > 0 and not self._drift:
            self.gating_reasons["drift"] = (
                "drift_dt > 0 but params carry no per-tile device state "
                "(core.devices.attach_device_state) — the deployment "
                "clock would advance nothing")
        self._recal = bool(scfg.recalibrate) and self._drift
        if scfg.recalibrate and not self._recal:
            self.gating_reasons["recalibrate"] = (
                "recalibration needs an active drift clock (drift_dt > 0 "
                "and per-tile device state attached to the params)")
        if self._recal and scfg.recal_interval < 1:
            raise ValueError("recal_interval must be >= 1")
        # fail fast on unsupported families
        T.cache_slot_spec(cfg, paged=paged, kv_bits=acfg.kv_bits,
                          state_snaps=self._snaps)
        self._n_state_snaps = state_snaps
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: list[Optional[_Slot]] = [None] * b
        self.results: dict[int, np.ndarray] = {}
        self.finished_at: dict[int, float] = {}
        # request lifecycle: per-uid state machine (queued → prefill →
        # decode → {finished, cancelled, timed_out, shed, errored}),
        # time.perf_counter stamps at submit (deadline math), admission
        # and first token (submit → admit is the queue wait, admit →
        # first token the prefill, submit → first token the TTFT), and
        # explicit reasons for every non-finished terminal state — the
        # gating_reasons honesty idiom applied per request
        self.status: dict[int, str] = {}
        self.errors: dict[int, str] = {}
        self.submit_time: dict[int, float] = {}
        self.admit_time: dict[int, float] = {}
        self.first_token_at: dict[int, float] = {}
        # streaming seam: (kind, uid, payload) event log the async
        # frontend drains after each commit — ("token", uid, tok) per
        # sampled token, ("done", uid, status) at every terminal state
        self.events: collections.deque[tuple] = collections.deque()
        # lifecycle telemetry (launch.serve report line)
        self.submitted = 0
        self.shed_count = 0
        self.timeout_count = 0
        self.cancel_count = 0
        self.fault_count = 0
        self.health_faults = 0
        self.step_faults: list[str] = []
        self.queue_high_water = 0
        # chaos/fault-tolerance seam (see __init__ docstring)
        self.chaos_hook = chaos_hook
        self._tolerant = bool(scfg.fault_tolerant) or chaos_hook is not None
        # in-flight step record between step_begin and step_commit;
        # cancels arriving in that span are deferred to the commit
        # boundary (the cancel-vs-rewind ordering contract)
        self._inflight: Optional[dict] = None
        self._deferred_cancels: list[tuple[int, str, Optional[str]]] = []
        self.decode_steps = 0
        # wall-clock phase attribution + fused-admission telemetry
        # (benchmarks/serve_bench.py reports these per engine row;
        # mixed_steps counts only steps that carried BOTH phases). The
        # per-step (decode, prefill) token log is bounded — telemetry for
        # the budget-invariant tests, not an unbounded history.
        self.phase_time = {"decode": 0.0, "mixed": 0.0, "prefill": 0.0}
        self.mixed_steps = 0
        self.prefill_chunks = 0
        self.decode_tokens_during_admission = 0
        # KV occupancy, summed over dispatched steps: cache positions
        # holding a live request's tokens (left padding excluded) and
        # the positions the layout holds; their ratio is the
        # step-weighted share of the reserved cache in use
        self.kv_used_tokens = 0
        self.kv_capacity_tokens = 0
        self._kv_capacity = (self.pool.num_blocks * self.pool.block_size
                             if self.pool is not None
                             else b * scfg.max_len)
        # prefix-cache telemetry (hit/skipped tokens count the padded
        # prompt positions the cache covered / the prefill never ran)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.prefix_skipped_tokens = 0
        self.prefix_cow_copies = 0
        # state-snapshot telemetry (ssm/hybrid prefix caching)
        self.state_snaps_captured = 0
        self.state_snap_restores = 0
        # speculative-decoding telemetry: windows dispatched, drafts
        # proposed/accepted (acceptance rate = accepted / proposed)
        self.spec_steps = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        # drift/recalibration telemetry: deployment hours accumulated,
        # watchdog health reads, in-place reprogrammings.
        # tile_scale_err mirrors the latest watchdog read (mean |scale-1|
        # over live tiles); dead/stuck counts are permanent faults.
        self.drift_hours = 0.0
        self.recal_count = 0
        self.watchdog_checks = 0
        self.tile_scale_err = 0.0
        self.dead_tiles = 0
        self.stuck_cols = 0
        self._steps_since_check = 0
        self._recal_key = jax.random.PRNGKey(0x5ECA1)
        if self._drift:
            h = devices_lib.health(params)
            self.tile_scale_err = h["mean_scale_err"]
            self.dead_tiles = h["dead_tiles"]
            self.stuck_cols = h["stuck_cols"]
        self.step_token_log: collections.deque[tuple[int, int]] = (
            collections.deque(maxlen=4096))
        self._admit_seq = 0
        # per-slot host mirrors of the device-side request state
        self._pos = np.zeros(b, np.int32)       # cache write cursor
        self._start = np.zeros(b, np.int32)     # left-pad count
        self._last_tok = np.zeros(b, np.int32)
        self._temp = np.ones(b, np.float32)
        self._topk = np.zeros(b, np.int32)
        self._topp = np.ones(b, np.float32)
        self._gfirst = np.zeros(b, np.int32)
        self._keys = np.zeros((b, 2), np.uint32)
        # device-resident step state, re-uploaded only when dirty
        # (admission / phase flip / retirement) — steady-state decode
        # blocks dispatch with zero host→device transfers
        self._dev: dict[str, jax.Array] = {}
        self._dirty = True

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> None:
        """Enqueue a request (admitted at the next free slot)."""
        if req.max_new < 1:
            raise ValueError("max_new must be >= 1")
        need = required_max_len(len(req.prompt), req.max_new,
                                self.scfg.prefill_chunk)
        if need > self.scfg.max_len:
            raise ValueError(
                f"request {req.uid}: padded prompt + max_new needs "
                f"max_len >= {need}, engine has {self.scfg.max_len}")
        if self.pool is not None:
            nblk = self._blocks_needed(req)
            if nblk > self.pool.num_blocks:
                # backpressure can only wait for blocks that exist: a
                # request larger than the whole pool would stall the FIFO
                # head forever
                raise ValueError(
                    f"request {req.uid}: needs {nblk} KV blocks, pool has "
                    f"{self.pool.num_blocks} total")
        self.submitted += 1
        self.status[req.uid] = "queued"
        self.submit_time[req.uid] = time.perf_counter()
        self.queue.append(req)
        self.queue_high_water = max(self.queue_high_water, len(self.queue))

    def try_submit(self, req: Request) -> Optional[str]:
        """Admission-controlled submit: accept ``req`` (returns ``None``)
        or *shed* it with an explicit reason string (returned, recorded
        in ``errors[uid]``, status ``"shed"``).

        Sheds when the bounded queue (``SchedulerConfig.max_queue``) is
        full, or when the request can never fit this engine (the
        conditions :meth:`submit` raises ``ValueError`` for) — open-loop
        backpressure the caller can surface to the client instead of a
        silent drop or an unbounded queue that hangs every deadline.
        """
        reason = None
        mq = self.scfg.max_queue
        if mq and len(self.queue) >= mq:
            reason = (f"admission queue full ({len(self.queue)}/{mq}) — "
                      f"engine saturated, retry later")
        else:
            try:
                self.submit(req)
                return None
            except ValueError as e:
                reason = str(e)
        self.submitted += 1
        self.shed_count += 1
        self._finish_unadmitted(req.uid, "shed", reason)
        return reason

    def cancel(self, uid: int, *, status: str = "cancelled",
               reason: Optional[str] = None) -> bool:
        """Cancel request ``uid`` at whatever lifecycle stage it is in.

        Queued requests leave the queue; an in-flight slot is retired
        immediately — partial output preserved in ``results[uid]``,
        every KV block, COW tail and state-snapshot ref released, the
        slot's block tables re-pointed at the write sink. Returns True
        when the request was live (queued or slotted), False when it was
        already terminal or unknown — cancelling a finished request is
        not an error, the finish simply won.

        Called between :meth:`step_begin` and :meth:`step_commit` the
        cancellation is *deferred* to the commit boundary: the in-flight
        device step may still rewind into (speculative window) or
        scatter-write through the slot's blocks, and its committed cache
        pytree would clobber an eager sink-reset — the cancel-vs-rewind
        ordering contract (``serve.kv_pool``).
        """
        for i, r in enumerate(self.queue):
            if r.uid == uid:
                del self.queue[i]
                if status == "cancelled":
                    self.cancel_count += 1
                self._finish_unadmitted(uid, status, reason)
                return True
        for b, s in enumerate(self.slots):
            if s is not None and s.req.uid == uid:
                if self._inflight is not None:
                    self._deferred_cancels.append((uid, status, reason))
                else:
                    if status == "cancelled":
                        self.cancel_count += 1
                    self._retire_slot(b, status, reason)
                return True
        return False

    def step(self) -> None:
        """One engine iteration: admit into free slots, then advance —
        :meth:`step_begin` (dispatch) immediately followed by
        :meth:`step_commit` (readback). The async frontend calls the two
        halves itself to overlap host work with the in-flight device
        step; everything else uses this closed-loop wrapper."""
        pending = self.step_begin()
        if pending is not None:
            self.step_commit(pending)

    def step_begin(self) -> Optional[dict]:
        """First half of an engine iteration: enforce deadlines, admit
        into free slots, and *dispatch* the step's fused device work
        without reading it back.

        JAX dispatch is asynchronous, so when this returns the device is
        (logically) computing while the host is free — the seam the
        async frontend's double-buffering exploits. Returns an opaque
        pending record to hand to :meth:`step_commit`, or ``None`` when
        the engine is idle. Exactly one step may be in flight.

        Admission only binds a slot and plans the prompt's chunks — the
        chunks themselves piggyback on subsequent fused steps, so decode
        slots keep emitting tokens throughout the admission window. Paged
        mode adds allocator backpressure: the queue head is admitted only
        when the pool can cover its worst-case block count *beyond* what
        a prefix-cache hit already supplies (free plus evictable cached
        blocks). Admission stays strict FIFO — a blocked head is *not*
        overtaken by smaller requests behind it, so no request can
        starve.
        """
        with TraceAnnotation("serve.step_begin"):
            if self._inflight is not None:
                raise RuntimeError("step_begin with a step already in "
                                   "flight — commit it first (step_commit)")
            try:
                with TraceAnnotation("serve.admit"):
                    self._enforce_deadlines()
                    self._admit_loop()
                pending = self._dispatch()
            except Exception as e:                # noqa: BLE001
                if not self._tolerant:
                    raise
                self._fault_reset(e)
                return None
            self._inflight = pending
            return pending

    def step_commit(self, pending: dict) -> None:
        """Second half: read the dispatched step's results back and run
        the host bookkeeping (token appends, phase flips, registration,
        retirement), then apply any cancellations deferred while the
        step was in flight, then tick the drift clock."""
        with TraceAnnotation("serve.step_commit"):
            if pending is not self._inflight:
                raise RuntimeError("step_commit of a step that is not the "
                                   "one in flight")
            try:
                {"mixed": self._mixed_commit,
                 "spec": self._spec_commit,
                 "decode": self._decode_commit}[pending["op"]](pending)
            except Exception as e:                # noqa: BLE001
                self._inflight = None
                if not self._tolerant:
                    raise
                self._fault_reset(e)
                return
            self._inflight = None
            self.phase_time[pending["kind"]] += (time.perf_counter()
                                                 - pending["t0"])
            for uid, status, reason in self._deferred_cancels:
                for b, s in enumerate(self.slots):
                    if s is not None and s.req.uid == uid:
                        if status == "cancelled":
                            self.cancel_count += 1
                        self._retire_slot(b, status, reason)
                        break          # a finish during commit simply won
            self._deferred_cancels.clear()
            # the chip only ages while it computes: idle iterations
            # never reach a commit, so the deployment clock ticks worked
            # steps only
            if self._drift:
                self._advance_drift()

    def _admit_loop(self) -> None:
        """Admit queue heads into free slots (strict FIFO, allocator
        backpressure); an allocator fault at admission sheds the head
        with an explicit reason instead of failing the whole step."""
        free = [b for b in range(self.scfg.num_slots)
                if self.slots[b] is None]
        while free and self.queue:
            try:
                self._chaos("alloc")
                plan = self._plan_admission(self.queue[0])
            except Exception as e:                # noqa: BLE001
                if not self._tolerant:
                    raise
                req = self.queue.popleft()
                self.shed_count += 1
                self._finish_unadmitted(
                    req.uid, "shed",
                    f"allocator fault at admission: "
                    f"{type(e).__name__}: {e}")
                continue
            if plan is None:
                break                          # out of blocks: head waits
            self._admit_request(self.queue.popleft(), free.pop(0), plan)

    def _dispatch(self) -> Optional[dict]:
        """Dispatch the step kind the current slot mix calls for; returns
        the pending record (``None`` = idle)."""
        decode_rows = [b for b, s in enumerate(self.slots)
                       if s is not None and not s.prefilling]
        prefill_rows = [b for b, s in enumerate(self.slots)
                        if s is not None and s.prefilling]
        t0 = time.perf_counter()
        if prefill_rows:
            pending = self._mixed_dispatch(decode_rows, prefill_rows)
            kind = "mixed" if decode_rows else "prefill"
        elif decode_rows:
            # model drafters take the spec path even when the window
            # clamps to k=0 (a row within one token of its budget): the
            # k=0 "window" is a plain decode step whose drafter scan
            # still consumes the emitted token, keeping the draft cache
            # position-synchronized. Host drafters have no cache, so
            # they fall back to the cheaper multi-step decode block.
            if self._spec and (self.draft_caches is not None
                               or self._spec_k(decode_rows)):
                pending = self._spec_dispatch(decode_rows)
            else:
                pending = self._decode_dispatch(decode_rows)
            kind = "decode"
        else:
            return None
        live = [b for b, s in enumerate(self.slots) if s is not None]
        self.kv_used_tokens += int(np.maximum(
            self._pos[live] - self._start[live], 0).sum())
        self.kv_capacity_tokens += self._kv_capacity
        pending["kind"], pending["t0"] = kind, t0
        return pending

    def _chaos(self, point: str) -> None:
        """Fire the chaos hook at a named fault-injection checkpoint."""
        if self.chaos_hook is not None:
            self.chaos_hook(point)

    def _enforce_deadlines(self) -> None:
        """Retire every request past its TTFT or end-to-end deadline —
        queued requests leave the queue, slotted requests release their
        blocks/snapshots and keep their partial output. Runs at step
        boundaries only (``step_begin``), so deadline enforcement never
        races an in-flight dispatch."""
        now = time.perf_counter()

        def overdue(req, started):
            born = self.submit_time.get(req.uid, now)
            dl = min(req.ttft_deadline or float("inf"),
                     req.deadline or float("inf")) if not started else (
                         req.deadline or float("inf"))
            return now - born > dl

        stale = [r.uid for r in self.queue if overdue(r, False)]
        for uid in stale:
            self.timeout_count += 1
            self.cancel(uid, status="timed_out",
                        reason="deadline passed while queued")
        for b, s in enumerate(self.slots):
            if s is None:
                continue
            if s.count == 0 and overdue(s.req, False):
                self.timeout_count += 1
                self._retire_slot(b, "timed_out",
                                  "TTFT deadline passed during prefill")
            elif s.count > 0 and overdue(s.req, True):
                self.timeout_count += 1
                self._retire_slot(b, "timed_out",
                                  "end-to-end deadline passed mid-decode")

    def _finish_unadmitted(self, uid: int, status: str,
                           reason: Optional[str]) -> None:
        """Terminal bookkeeping for a request that never held a slot
        (shed at submit, or cancelled/timed out while queued)."""
        self.results[uid] = np.zeros(0, np.int32)
        self.status[uid] = status
        if reason is not None:
            self.errors[uid] = reason
        self.finished_at[uid] = time.perf_counter()
        self.events.append(("done", uid, status))

    def _retire_slot(self, b: int, status: str,
                     reason: Optional[str] = None) -> None:
        """Retire slot ``b`` into terminal ``status``: record its (full
        or partial) output, release every pool reference it holds — KV
        blocks, COW tail, un-registered in-flight state snapshots — and
        point its block tables at the write sink so the freed row's
        static-shape scatter-writes stay harmless. The single retirement
        path for finish, cancel, timeout and deadline alike, so pool
        conservation holds under any interleaving."""
        slot = self.slots[b]
        uid = slot.req.uid
        self.results[uid] = np.array(slot.out, np.int32)
        self.finished_at[uid] = time.perf_counter()
        self.status[uid] = status
        if reason is not None:
            self.errors[uid] = reason
        self.events.append(("done", uid, status))
        self.slots[b] = None
        self._dirty = True
        if self.state_pool is not None and self.state_pool.owns(uid):
            # snapshots captured mid-prefill and never registered (a
            # cancelled/timed-out prefill): refs drop, unindexed slots
            # go straight back to the free list
            self.state_pool.release(uid)
        if self.pool is not None:
            # Drop the request's block references (indexed zero-ref
            # blocks are retained in the pool's LRU for prefix reuse,
            # the rest return to the free list) and point the slot's
            # block tables at the reserved sink block: the retired
            # row keeps executing its static-shape scatter-writes in
            # subsequent decode blocks, and those must not land in
            # blocks the allocator may hand to the next admission —
            # or in retained cache blocks.
            self.pool.release(uid)
            zrow = jnp.zeros(self.caches_tbl_width, jnp.int32)
            self.caches = _admit_jit(
                self.caches, jnp.int32(b), jnp.int32(0), jnp.int32(0),
                zrow, zrow, jnp.int32(0), jnp.int32(0), jnp.int32(0),
                cfg=self.cfg, paged=self._paged,
                kv_bits=self.acfg.kv_bits, snaps=self._snaps)

    def _fault_reset(self, exc: BaseException) -> None:
        """Degrade gracefully after a mid-step fault: every in-flight
        request surfaces an explicit ``errored`` result (partial output
        + the fault message), then the device-side state — caches,
        pools, drafter caches, step mirrors — is rebuilt from scratch
        (its contents are suspect after a fault mid-dispatch) and the
        engine keeps serving the queue. Queued requests are untouched."""
        msg = f"step fault: {type(exc).__name__}: {exc}"
        self.step_faults.append(msg)
        self.fault_count += 1
        now = time.perf_counter()
        for b, s in enumerate(self.slots):
            if s is None:
                continue
            uid = s.req.uid
            self.results[uid] = np.array(s.out, np.int32)
            self.finished_at[uid] = now
            self.status[uid] = "errored"
            self.errors[uid] = msg
            self.events.append(("done", uid, "errored"))
            self.slots[b] = None
        self._deferred_cancels.clear()
        self._inflight = None
        scfg = self.scfg
        if self.pool is not None:
            self.pool = KVPool(self.pool.num_blocks, scfg.kv_block_size,
                               salt=scfg.cache_salt)
        if self.state_pool is not None:
            self.state_pool = StateSnapshotPool(
                self.state_pool.num_blocks, scfg.kv_block_size,
                salt=scfg.cache_salt)
        self.caches = T.init_caches(
            self.cfg, scfg.num_slots, scfg.max_len, scfg.cache_dtype,
            per_slot=True, paged=self._paged,
            kv_block_size=scfg.kv_block_size,
            kv_blocks=scfg.kv_blocks or None,
            kv_bits=self.acfg.kv_bits if self._paged else 0,
            state_snaps=self._n_state_snaps)
        if self.draft_caches is not None:
            self.draft_caches = T.init_caches(
                self.draft_cfg, scfg.num_slots, scfg.max_len,
                scfg.cache_dtype, per_slot=True)
        if self.mesh is not None:
            # the rebuilt caches are fresh single-device arrays — commit
            # them back to the serve mesh before the next sharded step
            self.caches = sharding.shard_caches_for_serve(self.mesh,
                                                          self.caches)
            if self.draft_caches is not None:
                self.draft_caches = sharding.shard_caches_for_serve(
                    self.mesh, self.draft_caches)
        self._pos[:] = 0
        self._start[:] = 0
        self._last_tok[:] = 0
        self._dev = {}
        self._dirty = True

    def _advance_drift(self) -> None:
        """Tick the deployment clock; run the recalibration watchdog.

        Advancing drift mutates only the tiny ``"device"`` subdicts of
        ``self.params`` (``core.devices.advance``) — params are dynamic
        arguments to every step jit, so neither aging nor an in-place
        recalibration recompiles any executable, and the KV pool, prefix
        index and in-flight requests keep serving across both. Every
        ``recal_interval`` worked steps the watchdog reads per-tile
        health host-side; when the mean ``|tile scale - 1|`` over live
        tiles exceeds ``recal_threshold`` (and ``recalibrate=True``) the
        analog tiles are reprogrammed in place: fresh gain/offset
        instances, drift clock restarted at the current deployment time
        — permanent faults (dead tiles, stuck columns) survive, as on a
        real chip.
        """
        self.params = devices_lib.advance(self.params, self.scfg.drift_dt)
        self.drift_hours += self.scfg.drift_dt
        self._steps_since_check += 1
        if self._steps_since_check < self.scfg.recal_interval:
            return
        self._steps_since_check = 0
        try:
            self._chaos("health")
            h = devices_lib.health(self.params)
            if not np.isfinite(h["mean_scale_err"]):
                raise ValueError(
                    f"non-finite tile health read: {h['mean_scale_err']}")
        except Exception as e:                    # noqa: BLE001
            if not self._tolerant:
                raise
            # a corrupted health read must never drive the watchdog —
            # skip this check (no recalibration on garbage), count the
            # fault, keep serving; the next interval reads fresh
            self.health_faults += 1
            self.step_faults.append(
                f"health-read fault (watchdog check skipped): "
                f"{type(e).__name__}: {e}")
            return
        self.watchdog_checks += 1
        self.tile_scale_err = h["mean_scale_err"]
        self.dead_tiles = h["dead_tiles"]
        self.stuck_cols = h["stuck_cols"]
        if self._recal and self.tile_scale_err > self.scfg.recal_threshold:
            key = jax.random.fold_in(self._recal_key, self.recal_count)
            self.params = devices_lib.recalibrate(self.params, key)
            if self.mesh is not None:
                # recalibration programs fresh gain/offset leaves on the
                # host device — re-commit them to the serve mesh so the
                # per-tile state keeps sharding with its owning weight
                self.params = sharding.shard_params_for_serve(self.mesh,
                                                              self.params)
            self.recal_count += 1
            h = devices_lib.health(self.params)
            self.tile_scale_err = h["mean_scale_err"]

    def _blocks_needed(self, req: Request) -> int:
        """Worst-case pool blocks a request holds (padded prompt + budget)."""
        return self.pool.blocks_for(
            padded_prompt_len(len(req.prompt), self.scfg.prefill_chunk),
            req.max_new)

    def run(self, requests: Sequence[Request] = ()) -> dict[int, np.ndarray]:
        """Drive until every queued/submitted request completes."""
        for r in requests:
            self.submit(r)
        while self.queue or any(s is not None for s in self.slots):
            self.step()
        return self.results

    @property
    def num_active(self) -> int:
        """Slots currently holding a request (prefilling or decoding)."""
        return sum(s is not None for s in self.slots)

    @property
    def queue_depth(self) -> int:
        """Requests accepted but not yet admitted to a slot."""
        return len(self.queue)

    def drain_events(self) -> list[tuple]:
        """Pop and return every pending stream event — ``("token", uid,
        tok)`` per sampled token, ``("done", uid, status)`` per terminal
        transition, in order. The async frontend calls this after each
        commit to feed per-request token streams."""
        out = list(self.events)
        self.events.clear()
        return out

    @property
    def prefix_enabled(self) -> bool:
        """True when this engine runs the radix prefix cache
        (``prefix_cache`` with ``paged=True``, any family — ssm/hybrid
        via the state-snapshot pool)."""
        return self._prefix

    @property
    def paged_enabled(self) -> bool:
        """True when the engine serves from the block-paged KV pool
        (false for attention-free stacks even when requested — see
        ``gating_reasons``)."""
        return self._paged

    @property
    def spec_enabled(self) -> bool:
        """True when pure-decode steps run draft-and-verify windows
        (``speculative=True`` on an attention-only family — see
        ``gating_reasons`` otherwise)."""
        return self._spec

    @property
    def spec_acceptance(self) -> float:
        """Fraction of proposed draft tokens the target accepted."""
        return self.spec_accepted / max(1, self.spec_proposed)

    @property
    def drift_enabled(self) -> bool:
        """True when the deployment clock advances conductance drift
        each worked step (``drift_dt > 0`` with per-tile device state on
        the params — see ``gating_reasons`` otherwise)."""
        return self._drift

    @property
    def recal_enabled(self) -> bool:
        """True when the drift watchdog may reprogram analog tiles in
        place (``recalibrate=True`` on a drift-enabled engine)."""
        return self._recal

    @property
    def step_budget(self) -> int:
        """Per-step token budget of the fused mixed step (see config)."""
        return (self.scfg.step_tokens
                or self.scfg.num_slots + 2 * self.scfg.prefill_chunk)

    @property
    def prefill_batch(self) -> int:
        """Compact width of the fused step's chunk forward: the most
        admitting slots one step's budget can carry (static — it shapes
        the compiled executable)."""
        return max(1, min(self.scfg.num_slots,
                          (self.step_budget - self.scfg.num_slots)
                          // self.scfg.prefill_chunk))

    @property
    def caches_tbl_width(self) -> int:
        """Block-table row width (logical blocks per slot) in paged mode."""
        return -(-self.scfg.max_len // self.scfg.kv_block_size)

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    def _plan_admission(self, req: Request):
        """Resolve the queue head's admission: padded prompt layout plus
        the prefix-cache match. Returns ``None`` when the pool cannot
        cover the blocks the request still needs (backpressure) —
        otherwise a dict consumed by :meth:`_admit_request` in the same
        scheduling iteration (nothing can intervene between the two)."""
        c = self.scfg.prefill_chunk
        plen = len(req.prompt)
        padded = padded_prompt_len(plen, c)
        npad = padded - plen
        toks = np.zeros(padded, np.int32)
        toks[npad:] = np.asarray(req.prompt, np.int32)
        mask = np.zeros(padded, np.float32)
        mask[npad:] = 1.0
        keys, hit, tail, snap = [], [], None, None
        if self._prefix:
            idx = self.pool if self.pool is not None else self.state_pool
            keys = idx.prefix_keys(toks, npad)
            if self.pool is not None:
                hit, tail = self.pool.match_prefix(toks, npad, keys=keys)
            if self.state_pool is not None:
                # state families can only skip to a boundary whose
                # snapshot exists. Bound the search by (a) the final
                # chunk, which always re-runs so first-token logits
                # exist, and (b) for hybrid, the KV hit — skipped
                # positions are never recomputed, so their attention
                # reads must land in cached blocks. The hybrid tail COW
                # is dropped: the region past the snapshot re-runs
                # anyway, so a donor copy would buy nothing.
                limit = (padded - c) // self.scfg.kv_block_size
                if self.pool is not None:
                    limit = min(limit, len(hit))
                    tail = None
                snap = self.state_pool.match_deepest(keys[:limit])
        if self.pool is not None:
            need = self._blocks_needed(req) - len(hit)
            # hit blocks stop being evictable the moment admission
            # acquires them; the COW source must survive until the copy
            protect = frozenset(hit) | (
                frozenset((tail[0],)) if tail else frozenset())
            if not self.pool.can_alloc(need, protect):
                return None
        return dict(toks=toks, mask=mask, npad=npad, keys=keys, hit=hit,
                    tail=tail, snap=snap)

    def _admit_request(self, req: Request, b: int, plan: dict) -> None:
        """Bind slot ``b`` to ``req``: map its block-table row onto the
        prefix-hit shared blocks plus fresh private ones, reset its cache
        rows with ``pos`` advanced past the (chunk-aligned) hit, and plan
        only the tail chunks. No model math — the chunks stream through
        subsequent fused steps."""
        c = self.scfg.prefill_chunk
        toks, mask, npad = plan["toks"], plan["mask"], plan["npad"]
        hit, tail, snap = plan["hit"], plan["tail"], plan["snap"]
        padded, nhit = len(toks), len(hit)

        tbl_row = wtbl_row = None
        skip, blocks, hit_tokens = 0, [], 0
        cow_src = cow_dst = snap_src = 0
        if self.pool is not None:
            protect = frozenset((tail[0],)) if tail else frozenset()
            fresh = self.pool.admit(req.uid, hit,
                                    self._blocks_needed(req) - nhit,
                                    protect)
            blocks = list(hit) + fresh
            nb_slot = self.caches_tbl_width
            row = np.zeros(nb_slot, np.int32)
            row[:len(blocks)] = blocks
            # write protection: chunk scatter-writes into shared
            # prefix-hit blocks land in the sink instead
            wrow = row.copy()
            wrow[:nhit] = SINK_BLOCK
            tbl_row, wtbl_row = jnp.asarray(row), jnp.asarray(wrow)
            bs = self.pool.block_size
            hit_tokens = min(nhit * bs + (tail[1] if tail else 0), padded)
            # pos starts past the hit, rounded down to a chunk boundary;
            # the final chunk always re-runs so first-token logits exist
            skip = min(hit_tokens - hit_tokens % c, padded - c)
            if tail:
                cow_src, cow_dst = tail[0], blocks[nhit]
                self.prefix_cow_copies += 1
        if self.state_pool is not None:
            # state families skip exactly to the restored snapshot's
            # boundary (or not at all): the SSM recurrence cannot jump
            # past tokens it never consumed, however many KV blocks hit.
            # Snapshots are only ever captured at chunk-boundary
            # positions, so the skip is chunk-aligned by construction.
            skip = snap[0] * self.state_pool.block_size if snap else 0
            assert skip % c == 0
            hit_tokens = max(hit_tokens, skip)
            if snap:
                snap_src = snap[1]
                self.state_snap_restores += 1
        if self._prefix:
            # one lookup per *admission* (a backpressured head's
            # per-step retries would deflate the reported hit rate)
            self.prefix_lookups += 1
            if hit_tokens:
                self.prefix_hits += 1
                self.prefix_hit_tokens += hit_tokens
                self.prefix_skipped_tokens += skip
        self.caches = _admit_jit(self.caches, jnp.int32(b), jnp.int32(npad),
                                 jnp.int32(skip), tbl_row, wtbl_row,
                                 jnp.int32(cow_src), jnp.int32(cow_dst),
                                 jnp.int32(snap_src),
                                 cfg=self.cfg, paged=self._paged,
                                 kv_bits=self.acfg.kv_bits,
                                 cow=tail is not None, snaps=self._snaps,
                                 restore=snap is not None)
        self._pos[b], self._start[b] = skip, npad
        self._temp[b], self._topp[b] = req.temperature, req.top_p
        self._topk[b], self._gfirst[b] = req.top_k, req.greedy_first
        self._keys[b] = np.asarray(jax.random.PRNGKey(req.seed))
        slot = _Slot(req, toks, mask, npad, c, self._admit_seq, skip=skip)
        slot.blocks, slot.keys, slot.hit_full = blocks, plan["keys"], nhit
        slot.hit_snap = snap[0] if snap else 0
        self.slots[b] = slot
        self.status[req.uid] = "prefill"
        self.admit_time[req.uid] = time.perf_counter()
        self._admit_seq += 1
        self._dirty = True

    def _register_slot(self, s: _Slot) -> None:
        """Index the slot's freshly computed prompt blocks the moment its
        prefill completes: private full blocks under their chain keys,
        plus the frozen partial tail (its content below the fill count is
        immutable from here on — writes are append-only)."""
        bs = self.pool.block_size
        nfull = len(s.toks) // bs
        self.pool.register(s.keys[s.hit_full:nfull],
                           s.blocks[s.hit_full:nfull])
        fill = len(s.toks) % bs
        if fill and nfull < len(s.blocks):
            parent = s.keys[nfull - 1] if nfull else (self.pool.salt,
                                                      s.npad)
            self.pool.register_tail(parent, s.blocks[nfull], fill,
                                    s.toks[nfull * bs:])

    def _maybe_snapshot(self, b: int, s: _Slot) -> None:
        """Capture slot ``b``'s SSM/conv state into the snapshot pool
        when its prefill cursor just landed on a KV-block boundary: the
        state at ``m * kv_block_size`` tokens summarizes exactly the
        padded prompt blocks chain key ``keys[m-1]`` addresses.
        Best-effort — when every snapshot slot is live the boundary
        simply stays cold (the request still serves correctly)."""
        bs = self.state_pool.block_size
        p = int(self._pos[b])
        if p % bs:
            return
        m = p // bs
        if m < 1 or m <= s.hit_snap or m > len(s.keys):
            return
        key = s.keys[m - 1]
        if self.state_pool.has(key) or any(k == key for k, _ in s.snaps):
            return
        dst = self.state_pool.acquire(s.req.uid)
        if dst is None:
            return
        self.caches = _snap_jit(self.caches, jnp.int32(b), jnp.int32(dst),
                                cfg=self.cfg, paged=self._paged,
                                kv_bits=self.acfg.kv_bits)
        s.snaps.append((key, dst))
        self.state_snaps_captured += 1

    def _register_snaps(self, s: _Slot) -> None:
        """Index the snapshots captured during the slot's prefill (at the
        prefill→decode flip, mirroring ``_register_slot``) and drop the
        request's ownership: indexed snapshots park in the pool's LRU
        awaiting reuse, a first-writer-wins loser goes straight back to
        the free list."""
        for key, dst in s.snaps:
            self.state_pool.register(key, dst)
        if s.snaps:
            self.state_pool.release(s.req.uid)

    def _sample_flags(self) -> tuple[bool, bool]:
        """Static sampler specialization over every in-flight request."""
        live = [s.req for s in self.slots if s is not None]
        return (any(r.top_k > 0 for r in live),
                any(r.top_p < 1.0 for r in live))

    def _refresh_device_state(self) -> None:
        """Re-upload the per-slot step state from the host mirrors (only
        called when the slot set changed since the last dispatch)."""
        with TraceAnnotation("serve.upload"):
            counts = np.array([s.count if s else 0 for s in self.slots],
                              np.int32)
            active = np.array([s is not None and not s.prefilling
                               for s in self.slots], np.float32)
            self._dev = {
                "toks": jnp.asarray(self._last_tok),
                "off": jnp.asarray(self._pos - self._start),
                "active": jnp.asarray(active),
                "keys": jnp.asarray(self._keys),
                "counts": jnp.asarray(counts),
                "temp": jnp.asarray(self._temp),
                "topk": jnp.asarray(self._topk),
                "topp": jnp.asarray(self._topp),
                "gfirst": jnp.asarray(self._gfirst),
            }
        self._dirty = False

    def _decode_args(self):
        """The device-resident positional args shared by both step jits."""
        d = self._dev
        return (d["toks"], d["off"], d["active"], d["keys"], d["counts"],
                d["temp"], d["topk"], d["topp"], d["gfirst"])

    def _stash(self, toks, off, counts) -> None:
        """Keep the updated step state device-resident for the next step."""
        self._dev.update(toks=toks, off=off, counts=counts)

    def _mixed_dispatch(self, decode_rows: list[int],
                        prefill_rows: list[int]) -> dict:
        """One fused step: a decode token for every decode-phase slot plus
        as many admitting slots' prefill chunks as the token budget allows
        (oldest admission first, floor of one chunk — see config). The
        chunk forward runs at the compact ``prefill_batch`` width; unused
        compact rows point at distinct filler slots with all-zero masks
        (cache-transparent by the layers' fully-masked-row contract).
        Dispatch half: returns the pending record, device work in
        flight."""
        if self._dirty:
            self._refresh_device_state()
        self._chaos("dispatch")
        c, pbw = self.scfg.prefill_chunk, self.prefill_batch
        n_dec = len(decode_rows)
        n_pf = int(np.clip((self.step_budget - n_dec) // c, 1,
                           min(len(prefill_rows), pbw)))
        pf_rows = sorted(prefill_rows,
                         key=lambda b: self.slots[b].seq)[:n_pf]
        # distinct filler slot ids for the unused compact rows
        filler = [b for b in range(self.scfg.num_slots) if b not in pf_rows]
        pf_idx = np.asarray(pf_rows + filler[:pbw - n_pf], np.int32)

        pf_toks = np.zeros((pbw, c), np.int32)
        pf_mask = np.zeros((pbw, c), np.float32)
        pf_off = np.zeros(pbw, np.int32)
        for i, b in enumerate(pf_rows):
            s = self.slots[b]
            j = s.chunk
            pf_toks[i] = s.toks[j * c:(j + 1) * c]
            pf_mask[i] = s.mask[j * c:(j + 1) * c]
            pf_off[i] = j * c - s.npad
        k = 1 if n_dec else 0
        if k and self.draft_caches is not None:
            # keep the model drafter position-synchronized through the
            # admission window (see _draft_step_jit); consumes the same
            # pre-step (toks, off, active) the decode substep reads
            d = self._dev
            self.draft_caches = _draft_step_jit(
                self.draft_params, self.draft_caches, d["toks"], d["off"],
                d["active"], dcfg=self.draft_cfg, dacfg=self.draft_acfg,
                mesh=self.mesh)

        use_top_k, use_top_p = self._sample_flags()
        with TraceAnnotation("serve.dispatch"):
            dec_toks, first, toks, off, counts, self.caches = (
                _mixed_step_jit(
                    self.params, self.caches, *self._decode_args(),
                    pf_idx=jnp.asarray(pf_idx), pf_toks=jnp.asarray(pf_toks),
                    pf_mask=jnp.asarray(pf_mask),
                    pf_off=jnp.asarray(pf_off), cfg=self.cfg,
                    acfg=self.acfg, use_top_k=use_top_k,
                    use_top_p=use_top_p, k=k, paged=self._paged,
                    snaps=self._snaps, mesh=self.mesh))
        self._stash(toks, off, counts)
        if k:
            self.mixed_steps += 1          # steps that fused both phases
        self.prefill_chunks += len(pf_rows)
        self.step_token_log.append((n_dec * k, len(pf_rows) * c))
        return dict(op="mixed", dec_toks=dec_toks, first=first,
                    pf_rows=pf_rows, decode_rows=decode_rows, k=k,
                    n_dec=n_dec)

    def _mixed_commit(self, p: dict) -> None:
        """Commit half of the fused step: host bookkeeping — chunk
        cursors, phase flips (block/snapshot registration + the sampled
        first token), decode-token appends."""
        c = self.scfg.prefill_chunk
        pf_rows, k = p["pf_rows"], p["k"]
        first = p["first"]
        first_host = None
        for i, b in enumerate(pf_rows):
            s = self.slots[b]
            s.chunk += 1
            self._pos[b] += c                  # the chunk advanced the row
            if self.state_pool is not None:
                self._maybe_snapshot(b, s)
            if not s.prefilling:               # prompt done: first token
                if first_host is None:
                    with TraceAnnotation("serve.readback"):
                        first_host = np.asarray(first)
                if self._prefix:
                    # index the prompt's blocks/snapshots before the
                    # first token can retire the request (release must
                    # see the entries so the blocks are retained, not
                    # freed)
                    if self.pool is not None:
                        self._register_slot(s)
                    if self.state_pool is not None:
                        self._register_snaps(s)
                self._dirty = True             # row flips to decode phase
                self._append_token(b, int(first_host[i]))
                if self.draft_caches is not None and (
                        self.slots[b] is not None):
                    # bring the model drafter's private cache to the same
                    # position before the slot's first verify window (the
                    # full prompt in one forward — the drafter has no
                    # prefix cache; skipped if the first token already
                    # retired the request)
                    self.draft_caches = _draft_prefill_jit(
                        self.draft_params, self.draft_caches,
                        jnp.int32(b), jnp.asarray(s.toks[None]),
                        jnp.asarray(s.mask[None]), jnp.int32(s.npad),
                        cfg=self.draft_cfg, acfg=self.draft_acfg,
                        mesh=self.mesh)
        if k:
            self.decode_steps += k
            self.decode_tokens_during_admission += p["n_dec"] * k
            with TraceAnnotation("serve.readback"):
                dec_toks = np.asarray(p["dec_toks"])
            self._consume_decode_tokens(dec_toks, p["decode_rows"])

    def _spec_k(self, decode_rows: list[int]) -> int:
        """Window size of the next speculative step: ``draft_k`` clipped
        so no in-flight row can write past its own budget — a window
        starting at ``pos`` scatter-writes positions up to ``pos + k``,
        and ``k <= min(remaining) - 1`` keeps that within the
        ``padded + max_new`` span every row's capacity (``max_len``,
        pool blocks) was validated for. Clips to powers of two below
        ``draft_k`` to bound executable count; 0 (some row has a single
        token of budget left) falls back to a plain decode step."""
        head = min(self.slots[b].req.max_new - self.slots[b].count
                   for b in decode_rows) - 1
        if head < 1:
            return 0
        k = self.scfg.draft_k
        if k > head:
            k = 1
            while k * 2 <= head:
                k *= 2
        return k

    def _host_drafts(self, decode_rows: list[int], k: int) -> np.ndarray:
        """Host-side draft proposals ``[k, B]`` for the active rows: each
        slot's ``draft_fn`` (if injected) or prompt-lookup n-grams over
        its prompt + generated context. Short proposals are zero-padded —
        exact-match verification simply rejects the padding."""
        drafts = np.zeros((k, self.scfg.num_slots), np.int32)
        for b in decode_rows:
            s = self.slots[b]
            ctx = np.concatenate([s.toks[s.npad:],
                                  np.asarray(s.out, np.int32)])
            prop = (np.asarray(self.draft_fn(ctx, k), np.int32)
                    if self.draft_fn is not None
                    else _ngram_propose(ctx, k))[:k]
            drafts[:len(prop), b] = prop
        return drafts

    def _spec_dispatch(self, decode_rows: list[int]) -> dict:
        """One draft-and-verify window over all decode slots: propose
        ``k`` tokens per row, score all ``k+1`` positions in one fused
        target dispatch, emit each row's accepted prefix plus the bonus
        draw, and roll rejected positions back as a ``pos`` rewind.
        Every emitted token flows through :meth:`_append_token`, so stop
        tokens and budgets retire requests mid-window exactly as a
        decode block would (extra tokens are discarded); the pool's
        rewind-safety contract is checked live for every surviving
        paged row. Dispatch half: opens the pool's rewind window over
        the participating uids — releasing any of them before the
        commit closes it is a pool-level error (cancel-vs-rewind
        ordering contract)."""
        if self._dirty:
            self._refresh_device_state()
        self._chaos("dispatch")
        k = self._spec_k(decode_rows)
        use_top_k, use_top_p = self._sample_flags()
        if self._draft_host:
            drafts = self._host_drafts(decode_rows, k)
            with TraceAnnotation("serve.dispatch"):
                target, n_emit, toks, off, counts, self.caches = (
                    _spec_verify_jit(
                        self.params, self.caches, *self._decode_args(),
                        jnp.asarray(drafts), cfg=self.cfg, acfg=self.acfg,
                        use_top_k=use_top_k, use_top_p=use_top_p,
                        paged=self._paged, snaps=self._snaps,
                        mesh=self.mesh))
        else:
            with TraceAnnotation("serve.dispatch"):
                (target, n_emit, toks, off, counts, self.caches,
                 self.draft_caches) = _spec_step_jit(
                    self.params, self.draft_params, self.caches,
                    self.draft_caches, *self._decode_args(),
                    cfg=self.cfg, acfg=self.acfg, dcfg=self.draft_cfg,
                    dacfg=self.draft_acfg, use_top_k=use_top_k,
                    use_top_p=use_top_p, k=k, paged=self._paged,
                    snaps=self._snaps, mesh=self.mesh)
        self._stash(toks, off, counts)
        if self.pool is not None:
            self.pool.begin_window(self.slots[b].req.uid
                                   for b in decode_rows)
        return dict(op="spec", target=target, n_emit=n_emit,
                    decode_rows=decode_rows, k=k)

    def _spec_commit(self, p: dict) -> None:
        """Commit half of the speculative window: force the readback
        (cursors are final), close the pool's rewind window, then append
        each row's emitted tokens and check the rewind-safety
        contract."""
        decode_rows, k = p["decode_rows"], p["k"]
        with TraceAnnotation("serve.readback"):
            target = np.asarray(p["target"])
            n_emit = np.asarray(p["n_emit"])
        if self.pool is not None:
            self.pool.end_window()
        if k:                     # a k=0 window is just a decode step
            self.spec_steps += 1
        self.decode_steps += 1
        emitted = 0
        for b in decode_rows:
            ne = int(n_emit[b])
            self.spec_proposed += k
            self.spec_accepted += ne - 1
            uid = self.slots[b].req.uid
            for i in range(ne):
                if self.slots[b] is None:
                    break              # stop/budget hit mid-window
                self._pos[b] += 1
                emitted += 1
                self._append_token(b, int(target[i, b]))
            if self.pool is not None and self.slots[b] is not None:
                self.pool.check_rewind(uid, int(self._pos[b]))
        self.step_token_log.append((emitted, 0))

    def _decode_dispatch(self, decode_rows: list[int]) -> dict:
        """One multi-step decode block over all slots (no admissions in
        flight): the largest power-of-two ``k <= decode_block`` that no
        in-flight budget can overshoot, in a single dispatch. Dispatch
        half: returns the pending record, device work in flight."""
        if self._dirty:
            self._refresh_device_state()
        self._chaos("dispatch")
        live = [self.slots[b] for b in decode_rows]
        k = 1
        remaining = min(s.req.max_new - s.count for s in live)
        while k * 2 <= min(remaining, self.scfg.decode_block):
            k *= 2
        use_top_k, use_top_p = self._sample_flags()
        with TraceAnnotation("serve.dispatch"):
            dec_toks, toks, off, counts, self.caches = _step_jit(
                self.params, self.caches, *self._decode_args(),
                cfg=self.cfg, acfg=self.acfg,
                use_top_k=use_top_k, use_top_p=use_top_p, k=k,
                mesh=self.mesh)
        self._stash(toks, off, counts)
        self.decode_steps += k
        self.step_token_log.append((len(decode_rows) * k, 0))
        return dict(op="decode", dec_toks=dec_toks,
                    decode_rows=decode_rows)

    def _decode_commit(self, p: dict) -> None:
        """Commit half of the decode block: read the sampled tokens back
        and append them to their requests."""
        with TraceAnnotation("serve.readback"):
            dec_toks = np.asarray(p["dec_toks"])
        self._consume_decode_tokens(dec_toks, p["decode_rows"])

    def _consume_decode_tokens(self, toks: np.ndarray,
                               decode_rows: list[int]) -> None:
        """Append a ``[k, B]`` decode block's tokens to their requests.
        Slots going None mid-block stop consuming their rows (tokens past
        a stop condition are discarded)."""
        for i in range(toks.shape[0]):
            for b in decode_rows:
                if self.slots[b] is not None:
                    self._pos[b] += 1
                    self._append_token(b, int(toks[i, b]))

    def _append_token(self, b: int, tok: int) -> None:
        """Record one sampled token (stream event + TTFT timestamp on
        the first); finish the request on stop/budget via the shared
        retirement path."""
        slot = self.slots[b]
        uid = slot.req.uid
        slot.out.append(tok)
        slot.count += 1
        self._last_tok[b] = tok
        if slot.count == 1:
            self.first_token_at[uid] = time.perf_counter()
            self.status[uid] = "decode"
        self.events.append(("token", uid, tok))
        if tok in slot.req.stop_tokens or slot.count >= slot.req.max_new:
            self._retire_slot(b, "finished")
