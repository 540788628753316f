"""Regenerate the KV-cache memory-math table in ``docs/serving.md``.

Computes attention-KV bytes per request slot for real configs under the
serving cache layouts:

* contiguous fp32 — ``2 * L_attn * max_len * KV * hd * 4`` (the pre-paging
  slot cache: every slot pays ``max_len`` regardless of fill),
* contiguous bf16 — same at 2 bytes (the ``cache_dtype`` lever),
* paged int8 — ``2 * L_attn * ceil(max_len/bs) * bs * KV * (hd + 4)`` plus
  the read + write block-table rows (int8 payload + one fp32 scale per
  token/head; still worst-case allocation — the refcounting allocator
  returns a *finished* request's blocks, so fleet-level memory
  additionally scales with live tokens),
* prefix-cached — what the radix prefix cache changes: the bytes a cached
  shared header costs once (``hdr`` column, default 64 tokens), and the
  *effective* int8 bytes per slot when ``--share`` requests serve the same
  header (every sharer after the first references the cached blocks
  instead of recomputing them — the best-of-n / system-prompt shape).

With ``--tp N`` the table switches to the tensor-parallel per-device view
(``docs/distributed.md``): each of the ``N`` shards holds ``KV/N`` heads
of every paged block, so attention-KV and SSM/conv state bytes divide by
``N`` while the host-side block table stays replicated.  The extra
``weights/dev`` column divides total parameter bytes (fp32) by ``N`` —
weights are column-parallel, so each device stores ``1/N`` of every
kernel — which is what lets ``dbrx-132b`` / ``jamba-v0.1-52b`` /
``qwen2.5-32b`` fit per device at tp=4 when tp=1 does not.

    PYTHONPATH=src python tools/kv_memory_table.py [--max-len 4096]
        [--header 64] [--share 8] [--tp 4]
"""

from __future__ import annotations

import argparse

from repro.configs import get_config

ARCHS = ["phi-3-mini-4k", "llama-3.2-1b", "granite-3-8b", "jamba-v0.1-52b"]

#: big configs the ``--tp`` table proves fit per device under sharding
TP_ARCHS = ["dbrx-132b", "jamba-v0.1-52b", "qwen2.5-32b"]


def attn_layers(cfg) -> int:
    """Attention layers in the stack (hybrid: one per super-block)."""
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.attn_every
    return 0 if cfg.is_attention_free else cfg.num_layers


def bytes_per_slot(cfg, max_len: int, block: int = 16):
    """(contiguous fp32, contiguous bf16, paged int8) bytes per slot."""
    la, kv, hd = attn_layers(cfg), cfg.num_kv_heads, cfg.head_dim
    fp32 = 2 * la * max_len * kv * hd * 4
    bf16 = fp32 // 2
    nb = -(-max_len // block)
    # two int32 table rows now: the read table + the write table
    int8 = 2 * la * nb * block * kv * (hd + 4) + 2 * la * nb * 4
    return fp32, bf16, int8


def cached_header_bytes(cfg, header: int, block: int = 16) -> int:
    """Paged-int8 bytes one cached shared header occupies (the one-time
    cost the prefix cache pays to make every sharer's prefill free)."""
    la, kv, hd = attn_layers(cfg), cfg.num_kv_heads, cfg.head_dim
    nb = -(-header // block)
    return 2 * la * nb * block * kv * (hd + 4)


def effective_bytes_per_slot(cfg, max_len: int, header: int, share: int,
                             block: int = 16) -> int:
    """Effective paged-int8 bytes per slot when ``share`` concurrent
    requests reference one cached ``header``-token prefix: the header is
    stored once, so each slot amortizes ``(share - 1) / share`` of it."""
    _, _, int8 = bytes_per_slot(cfg, max_len, block)
    hdr = cached_header_bytes(cfg, header, block)
    return int8 - hdr * (share - 1) // share


def _fmt(n: int) -> str:
    """Human MiB with 1 decimal."""
    return f"{n / 2**20:.1f}"


def _gib(n: int) -> str:
    """Human GiB with 1 decimal."""
    return f"{n / 2**30:.1f}"


def weight_bytes(cfg, tp: int, wbits: int = 32):
    """(total, per-device) parameter bytes under ``tp``-way serving.

    ``wbits`` prices the *sharded* kernel leaves (exactly the analog
    matmul sites plus the LM head) at that storage width — 4 for the
    packed-int4 serve path — while replicated leaves (norms, biases,
    the embedding table) stay fp32.

    Exact, allocation-free: ``jax.eval_shape`` over ``init_model`` gives
    every leaf's shape, and the *real* serve-mode spec table
    (:func:`repro.distributed.sharding.param_spec_tree` under
    ``serve_rules``) decides which leaves shard on the "model" axis
    (column-parallel kernels divide by ``tp``) and which replicate
    (norms, biases, the embedding table)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding
    from repro.models import transformer as T

    # [0]: labels are strings, which eval_shape cannot return
    params = jax.eval_shape(
        lambda: T.init_model(jax.random.PRNGKey(0), cfg)[0])
    mesh = jax.sharding.AbstractMesh((1, tp), ("data", "model"))
    with sharding.activate(mesh, sharding.serve_rules(mesh)):
        specs = sharding.param_spec_tree(params)
    total = 0
    per_dev = 0

    def add(spec, p):
        nonlocal total, per_dev
        sharded = "model" in tuple(spec)
        nbytes = (p.size * wbits // 8 if sharded
                  else p.size * p.dtype.itemsize)
        total += nbytes
        per_dev += nbytes // (tp if sharded else 1)

    jax.tree.map(add, specs, params,
                 is_leaf=lambda s: isinstance(s, P))
    return total, per_dev


def ssm_state_bytes(cfg) -> int:
    """Exact recurrent-state (SSD state + conv tail) bytes per slot,
    summed over mamba layers via ``eval_shape`` on ``init_caches`` — the
    part of a hybrid/SSM slot the attention-KV columns miss."""
    import jax
    from repro.models import transformer as T

    caches = jax.eval_shape(lambda: T.init_caches(cfg, 1, 16))
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        keys = {getattr(p, "key", getattr(p, "name", "")) for p in path}
        if keys & {"ssm", "conv"}:
            total += leaf.size * leaf.dtype.itemsize
    return total


def tp_table(args) -> None:
    """Print the tensor-parallel bytes-per-device markdown table
    (``docs/distributed.md``): weights, per-slot KV + recurrent state,
    and whether each big config fits ``--budget-gib`` per device at tp=1
    vs ``--tp`` (kv_heads and ssm_heads shard; the block table and the
    host-side allocator stay replicated and cost nothing per shard)."""
    tp = args.tp
    wb = args.weight_bits
    print(f"| arch | params W{wb} | weights/dev tp=1 | tp={tp} "
          f"| KV+state /slot/dev tp=1 (MiB) | tp={tp} "
          f"| total/dev @{args.slots} slots tp=1 | tp={tp} "
          f"| fits {args.budget_gib:.0f} GiB |")
    print("|---|---|---|---|---|---|---|---|---|")
    for name in TP_ARCHS:
        cfg = get_config(name)
        total, wdev = weight_bytes(cfg, tp, wb)
        _, _, int8 = bytes_per_slot(cfg, args.max_len, args.block)
        ssm = ssm_state_bytes(cfg)
        kv = getattr(cfg, "num_kv_heads", 0) or 1
        kv_dev = int8 // tp if kv % tp == 0 else int8
        ssm_dev = ssm // tp if (not ssm or cfg.ssm_heads % tp == 0) else ssm
        slot1, slotn = int8 + ssm, kv_dev + ssm_dev
        tot1 = total + args.slots * slot1
        totn = wdev + args.slots * slotn
        budget = int(args.budget_gib * 2**30)
        fits = (f"{'yes' if tot1 <= budget else 'no'} → "
                f"{'yes' if totn <= budget else 'no'}")
        print(f"| {cfg.name} | {_gib(total)} | {_gib(total)} | {_gib(wdev)} "
              f"| {_fmt(slot1)} | {_fmt(slotn)} "
              f"| {_gib(tot1)} | {_gib(totn)} | {fits} |")


def main() -> None:
    """Print the markdown table docs/serving.md embeds."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-len", type=int, default=4096)
    ap.add_argument("--block", type=int, default=16)
    ap.add_argument("--header", type=int, default=64,
                    help="shared-prefix header length for the "
                         "cached-bytes / effective-capacity columns")
    ap.add_argument("--share", type=int, default=8,
                    help="requests sharing one cached header (the "
                         "best-of-n fan-out)")
    ap.add_argument("--tp", type=int, default=0,
                    help="print the tensor-parallel bytes-per-device "
                         "table for this shard count instead of the "
                         "per-slot table (docs/distributed.md)")
    ap.add_argument("--slots", type=int, default=8,
                    help="concurrent request slots in the --tp "
                         "total-per-device column")
    ap.add_argument("--budget-gib", type=float, default=80.0,
                    help="per-device memory budget the --tp fits "
                         "column checks against")
    ap.add_argument("--weight-bits", type=int, default=32,
                    help="storage bits for sharded kernel leaves in the "
                         "--tp table (4 = packed-int4 serve path)")
    args = ap.parse_args()
    if args.tp > 1:
        tp_table(args)
        return
    print(f"| arch | attn layers | KV x hd | contiguous fp32 (MiB/slot) "
          f"| bf16 | paged int8 | reduction "
          f"| hdr{args.header} cached (MiB) "
          f"| int8 @{args.share}-way hdr | eff. reduction |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for name in ARCHS:
        cfg = get_config(name)
        f32, b16, i8 = bytes_per_slot(cfg, args.max_len, args.block)
        hdr = cached_header_bytes(cfg, args.header, args.block)
        eff = effective_bytes_per_slot(cfg, args.max_len, args.header,
                                       args.share, args.block)
        print(f"| {cfg.name} | {attn_layers(cfg)} "
              f"| {cfg.num_kv_heads}x{cfg.head_dim} | {_fmt(f32)} "
              f"| {_fmt(b16)} | {_fmt(i8)} | {f32 / i8:.1f}x "
              f"| {_fmt(hdr)} | {_fmt(eff)} | {f32 / eff:.1f}x |")


if __name__ == "__main__":
    main()
