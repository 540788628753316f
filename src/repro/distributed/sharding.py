"""Logical-axis sharding rules (DP / TP / EP / SP) for all architectures.

The scheme is MaxText-style: model code annotates activations with *logical*
axes via :func:`shard_hint`; parameters get PartitionSpecs from a rule table
keyed by site name. A mesh + rule mapping is activated with
:func:`activate` (no-op when inactive, so CPU unit tests are unaffected).

Baseline mapping (paper-faithful Megatron TP + DP):

    batch   → ("pod", "data")     heads  → "model"      mlp    → "model"
    vocab   → "model"             experts→ "model" (EP) embed  → None
    kv_seq  → "data" only when the batch axis cannot be sharded
              (long_500k, global_batch=1) — context/sequence sharding.

ZeRO optimizer-state sharding: Adam moments additionally shard their first
model-unsharded dim over "data" when divisible (the GSPMD equivalent of the
paper's DeepSpeed ZeRO-2 partitioning).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


_state = threading.local()


def _active():
    """The thread-local active (mesh, rules) context, or None."""
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def activate(mesh: Mesh, rules: dict[str, Any]):
    """Enable shard_hint / spec resolution inside the block.

    The rules are GSPMD hints, so ``mesh`` must have Auto axes
    (``launch.mesh.auto_mesh``); an Explicit mesh — ``jax.make_mesh``'s
    default — is refused here, before a gather or a constraint deep in
    the model fails on it.
    """
    if any(t == AxisType.Explicit for t in getattr(mesh, "axis_types", ())):
        raise ValueError(
            "distributed.sharding needs a mesh with Auto axes (its rules "
            "are with_sharding_constraint hints); got axis_types="
            f"{mesh.axis_types} — build it with launch.mesh.auto_mesh")
    prev = _active()
    _state.ctx = {"mesh": mesh, "rules": dict(rules)}
    try:
        yield
    finally:
        _state.ctx = prev


def default_rules(mesh: Mesh, *, batch_shardable: bool = True,
                  seq_shard_kv: bool = False) -> dict[str, Any]:
    """Logical-axis → mesh-axis mapping for the standard 3-axis mesh."""
    pod = ("pod",) if "pod" in mesh.axis_names else ()
    rules = {
        "batch": pod + ("data",) if batch_shardable else None,
        "heads": "model",
        "mlp": "model",
        "vocab": "model",
        "experts": "model",
        "moe_buf": "model",      # MoE dispatch-buffer hint (hillclimb knob)
        "embed": None,
        "seq": None,
        "kv_seq": ("data",) if seq_shard_kv else None,
        "kv_seq_model": "model",
        "zero": "data",
        # activation *outputs* at reduction boundaries (attention out
        # before o-proj, post-activation MLP hidden before down-proj, MoE
        # expert outputs before the combine, SSM head/conv state). Under
        # training rules these stay sharded like their inputs ("model");
        # serve_rules maps them to None instead, forcing an all-gather so
        # no cross-shard reduction ever happens (the bitwise-TP contract).
        "attn_out": "model",
        "mlp_act": "model",
        "moe_out": "model",
        "ssm_heads": "model",
        "ssm_conv": "model",
        # serve-only gather points (after mamba in_proj, on the final
        # logits, at mamba layer ends): disabled outright in training so
        # those jaxprs carry no new constraints at all
        "serve_act": "skip",
    }
    return rules


def _axis_size(mesh: Mesh, axis) -> int:
    """Product of mesh-axis sizes for an axis name (1 for None)."""
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape.get(a, 1)
        return n
    return mesh.shape.get(axis, 1)


def _guard(mesh: Mesh, shape: tuple, axes: tuple) -> tuple:
    """Sanitize a spec: drop (replicate) any axis whose extent does not
    divide the dim, and deduplicate mesh axes (a NamedSharding may map each
    mesh axis to at most one positional dim).

    pjit argument/output shardings require exact divisibility; GSPMD only
    pads *internal* values. Non-divisible cases in this repo: mamba2-130m's
    in_proj fan-out (3352) and its 24 SSD heads; everything else divides by
    construction (vocab is padded to a multiple of 256 in the model)."""
    out, used = [], set()
    for dim, ax in zip(shape, axes):
        names = tuple(ax) if isinstance(ax, (tuple, list)) else (ax,)
        if ax is None or dim % _axis_size(mesh, ax) != 0 or                 any(n in used for n in names):
            out.append(None)
        else:
            out.append(ax)
            used.update(names)
    return tuple(out)


def resolve(logical: tuple, shape: tuple | None = None) -> P:
    """Logical axes tuple → PartitionSpec under the active context."""
    ctx = _active()
    assert ctx is not None
    axes = tuple(ctx["rules"].get(ax) if ax is not None else None
                 for ax in logical)
    if shape is not None:
        axes = _guard(ctx["mesh"], shape, axes)
    return P(*axes)


def shard_hint(x: jax.Array, *logical) -> jax.Array:
    """Constrain ``x`` to the mesh axes mapped from logical axes. No-op when
    no mesh is active, or when any logical axis maps to the "skip" sentinel
    (a true disable — P(None) would instead *force* replication)."""
    ctx = _active()
    if ctx is None:
        return x
    if x.ndim != len(logical):
        return x
    if any(ctx["rules"].get(ax) == "skip" for ax in logical if ax):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx["mesh"], resolve(tuple(logical), x.shape)))


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

#: (site, leaf) → logical axes of the *rightmost* dims; leading stacked-layer
#: dims are unsharded. "M:" prefix marks MoE-expert variants (kernel has a
#: trailing [E, in, out]).
_PARAM_RULES = {
    ("qkv", "kernel"): (None, "heads"),
    ("qkv", "bias"): ("heads",),
    ("q", "kernel"): (None, "heads"),
    ("q", "bias"): ("heads",),
    ("k", "kernel"): (None, "heads"),
    ("k", "bias"): ("heads",),
    ("v", "kernel"): (None, "heads"),
    ("v", "bias"): ("heads",),
    ("o", "kernel"): ("heads", None),
    ("gate_up", "kernel"): (None, "mlp"),
    ("up", "kernel"): (None, "mlp"),
    ("up", "bias"): ("mlp",),
    ("down", "kernel"): ("mlp", None),
    ("down", "bias"): (None,),
    # expert-parallel only: the expert dim maps to "model"; mapping d_ff to
    # "model" as well would double-book the axis (specs must be injective)
    ("M:gate_up", "kernel"): ("experts", None, None),
    ("M:down", "kernel"): ("experts", None, None),
    ("router", "kernel"): (None, None),
    ("in_proj", "kernel"): (None, "mlp"),
    ("out_proj", "kernel"): ("mlp", None),
    ("conv_w", None): (None, "mlp"),
    ("conv_b", None): ("mlp",),
    ("gate_norm", None): ("mlp",),
    ("a_log", None): (None,),
    ("d_skip", None): (None,),
    ("dt_bias", None): (None,),
    ("tokens", None): ("vocab", None),       # embedding table
    ("codebooks", None): (None, "vocab", None),
    ("lm_head", "kernel"): (None, "vocab"),
    ("projector", "kernel"): (None, None),
}

#: Serve-mode variant of :data:`_PARAM_RULES`, selected when the active
#: rules carry the ``"__serve_params__"`` marker (see :func:`serve_rules`).
#: Every matmul weight is *column-parallel* — sharded on its OUTPUT dim —
#: so each shard computes full contractions over replicated inputs and no
#: floating-point reduction ever spans shards; combined with the forced
#: activation gathers of :func:`serve_rules` this makes tensor-parallel
#: decode bitwise identical to single-device decode (docs/distributed.md).
#: Projections back to d_model (o / down / out_proj) therefore shard on
#: d_model rather than row-parallel + psum: a psum reassociates the FP sum
#: and would break the bitwise gate. Everything not listed — norms, biases,
#: router, conv/gate_norm, embedding table, int4 carriers, input ranges —
#: falls through to P() and replicates. The embedding table is replicated
#: deliberately: a vocab-sharded gather lowers to a masked one-hot psum
#: with a -0.0 bitwise edge case.
_SERVE_PARAM_RULES = {
    ("qkv", "kernel"): (None, "heads"),
    ("q", "kernel"): (None, "heads"),
    ("k", "kernel"): (None, "heads"),
    ("v", "kernel"): (None, "heads"),
    ("o", "kernel"): (None, "heads"),        # column on d_model
    ("gate_up", "kernel"): (None, "mlp"),
    ("up", "kernel"): (None, "mlp"),
    ("down", "kernel"): (None, "mlp"),       # column on d_model
    ("M:gate_up", "kernel"): ("experts", None, None),
    ("M:down", "kernel"): ("experts", None, None),
    ("in_proj", "kernel"): (None, "mlp"),
    ("out_proj", "kernel"): (None, "mlp"),   # column on d_model
    ("lm_head", "kernel"): (None, "vocab"),
    # per-tile device state (core.devices) shards with its owning weight:
    # the tile-grid column dim [.., TK, TN] rides the same mesh axis as
    # the kernel's output dim, stuck columns [.., N] likewise; MoE expert
    # grids shard on the expert dim like their kernels
    ("device", "gain"): (None, "mlp"),
    ("device", "nu"): (None, "mlp"),
    ("device", "off"): (None, "mlp"),
    ("device", "dead"): (None, "mlp"),
    ("device", "stuck"): ("mlp",),
    ("M:device", "gain"): ("experts", None, None),
    ("M:device", "nu"): ("experts", None, None),
    ("M:device", "off"): ("experts", None, None),
    ("M:device", "dead"): ("experts", None, None),
    ("M:device", "stuck"): ("experts", None),
}


def param_spec_tree(params) -> Any:
    """PartitionSpec pytree for a model/optimizer param tree."""
    def walk(node, site: Optional[str], in_moe: bool):
        if isinstance(node, dict):
            moe_here = in_moe or ("router" in node)
            out = {}
            for k, v in node.items():
                if isinstance(v, dict):
                    out[k] = walk(v, k, moe_here)
                else:
                    out[k] = _leaf_spec(site, k, v, moe_here)
            return out
        return _leaf_spec(site, None, node, in_moe)

    return walk(params, None, False)


def _leaf_spec(site, leaf, value, in_moe) -> P:
    """PartitionSpec for one named parameter leaf (site-based rules)."""
    rules = _active()["rules"]
    table = (_SERVE_PARAM_RULES if rules.get("__serve_params__")
             else _PARAM_RULES)
    key = None
    if site is not None:
        prefixed = (f"M:{site}", leaf) if in_moe else None
        if prefixed in table:
            key = prefixed
        elif (site, leaf) in table:
            key = (site, leaf)
    if key is None and (leaf, None) in table:
        key = (leaf, None)
    if key is None and (site, None) in table:
        key = (site, None)
    if key is None:
        return P()                       # norms, scalars, input ranges
    logical = table[key]
    ndim = value.ndim if hasattr(value, "ndim") else len(value.shape)
    pad = (None,) * (ndim - len(logical))
    ctx = _active()
    axes = tuple(ctx["rules"].get(ax) if ax is not None else None
                 for ax in logical)
    axes = pad + axes
    return P(*_guard(ctx["mesh"], tuple(value.shape), axes))


def zero_spec_tree(params) -> Any:
    """Optimizer-moment specs: param spec + "data" on the first free dim
    whose size divides the data-axis size (ZeRO-1/2 sharding)."""
    ctx = _active()
    mesh = ctx["mesh"]
    dsize = mesh.shape.get("data", 1)
    specs = param_spec_tree(params)

    def upgrade(p, spec):
        if not hasattr(p, "ndim") or p.ndim == 0 or dsize == 1:
            return spec
        parts = list(spec) + [None] * (p.ndim - len(spec))
        for i, ax in enumerate(parts):
            if ax is None and p.shape[i] % dsize == 0 and p.shape[i] >= dsize:
                parts[i] = ctx["rules"].get("zero")
                break
        return P(*_guard(mesh, tuple(p.shape), tuple(parts)))

    return jax.tree.map(upgrade, params, specs)


def named(tree_specs) -> Any:
    """Resolve a tree of logical-axis tuples to PartitionSpecs."""
    ctx = _active()
    mesh = ctx["mesh"]
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------

def batch_spec(ndim: int) -> P:
    """Batch-sharded spec with ``ndim - 1`` trailing replicated dims."""
    ctx = _active()
    b = ctx["rules"].get("batch")
    return P(*((b,) + (None,) * (ndim - 1)))


def batch_spec_for(shape: tuple) -> P:
    """Like :func:`batch_spec` but guarded against non-divisible shapes."""
    ctx = _active()
    b = ctx["rules"].get("batch")
    axes = (b,) + (None,) * (len(shape) - 1)
    return P(*_guard(ctx["mesh"], shape, axes))


def cache_spec_tree(caches) -> Any:
    """Decode-cache specs: KV [B, T, KV, hd] → (batch, kv_seq, heads, None);
    paged pools kp/vp [.., P, bs, KV, hd] → heads on KV (their int8 scale
    siblings ks/vs likewise); SSM state [B, H, N, P] → (batch, ssm_heads,
    None, None); conv [B, W-1, C] → (batch, None, ssm_conv). Leading
    stacked-layer dims unsharded; block tables / cursors / snapshot pools
    fall through to P() (replicated — they are tiny and shard-agnostic,
    see serve.kv_pool)."""
    ctx = _active()
    rules = ctx["rules"]

    mesh = ctx["mesh"]
    hsize = _axis_size(mesh, rules.get("heads"))

    def leaf(path, x):
        name = str(getattr(path[-1], "key", ""))
        nd = x.ndim
        if name in ("k", "v"):
            # KV [.., B, T, KV, hd]: shard heads over "model" when the head
            # count divides; otherwise fall back to sharding the *sequence*
            # dim over "model" (kv=8/40 archs on a 16-way model axis — the
            # cache would otherwise replicate 16x and blow HBM). Softmax
            # over the sharded T axis lowers to cheap scalar all-reduces.
            # (serve_rules maps kv_seq_model to None: the fallback would
            # partial-sum the softmax and break the bitwise-TP contract.)
            kv_heads = x.shape[-2]
            if kv_heads % hsize == 0:
                logical = ("batch", "kv_seq", "heads", None)
            else:
                logical = ("batch", "kv_seq_model", None, None)
        elif name in ("kp", "vp"):
            # paged pool [.., pool, bs, KV, hd]: every shard holds
            # kv_heads/tp heads of EVERY physical block, so the host-side
            # block table / refcounts / prefix index stay shard-agnostic
            logical = (("heads", None) if x.shape[-2] % hsize == 0
                       else (None, None))
        elif name in ("ks", "vs"):
            # int8-pool scales [.., pool, bs, KV]: heads on the last dim
            logical = (("heads",) if x.shape[-1] % hsize == 0
                       else (None,))
        elif name == "ssm":
            # [.., B, H, N, P] slot-major SSM state (batch leads, heads
            # next). "ssm_heads" == "heads" under training rules; serve
            # rules replicate it (mamba internals compute replicated)
            logical = ("batch", "ssm_heads", None, None)
        elif name == "conv":
            logical = ("batch", None, "ssm_conv")
        else:
            return P()
        pad = (None,) * (nd - len(logical))
        axes = pad + tuple(rules.get(ax) if ax else None for ax in logical)
        return P(*_guard(mesh, tuple(x.shape), axes))

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf(p, x) for p, x in flat])


# ---------------------------------------------------------------------------
# tensor-parallel serving (ServeEngine, launch.serve --tp N)
# ---------------------------------------------------------------------------
# Serving shards *within* one replica: a (1, tp) mesh whose "model" axis
# carries every weight's output dim while serve_rules forces activations
# replicated at every reduction boundary. The resulting computation contains
# no cross-shard floating-point reduction — all GSPMD-inserted collectives
# are arithmetic-free data movement — so tensor-parallel greedy decode is
# bitwise identical to single-device decode (the TP parity contract,
# docs/distributed.md; tested in tests/test_tp_serve.py).

def serve_mesh(tp: int) -> Mesh:
    """A ``(1, tp)`` ("data", "model") mesh over the first ``tp`` devices."""
    devs = np.asarray(jax.devices()[:tp]).reshape(1, tp)
    return Mesh(devs, ("data", "model"))


def serve_rules(mesh: Mesh) -> dict[str, Any]:
    """Logical-axis rules for bitwise-parity tensor-parallel serving.

    Weight axes (heads/mlp/vocab/experts) shard over "model"; every
    activation-output axis (attn_out/mlp_act/moe_out/serve_act/ssm_*) maps
    to None — :func:`shard_hint` then *forces* replication, inserting the
    all-gather that keeps the next contraction local to each shard. The
    ``"__serve_params__"`` marker switches :func:`param_spec_tree` onto
    the column-parallel :data:`_SERVE_PARAM_RULES` table.
    """
    del mesh                # rules are mesh-independent; keep the signature
    return {
        "batch": None,
        "seq": None,
        "kv_seq": None,
        "kv_seq_model": None,        # never shard cache T: softmax psum
        "heads": "model",
        "mlp": "model",
        "vocab": "model",
        "experts": "model",
        "moe_buf": "model",
        "attn_out": None,
        "mlp_act": None,
        "moe_out": None,
        "serve_act": None,
        "ssm_heads": None,
        "ssm_conv": None,
        "embed": None,
        "zero": None,
        "__serve_params__": True,
    }


def serve_ctx(mesh: Optional[Mesh]):
    """Context manager activating serve-mode sharding (no-op for tp=1).

    The serving step jits take the mesh as a static argument and trace
    their bodies under this context, so every :func:`shard_hint` in the
    model resolves against :func:`serve_rules` — one executable per mesh.
    """
    if mesh is None:
        return contextlib.nullcontext()
    return activate(mesh, serve_rules(mesh))


def shard_params_for_serve(mesh: Mesh, params):
    """Commit a param tree to the serve mesh (column-parallel weights)."""
    with activate(mesh, serve_rules(mesh)):
        return jax.device_put(params, named(param_spec_tree(params)))


def shard_caches_for_serve(mesh: Mesh, caches):
    """Commit a cache tree to the serve mesh (per-shard KV heads)."""
    with activate(mesh, serve_rules(mesh)):
        return jax.device_put(caches, named(cache_spec_tree(caches)))


def serve_tp_unsupported(cfg, acfg, tp: int, *, paged: bool,
                         chunk: int) -> Optional[str]:
    """Why ``tp``-way tensor parallelism cannot serve this config, or None.

    The honest-gating seam for ``ServeEngine``: a reason string here
    becomes ``gating_reasons["tensor_parallel"]`` and the engine falls
    back to tp=1 — never a silent downgrade, never a wrong answer.
    ``paged`` says whether the engine's attention runs on the paged pool
    and ``chunk`` is its prefill chunk length: together they decide
    which routes reach a ``pallas_call``, which GSPMD cannot partition
    without ``shard_map`` wiring. Only those routes are refused.
    """
    if tp <= 1:
        return None
    n = len(jax.devices())
    if n < tp:
        return (f"tp={tp} needs {tp} devices, runtime has {n} "
                "(XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "provides host devices for CPU testing)")
    if getattr(acfg, "use_pallas", False):
        return ("Pallas kernels are single-device (pallas_call does not "
                "partition under GSPMD without shard_map wiring) — serve "
                "with use_pallas=False under tensor parallelism")
    from repro.kernels import dispatch, ops    # lazy: kernels never import us
    if dispatch.on_tpu():
        if paged:
            return ("on TPU the paged pool's attention runs the paged "
                    "flash Pallas kernels, which GSPMD cannot partition "
                    "without shard_map wiring — serve tensor-parallel on "
                    "the contiguous cache (paged=False)")
        if (cfg.family in ("ssm", "hybrid")
                and ops.ssd_uses_kernel(chunk, ops.SSD_CHUNK)):
            return (f"on TPU a prefill chunk of {chunk} tokens runs the "
                    "ssd_scan Pallas kernel, which GSPMD cannot partition "
                    "without shard_map wiring — pick a chunk that is not "
                    f"a multiple of {ops.SSD_CHUNK}")
    if cfg.family in ("dense", "moe", "hybrid"):
        kv = getattr(cfg, "num_kv_heads", 0) or cfg.num_heads
        if cfg.num_heads % tp:
            return (f"num_heads={cfg.num_heads} is not divisible by "
                    f"tp={tp} — attention heads cannot split evenly")
        if kv % tp:
            return (f"num_kv_heads={kv} is not divisible by tp={tp} — "
                    "the per-shard KV pool cannot split the head dim "
                    "evenly")
    return None
