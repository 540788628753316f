"""Distribution-layer tests.

The in-process tests exercise spec construction logic; the subprocess test
forces 8 host devices and runs a REAL sharded train step + elastic reshard
(jax locks device count at init, hence the subprocess).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import auto_mesh


def _mesh11():
    return auto_mesh((1, 1), ("data", "model"))


def test_param_specs_sites():
    mesh = _mesh11()
    rules = shd.default_rules(mesh)
    params = {
        "blocks": {
            "attn": {"qkv": {"kernel": jnp.zeros((4, 8, 16)),
                             "input_range": jnp.zeros((4, 1))},
                     "o": {"kernel": jnp.zeros((4, 16, 8))}},
            "ffn": {"router": {"kernel": jnp.zeros((4, 8, 4))},
                    "gate_up": {"kernel": jnp.zeros((4, 2, 8, 32)),
                                "input_range": jnp.zeros((4, 1))},
                    "down": {"kernel": jnp.zeros((4, 2, 16, 8))}}},
        "embed": {"tokens": jnp.zeros((256, 8))},
        "lm_head": {"kernel": jnp.zeros((8, 256))},
    }
    with shd.activate(mesh, rules):
        specs = shd.param_spec_tree(params)
    assert specs["blocks"]["attn"]["qkv"]["kernel"] == P(None, None, "model")
    assert specs["blocks"]["attn"]["o"]["kernel"] == P(None, "model", None)
    assert specs["blocks"]["attn"]["qkv"]["input_range"] == P()
    # MoE detected via sibling router: expert-parallel only (injective spec)
    assert specs["blocks"]["ffn"]["gate_up"]["kernel"] == \
        P(None, "model", None, None)
    assert specs["blocks"]["ffn"]["router"]["kernel"] == P(None, None, None)
    assert specs["embed"]["tokens"] == P("model", None)
    assert specs["lm_head"]["kernel"] == P(None, "model")


def test_divisibility_guard_drops_axes():
    mesh = AbstractMesh((1, 2), ("data", "model"))
    rules = shd.default_rules(mesh)
    with shd.activate(mesh, rules):
        # 7 not divisible by model=2 → replicated
        spec = shd._leaf_spec("qkv", "kernel", jnp.zeros((4, 7)), False)
        assert spec == P(None, None)
        spec2 = shd._leaf_spec("qkv", "kernel", jnp.zeros((4, 8)), False)
        assert spec2 == P(None, "model")


def test_zero_spec_upgrades_free_dim():
    mesh = AbstractMesh((2, 1), ("data", "model"))
    rules = shd.default_rules(mesh)
    params = {"w": jnp.zeros((8, 6))}
    with shd.activate(mesh, rules):
        z = shd.zero_spec_tree(params)
    assert z["w"] == P("data", None)


def test_shard_hint_noop_without_context():
    x = jnp.ones((4, 4))
    y = shd.shard_hint(x, "batch", None)
    assert y is x


def test_serve_param_specs_column_parallel():
    """Serve table (``serve_rules``): every kernel shards its OUTPUT dim
    on "model" (column-parallel — no FP contraction ever spans shards);
    the router, input ranges, and the embedding table replicate; MoE
    experts shard on the expert dim."""
    mesh = AbstractMesh((1, 2), ("data", "model"))
    params = {
        "blocks": {
            "attn": {"qkv": {"kernel": jnp.zeros((4, 8, 16)),
                             "input_range": jnp.zeros((4, 1))},
                     "o": {"kernel": jnp.zeros((4, 16, 8))}},
            "ffn": {"router": {"kernel": jnp.zeros((4, 8, 4))},
                    "gate_up": {"kernel": jnp.zeros((4, 2, 8, 32)),
                                "input_range": jnp.zeros((4, 1))},
                    "down": {"kernel": jnp.zeros((4, 2, 16, 8))}}},
        "embed": {"tokens": jnp.zeros((256, 8))},
        "lm_head": {"kernel": jnp.zeros((8, 256))},
    }
    with shd.activate(mesh, shd.serve_rules(mesh)):
        specs = shd.param_spec_tree(params)
    assert specs["blocks"]["attn"]["qkv"]["kernel"] == P(None, None, "model")
    # column-parallel o (train shards its INPUT): output dim on "model"
    assert specs["blocks"]["attn"]["o"]["kernel"] == P(None, None, "model")
    assert specs["blocks"]["attn"]["qkv"]["input_range"] == P()
    # MoE: expert-parallel kernels, replicated router (it feeds top-k)
    assert specs["blocks"]["ffn"]["gate_up"]["kernel"] == \
        P(None, "model", None, None)
    assert specs["blocks"]["ffn"]["down"]["kernel"] == \
        P(None, "model", None, None)
    assert specs["blocks"]["ffn"]["router"]["kernel"] == P()
    # embedding replicates (one-hot gather stays local); LM head is
    # vocab-column-parallel
    assert specs["embed"]["tokens"] == P()
    assert specs["lm_head"]["kernel"] == P(None, "model")


def test_serve_param_specs_moe_real_config():
    """The serve table resolves on a REAL reduced MoE param tree (dbrx)
    with no exceptions and shards every analog kernel's output dim."""
    import dataclasses as dc

    from repro.models import build
    cfg = get_config("dbrx-132b").reduce()
    cfg = dc.replace(cfg, capacity_factor=float(cfg.num_experts))
    cfg, params, labels = build(cfg, jax.random.PRNGKey(0))
    mesh = AbstractMesh((1, 2), ("data", "model"))
    with shd.activate(mesh, shd.serve_rules(mesh)):
        specs = shd.param_spec_tree(params)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    sharded = [p for p, s in flat if "model" in tuple(s)]
    assert sharded, "no leaf sharded on the serve mesh"
    for path, spec in flat:
        keys = [str(getattr(k, "key", "")) for k in path]
        if keys[-1] in ("scale", "bias") or "norm" in " ".join(keys):
            assert spec == P(), (keys, spec)


def test_cache_spec_tree_paged_and_snapshot_layouts():
    """Paged pools shard kv_heads per device; non-divisible head counts,
    block tables, cursors, and snapshot pools replicate. Under serve
    rules SSM/conv state replicates (mamba computes replicated); under
    training rules it shards heads/channels."""
    mesh = AbstractMesh((1, 2), ("data", "model"))
    caches = {
        "kp": jnp.zeros((8, 16, 4, 16), jnp.int8),   # pool,bs,KV,hd
        "vp": jnp.zeros((8, 16, 3, 16), jnp.int8),   # KV=3: not divisible
        "ks": jnp.zeros((8, 16, 4)),
        "k": jnp.zeros((2, 10, 4, 16)),
        "ssm": jnp.zeros((2, 4, 8, 16)),
        "conv": jnp.zeros((2, 3, 8)),
        "block_tbl": jnp.zeros((2, 4), jnp.int32),
        "snap_pool": jnp.zeros((4, 8, 16)),
    }
    with shd.activate(mesh, shd.serve_rules(mesh)):
        specs = shd.cache_spec_tree(caches)
    assert specs["kp"] == P(None, None, "model", None)
    assert tuple(specs["vp"]) == (None,) * 4   # honest fallback: replicate
    assert specs["ks"] == P(None, None, "model")
    assert specs["k"] == P(None, None, "model", None)
    # serve rules replicate SSM internals (bitwise-parity contract)
    assert not any(tuple(specs["ssm"]))
    assert not any(tuple(specs["conv"]))
    assert specs["block_tbl"] == P()   # host-side, shard-agnostic
    assert specs["snap_pool"] == P()   # snapshot pool rides along whole
    with shd.activate(mesh, shd.default_rules(mesh)):
        tspecs = shd.cache_spec_tree(caches)
    assert tspecs["ssm"][1] == "model"
    assert tspecs["conv"][2] == "model"


def test_shrink_batch_plan():
    from repro.distributed.elastic import shrink_batch_plan
    assert shrink_batch_plan(256, 16, 8) == (32, 1)
    per_dev, accum = shrink_batch_plan(96, 16, 12)
    assert per_dev * 12 * accum == 96
    with pytest.raises(ValueError):
        shrink_batch_plan(256, 16, 12)   # 3 ∤ 256: no exact re-split


_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from repro.configs import get_config
    from repro.core.analog import AnalogConfig
    from repro.distributed import sharding as shd
    from repro.distributed.elastic import reshard
    from repro.models import build
    from repro.optim.schedule import polynomial_with_warmup
    from repro.train.train_step import (TrainConfig, init_train_state,
                                        make_train_step)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((4, 2), ("data", "model"))
    rules = shd.default_rules(mesh)
    cfg = get_config("granite-3-8b").reduce()
    key = jax.random.PRNGKey(0)
    cfg, params, labels = build(cfg, key)

    acfg = AnalogConfig(mode="analog", init_steps=2)
    tcfg = TrainConfig(peak_lr=1e-3, total_steps=8, kd_beta=0.0,
                       ce_weight=1.0, remat=True)
    lr = lambda s: polynomial_with_warmup(s, peak_lr=1e-3, total_steps=8)

    with shd.activate(mesh, rules):
        p_specs = shd.zero_spec_tree(params)
        p_sh = shd.named(p_specs)
        params = jax.tree.map(jax.device_put, params, p_sh)
        state = init_train_state(params)
        step = jax.jit(make_train_step(cfg, acfg, tcfg, labels, lr))
        toks = jax.random.randint(key, (8, 16), 0, cfg.vocab_size)
        toks = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
        batch = {"tokens": toks, "labels": toks}
        losses = []
        for i in range(3):
            params, state, m = step(params, state, batch, key)
            losses.append(float(m["loss"]))

    # elastic: shrink data axis 4 -> 2 (device loss), values must be intact
    small = auto_mesh((2, 2), ("data", "model"))
    before = [np.asarray(x) for x in jax.tree.leaves(params)]
    params2 = reshard(params, small)
    after = [np.asarray(x) for x in jax.tree.leaves(params2)]
    exact = all(np.array_equal(a, b) for a, b in zip(before, after))

    # resume training on the shrunk mesh (batch re-split over the new
    # data axis, exactly what the elastic controller does on restart)
    with shd.activate(small, shd.default_rules(small)):
        state2 = jax.tree.map(jax.device_put, state,
                              shd.named(jax.tree.map(lambda t: P(), state)))
        batch2 = {k: jax.device_put(np.asarray(v),
                                    NamedSharding(small, P("data", None)))
                  for k, v in batch.items()}
        key2 = jax.device_put(np.asarray(key), NamedSharding(small, P()))
        step2 = jax.jit(make_train_step(cfg, acfg, tcfg, labels, lr))
        params2, state2, m2 = step2(params2, state2, batch2, key2)

    print(json.dumps({"losses": losses, "exact": exact,
                      "resumed_loss": float(m2["loss"]),
                      "devices": len(jax.devices())}))
""")


@pytest.mark.slow
def test_sharded_train_and_elastic_reshard_subprocess():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_PROG], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 8
    assert rec["exact"] is True
    assert np.isfinite(rec["resumed_loss"])
    assert rec["losses"][-1] < rec["losses"][0] + 0.5
