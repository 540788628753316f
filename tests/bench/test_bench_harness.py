"""The harness end to end at a small size on the CPU: a sound run is
correct, a served token altered where it is produced is not, the fp8
control is not, and a run without a chip or a program prints no result."""

import copy
import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import model, reference, run, traffic  # noqa: E402

BENCH = run.load_benchmark()
CHAT = run.find_cell(BENCH, "phi3.analog.chat")
LIMITS = run.load_limits("phi3.analog.chat")


def small_chat():
    """The chat cell's files at a size the CPU serves in seconds."""
    conf = model.load_config(CHAT["config"])
    conf.update(hidden_size=128, intermediate_size=256,
                num_attention_heads=4, num_key_value_heads=4,
                num_hidden_layers=2, vocab_size=512)
    mix = copy.deepcopy(traffic.load_mix(CHAT["traffic"]))
    mix["arrivals"]["rate_per_s"] = 8.0
    mix["prompt_tokens"].update(median=24, min=8, max=64)
    mix["output_tokens"].update(median=12, min=4, max=32)
    mix["engine"].update(num_slots=4, max_len=96)
    return conf, mix


def run_small(seed=2 ** 33 + 3):
    conf, mix = small_chat()
    limits = dict(LIMITS, min_checked_tokens=20)
    return run.run_cell(BENCH, CHAT, conf, mix, limits, seed, 3.0,
                        trace=False)


def test_sound_run_is_correct():
    result, notes = run_small()
    assert result["correct"], notes
    assert result["attempted"] == 24 and result["failed"] == 0
    assert set(result["metrics"]) == {"tpot_p90_ms", "setup_s"}
    assert list(result)[-1] == "compared"
    assert notes[-1].startswith("compared gap_ratio ")


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serve.scheduler import ServeEngine
    produce = ServeEngine._append_token

    def altered(self, b, tok):
        return produce(self, b, (tok + 1) % self.cfg.vocab_size)

    monkeypatch.setattr(ServeEngine, "_append_token", altered)
    result, notes = run_small()
    assert not result["correct"], notes
    assert result["compared"]["gap_ratio"]["value"] > LIMITS["gap_ratio"]


@pytest.mark.parametrize("precision,correct", [("highest", True),
                                               ("fp8", False)])
def test_control_is_not_correct(precision, correct):
    """The fp8 control put in the program's place serves its own greedy
    tokens: its gap ratio is 1 by construction and fails the cell's
    limit. The reference in that place reads 0 and passes."""
    conf, mix = small_chat()
    spec = model.spec_of(conf)
    params = model.make_params(model.key_of(11), spec)
    served = []
    for it in traffic.schedule(mix, spec.vocab, 11, 3.0)[:8]:
        seq = list(it.prompt)
        for _ in range(16):
            padded = np.zeros(mix["engine"]["max_len"], np.int32)
            padded[:len(seq)] = seq
            logits = reference.logits_at(
                params, jnp.asarray(padded), jnp.int32(len(seq)),
                jnp.asarray([len(seq) - 1]), spec=spec, precision=precision)
            seq.append(int(np.asarray(logits)[0].argmax()))
        served.append((it.prompt, np.asarray(seq[len(it.prompt):])))
    out = reference.check_requests(params, spec, served,
                                   pad_to=mix["engine"]["max_len"],
                                   rows_to=64)
    assert out["control_gap_mean"] > 0
    assert out["gap_ratio"] == (0.0 if correct else pytest.approx(1.0))
    ok, _ = run.compare(out, LIMITS)
    assert ok is correct


def _program_vs_reference(name, float_model):
    """Relative L2 gap of whole-sequence logits: the program's
    ``models.apply`` (no cache) against the reference, at a small size."""
    import argparse

    from repro.core.analog import AnalogConfig, AnalogCtx
    from repro.launch.serve import deploy_model
    from repro.models import apply as model_apply

    conf = model.load_config(name)
    conf.update(hidden_size=256, intermediate_size=512,
                num_attention_heads=4, num_hidden_layers=2, vocab_size=300,
                num_key_value_heads=2 if conf["tie_word_embeddings"] else 4)
    spec = model.spec_of(conf)
    key = model.key_of(5)
    params = model.make_params(key, spec)
    cfg = run.program_config(spec)
    served, acfg = deploy_model(argparse.Namespace(deploy=spec.deploy), cfg,
                                params, None, key)
    if float_model:
        spec = dataclasses.replace(spec, mode="off")
        served, acfg = params, AnalogConfig(mode="off")
    toks = np.random.default_rng(0).integers(0, spec.vocab, 24)
    with jax.default_matmul_precision("highest"):
        got, _, _ = model_apply(served, cfg, acfg,
                                AnalogCtx(key=None, training=False),
                                {"tokens": jnp.asarray(toks)[None]})
    ref = reference.logits_at(params, jnp.asarray(toks, jnp.int32),
                              jnp.int32(24), jnp.arange(24), spec=spec,
                              precision="highest")
    got, ref = np.asarray(got[0]), np.asarray(ref)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


@pytest.mark.parametrize("name", ["phi3-mini-4k", "granite-3-8b"])
def test_reference_matches_the_program(name):
    # Float model: the reference rounds keys and values to the stored
    # bfloat16 (about 0.4% each), the program's no-cache path does not;
    # that alone measures 0.35%. A wrong layout, rotation or scale errs
    # by order 1.
    assert _program_vs_reference(name, float_model=True) < 0.02
    # Deployed: 8-bit DAC and ADC turn that difference, and the program's
    # ADC tie-break, into whole quantization levels that grow from layer
    # to layer (measured 0.08-0.10 here); still far from order 1.
    assert _program_vs_reference(name, float_model=False) < 0.25


def _bench_subprocess(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CHAT["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    proc = _bench_subprocess(ROOT, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_no_program_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _bench_subprocess(tmp_path, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no program" in proc.stderr
