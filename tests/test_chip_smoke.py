"""``chip_smoke.py`` rehearsed on the CPU.

The script's body runs here at a ``reduce()``d config (the paged
attention goes through its reference path and the fused/int4 kernels
through interpret mode), its tensor-parallel phase on four forced host
devices, and its refusals — no TPU, no checkout — are checked as a user
would meet them.
"""

import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

from repro.configs import get_config

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_body_at_reduced_config():
    """The float kernel check passes, and all three deployments serve
    every request to its budget and pass the logits check."""
    cs = _load()
    cfg = get_config("llama-3.2-1b").reduce()
    res = cs.smoke(cfg, num_requests=3, prompt_len=12, new_tokens=4)
    assert res["failures"] == []
    assert max(res["float_rel_err"]) < 1e-5
    deps = res["deployments"]
    assert set(deps) == {"analog_hw", "analog_hw_fused", "digital_int4"}
    for d in deps.values():
        assert d["finished"] == 3 and d["tokens"] > 0
        assert max(d["logits_rel_err"]) < 1e-3


def test_chip_smoke_tp_body_on_host_devices():
    """The --chips 4 phase on forced host devices: a real mesh, and tp
    bitwise equal to tp=1 (the CPU contract). The reduced config has two
    kv heads, so the mesh here is tp=2."""
    prog = textwrap.dedent(f"""
        import json, os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.configs import get_config
        res = cs.smoke_tp(get_config("llama-3.2-1b").reduce(), tp=2,
                          num_requests=3, prompt_len=12, new_tokens=4)
        print(json.dumps(dict(failures=res["failures"],
                              same=res["tokens_identical"],
                              bitwise=res["bitwise_logits"],
                              mesh=res["runs"][2]["mesh"])))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["failures"] == []
    assert rec["mesh"] == [0, 1]
    assert rec["same"] == 3 and rec["bitwise"] is True


def _run_script(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu():
    """No accelerator: non-zero exit naming the platform, no result."""
    out = _run_script(ROOT, ROOT / "chip_smoke.py")
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_refuses_outside_checkout(tmp_path):
    """The script alone is not the program: it fails, with no result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run_script(tmp_path, tmp_path / "chip_smoke.py")
    assert out.returncode != 0
    assert "checkout" in out.stderr
    assert '"ok"' not in out.stdout
