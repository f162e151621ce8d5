"""chip_smoke.py and the compile-cache placement, as far as a machine
without a chip can check them (ISSUE 21): the smoke refuses the CPU before
building anything, its --dry-cpu walk of the same code path passes with
every line marked, and the runtime's one cache-placing function obeys
JAX_COMPILATION_CACHE_DIR. Everything runs in subprocesses: the smoke is
an entry point, and enabling the persistent cache in the pytest process
would change the compile counts other tier-1 tests assert."""

import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")
_DRY_MARK = "DRY RUN (cpu) — not a chip result"


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_refuses_the_cpu_before_building_anything(tmp_path):
    t0 = time.monotonic()
    res = subprocess.run(
        [sys.executable, _SMOKE, "--out", str(tmp_path / "out")],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=120)
    assert res.returncode not in (0, None), res.stdout
    assert time.monotonic() - t0 < 60
    assert "not a TPU" in res.stderr
    # the device line and nothing else: no phase started, no result line,
    # nothing written
    assert "[" not in res.stdout and '"ok"' not in res.stdout, res.stdout
    assert not (tmp_path / "out").exists()


def test_dry_cpu_walks_the_code_path_with_every_line_marked(tmp_path):
    res = subprocess.run(
        [sys.executable, _SMOKE, "--dry-cpu", "--only",
         "bert_train,decode_serve", "--out", str(tmp_path / "out")],
        cwd=tmp_path,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, f"{res.stdout}\n{res.stderr}"
    lines = res.stdout.splitlines()
    assert lines and all(ln.startswith(_DRY_MARK) for ln in lines), lines
    assert any("[bert_train] PASS" in ln for ln in lines)
    assert any("[decode_serve] PASS" in ln for ln in lines)
    # a dry run can never be read as the chip contract's last line
    assert not lines[-1].startswith("{")
    summary = json.loads(lines[-1].split(" | ", 1)[1])
    assert summary["ok"] is True and summary["device"]["platform"] == "cpu"


_CACHE_PROBE = """
import jax
from deeplearning4j_tpu.runtime import RuntimeConfig
print(RuntimeConfig.enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


def _cache_probe(cwd, **extra):
    res = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], cwd=cwd,
        env=_env(PYTHONPATH=_REPO, **extra), capture_output=True,
        text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    returned, configured = res.stdout.split()[-2:]
    return returned, configured


def test_compile_cache_is_placed_from_outside_or_inside_the_checkout(
        tmp_path):
    # set: the function sets nothing — jax reads the variable itself
    outside = str(tmp_path / "elsewhere")
    returned, configured = _cache_probe(
        tmp_path, JAX_COMPILATION_CACHE_DIR=outside)
    assert returned == outside and configured == outside
    # unset: one fixed path inside the checkout, whatever the cwd
    a = _cache_probe(tmp_path)
    b = _cache_probe(_REPO)
    assert a == b
    assert a[0] == a[1] == os.path.join(_REPO, ".jax_cache")
