"""chip_smoke.py's CPU rehearsal, and the rule it depends on: one process
for each chip, so importing the package must not initialise a backend."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args):
    # four virtual devices: the fewest that run the four-chip phases
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"),
                           *args], cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


def test_chip_smoke_tiny_rehearses_every_phase_on_cpu():
    r = _smoke("--tiny")
    assert r.returncode == 0, r.stderr[-3000:]
    report, result = map(json.loads, r.stdout.strip().splitlines())
    # the result line carries these keys and no others
    assert result == {"ok": True, "device": {
        "platform": "cpu", "kind": result["device"]["kind"], "count": 4}}
    assert isinstance(result["device"]["kind"], str)
    assert set(report["report"]["phases"]) == {
        "init", "serve", "agree", "train", "publish", "kernels",
        "fsdp4_train", "fleet4_serve"}


def test_chip_smoke_needs_a_tpu_unless_tiny():
    r = _smoke()
    assert r.returncode != 0
    assert r.stdout.strip() == ""        # no result line without a chip
    assert "--tiny" in r.stderr


def test_importing_any_subpackage_initialises_no_backend():
    # With a platform that does not exist, the first backend
    # initialisation raises — so every import succeeding IS the check.
    code = (
        "import importlib, pkgutil, senweaver_ide_tpu as pkg\n"
        "for m in pkgutil.walk_packages(pkg.__path__, pkg.__name__ + '.'):\n"
        "    if not m.name.endswith('__main__'):\n"
        "        importlib.import_module(m.name)\n")
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
