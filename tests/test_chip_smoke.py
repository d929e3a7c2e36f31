"""``chip_smoke.py`` off the chip: without a TPU it fails at once and
prints no result, and its one-chip phases run end to end at a tiny size
on the CPU (Pallas interpreted), so a change that breaks the smoke shows
here before any chip run."""

import importlib.util
import json
import os
import subprocess
import sys

import jax

from repro.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_one_chip_phases_at_tiny_size(monkeypatch, capsys):
    smoke = _load_chip_smoke()
    for name, rows in (("FULL_ROWS", 1 << 12), ("FAMILY_ROWS", 3000),
                       ("SERVE_ROWS", 100), ("MOSAIC_ROWS", 1 << 13)):
        monkeypatch.setattr(smoke, name, rows)
    monkeypatch.setattr(smoke, "require_tpu", lambda: (jax, jax.devices()))
    monkeypatch.setattr(smoke, "require_mosaic", lambda kernel, text: None)
    monkeypatch.setattr(compile_cache, "enable", lambda: "off")
    assert smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": 1}}
    phases = [json.loads(line)["phase"] for line in lines[:-1]]
    assert phases.count("family") == 8 and phases.count("mosaic") == 2
    assert {"start", "full_memory", "serve", "end"} <= set(phases)
