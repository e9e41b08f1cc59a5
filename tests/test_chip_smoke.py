"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and
its phases (fit, reference check, serve, sharded comparison) pass at a
tiny size with the platform checks steered in the test."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke as cs  # noqa: E402


def test_refuses_cpu_before_any_work(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, cwd=tmp_path, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


class _Dev:
    platform, device_kind = "tpu", "cpu-stand-in"

    def memory_stats(self):
        return {"peak_bytes_in_use": 0}


@pytest.fixture
def tiny(monkeypatch):
    """chip_smoke at a tiny size, with the pallas executor interpreting
    on the CPU in place of ``auto``'s TPU resolution."""
    for name, value in dict(N_TRAIN=2048, N_TEST=256, P=64, REF_BLOCK=512,
                            N_REQUESTS=64, BUCKET=16).items():
        monkeypatch.setattr(cs, name, value)
    config = cs.config
    monkeypatch.setattr(cs, "config", lambda **kw: config(
        **dict(kw, backend="pallas" if kw["backend"] == "auto"
               else kw["backend"])))
    monkeypatch.setattr(cs, "assert_tpu_kernel", lambda name, text: None)
    monkeypatch.setattr(jax.config, "update", lambda *a: None)  # no cache
    X, y = cs.higgs_like(cs.N_TRAIN + cs.N_TEST, 0)
    return X[:cs.N_TRAIN], y[:cs.N_TRAIN], X[cs.N_TRAIN:], y[cs.N_TRAIN:]


def test_one_chip_phases(tiny, capsys):
    X, y, Xt, yt = tiny
    cs.one_chip(X, y, Xt, yt, _Dev())
    out = capsys.readouterr().out
    assert "answers match model.predict" in out
    assert "deviation from the f32 reference" in out


def test_main_prints_the_json_line_last(tiny, monkeypatch, capsys):
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    monkeypatch.setattr(cs, "one_chip", lambda *a: None)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert cs.main() == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "cpu-stand-in", "count": 1}}
