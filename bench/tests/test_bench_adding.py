"""A configuration is added as files and entries in ``BENCHMARK.json``
alone: its own rows, reference, kernel parameters, driver, traffic,
limits and metric, in a copy of the benchmark in which no file that was
there is edited; and a ``sharded`` configuration gets its mesh over the
cell's own devices. Each runs in a process of its own, with the harness
imported from the copy or with four CPU devices."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

from harness.cells import BENCH, ROOT

ROWS = '''
"""d = 28 rows, another target: sin(x.w) + 0.1 noise."""
import math
from functools import partial

import jax
import jax.numpy as jnp

from harness.data import seed31


@partial(jax.jit, static_argnums=(1, 2, 3))
def _rows(key, n, m, d):
    kx, kw, ke = jax.random.split(key, 3)
    X = jax.random.normal(kx, (n + m, d), jnp.float32)
    w = jax.random.normal(kw, (d,), jnp.float32) / math.sqrt(d)
    y = jnp.sin(X @ w) + 0.1 * jax.random.normal(ke, (n + m,), jnp.float32)
    return X[:n], y[:n], X[n:], y[n:]


def make(config, seed):
    return _rows(jax.random.key(seed31(seed)), config["n_train"],
                 config["n_test"], config["d"])
'''

REFERENCE = '''
"""The RBF reference, under a name of this configuration's own."""
from harness.reference import (KERNELS, max_gap, rel_rms,  # noqa: F401
                               scores_and_predictions)
'''

DRIVER = '''
"""One fit in the window, timed; its predictions compared."""
import time

import jax
import numpy as np

from harness.drivers import CellDriver


class Driver(CellDriver):
    def setup(self):
        self._data()

    def window(self, seconds):
        from repro.api import SketchedKRR
        t0 = time.perf_counter()
        self.model = SketchedKRR(self.cfg).fit(self.X, self.y)
        jax.block_until_ready(self.model.state().beta)
        return {"one_fit_s": time.perf_counter() - t0, "attempted": 1,
                "failed": 0}

    def counters(self):
        return {}

    def check(self):
        pred = np.asarray(self.model.predict(self.Xt))
        idx = np.asarray(self.model.sample().idx)
        self.model = None
        _, want = self._reference_parts(idx, self.cfg.seed)
        return {"pred_rel_rms": self.ref.rel_rms(pred, want)}
'''

METRIC = '''
def read(run):
    return None
'''

CONFIG = {
    "name": "wide28", "source": "https://arxiv.org/abs/1705.10958",
    "d": 28, "n_train": 4096, "n_test": 512, "kernel": "rbf",
    "rows": "sin28", "bandwidth": 5.0, "p": 128, "lam": 1e-6,
    "solver": "falkon_pcg", "sampler": "rls_fast", "p_scores": 128,
    "eps": 0.5, "backend": "auto", "reduced": [],
    "reference": "bench/refs/wide28.py",
}


def _digests(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def _python(script, cwd, extra_env=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"), **(extra_env or {}))
    return subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_a_configuration_is_added_as_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)
    bench = tmp_path / "bench"
    new = {"rows/sin28.py": ROWS, "refs/wide28.py": REFERENCE,
           "drivers/fit_once.py": DRIVER, "metrics/fit_once_note.py": METRIC,
           "configs/wide28.json": json.dumps(CONFIG),
           "traffic/fit_once.json": json.dumps({"driver": "fit_once"}),
           "limits/wide28.fit_once.json": json.dumps({
               "checks": {"pred_rel_rms": {"limit": 0.02}},
               "control": {"precision": {"data_dtype": "bfloat16"}}})}
    for name, text in new.items():
        (bench / name).parent.mkdir(exist_ok=True)
        (bench / name).write_text(text)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "wide28", "source": CONFIG["source"],
                            "file": "bench/configs/wide28.json",
                            "reduced": [], "why": "a test's configuration"})
    spec["workloads"].append({"name": "wide28.fit_once", "config": "wide28",
                              "traffic": "fit_once", "chips": 1,
                              "why": "one fit"})
    spec["end_to_end"].append({"name": "one_fit_s", "unit": "s",
                               "better": "lower", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["wide28.fit_once"]})
    spec["per_layer"].append({"name": "fit_once_note", "unit": "s",
                              "better": "lower", "source": "program_span",
                              "layer": "solver", "moves": "one_fit_s",
                              "workloads": ["wide28.fit_once"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    out = _python("""
        import json, sys
        from pathlib import Path
        sys.path[:0] = ["bench", "bench/tests"]
        from bench_tiny import run_tiny
        from harness.cells import ROOT, check_pieces, load_cell
        assert ROOT == Path.cwd().resolve(), ROOT    # the copy's harness
        check_pieces(load_cell("wide28.fit_once"))
        print(json.dumps(run_tiny("wide28.fit_once",
                                  size=dict(n_train=4096))))
        """, tmp_path)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "one_fit_s"}
    assert set(res["checks"]) == {"pred_rel_rms"}
    after = _digests(tmp_path)
    edited = [p for p in before if before[p] != after.get(p)]
    assert edited == ["BENCHMARK.json"]


def test_sharded_backend_meshes_the_cells_devices():
    """On four CPU devices, a ``sharded`` configuration's executor spans
    exactly the devices the driver is given, and a whole run of the fit
    cell on them is correct."""
    out = _python(f"""
        import dataclasses, json, sys, time
        sys.path[:0] = [{str(BENCH)!r}, {str(BENCH / "tests")!r}]
        import jax
        from bench_tiny import tiny_cell
        from harness.drivers import sketch_config
        from harness.main import peak_for, run
        from repro.core.backends import ops_for_config
        devices = jax.devices()
        assert len(devices) == 4, devices
        config = dict(tiny_cell("susy_falkon.fit").config, backend="sharded")
        for k in (1, 2, 4):
            cfg = sketch_config(config, 3, devices=devices[:k])
            assert list(ops_for_config(cfg).mesh().devices.flat) \\
                == devices[:k], k
        cell = dataclasses.replace(tiny_cell("susy_falkon.fit"),
                                   config=config, chips=4)
        res = run(cell, 11, 0.5, False, time.perf_counter(), devices,
                  peak_for("TPU v5 lite"))
        print(json.dumps(res))
        """, ROOT, {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
