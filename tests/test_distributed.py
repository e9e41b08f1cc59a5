"""Distributed (shard_map) core + sharding-rule tests.

Runs in a subprocess with 8 fake devices — the main pytest process must
keep seeing 1 device (conftest note).
"""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestDistributedRLS:
    def test_matches_single_device(self):
        code = textwrap.dedent("""
            import jax, json
            jax.config.update("jax_enable_x64", True)
            import jax.numpy as jnp, numpy as np
            from repro.core import RBFKernel, fast_ridge_leverage_from_columns
            from repro.core.kernels import kernel_columns
            from repro.core.distributed import (data_mesh,
                distributed_fast_leverage, distributed_nystrom_krr,
                distributed_pcg_krr)
            from repro.core import krr_fit, gram_matrix, woodbury_solve
            n, d, p = 512, 5, 96
            X = jax.random.normal(jax.random.key(0), (n, d))
            ker = RBFKernel(2.0); lam = 1e-3
            mesh = data_mesh()
            idx = jax.random.choice(jax.random.key(1), n, (p,), replace=True)
            res = distributed_fast_leverage(ker, X, X[idx], lam, mesh)
            ref = fast_ridge_leverage_from_columns(
                kernel_columns(ker, X, idx), idx, lam, n)
            ok1 = bool(np.allclose(res.scores, ref, atol=1e-8))
            y = jnp.sin(3*X[:,0])
            alpha = distributed_nystrom_krr(res.B, y, lam, mesh)
            ok2 = bool(np.allclose(alpha, woodbury_solve(res.B, n*lam, y),
                                   atol=1e-8))
            pcg = distributed_pcg_krr(ker, X, y, lam, res.B, mesh, iters=25)
            exact = krr_fit(gram_matrix(ker, X), y, lam)
            ok3 = float(jnp.max(jnp.abs(pcg.alpha - exact))) < 1e-8
            print(json.dumps({"rls": ok1, "woodbury": ok2, "pcg": ok3}))
        """)
        res = json.loads(run_with_devices(code).strip().splitlines()[-1])
        assert res == {"rls": True, "woodbury": True, "pcg": True}


class TestShardingRules:
    def test_param_specs_divisibility(self):
        code = textwrap.dedent("""
            import jax, json, numpy as np
            from repro.configs import get_config
            from repro.launch.mesh import make_mesh
            from repro.launch.specs import abstract_params
            from repro.runtime.shardings import param_shardings
            mesh = make_mesh((2, 4), ("data", "model"))
            bad = []
            for arch in ["chatglm3-6b", "deepseek-moe-16b", "mamba2-780m",
                         "zamba2-7b", "musicgen-medium"]:
                cfg = get_config(arch)
                pa = abstract_params(cfg)
                sh = param_shardings(pa, mesh)
                for (pth, leaf), (_, s) in zip(
                        jax.tree_util.tree_flatten_with_path(pa)[0],
                        jax.tree_util.tree_flatten_with_path(sh)[0]):
                    spec = s.spec
                    for dim, ax in enumerate(spec):
                        if ax is None: continue
                        axes = (ax,) if isinstance(ax, str) else ax
                        size = 1
                        for a in axes: size *= mesh.shape[a]
                        if leaf.shape[dim] % size:
                            bad.append((arch, str(pth), dim))
            print(json.dumps({"bad": bad}))
        """)
        res = json.loads(run_with_devices(code).strip().splitlines()[-1])
        assert res["bad"] == []

    def test_elastic_mesh_resize(self):
        code = textwrap.dedent("""
            import jax, json
            from repro.runtime import elastic_mesh
            m8 = elastic_mesh(8, model_parallel=2)
            m6 = elastic_mesh(6, model_parallel=2)
            print(json.dumps({"m8": dict(m8.shape), "m6": dict(m6.shape)}))
        """)
        res = json.loads(run_with_devices(code).strip().splitlines()[-1])
        assert res["m8"] == {"data": 4, "model": 2}
        assert res["m6"] == {"data": 3, "model": 2}

    def test_train_step_shards_and_runs(self):
        """End-to-end: jit train step with explicit shardings on 8 devices."""
        code = textwrap.dedent("""
            import jax, json
            import jax.numpy as jnp
            from tests_helpers import small_cfg_for
            from repro.models import init_model
            from repro.optim import AdamWConfig
            from repro.runtime import (init_train_state, make_train_step,
                                       param_shardings, data_shardings)
            from repro.launch.mesh import make_mesh
            cfg = small_cfg_for("phi4-mini-3.8b")
            mesh = make_mesh((2, 4), ("data", "model"))
            with jax.set_mesh(mesh):
                params = init_model(cfg, jax.random.key(0))
                params = jax.device_put(params,
                                        param_shardings(params, mesh))
                opt, comp = init_train_state(cfg, params)
                toks = jax.random.randint(jax.random.key(1), (8, 65), 0,
                                          cfg.vocab_size)
                batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
                batch = jax.device_put(batch, data_shardings(batch, mesh))
                step = jax.jit(make_train_step(cfg, AdamWConfig()))
                out = step(params, opt, comp, batch)
                out2 = step(out.params, out.opt_state, out.comp_state, batch)
                print(json.dumps({
                    "loss0": float(out.metrics["loss"]),
                    "loss1": float(out2.metrics["loss"])}))
        """)
        helper = textwrap.dedent("""
            import dataclasses
            from repro.configs import get_config
            def small_cfg_for(name):
                cfg = get_config(name)
                return dataclasses.replace(
                    cfg, n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
                    head_dim=32, d_ff=256, vocab_size=512,
                    vocab_pad_multiple=128, dtype="float32")
        """)
        os.makedirs("/tmp/repro_test_helpers", exist_ok=True)
        with open("/tmp/repro_test_helpers/tests_helpers.py", "w") as f:
            f.write(helper)
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   PYTHONPATH=os.path.join(REPO, "src")
                   + ":/tmp/repro_test_helpers")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=480)
        assert out.returncode == 0, out.stderr[-3000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["loss1"] < res["loss0"]
