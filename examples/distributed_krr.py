"""Distributed KRR end-to-end on an 8-device mesh (run standalone):

    PYTHONPATH=src python examples/distributed_krr.py

The whole pipeline is one estimator now, and since PR 3 the whole fit AND
serve are SPMD: ``backend="sharded"`` row-shards every kernel touch over
``mesh_shape`` devices with only p-sized collectives (the Theorem-4 score
pass psums one p×p Gram), ``inner_backend`` picks the per-shard executor
(xla | pallas tiles | streaming row-chunks), and ``solver="distributed"``
runs the shard_map leverage factor + p×p-collective Woodbury solve on the
same executor. Nothing n×n is ever built, and the sampler's score pass no
longer falls back to one device. The FALKON-style preconditioned-CG
upgrade reuses the fitted state's row-sharded Nyström factor as its
preconditioner (its exact-K matvec is the one all-gathering step).
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import sys
sys.path.insert(0, "src")

import jax
jax.config.update("jax_enable_x64", True)
from repro.compile_cache import enable_compile_cache
enable_compile_cache()
import jax.numpy as jnp
import numpy as np

from repro.api import SketchConfig, SketchedKRR
from repro.core import RBFKernel, empirical_risk, ops_for_config
from repro.core.distributed import distributed_pcg_krr
from repro.data import gas_sensor_like

n, p = 4096, 256
data = gas_sensor_like(n, seed=0)
X = jnp.asarray(data["x"])
y = jnp.asarray(data["y"])
f_star = jnp.asarray(data["f_star"])
ker = RBFKernel(bandwidth=float(np.sqrt(X.shape[1])))
lam = 1e-3
n_dev = len(jax.devices())
print(f"mesh: {{'data': {n_dev}}} over {n_dev} devices")

# leverage-sampled landmarks + sharded score pass + distributed
# factor/solve, one fit call — every kernel block SPMD over the mesh
config = SketchConfig(kernel=ker, p=p, lam=lam, sampler="rls_fast",
                      solver="distributed", seed=0, backend="sharded",
                      mesh_shape=n_dev, inner_backend="auto")
model = SketchedKRR(config).fit(X, y)
state = model.state()
print(f"distributed d_eff estimate: {float(state.d_eff):.1f}")

pred_nys = model.predict_train()
print(f"Nyström-KRR train risk:  "
      f"{float(empirical_risk(pred_nys, f_star)):.5f}")

# FALKON-style preconditioned CG — exact KRR solve, per-shard inner-
# executor matvec, preconditioned by the already-fitted row-sharded
# factor B (mesh/inner settings mirror the estimator's config)
pcg = distributed_pcg_krr(ker, X, y, lam, state.approx.F, n_dev, iters=30,
                          inner_backend=config.inner_backend)
print(f"PCG residual: first={float(pcg.residual_norms[0]):.2e} "
      f"last={float(pcg.residual_norms[-1]):.2e} (30 iters)")
# f̂ = Kα evaluated through the sharded executor's implicit matvec —
# never materializes the n×n Gram, rows stay on their shard
pred = ops_for_config(config).matvec(X, X, pcg.alpha)
print(f"PCG-KRR train risk:      "
      f"{float(empirical_risk(pred, f_star)):.5f}")
