"""Out-of-core fit: KRR from a memory-mapped ``.npy`` bigger than any chunk.

    PYTHONPATH=src python examples/out_of_core.py

The paper's O(np²) pipeline touches the data only through row-block kernel
evaluations, so the training set never needs to be resident: this example

1. writes a regression problem to disk as ``.npy`` files (the stand-in for
   a dataset that does not fit in device memory),
2. fits ``SketchedKRR`` from a ``MemmapChunkSource`` — every pass streams
   ``chunk_rows`` rows at a time; X, C and B are never materialized and
   cross-chunk state is O(p²),
3. verifies the coefficients are bit-identical to an in-memory fit of the
   same rows at the same ``chunk_rows`` (the source abstraction is
   numerically transparent),
4. shows the incremental twin: ``partial_fit`` over arriving chunks +
   ``finalize()``,
5. serves predictions from the out-of-core model through the same jitted
   batched path every other fit uses.
"""
import os
import sys
import tempfile

sys.path.insert(0, "src")

import jax

jax.config.update("jax_enable_x64", True)
from repro.compile_cache import enable_compile_cache
enable_compile_cache()
import jax.numpy as jnp
import numpy as np

from repro.api import MemmapChunkSource, SketchConfig, SketchedKRR
from repro.core import RBFKernel
from repro.data import pumadyn_like

N, CHUNK = 20_000, 2_048
data = pumadyn_like(n=N, seed=0, noise=0.2)
X, y = np.asarray(data["x"]), np.asarray(data["y"])

workdir = tempfile.mkdtemp(prefix="ooc_")
x_path, y_path = os.path.join(workdir, "X.npy"), os.path.join(workdir, "y.npy")
np.save(x_path, X)
np.save(y_path, y)
print(f"dataset on disk: {X.shape} f64 "
      f"({os.path.getsize(x_path) / 1e6:.1f} MB), chunk_rows={CHUNK} "
      f"({CHUNK / N:.1%} of the rows resident per pass)")

ker = RBFKernel(bandwidth=float(np.sqrt(X.shape[1])))
config = SketchConfig(kernel=ker, p=200, lam=1e-3, sampler="rls_fast",
                      solver="nystrom_regularized", p_scores=400, seed=0,
                      chunk_rows=CHUNK)

# -- the out-of-core fit: five streamed passes, no (n, d) array on device
source = MemmapChunkSource(x_path, y_path, chunk_rows=CHUNK)
model = SketchedKRR(config).fit(source)
print(f"fit from memmap: d_eff estimate "
      f"{float(jnp.sum(model.scores())):.1f}, "
      f"state = {model.state().beta.shape} landmark dual (O(p), not O(n))")

# -- bit-identity: the same rows fitted in memory at the same chunk_rows
in_memory = SketchedKRR(config).fit(jnp.asarray(X), jnp.asarray(y))
identical = bool(jnp.all(model.state().beta == in_memory.state().beta))
print(f"coefficients bit-identical to the in-memory chunked fit: "
      f"{identical}")
assert identical

# -- the incremental twin: chunks arriving over time
stream_model = SketchedKRR(config.replace(chunk_rows=None))
for start in range(0, N, CHUNK):
    stream_model.partial_fit(X[start:start + CHUNK], y[start:start + CHUNK])
stream_model.finalize()

# -- serve from the out-of-core model (same jitted batched path as always)
X_test = jnp.asarray(X[:512])
f_star = jnp.asarray(data["f_star"][:512])
for name, m in [("memmap fit", model), ("partial_fit", stream_model)]:
    y_hat = m.predict_batched(X_test, batch_size=128)
    mse = float(jnp.mean((y_hat - f_star) ** 2))
    print(f"{name:>12}: batched predict over {y_hat.shape[0]} points, "
          f"MSE vs f* = {mse:.4f}")
