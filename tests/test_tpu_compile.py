"""Ahead-of-time compiles of the Pallas tiles for a described TPU v5e.

Interpret-mode tests run the tile bodies on the CPU; they cannot see what
only Mosaic refuses (an unaligned block, a kernel that overflows VMEM, a
64-bit index). These tests lower and compile the tiles for one chip of a
``v5e:2x2`` topology described to the TPU compiler — nothing runs, so they
check lowering and compiler limits, not results or times. They run under
``conftest.py``'s ``jax_enable_x64``, the condition under which every tile
once failed to lower.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and pytest-xdist workers all import
this file. Code that asks ``jax.default_backend()`` still sees the CPU
here, so tests that must take the TPU branch set it in the test.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LinearKernel, PolynomialKernel, RBFKernel, ops_for
from repro.core.distributed import (cholesky_msolve, pcg_step,
                                    sketch_normal_matvec)
from repro.data.sparse import CsrMatrix, SparseChunkSource
from repro.kernels import ops as kops
from repro.kernels.rbf_block import kernel_block
from repro.kernels.rls_scores import rls_scores_fused
from repro.kernels.sparse_block import onehot_tile_fits

D = 28          # UCI HIGGS width — the deployment shape of the dense tiles
N_ROWS = 4096
P = 1024


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """``shape, dtype -> ShapeDtypeStruct`` placed on one described chip."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the platform checks (``auto`` resolution, interpret mode) to
    their TPU branch, as they would run on the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_x64_is_on():
    """The compiles below are meant to run with 64-bit types enabled."""
    assert jax.config.jax_enable_x64


@pytest.mark.parametrize("kind", ["rbf", "linear", "poly"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_block(chip, kind, dtype):
    text = compiled_text(lambda X, Z: kernel_block(X, Z, kind=kind),
                         chip((N_ROWS, D), dtype), chip((P, D), dtype))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("p", [1024, 2048, 8192])
def test_rls_scores_fused(chip, p):
    """M is tiled, so the kernel's VMEM stays bounded at every p (a whole
    (p, p) f32 M overflows VMEM from p = 2048)."""
    text = compiled_text(rls_scores_fused, chip((N_ROWS, p)),
                         chip((p, p)))
    assert "tpu_custom_call" in text


def test_rls_scores_fused_bf16_with_wide_m(chip):
    """bf16 B with an f64 M (the policy's widened solve under x64): M is
    rounded to the f32 accumulator before it reaches the tile."""
    text = compiled_text(rls_scores_fused, chip((N_ROWS, P), jnp.bfloat16),
                         chip((P, P), jnp.float64))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", [RBFKernel(5.0), LinearKernel(),
                                    PolynomialKernel(degree=2)],
                         ids=["rbf", "linear", "poly"])
def test_auto_cross_block_on_tpu(chip, on_tpu, kernel):
    """``backend="auto"`` on a TPU sends f32 blocks to the tiles."""
    ops = ops_for(kernel, "auto")
    assert ops.name == "pallas"
    text = compiled_text(ops.cross, chip((N_ROWS, D)), chip((P, D)))
    assert "tpu_custom_call" in text


def _chunk(chunk_rows: int, d: int, nnz_per_row: int):
    """First chunk of a ``SparseChunkSource`` over seeded CSR rows."""
    rng = np.random.default_rng(0)
    n = 2 * chunk_rows
    indptr = np.arange(n + 1, dtype=np.int32) * nnz_per_row
    indices = rng.integers(0, d, n * nnz_per_row).astype(np.int32)
    data = rng.standard_normal(n * nnz_per_row).astype(np.float32)
    src = SparseChunkSource(CsrMatrix(data, indices, indptr, d),
                            np.zeros(n, np.float32), chunk_rows)
    return next(src.chunks()).X


@pytest.mark.parametrize("chunk_rows,d,p,tile_expected", [
    (256, 512, 128, True),        # bag-of-words width: one-hot tile
    (4096, 2**20, 128, False),    # hashed width: XLA take + segment-sum
])
def test_sparse_block_rule(chip, on_tpu, chunk_rows, d, p, tile_expected):
    """``kernels.ops.sparse_block`` picks the one-hot MXU tile exactly
    when its VMEM estimate fits, and either choice compiles."""
    X = _chunk(chunk_rows, d, nnz_per_row=8)
    assert onehot_tile_fits(X.data.shape[0], chunk_rows, p, d) \
        is tile_expected
    text = compiled_text(
        lambda data, idx, ptr, Z: kops.sparse_block(data, idx, ptr, Z),
        chip(X.data.shape), chip(X.indices.shape, jnp.int32),
        chip(X.indptr.shape, jnp.int32), chip((p, d)))
    assert ("tpu_custom_call" in text) is tile_expected


def test_explicit_pallas_refuses_f64_on_tpu(chip, on_tpu):
    """Mosaic has no f64: an explicit ``backend="pallas"`` raises, naming
    the dtype and the policy field that fixes it."""
    ops = ops_for(RBFKernel(5.0), "pallas")
    with pytest.raises(ValueError, match=r"float64.*Precision\.data_dtype"):
        jax.jit(ops.cross).lower(chip((N_ROWS, D), jnp.float64),
                                 chip((P, D), jnp.float64))


def test_falkon_pcg_step(chip, on_tpu):
    """FALKON's PCG step at the SUSY cell's shapes (n = 2^17 rows of width
    18, p = 10^4 landmarks, f32) on the tiles: the kernel block runs in
    Mosaic, every array is an argument of the executable (the p×p penalty
    and factor, X and the landmarks) rather than a constant inside it, and
    arguments plus scratch fit one chip's 16 GB."""
    n, d, p = 2**17, 18, 10**4
    ops = ops_for(RBFKernel(4.0), "pallas")
    operands = dict(X=chip((n, d)), Z=chip((p, d)), w=chip((p,)),
                    A=chip((p, p)), nlam=chip(()), L=chip((p, p)))
    v = chip((p,))
    compiled = pcg_step.lower(sketch_normal_matvec(ops), cholesky_msolve,
                              operands, chip(()), v, v, v,
                              chip(())).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert 2 * p * p * 4 <= mem.argument_size_in_bytes < 2**30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
