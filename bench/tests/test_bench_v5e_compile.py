"""Each configuration's score pass and PCG kernel pass, compiled at their
real size for a described TPU v5e: the programs a cell's fit runs must
lower with their Mosaic kernels and fit one chip's memory. Nothing runs, so this checks
lowering and sizes, never results or times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every pytest-xdist worker
imports this file."""
import jax
import jax.numpy as jnp
import pytest

from harness.cells import load_cell
from harness.drivers import sketch_config

HBM_USABLE = 15.75e9       # bytes a v5e program may hold


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


@pytest.fixture
def as_benchmarked():
    """x64 off, as the benchmark runs; and no persistent cache, which
    cannot read back a compile made for a described chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("workload", ["susy_falkon.serve_poisson"])
def test_one_chip_score_pass(topo, as_benchmarked, monkeypatch, workload):
    """The sampler as the fit calls it, ``backend="auto"`` steered to its
    TPU branch (the Pallas tiles)."""
    from jax.sharding import SingleDeviceSharding
    from repro.api.samplers import SAMPLERS
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = load_cell(workload).config
    cfg = sketch_config(c, 0)

    def scores(X):
        return SAMPLERS.get(cfg.sampler)(jax.random.key(0), cfg.kernel, X,
                                         cfg).scores
    X = jax.ShapeDtypeStruct((c["n_train"], c["d"]), jnp.float32,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    compiled = jax.jit(scores).lower(X).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes(compiled) < HBM_USABLE


def test_one_chip_pcg_matvec(topo, as_benchmarked, monkeypatch):
    """The kernel pass of every PCG iteration, k(X, Z)ᵀ(k(X, Z) v), at
    the configuration's n and p on the Pallas executor."""
    from jax.sharding import SingleDeviceSharding
    from repro.core.backends import ops_for
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = load_cell("susy_falkon.serve_poisson").config
    ops = ops_for(sketch_config(c, 0).kernel, "pallas")
    one = SingleDeviceSharding(topo.devices[0])
    X, Z, v = (jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
               for s in ((c["n_train"], c["d"]), (c["p"], c["d"]), (c["p"],)))
    compiled = jax.jit(ops.gram_matvec).lower(X, Z, v).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes(compiled) < HBM_USABLE
