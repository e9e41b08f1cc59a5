"""Each cell cut to a tiny size, for runs of the harness on the CPU.

The runs go through the harness's own ``run`` with every piece the cell
names (driver, reference, limits, readers); only the look for a chip is
skipped, and the configuration is shrunk. A CPU run gives no device
number: the readers of the trace find no chip plane and stay silent."""
import dataclasses
import time

TINY = dict(n_train=2048, n_test=256, p=64, p_scores=64)
# Large enough that, at lambda = 1e-6, the control's bf16 rows cost
# what they cost at the cell's size: an error of several percent.
CONTROL_SIZE = dict(n_train=8192, n_test=256, p=512, p_scores=256)
TINY_RATE = 200.0


def tiny_cell(name: str, size=None):
    from harness.cells import load_cell
    cell = load_cell(name)
    traffic = dict(cell.traffic)
    if "rate_per_s" in traffic:
        traffic["rate_per_s"] = TINY_RATE
    config = dict(cell.config, **(size or TINY))
    return dataclasses.replace(cell, config=config, traffic=traffic)


def run_tiny(name: str, *, seed: int = 2**33 + 7, seconds: float = 1.0,
             trace: bool = False, precision=None, size=None):
    """One run of the cell at the tiny size, with x64 off as the
    benchmark runs (an earlier test may have turned it on)."""
    import jax
    from harness.main import peak_for, run
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        return run(tiny_cell(name, size), seed, seconds, trace,
                   time.perf_counter(), jax.devices(),
                   peak_for("TPU v5 lite"), precision)
    finally:
        jax.config.update("jax_enable_x64", was)
