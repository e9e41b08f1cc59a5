"""device_idle_share.fit: the share of the traced fit window, in %, in
which the chip ran no operation: 100 · (1 − union of the ``XLA Ops``
intervals / window). Back-to-back fits leave the chip idle where the
host works between device programs: each PCG iteration's read of the
residual norm, the eager steps before the loop, the copies of a fit's
answers to the host. Moves ``fit_s``."""
from harness.trace import mean_busy_s


def read(run):
    if run.trace is None or run.driver != "fit_loop":
        return None
    return 100.0 * (1.0 - mean_busy_s(run.trace, run.planes)
                    / run.trace.window_s)
