"""device_idle_share.serve: the share of the traced serve window, in %, in
which the chip ran no operation: 100 · (1 − union of the ``XLA Ops``
intervals / window). Below the knee most of the window is idle; what is
left is how long each batch holds the chip. Moves ``serve_p95_ms``."""
from harness.trace import mean_busy_s


def read(run):
    if run.trace is None or run.driver != "serve_poisson":
        return None
    return 100.0 * (1.0 - mean_busy_s(run.trace, run.planes)
                    / run.trace.window_s)
