"""pcg_step_ms: the median length, in ms, of the ``solver.pcg_step``
spans inside the ``solver.fit`` spans wholly inside the traced fit
window, leaving out each fit's first step (a process's first holds the
step's compile, or its load from the persistent cache): one PCG
iteration, its kernel pass over the rows and the host's read of the
residual norm that ends it. Moves ``fit_s``. None where no fit records a second step."""
import statistics

from harness.trace import whole_spans, within


def read(run):
    if run.trace is None:
        return None
    steps = whole_spans(run.trace, "solver.pcg_step")
    later = [e - s for fit in whole_spans(run.trace, "solver.fit")
             for s, e, _ in sorted(within(steps, fit))[1:]]
    if not later:
        return None
    return statistics.median(later) / 1e6
