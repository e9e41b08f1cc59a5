"""pcg_steps: the mean count of ``solver.pcg_step`` spans inside each
``solver.fit`` span wholly inside the traced fit window: the PCG
iterations a fit runs before its residual meets the tolerance or the
iteration cap stops it. Moves ``fit_s``. None where the program records
no ``solver.fit``."""
from harness.trace import whole_spans, within


def read(run):
    if run.trace is None:
        return None
    fits = whole_spans(run.trace, "solver.fit")
    if not fits:
        return None
    steps = whole_spans(run.trace, "solver.pcg_step")
    return sum(len(within(steps, fit)) for fit in fits) / len(fits)
