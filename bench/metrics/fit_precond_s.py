"""fit_precond_s: the mean time, in s, of each ``solver.fit`` span wholly
inside the traced fit window, less the ``solver.pcg_step`` spans inside
it and less the stretches of it in which the chip ran the score pass's
program (``jit_score_pass``, dispatched before the solver starts: the
solver's first blocking operation waits it out). What is left is the
solver's own work before its CG loop (the eager Cholesky of the landmark
preconditioner, its first triangular solves, the right-hand side's
kernel pass) and the host's work between steps. Moves ``fit_s``. None
where the program records no ``solver.fit``."""
from harness.trace import clip, program_runs, union, whole_spans, within

SCORE_PASS = "jit_score_pass"


def read(run):
    if run.trace is None:
        return None
    fits = whole_spans(run.trace, "solver.fit")
    if not fits:
        return None
    steps = whole_spans(run.trace, "solver.pcg_step")
    passes = [(s, e, SCORE_PASS) for s, e in
              union(program_runs(run.trace, run.planes, SCORE_PASS))]
    own = [(e - s) - sum(se - ss for ss, se, _ in within(steps, (s, e)))
           - sum(pe - ps for ps, pe, _ in clip(passes, (s, e)))
           for s, e, _ in fits]
    return sum(own) / len(own) / 1e9
