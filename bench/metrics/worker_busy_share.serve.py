"""worker_busy_share.serve: the share of the traced serve window, in %,
in which the engine's one worker was serving a batch: 100 · union of the
program's ``serve.batch`` spans, cut to the window, / window. The rest
of the window the worker waits in ``serve.wait`` for a batch to form.
As this nears 100 % batches queue behind each other and the tail grows.
Moves ``serve_p95_ms``. None where the program records no such span."""
from harness.trace import clip, union


def read(run):
    if run.trace is None:
        return None
    batches = clip([h for h in run.trace.host if h[2] == "serve.batch"],
                   run.trace.window)
    if not batches:
        return None
    busy = sum(e - s for s, e in union(batches))
    return 100.0 * busy / (run.trace.window[1] - run.trace.window[0])
