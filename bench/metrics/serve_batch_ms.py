"""serve_batch_ms: the mean length, in ms, of the program's
``serve.batch`` spans that lie wholly inside the traced serve window:
the service time each request pays once its batch leaves the queue
(deadline filter, host pad, upload, predict and readback, delivery).
Moves ``serve_p50_ms``. None where the program records no such span."""


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    batches = [e - s for s, e, n in run.trace.host
               if n == "serve.batch" and lo <= s and e <= hi]
    if not batches:
        return None
    return sum(batches) / len(batches) / 1e6
