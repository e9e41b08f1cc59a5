"""serve_respond_ms: the mean time, in ms, per ``serve.batch`` span
wholly inside the traced serve window, of its ``serve.respond`` span:
the delivery of the answers to the requests' futures, whose
done-callbacks run on the worker, and the update of the engine's
counters. The children are those between the first such batch's start
and the last one's end: the engine serves from one worker thread, so
its batches never overlap. Moves ``serve_p50_ms``. None where the
program records no ``serve.batch``."""

SPANS = ("serve.respond",)


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    host = run.trace.host
    batches = [(s, e) for s, e, n in host
               if n == "serve.batch" and lo <= s and e <= hi]
    if not batches:
        return None
    first, last = min(batches)[0], max(e for _, e in batches)
    total = sum(e - s for s, e, n in host
                if n in SPANS and first <= s and e <= last)
    return total / len(batches) / 1e6
