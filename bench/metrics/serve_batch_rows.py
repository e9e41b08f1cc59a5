"""serve_batch_rows: mean number of live requests per batch the engine
ran in the window (``ServeStats.batch_sizes``). More rows per batch means
fewer batches for the one worker, and less queueing. Moves
``serve_p50_ms``."""


def read(run):
    sizes = run.counters.get("batch_sizes")
    if not sizes:
        return None
    return sum(sizes) / len(sizes)
