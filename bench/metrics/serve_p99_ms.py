"""serve_p99_ms: the 99th percentile of the window's request latencies,
each timed from its scheduled send time to its answer. Host stalls of
about 115 ms, a few in a window, each hold back some 900 requests at
8,000/s, so this tail swings from run to run where the p95 does not; it
shows those stalls. Moves ``serve_p95_ms``."""


def read(run):
    return run.counters.get("latency_p99_ms")
