"""Benchmark runner — one benchmark per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--fast]``
prints ``name,us_per_call,derived`` CSV rows per the harness contract.
``--json PATH`` additionally writes the same rows as a JSON list of
``{"name", "us_per_call", "derived"}`` objects (e.g. ``BENCH_core.json``),
so the perf trajectory is machine-readable; the stdout CSV contract is
unchanged.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax

# Paper-math benchmarks are float64 (CPU statistical experiments — DESIGN.md
# §5); the LM/roofline paths use explicit bf16/f32 dtypes regardless.
jax.config.update("jax_enable_x64", True)

_collected: list[dict] = []


def time_min(fn, reps: int = 3) -> float:
    """Min over ``reps`` timed calls in µs, after one untimed warm call
    (compile / jit-cache population excluded). The shared harness helper:
    every fit-level benchmark (``bench_out_of_core``, ``bench_iterative``)
    times through this so their rows are comparable min-of-reps numbers.
    """
    import time

    fn()  # compile / warm the jit caches
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _emit(rows: list[dict]) -> None:
    for r in rows:
        r = dict(r)
        name = r.pop("name")
        us = r.pop("us_per_call", "")
        print(f"{name},{us},{json.dumps(r, default=str)}")
        _collected.append({"name": name, "us_per_call": us, "derived": r})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller n / fewer seeds")
    ap.add_argument("--only", default=None,
                    help="fig1|table1|thm4|backends|ooc|scaling|iter|serve|"
                         "sparse|roofline")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows to PATH as JSON "
                         "(name, us_per_call, derived)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    only = args.only

    if only in (None, "fig1"):
        from . import bench_fig1_synthetic
        _emit(bench_fig1_synthetic.run(
            n=300 if args.fast else 500, seeds=2 if args.fast else 5))
    if only in (None, "table1"):
        from . import bench_table1
        _emit(bench_table1.run(seeds=1 if args.fast else 3))
    if only in (None, "thm4"):
        from . import bench_fast_leverage
        _emit(bench_fast_leverage.run())
    if only in (None, "backends"):
        from . import bench_backends
        _emit(bench_backends.run(n=1500 if args.fast else 4000,
                                 p=64 if args.fast else 128))
    if only in (None, "ooc"):
        from . import bench_out_of_core
        _emit(bench_out_of_core.run(n=6000 if args.fast else 20_000,
                                    p=48 if args.fast else 96,
                                    chunk_rows=512 if args.fast else 2048))
    if only in (None, "scaling"):
        from . import bench_scaling
        _emit(bench_scaling.run(n=1000 if args.fast else 2000))
    if only in (None, "iter"):
        from . import bench_iterative
        _emit(bench_iterative.run(fast=args.fast))
    if only in (None, "sparse"):
        from . import bench_sparse
        _emit(bench_sparse.run(n=2000 if args.fast else 8000,
                               d=128 if args.fast else 512,
                               p=48 if args.fast else 64,
                               block_rows=512 if args.fast else 1024))
    if only == "serve":
        # Not part of the default full sweep: the latency rows are
        # wall-clock-sensitive, so the serve lane runs them explicitly
        # (CI: bench_serve smoke artifact). The serve-dtype ladder is
        # re-emitted here standalone so the lane carries the gated
        # backends.serve.* rows without the full backend matrix.
        from . import bench_backends, bench_serve
        _emit(bench_serve.run(fast=args.fast))
        _emit(bench_backends.run_serve_ladder(n=1500 if args.fast else 4000,
                                              p=64 if args.fast else 128))
    if only in (None, "roofline"):
        import os
        from . import roofline
        path = os.environ.get("ROOFLINE_JSONL",
                              "benchmarks/results/dryrun_16x16.jsonl")
        if os.path.exists(path):
            rows = [roofline.roofline_row(r) for r in roofline.load(path)]
            rows.sort(key=lambda r: (r["arch"], r["shape"]))
            _emit([{"name": f"roofline.{r['arch']}.{r['shape']}",
                    **{k: v for k, v in r.items()
                       if k not in ("arch", "shape")}} for r in rows])
        else:
            print("roofline.skipped,,\"run launch.dryrun first\"",
                  file=sys.stderr)

    if args.json:
        with open(args.json, "w") as fh:
            json.dump(_collected, fh, indent=2, default=str)
            fh.write("\n")


if __name__ == "__main__":
    main()
