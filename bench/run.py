#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload sift1m.batch64 --seed 7 --seconds 10 \
        --trace 0

The cell (its configuration, traffic mix and metrics) is looked up by name
in ``BENCHMARK.json``.  The run makes its data and queries on the device
from ``--seed``, builds the index, warms the engine, measures for
``--seconds``, checks the answers against a plain brute-force reference and
prints one JSON object as the last line of standard output (the checks it
made go last on standard error too).  ``--trace 1`` takes a profiler trace
of the window and reports the per-layer metrics instead of the end-to-end
ones.  Without a TPU, or with fewer chips than the cell asks for, it exits
with code 2 and prints no result.  JAX's compilation cache is kept in
``.jax_cache`` at the root of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = os.path.join(ROOT, "BENCHMARK.json")
CACHE = os.path.join(ROOT, ".jax_cache")     # JAX's compilation cache
DIRS = ()            # directories searched before bench/ for a cell's files


def chips_missing(chips: int):
    """Why this machine cannot run a cell on ``chips`` TPU chips, or None."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        return (f"needs {chips} TPU chip(s); JAX found {len(devices)} "
                f"{devices[0].platform} device(s)")
    return None


def _print_checks(checks: dict) -> None:
    for name, c in checks.items():
        bound = (f"at_least {c['at_least']}" if "at_least" in c
                 else f"at_most {c['at_most']}")
        print(f"check {name} {c['value']} {bound}", file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    try:
        from bench import harness
        cell = harness.resolve(harness.load_spec(SPEC), args.workload,
                               root=os.path.dirname(SPEC), dirs=DIRS)
        import repro.serve.engine  # noqa: F401  (the system under test)
    except (ImportError, OSError, KeyError) as err:
        print(f"cannot set up {args.workload!r}: {err!r}", file=sys.stderr)
        return 2
    missing = chips_missing(int(cell["cell"]["chips"]))
    if missing:
        print(missing, file=sys.stderr)
        return 2
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE   # the engine reads it
    jax.config.update("jax_compilation_cache_dir", CACHE)

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    _print_checks(result["checks"])
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
