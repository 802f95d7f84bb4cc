"""One run of one benchmark cell on the machine this is started on:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (data from the seed, one warm-up fit and predict), a window of
``--seconds`` of fit-then-predict cycles, the check against the benchmark's
own plain reference, and one JSON object as the last line of standard output.
One process; it exits non-zero and prints no result line unless jax's
default backend is a TPU holding exactly the chips the cell asks for. A
``RuntimeWarning`` from ``dask_ml_tpu`` is an error. See ``harness.py``.

``--dump`` (``tools/measure.py`` passes it) names a directory for the run's
details: every cycle's times and facts and, traced, the trace's table.
"""

import time

_T0 = time.perf_counter()          # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import warnings  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pin_malloc():
    """Fix glibc malloc's two thresholds where a long-lived process ends up.

    Left alone, glibc raises its mmap threshold to the size of the largest
    mmapped chunk (up to 32 MiB) the process has FREED so far, and the trim
    threshold to twice that. ``predict_proba`` over 4,194,304 rows makes
    three 16 MiB numpy temporaries a call. In a process that only loaded its
    programs from the cache the heap is trimmed after every call and each
    temporary is page-faulted in anew (20,449 faults a call); in one that
    compiled, or ran under the profiler, some 16-32 MiB chunk was freed
    before, the heap keeps the temporaries and only the 32 MiB result faults
    (8,193): 0.080 s a call against 0.115 s on the chip, by the process's
    history alone (PERF.md, section 6). Setting either threshold turns the
    adjustment off; the values are the ones it ends at, so the first call of
    a run is like the thousandth of a service."""
    import ctypes

    mallopt = ctypes.CDLL(None).mallopt
    M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3
    if not (mallopt(M_MMAP_THRESHOLD, 32 << 20)
            and mallopt(M_TRIM_THRESHOLD, 64 << 20)):
        sys.exit("benchmark: mallopt refused the thresholds")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--dump", default=None,
                    help="directory for the run's details")
    args = ap.parse_args(argv)
    pin_malloc()

    from benchmark import harness

    try:
        cell = harness.load_cell(args.workload)
        import jax

        backend = jax.default_backend()
        if backend != "tpu":
            raise harness.BenchmarkError(
                f"needs a TPU; jax's default backend here is {backend!r} "
                f"({jax.devices()[0].device_kind})")
        if len(jax.devices()) != cell.chips:
            raise harness.BenchmarkError(
                f"{cell.name} asks for {cell.chips} chips; jax shows "
                f"{len(jax.devices())}")
        harness.peaks_for(jax.devices()[0].device_kind)
        warnings.filterwarnings("error", category=RuntimeWarning,
                                module=r"dask_ml_tpu")
        result = harness.run_cell(cell, args.seed, args.seconds, args.trace,
                                  t0=_T0, dump=args.dump)
    except harness.BenchmarkError as e:
        sys.exit(f"benchmark: {e}")
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
