#!/usr/bin/env python3
"""Run one cell of the benchmark once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <open cell> --seed <n> --seconds <s> \\
        --sweep 2000,4000,8000        # knee sweep: one set-up, several rates

The last line of standard output is the result: ``correct``, ``attempted``
and ``failed`` (chunks), the cell's end-to-end metrics (``--trace 0``) or
per-layer metrics (``--trace 1``, with the trace's ``breakdown``), the
device, and last ``checks``: each number compared with the reference,
beside its limit (also the last lines of standard error).  Without a TPU,
or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if sys.path and pathlib.Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)     # the benchmark's modules load as the package bench
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated aggregate rates (events/s)")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        return fail(f"the program is not here ({e})")
    from bench import harness

    bench = harness.Bench(ROOT)
    wl = bench.workload(args.workload)
    enable_compile_cache()
    import jax

    # every program of the cell goes to the persistent cache, however
    # quickly it compiled, so a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = harness.device_info()
    if dev["platform"] != "tpu":
        return fail(f"needs a TPU, found {dev['platform']}")
    if dev["count"] < int(wl["chips"]):
        return fail(f"{args.workload} needs {wl['chips']} chips, "
                    f"found {dev['count']}")
    opts = harness.Options(args.workload, args.seed, args.seconds,
                           bool(args.trace), ROOT / ".bench_out" / "trace")
    if args.sweep:
        harness.sweep(bench, opts, [float(r) for r in args.sweep.split(",")])
        return 0
    if opts.trace:
        shutil.rmtree(opts.out_dir, ignore_errors=True)
    result = harness.run_cell(bench, opts, T_START)
    if opts.trace:
        shutil.rmtree(opts.out_dir, ignore_errors=True)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
