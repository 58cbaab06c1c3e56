"""The benchmark's one command: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (see ``benchkit/cells.py``).  A run that finds
no TPU, or fewer chips than the cell asks for, exits non-zero and prints no
result.  The last stdout line is the result; the last stderr lines are the
numbers the check compared, each beside its limit.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".jax_cache"          # fixed, inside the checkout
TRACE_DIR = ROOT / ".bench_out" / "trace"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    # cache every program, the sub-second index-build ones included
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchkit import cells, harness, report, trace

    cell = cells.resolve(cells.load_spec(ROOT), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"reports {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    peaks = cells.peaks(devices[0].device_kind)
    trace_dir = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = str(TRACE_DIR)
    res = harness.run(cell, args.seed, args.seconds, trace_dir, T_PROCESS)
    res["device_count"] = len(devices)
    red = None
    if trace_dir is not None:
        data = trace.load(trace.find_xplane(trace_dir))
        harness.log("trace planes:\n" + trace.describe(data))
        red = trace.reduce(data)
        red["window_s"] = res["ctx"]["window"]["traced_window_s"]
        harness.log(f"trace: busy_s={red['busy_s']} "
                    f"window_s={red['window_s']} ops={red['n_ops']}")
    report.emit(cell, res, red, peaks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
