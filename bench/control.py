"""Readings for the limits of the check, on the chip, at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process: a run of the cell with a short window, then
the check twice over the same seeded sample of its answers: once with the
program's answers (the program's reading), once with the float32
reference's answers in their place (the control's reading).  Both go
through the run's own verdict (``report.check_lines``); the control has to
come out not correct.  One JSON line per seed on stdout.  Not part of a
benchmark run.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchkit import cells, harness, report

    cell = cells.resolve(cells.load_spec(ROOT), args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    t = T_PROCESS
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run(cell, seed, args.seconds, None, t, control=True)
        correct, shown = report.check_lines(res["check"])
        c_correct, c_shown = report.check_lines(res["control_check"])
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "correct": correct,
            "check": {k: v["value"] for k, v in shown.items()},
            "control_correct": c_correct,
            "control_check": {k: v["value"] for k, v in c_shown.items()},
            "epochs": len(res["check"]["epochs"]),
            "queries_per_s": res["e2e"]["queries_per_s"]}), flush=True)
        t = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
