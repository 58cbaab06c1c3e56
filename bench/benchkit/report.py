"""Turn a run's readings into the result line, and the check lines."""
from __future__ import annotations

import json
import sys

from benchkit import cells

# every number compared, with its limit (readings in PERF.md)
LIMITS = {"wrong_answers": 0, "missing_answers": 0}


def check_lines(check: dict) -> tuple[bool, dict]:
    """Whether the run is correct, and each number beside its limit."""
    shown = {k: {"value": check[k], "limit": lim} for k, lim in LIMITS.items()}
    ok = check["sampled"] > 0 and all(
        check[k] <= lim for k, lim in LIMITS.items())
    shown["sampled"] = {"value": check["sampled"], "limit": 1}
    return ok, shown


def metrics(cell, res: dict, trace: dict | None, peaks: dict) -> dict:
    """End-to-end metrics untraced; per-layer metrics traced."""
    out = {}
    if trace is None:
        for m in cell.end_to_end:
            out[m["name"]] = {"value": res["e2e"][m["name"]],
                              "unit": m["unit"]}
        return out
    ctx = dict(res["ctx"], trace=trace, peaks=peaks)
    for m in cell.per_layer:
        v = cells.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def emit(cell, res: dict, trace: dict | None, peaks: dict) -> dict:
    """Print the check lines (last on stderr) and the result line (last on
    stdout); return the result."""
    correct, shown = check_lines(res["check"])
    dev = res["device"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": res["device_count"],
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"],
            "metrics": metrics(cell, res, trace, peaks), "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["check"] = shown
    for k, v in shown.items():
        print(f"check {k}={v['value']} limit={v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return line
