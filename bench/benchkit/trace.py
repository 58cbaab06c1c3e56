"""Reduce a profiler trace (``.xplane.pb``) to device busy time and a
breakdown.

Busy time is the union of the intervals in which an operation ran on a
device (overlapping ops count once), averaged over the devices traced.
Device ops are the events of each device plane's ``XLA Ops`` line.  Each
idle gap between busy intervals is named by the benchmark's own host span
that covers the gap's midpoint (``dispatch ...``, ``append``, ``upsert``),
or ``no host span``.
"""
from __future__ import annotations

import bisect
import collections
import gzip
import os

OPS_LINE = "XLA Ops"
# host spans that name an idle gap, most specific first
HOST_SPAN_PREFIXES = ("dispatch", "upsert", "append", "rewarm")


def load(path: str):
    """``ProfileData`` from an ``.xplane.pb`` file (gzip accepted)."""
    from jax.profiler import ProfileData

    if not path.endswith(".gz"):
        return ProfileData.from_file(path)
    with gzip.open(path, "rb") as f:
        raw = f.read()
    return ProfileData.from_serialized_xspace(raw)


def device_intervals(data) -> dict[str, list[tuple[float, float, str]]]:
    """Per device plane: (start_ns, end_ns, op name) of every op event."""
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        evs = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                evs += [(e.start_ns, e.end_ns, e.name) for e in line.events]
        if evs:
            out[plane.name] = evs
    return out


def host_spans(data) -> list[tuple[float, float, str]]:
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_SPAN_PREFIXES):
                    out.append((e.start_ns, e.end_ns, e.name))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge intervals; the total of the result counts overlaps once."""
    merged: list[list[float]] = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def reduce(data, top: int = 10) -> dict:
    """Busy seconds per device and averaged, op-time ranking, idle gaps."""
    devs = device_intervals(data)
    if not devs:
        raise ValueError("trace holds no device op events")
    busy = {}
    gaps = []
    op_time: collections.Counter = collections.Counter()
    for name, evs in devs.items():
        merged = union(evs)
        busy[name] = sum(e - s for s, e in merged) * 1e-9
        for s, e, op in evs:
            op_time[op] += (e - s) * 1e-9
        gaps += [(merged[i][1], merged[i + 1][0])
                 for i in range(len(merged) - 1)]
    # per span kind, the merged intervals in which the host was in it
    kinds = {p: union([sp for sp in host_spans(data) if sp[2].startswith(p)])
             for p in HOST_SPAN_PREFIXES}
    starts = {p: [s for s, _ in iv] for p, iv in kinds.items()}
    gap_time: collections.Counter = collections.Counter()
    for s, e in gaps:
        mid = 0.5 * (s + e)
        label = "no host span"
        for p in HOST_SPAN_PREFIXES:
            i = bisect.bisect_right(starts[p], mid) - 1
            if i >= 0 and kinds[p][i][1] >= mid:
                label = p
                break
        gap_time[label] += (e - s) * 1e-9
    n_ops = {name: len(evs) for name, evs in devs.items()}
    return {
        "busy_s": sum(busy.values()) / len(busy),
        "n_ops": n_ops,
        "device_ops": [[k, v] for k, v in op_time.most_common(top)],
        "idle_gaps": [[k, v] for k, v in gap_time.most_common(top)],
    }


def find_xplane(log_dir: str) -> str:
    found = []
    for root, _, files in os.walk(log_dir):
        found += [os.path.join(root, f) for f in files
                  if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(found)}")
    return found[0]


def describe(data) -> str:
    """One line per plane and line with its event count (a look by hand)."""
    rows = []
    for plane in data.planes:
        for line in plane.lines:
            rows.append(f"{plane.name} | {line.name} | "
                        f"{sum(1 for _ in line.events)}")
    return "\n".join(rows)

