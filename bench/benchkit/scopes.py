"""Device time by program scope, idle gaps by program span, and queue
waits, from a traced run's ``.xplane.pb``.

The program names its serving programs ``serve_<flavor>_<query>`` (HLO
module ``jit_serve_batch_Q2_1``), puts the query tail's ops under the
named scopes ``probe``, ``filter``, ``group_key`` and ``aggregate``, and
opens host spans ``serve.*`` and ``engine.*``; ``serve.batch`` carries its
requests' queue waits (``wait_us``, ``;``-separated).

``jax.profiler.ProfileData`` gives an event's own stats but not the stats
of its metadata, and on a TPU an op's metadata holds ``tf_op``: the HLO
``op_name`` (``jit(serve_batch_Q2_1)/vmap(filter)/gather``).  So the
device planes are read here from the raw protobuf (the XSpace message,
walked field by field), the host spans through ``ProfileData``.

A device op belongs to the ``XLA Modules`` event that holds its start, and
to the first scope its ``op_name`` names, else ``other``.  One exception:
the TPU compiler emits the group-by scatter-add as a custom fusion
(``kind=kCustom``) and leaves that fusion without an ``op_name`` (every
named op inside it is ``aggregate``); such an op is counted apart as
``unnamed_custom_fusion``.  ``tests/test_tpu_compile.py`` checks, for
every serving program compiled for a v5e, that each custom fusion without
an ``op_name`` holds ``aggregate`` ops only.  An op that lies inside an
earlier op on its line counts once, with that op.  On a program without
these names and spans every number here is absent, not 0.
"""
from __future__ import annotations

import bisect
import collections
import functools
import gzip
import os
import re
import struct
from pathlib import Path

import numpy as np

from benchkit import trace

SCOPES = ("probe", "filter", "group_key", "aggregate")
UNNAMED_CUSTOM = "unnamed_custom_fusion"
SCOPE_RE = re.compile(r"(?<![\w])(" + "|".join(SCOPES) + r")(?![\w])")
SERVE_MODULE = re.compile(r"jit_(serve_\w+)")
OTHER_MODULES = "other modules"
PROGRAM_SPANS = ("serve.", "engine.")
# the directory ``run.py`` writes a traced run's profile into
TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_out" / "trace"

# XSpace field numbers (tsl/profiler/protobuf/xplane.proto)
SPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_MD, PLANE_STAT_MD = 2, 3, 4, 5
LINE_NAME, LINE_TIMESTAMP_NS, LINE_EVENTS = 2, 3, 4
EVENT_MD_ID, EVENT_OFFSET_PS, EVENT_DURATION_PS = 1, 2, 3
MD_ID, MD_NAME, EVENT_MD_STATS = 1, 2, 5
STAT_MD_ID, STAT_STR, STAT_REF = 1, 5, 7
MAP_VALUE = 2


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of each field of the message in
    ``buf[i:end]``; a length-delimited value is its (start, end)."""
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif wire == 5:
            v, i = struct.unpack_from("<i", buf, i)[0], i + 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield field, v


def _str(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, span: tuple[int, int]):
    """The value span of one ``map<int64, message>`` entry."""
    for f, v in _fields(buf, *span):
        if f == MAP_VALUE:
            return v
    return None


def _device_plane(buf: bytes, span: tuple[int, int]):
    """Of one device plane: its lines' events (name -> [(metadata id,
    start ns, end ns)]) and each event metadata's (name, tf_op)."""
    stat_names: dict[int, str] = {}
    md_spans: list[tuple[int, int]] = []
    lines: dict[str, list[tuple[int, float, float]]] = {}
    for f, v in _fields(buf, *span):
        if f == PLANE_STAT_MD:
            val = _map_value(buf, v)
            if val is not None:
                sid, name = 0, ""
                for g, w in _fields(buf, *val):
                    if g == MD_ID:
                        sid = w
                    elif g == MD_NAME:
                        name = _str(buf, w)
                stat_names[sid] = name
        elif f == PLANE_EVENT_MD:
            val = _map_value(buf, v)
            if val is not None:
                md_spans.append(val)
        elif f == PLANE_LINES:
            name, t0, evs = "", 0, []
            for g, w in _fields(buf, *v):
                if g == LINE_NAME:
                    name = _str(buf, w)
                elif g == LINE_TIMESTAMP_NS:
                    t0 = w
                elif g == LINE_EVENTS:
                    evs.append(w)
            if name not in (trace.OPS_LINE, "XLA Modules"):
                continue
            out = lines.setdefault(name, [])
            for ev in evs:
                mid = off = dur = 0
                for g, w in _fields(buf, *ev):
                    if g == EVENT_MD_ID:
                        mid = w
                    elif g == EVENT_OFFSET_PS:
                        off = w
                    elif g == EVENT_DURATION_PS:
                        dur = w
                start = t0 + off * 1e-3
                out.append((mid, start, start + dur * 1e-3))
    tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
    meta: dict[int, tuple[str, str | None]] = {}
    for val in md_spans:
        mid, name, op = 0, "", None
        for g, w in _fields(buf, *val):
            if g == MD_ID:
                mid = w
            elif g == MD_NAME:
                name = _str(buf, w)
            elif g == EVENT_MD_STATS:
                sid, s = 0, None
                for h, x in _fields(buf, *w):
                    if h == STAT_MD_ID:
                        sid = x
                    elif h == STAT_STR:
                        s = _str(buf, x)
                    elif h == STAT_REF:
                        s = stat_names.get(x)
                if sid in tf_op:
                    op = s
        meta[mid] = (name, op)
    return lines, meta


def device_planes(raw: bytes) -> dict[str, tuple[dict, dict]]:
    """Per device plane name: ``_device_plane``'s lines and metadata."""
    out = {}
    for f, v in _fields(raw, 0, len(raw)):
        if f != SPACE_PLANES:
            continue
        name = next((_str(raw, w) for g, w in _fields(raw, *v)
                     if g == PLANE_NAME), "")
        if name.startswith("/device:"):
            out[name] = _device_plane(raw, v)
    return out


def scope_of(hlo: str, op_name: str | None) -> str:
    """The scope of the op whose HLO text is ``hlo``."""
    if op_name is None and "kind=kCustom" in hlo:
        return UNNAMED_CUSTOM
    m = SCOPE_RE.search(op_name or "")
    return m.group(1) if m else "other"


def _top_level(evs):
    """The events not inside an earlier event of the same line."""
    out, end = [], float("-inf")
    for ev in sorted(evs, key=lambda e: (e[1], -e[2])):
        if ev[2] <= end:
            continue
        out.append(ev)
        end = max(end, ev[2])
    return out


def device_scopes(planes) -> dict[str, dict[str, float]]:
    """Seconds per scope per serving module (``serve_batch_Q2_1``), and
    ``other modules``: ops of any other module or of none.  Averaged over
    the device planes, like ``trace.reduce``'s busy time."""
    acc: dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    n = 0
    for lines, meta in planes.values():
        ops = lines.get(trace.OPS_LINE, [])
        if not ops:
            continue
        n += 1
        mods = sorted((s, e, meta.get(m, ("", None))[0].split("(")[0])
                      for m, s, e in lines.get("XLA Modules", []))
        starts = [s for s, _, _ in mods]
        for mid, s, e in _top_level(ops):
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and s < mods[i][1] else ""
            m = SERVE_MODULE.fullmatch(mod)
            if m is None:
                acc[OTHER_MODULES]["other"] += (e - s) * 1e-9
            else:
                scope = scope_of(*meta.get(mid, ("", None)))
                acc[m.group(1)][scope] += (e - s) * 1e-9
    return {k: {s: v / n for s, v in c.items()} for k, c in acc.items()}


def program_spans(data) -> list[tuple[float, float, str, dict]]:
    """Every ``serve.*`` / ``engine.*`` host span: (start ns, end ns, name,
    arguments)."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_SPANS):
                    out.append((e.start_ns, e.end_ns, e.name,
                                dict(e.stats)))
    return out


def idle_gaps_program(data, spans) -> list[list]:
    """Idle seconds between busy device intervals, each gap named by the
    shortest program span over its midpoint, or ``no program span``."""
    spans = sorted(spans, key=lambda sp: sp[0])
    starts = [s for s, *_ in spans]
    longest = max((e - s for s, e, *_ in spans), default=0.0)
    gap_time: collections.Counter = collections.Counter()
    for evs in trace.device_intervals(data).values():
        merged = trace.union(evs)
        for (_, a), (b, _) in zip(merged, merged[1:]):
            mid = 0.5 * (a + b)
            lo = bisect.bisect_left(starts, mid - longest)
            hi = bisect.bisect_right(starts, mid)
            over = [(e - s, name) for s, e, name, _ in spans[lo:hi]
                    if e >= mid]
            label = min(over)[1] if over else "no program span"
            gap_time[label] += (b - a) * 1e-9
    return [[k, v] for k, v in gap_time.most_common()]


def queue_waits_s(spans) -> list[float]:
    """The queue wait of every request of every ``serve.batch`` span."""
    out = []
    for _, _, name, args in spans:
        if name == "serve.batch" and "wait_us" in args:
            out += [1e-6 * float(w) for w in str(args["wait_us"]).split(";")
                    if w]
    return out


def read_raw(path: str) -> bytes:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


@functools.lru_cache(maxsize=4)
def analyse(path: str) -> dict:
    """Everything above for one trace file; logs the breakdowns."""
    from jax.profiler import ProfileData

    from benchkit.harness import log

    raw = read_raw(path)
    data = ProfileData.from_serialized_xspace(raw)
    spans = program_spans(data)
    out = {"device_scopes": device_scopes(device_planes(raw)),
           "idle_gaps_program": idle_gaps_program(data, spans),
           "queue_waits_s": queue_waits_s(spans)}
    log(f"device scopes (s): {out['device_scopes']}")
    log(f"idle gaps by program span (s): {out['idle_gaps_program']}")
    w = out["queue_waits_s"]
    if w:
        log(f"queue waits: n={len(w)} "
            f"p50_ms={1e3 * np.percentile(w, 50):.3f} "
            f"p95_ms={1e3 * np.percentile(w, 95):.3f}")
    return out


def of_run(ctx) -> dict | None:
    """``analyse`` of the traced run's profile, or None untraced."""
    if not ctx.get("trace"):
        return None
    try:
        path = trace.find_xplane(str(TRACE_DIR))
    except ValueError:
        return None
    return analyse(os.path.abspath(path))


def scope_ms_per_query(ctx, names: tuple[str, ...]) -> float | None:
    """Device ms per completed query in the named scopes, summed over the
    serving modules; None where the trace has no serving module."""
    a = of_run(ctx)
    if a is None or not ctx.get("queries_done"):
        return None
    serve = [v for k, v in a["device_scopes"].items() if k != OTHER_MODULES]
    if not serve:
        return None
    s = sum(v.get(n, 0.0) for v in serve for n in names)
    return 1e3 * s / ctx["queries_done"]
