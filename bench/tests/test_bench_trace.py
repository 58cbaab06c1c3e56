"""The trace-to-metrics reduction: busy time is a union of device-op
intervals, idle gaps are named by host spans, and a trace recorded on a
TPU v5e reduces to fixed metric values."""
import json
from pathlib import Path

import pytest

from benchkit import cells, trace

DATA = Path(__file__).resolve().parent / "data"
READERS = ("device_idle_share", "query_device_ms", "scan_roofline")

# three ops on one device, two overlapping: [0,10) + [5,15) + [20,25) us,
# and one host dispatch span over the gap [15,20) us
HAND = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 25000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "gather.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_program" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 14000000 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "dispatch Q1.1 x1" } } }
"""


def test_busy_time_is_a_union_not_a_sum():
    from jax.profiler import ProfileData

    red = trace.reduce(ProfileData.from_text_proto(HAND))
    assert red["busy_s"] == pytest.approx(20e-6)        # a sum would be 25
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(15e-6)
    assert ops["gather.2"] == pytest.approx(10e-6)
    assert "jit_program" not in ops                     # modules are not ops
    assert red["idle_gaps"] == [["dispatch", pytest.approx(5e-6)]]


def test_union_merges_touching_and_nested_intervals():
    assert trace.union([(0, 4), (4, 6), (1, 2), (8, 9)]) == [(0, 6), (8, 9)]


def test_trace_without_device_ops_is_an_error():
    from jax.profiler import ProfileData

    host_only = HAND[HAND.index("planes { id: 2"):]
    with pytest.raises(ValueError):
        trace.reduce(ProfileData.from_text_proto(host_only))


def test_chip_trace_reduces_to_fixed_values():
    """The committed v5e trace gives the values its run reported.  The
    dispatch list is read back from the run's own host spans
    (``dispatch <query> x<width>``)."""
    import re

    side = json.loads((DATA / "ssb1_uniform_read.json").read_text())
    data = trace.load(str(DATA / "ssb1_uniform_read.xplane.pb.gz"))
    red = trace.reduce(data)
    assert red["busy_s"] == pytest.approx(8.314890471, rel=1e-12)
    assert list(red["n_ops"].values()) == [2349]
    dispatches = []
    for _, end, name in trace.host_spans(data):
        m = re.fullmatch(r"dispatch (\S+) x(\d+)", name)
        if m:
            dispatches.append((end, m.group(1), int(m.group(2)),
                               side["n_fact"]))
    assert len(dispatches) == 32
    ctx = {"trace": {"busy_s": red["busy_s"], "window_s": side["window_s"]},
           "queries_done": side["queries_done"], "dispatches": dispatches,
           "dim_rows": side["dim_rows"], "peaks": cells.peaks("TPU v5 lite")}
    got = {m: cells.reader(m)(ctx) for m in READERS}
    assert got["device_idle_share"] == pytest.approx(16.852455592141247,
                                                     rel=1e-12)
    assert got["query_device_ms"] == pytest.approx(188.9747834318182,
                                                   rel=1e-12)
    assert got["scan_roofline"] == pytest.approx(0.05109708941005506,
                                                 rel=1e-12)
    for m in READERS:
        assert got[m] == pytest.approx(side["reported"][m], rel=1e-12)
    assert [g[0] for g in red["idle_gaps"]][0] == "dispatch"
