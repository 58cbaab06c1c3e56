"""Device time by program scope, idle gaps by program span and queue waits
(``benchkit/scopes.py``): a hand-written trace of a named serving program
gives fixed values, the committed v5e trace (a program without scopes or
spans) gives none, and the three metrics that read them are declared."""
import json
from pathlib import Path

import pytest

from benchkit import cells, scopes, trace

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
NEW = ("queue_wait_p95_ms", "filter_device_ms", "groupby_device_ms")

# One serving module [0,20) us and one other module [30,40) us; ops (us):
# filter [0,6), group_key [6,8), an unnamed custom fusion [8,15) holding a
# nested op [9,10), aggregate [15,16) (its op_name by reference), an
# unnamed loop fusion [16,18), an op of the other module [30,35) and one
# outside any module [50,52).  Host: serve.batch [0,27) with waits 1 ms
# and 3 ms, its serve.run [1,3) and serve.fetch [17,26), a second
# serve.batch [28,38) with one wait of 2 ms, and on that thread another
# serve.run [1,3) (equal spans must sort).
HAND = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 11 offset_ps: 30000000 duration_ps: 10000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 7000000 }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 15000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 16000000 duration_ps: 2000000 }
    events { metadata_id: 7 offset_ps: 30000000 duration_ps: 5000000 }
    events { metadata_id: 7 offset_ps: 50000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fusion = pred[6000000]{0} fusion(pred[2000]{0} %a), kind=kCustom"
    stats { metadata_id: 26
            str_value: "jit(serve_batch_Q2_1)/vmap(filter)/gather:" } } }
  event_metadata { key: 2 value { id: 2
    name: "%fusion.2 = s32[6000000]{0} fusion(s32[2556]{0} %b), kind=kCustom"
    stats { metadata_id: 26
            str_value: "jit(serve_batch_Q2_1)/vmap(group_key)/gather:" } } }
  event_metadata { key: 3 value { id: 3
    name: "%fusion.4 = s32[7000]{0} fusion(s32[6000000]{0} %c), kind=kCustom" } }
  event_metadata { key: 4 value { id: 4
    name: "%copy.1 = s32[7000]{0} copy(s32[7000]{0} %d)" } }
  event_metadata { key: 5 value { id: 5
    name: "%reshape.1 = s32[1,7000]{1,0} reshape(s32[7000]{0} %fusion.4)"
    stats { metadata_id: 26 ref_value: 27 } } }
  event_metadata { key: 6 value { id: 6
    name: "%and_fusion = pred[6000000]{0} fusion(pred[6000000]{0} %e), kind=kLoop" } }
  event_metadata { key: 7 value { id: 7
    name: "%gather = s32[6000000]{0} gather(s32[2000]{0} %f)"
    stats { metadata_id: 26 str_value: "jit(program)/vmap()/gather:" } } }
  event_metadata { key: 10 value { id: 10 name: "jit_serve_batch_Q2_1(123)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_program(456)" } }
  stat_metadata { key: 26 value { id: 26 name: "tf_op" } }
  stat_metadata { key: 27 value { id: 27
    name: "jit(serve_batch_Q2_1)/vmap(aggregate)/scatter-add" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 27000000
      stats { metadata_id: 1 str_value: "Q2.1" }
      stats { metadata_id: 2 int64_value: 2 }
      stats { metadata_id: 3 int64_value: 1 }
      stats { metadata_id: 4 str_value: "1000;3000" } }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 17000000 duration_ps: 9000000 } }
  lines { id: 2 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 28000000 duration_ps: 10000000
      stats { metadata_id: 4 int64_value: 2000 } }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000
      stats { metadata_id: 2 int64_value: 1 } } }
  event_metadata { key: 1 value { id: 1 name: "serve.batch" } }
  event_metadata { key: 2 value { id: 2 name: "serve.run" } }
  event_metadata { key: 3 value { id: 3 name: "serve.fetch" } }
  stat_metadata { key: 1 value { id: 1 name: "query" } }
  stat_metadata { key: 2 value { id: 2 name: "n" } }
  stat_metadata { key: 3 value { id: 3 name: "batch" } }
  stat_metadata { key: 4 value { id: 4 name: "wait_us" } } }
"""


def _hand_raw() -> bytes:
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(HAND)


def _traced_run_dir(tmp_path, monkeypatch, raw: bytes) -> None:
    """Put ``raw`` where ``run.py`` leaves a traced run's profile."""
    d = tmp_path / "trace" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path / "trace")


def test_hand_trace_gives_fixed_scopes_gaps_and_waits():
    from jax.profiler import ProfileData

    raw = _hand_raw()
    got = scopes.device_scopes(scopes.device_planes(raw))
    assert set(got) == {"serve_batch_Q2_1", scopes.OTHER_MODULES}
    serve = got["serve_batch_Q2_1"]
    assert serve == pytest.approx({
        "filter": 6e-6, "group_key": 2e-6, scopes.UNNAMED_CUSTOM: 7e-6,
        "aggregate": 1e-6, "other": 2e-6})          # nested op counted once
    assert got[scopes.OTHER_MODULES] == pytest.approx({"other": 7e-6})
    data = ProfileData.from_serialized_xspace(raw)
    red = trace.reduce(data)
    # every top-level op lands in one scope: the scopes sum to busy time
    total = sum(v for d in got.values() for v in d.values())
    assert total == pytest.approx(red["busy_s"])
    spans = scopes.program_spans(data)
    assert scopes.idle_gaps_program(data, spans) == [
        ["no program span", pytest.approx(15e-6)],
        ["serve.fetch", pytest.approx(12e-6)]]      # shortest span wins
    assert scopes.queue_waits_s(spans) == pytest.approx([1e-3, 3e-3, 2e-3])
    # the benchmark's own labels are untouched by program spans
    assert [g[0] for g in red["idle_gaps"]] == ["no host span"]


def test_readers_read_the_traced_run(tmp_path, monkeypatch):
    _traced_run_dir(tmp_path, monkeypatch, _hand_raw())
    ctx = {"trace": {"busy_s": 25e-6, "window_s": 52e-6},
           "queries_done": 2}
    got = {m: cells.reader(m)(ctx) for m in NEW}
    assert got["queue_wait_p95_ms"] == pytest.approx(2.9)   # of 1, 2, 3
    assert got["filter_device_ms"] == pytest.approx(1e3 * 6e-6 / 2)
    assert got["groupby_device_ms"] == pytest.approx(1e3 * 10e-6 / 2)
    untraced = {"queries_done": 2}
    assert all(cells.reader(m)(untraced) is None for m in NEW)


def test_chip_trace_of_a_program_without_scopes_reads_none(tmp_path,
                                                          monkeypatch):
    """The committed v5e trace predates the program's names, scopes and
    spans: its ops all fall in ``other modules``, summing to the busy time
    ``trace.reduce`` gives, and the three metrics read None, not 0."""
    raw = scopes.read_raw(str(DATA / "ssb1_uniform_read.xplane.pb.gz"))
    got = scopes.device_scopes(scopes.device_planes(raw))
    assert list(got) == [scopes.OTHER_MODULES]
    assert got[scopes.OTHER_MODULES]["other"] == pytest.approx(
        8.314890471, rel=1e-6)
    _traced_run_dir(tmp_path, monkeypatch, raw)
    side = json.loads((DATA / "ssb1_uniform_read.json").read_text())
    ctx = {"trace": {"busy_s": 8.314890471, "window_s": side["window_s"]},
           "queries_done": side["queries_done"]}
    assert {m: cells.reader(m)(ctx) for m in NEW} == dict.fromkeys(NEW)


def test_device_op_times_match_profile_data():
    """The protobuf walk reads the same op intervals as ``ProfileData``."""
    from jax.profiler import ProfileData

    raw = scopes.read_raw(str(DATA / "ssb1_uniform_read.xplane.pb.gz"))
    mine = scopes.device_planes(raw)["/device:TPU:0"][0][trace.OPS_LINE]
    ref = trace.device_intervals(ProfileData.from_serialized_xspace(raw))
    ref = ref["/device:TPU:0"]
    assert len(mine) == len(ref) == 2349
    # ProfileData gives whole nanoseconds; the walk keeps picoseconds
    assert sum(e - s for _, s, e in mine) == pytest.approx(
        sum(e - s for s, e, _ in ref), abs=len(ref))
    assert sorted(s for _, s, _ in mine) == pytest.approx(
        sorted(s for s, _, _ in ref), abs=1.0)


def test_new_metrics_are_declared_for_the_uniform_cell():
    spec = cells.load_spec(ROOT)
    by = {m["name"]: m for m in spec["per_layer"]}
    want = {"queue_wait_p95_ms": ("program_span", "serving", "query_p95_ms"),
            "filter_device_ms": ("device_trace",
                                 "query programs and kernels",
                                 "queries_per_s"),
            "groupby_device_ms": ("device_trace",
                                  "query programs and kernels",
                                  "queries_per_s")}
    for name, (source, layer, moves) in want.items():
        m = by[name]
        assert (m["source"], m["layer"], m["moves"]) == (source, layer,
                                                         moves)
        assert m["unit"] == "ms" and m["better"] == "lower"
        assert m["workloads"] == ["ssb1_uniform_read"]
        assert callable(cells.reader(name))
    assert [m["name"] for m in spec["per_layer"]][-3:] == list(NEW)
