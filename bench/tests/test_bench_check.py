"""The check that decides ``correct``: the float32 control fails it, and a
run with its timed path broken underneath comes out not correct.

These drive the whole run but the look for a chip, at a size a CPU test
holds: SF 0.005 and two query streams for two seconds.  The writer's path
runs in a churn cell the test adds to the spec (``ssb_skew_sf1`` under the
``streams8_ingest`` mix), as a later benchmark PR would add it.
"""
import dataclasses
import time

import numpy as np
import pytest

from benchkit import cells, harness, report, ssbgen
from benchkit.reference import Reference
from benchkit.ssbq import QUERIES, QUERY_IDS

SF = 0.005


CHURN = {"name": "ssb1_zipf_churn", "config": "ssb_skew_sf1",
         "traffic": "streams8_ingest", "chips": 1, "why": "test"}
SKEW = {"name": "ssb_skew_sf1", "file": "bench/configs/ssb_skew_sf1.json"}


def _cell(name, **traffic):
    spec = cells.load_spec()
    spec["workloads"].append(CHURN)
    if SKEW["name"] not in {c["name"] for c in spec["configs"]}:
        spec["configs"].append(SKEW)
    c = cells.resolve(spec, name)
    t = dict(c.traffic, query_streams=2, check_sample=16, **traffic)
    if t.get("appends_per_s"):
        t["append_rows"] = 30       # SF 1's 0.1% of the table per append
    return dataclasses.replace(c, config=dict(c.config, sf=SF), traffic=t)


def _correct(cell, seed=4_000_000_007):
    res = harness.run(cell, seed, 2.0, None, time.monotonic())
    ok, shown = report.check_lines(res["check"])
    return ok, res


def test_float32_control_is_caught_at_cell_shape():
    rng = np.random.default_rng(21)
    dims = ssbgen.dims(rng, 0.05)
    fact = ssbgen.fact(rng, 0.05, {"custkey": 1.5, "partkey": 1.5,
                                   "suppkey": 1.5})
    ref = Reference(fact, dims, fact["orderkey"].shape[0])
    qrng = np.random.default_rng(5)
    differ = 0
    for name, _ in [(n, i) for n in QUERY_IDS for i in range(4)]:
        p = QUERIES[name].sample(qrng)
        exact = ref.answer(name, p, 0)
        low = ref.answer(name, p, 0, accumulate="float32")
        differ += not (exact[0] == low[0]
                       and np.array_equal(exact[1], low[1]))
    assert differ >= 3


@pytest.mark.parametrize("name", ["ssb1_uniform_read", "ssb1_zipf_churn"])
def test_sound_run_is_correct_and_its_control_is_not(name):
    """A whole run at test size is correct, and the same check with the
    float32 reference's answers in the program's place is not."""
    cell = _cell(name)
    res = harness.run(cell, 4_000_000_011, 2.0, None, time.monotonic(),
                      control=True)
    ok, shown = report.check_lines(res["check"])
    assert ok, res["check"]
    assert shown["wrong_answers"]["value"] == 0
    c_ok, c_shown = report.check_lines(res["control_check"])
    assert not c_ok
    assert c_shown["wrong_answers"]["value"] > 0
    assert c_shown["sampled"]["value"] == shown["sampled"]["value"]
    assert res["e2e"]["queries_per_s"] > 0


def test_altered_answer_is_not_correct(monkeypatch):
    from repro.serving.batch import BatchRunner

    real = BatchRunner.run_batch

    def altered(self, *a, **k):
        return [(t + 1, g) for t, g in real(self, *a, **k)]

    monkeypatch.setattr(BatchRunner, "run_batch", altered)
    ok, res = _correct(_cell("ssb1_uniform_read"))
    assert not ok
    assert res["check"]["wrong_answers"] > 0


def test_churn_run_is_correct():
    ok, res = _correct(_cell("ssb1_zipf_churn"))
    assert ok, res["check"]
    assert len(res["check"]["epochs"]) >= 1
    assert res["ctx"]["fact_append"][1]["appends"] > 4
    # every write due in the window is attempted and applied
    assert res["failed"] == 0
    assert res["e2e"]["append_p95_ms"] > 0


@pytest.mark.parametrize("fault", ["append_dropped", "upsert_dropped"])
def test_write_left_unapplied_is_not_correct(monkeypatch, fault):
    from repro.engine import SSBEngine

    if fault == "append_dropped":
        monkeypatch.setattr(SSBEngine, "append_fact_rows",
                            lambda self, rows, **k: {"appended": 0})
    else:
        monkeypatch.setattr(SSBEngine, "ingest",
                            lambda self, *a, **k: None)
    # upserts every append, so a dropped one shows within two seconds
    ok, res = _correct(_cell("ssb1_zipf_churn", upsert_every=1))
    assert not ok
    assert res["check"]["wrong_answers"] > 0


def test_writes_due_but_never_started_count_as_failed(monkeypatch):
    """Offered far above what the writer sustains, with no time to drain:
    every write due in the window is attempted, and those never started
    are failed, not dropped from the count."""
    monkeypatch.setattr(harness, "DRAIN_S", 0.0)
    cell = _cell("ssb1_zipf_churn", appends_per_s=500)
    res = harness.run(cell, 4_000_000_013, 2.0, None, time.monotonic())
    n_writes = 1000 + 1000 // cell.traffic["upsert_every"]
    assert res["attempted"] >= n_writes
    assert res["failed"] > n_writes // 2
