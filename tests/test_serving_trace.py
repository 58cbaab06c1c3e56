"""The serving path's tracing: queue-wait stamps and counters, host spans
in a profiler trace, named query programs with device scopes, and the
engine-wide probe and column-gather counters."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import SSBEngine, generate_ssb
from repro.serving import PARAM_QUERIES, BatchRunner, QueryScheduler, \
    ServeConfig


@pytest.fixture(scope="module")
def tables():
    return generate_ssb(sf=0.002, seed=13)


def _submit_mix(sched, rng):
    names = ["Q2.1", "Q1.1", "Q2.1", "Q3.1", "Q2.1"]
    return [sched.submit(n, PARAM_QUERIES[n].sample(rng)) for n in names]


def test_pumped_tickets_carry_dequeue_stamp_and_batch_id(tables):
    eng = SSBEngine(dict(tables), mode="jspim")
    eng.warm_cache()
    sched = QueryScheduler(eng, ServeConfig(serve_maintained=False))
    try:
        tickets = _submit_mix(sched, np.random.default_rng(1))
        assert sched.pump() == 3           # Q2.1 x3, Q1.1, Q3.1
        info = sched.info()
    finally:
        sched.close()
    for tk in tickets:
        assert tk.response.ok
        assert tk.submitted_at <= tk.dequeued_at <= tk.resolved_at
    ids = [tk.batch_id for tk in tickets]
    assert ids[0] == ids[2] == ids[4] != ids[1] != ids[3]
    assert sorted(set(ids)) == [1, 2, 3]
    assert info["dequeued"] == len(tickets)
    assert info["queue_wait_s"] == pytest.approx(
        sum(tk.dequeued_at - tk.submitted_at for tk in tickets), abs=1e-9)


def _host_spans(path):
    """Every host event named ``serve.*`` / ``engine.*``:
    (name, start, end, stats, line)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("serve.", "engine.")):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats), (plane.name, k)))
    return out


def _inside(child, parent):
    return (child[4] == parent[4] and parent[1] <= child[1]
            and child[2] <= parent[2])


def test_profiler_trace_holds_serving_and_probe_spans(tables, tmp_path):
    eng = SSBEngine(dict(tables), mode="jspim")   # cold: nothing cached
    sched = QueryScheduler(eng, ServeConfig(serve_maintained=False))
    try:
        rng = np.random.default_rng(2)
        n0 = eng.cache_info()["probes_run"]
        with jax.profiler.trace(str(tmp_path)):
            tk = sched.submit("Q2.1", PARAM_QUERIES["Q2.1"].sample(rng))
            assert sched.pump() == 1
        assert tk.response.ok
        # Q2.1 joins part, supplier and date: the pinned snapshot probes
        # each once, and the engine counts those probes
        assert eng.cache_info()["probes_run"] == n0 + 3
        assert eng.cache_info()["misses"] == 0
    finally:
        sched.close()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = _host_spans(path)
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    batch, = by["serve.batch"]
    assert batch[3]["query"] == "Q2.1" and batch[3]["n"] == 1
    assert batch[3]["batch"] == tk.batch_id
    # one request: the profiler reads the one wait back as a number
    assert batch[3]["wait_us"] == round(
        1e6 * (tk.dequeued_at - tk.submitted_at))
    run, = by["serve.run"]
    fetch, = by["serve.fetch"]
    assert run[3] == {"flavor": "batch", "width": 1}
    assert _inside(run, batch) and _inside(fetch, batch)
    assert run[2] <= fetch[1]
    assert any(_inside(c, batch) for c in by["serve.checkout"])
    probes = by["engine.probe"]
    assert sorted(p[3]["dim"] for p in probes) == ["date", "part",
                                                   "supplier"]
    assert all(_inside(p, run) for p in probes)
    # the snapshot gathers each dimension's fact-aligned columns once
    gathers = by["engine.attr_gather"]
    assert sorted(g[3]["dim"] for g in gathers) == ["date", "part",
                                                    "supplier"]
    assert all(_inside(g, run) for g in gathers)
    assert eng.cache_info()["attr_gathers"] == 3 + 3 + 3


def _compiled_text(prog, *args):
    return prog.lower(*args).compile().as_text()


def test_serving_programs_are_named_and_scoped(tables):
    eng = SSBEngine(dict(tables), mode="jspim")
    eng.warm_cache()
    runner = BatchRunner()
    name = "Q2.1"
    fact, attrs, probes = runner._operands(eng, name)
    params = jnp.asarray(np.asarray([PARAM_QUERIES[name].defaults],
                                    np.int32))
    text = _compiled_text(runner._batch_program(name), fact, attrs, probes,
                          params)
    assert text.startswith("HloModule jit_serve_batch_Q2_1")
    for scope in ("filter", "group_key", "aggregate"):
        assert f'op_name="jit(serve_batch_Q2_1)/vmap({scope})/' in text
    text = _compiled_text(runner._single_program(name), fact, attrs, probes,
                          params[0])
    assert text.startswith("HloModule jit_serve_composed_Q2_1")
    assert 'op_name="jit(serve_composed_Q2_1)/filter/' in text
    dims = {d: dict(eng.tables[d].columns) for d in attrs}
    idx = {d: eng.indexes[d] for d in dims}
    text = _compiled_text(runner._mega_program(name), fact, dims, idx,
                          params)
    assert text.startswith("HloModule jit_serve_mega_Q2_1")
    assert 'op_name="jit(serve_mega_Q2_1)/probe/' in text
    hots = {d: eng._hot_codes[d] for d in dims if d in eng._hot_codes}
    text = _compiled_text(eng._full_program(name), fact, dims, idx, hots)
    assert 'op_name="jit(program)/probe/' in text
    assert 'op_name="jit(program)/filter/' in text


def test_snapshot_miss_counts_a_probe_and_a_hit_does_not(tables):
    eng = SSBEngine(dict(tables), mode="jspim")
    n = eng.cache_info()["probes_run"]
    with eng.snapshot() as snap:
        snap.probe_dim("part")                      # snapshot miss
        assert eng.cache_info()["probes_run"] == n + 1
        snap.probe_dim("part")                      # snapshot hit
        assert eng.cache_info()["probes_run"] == n + 1
    eng.probe_dim("part")                           # engine miss
    eng.probe_dim("part")                           # engine hit
    info = eng.cache_info()
    assert info["probes_run"] == n + 2
    assert (info["misses"], info["hits"]) == (1, 1)


def test_probe_counter_loses_no_update_under_threads(tables):
    """Snapshots probe from serving threads; the engine-wide counter is
    locked, so no increment is lost even with a tiny switch interval (an
    unlocked ``+=`` happens to survive this on CPython 3.12; the lock is
    what the language guarantees)."""
    import sys
    import threading

    eng = SSBEngine(dict(tables), mode="jspim")
    n0 = eng.cache_info()["probes_run"]
    n_threads, per = 16, 1000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda: [eng._count_probe() for _ in range(per)])
            for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert eng.cache_info()["probes_run"] == n0 + n_threads * per
