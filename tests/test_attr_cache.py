"""The fact-aligned dimension-column cache (``_QueryRunner.dim_attrs``).

Parity: every path that reads the cached columns answers bit-identically
to the paths that gather dimension predicates and group columns through
the probe's rows inside the program (``run_eager``, the cache-cold full
program, the mega flavors) and to the numpy oracle.  Lifetime: the cache
lives and dies with the probe entry it was gathered from, across upserts,
fact appends, compaction, invalidation and snapshots.  Column set: the
templates read no dimension column outside ``ATTR_COLUMNS``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import SSB_QUERIES, SSBEngine, generate_ssb
from repro.engine.queries import ATTR_COLUMNS, _filter_aggregate, \
    _gather_attrs
from repro.engine.ssb import generate_fact_batch
from repro.serving import PARAM_QUERIES, BatchRunner, LogicalModel

QUERIES = sorted(SSB_QUERIES)


@pytest.fixture(scope="module")
def tables():
    return generate_ssb(sf=0.002, seed=21)


@pytest.fixture(scope="module")
def engine(tables):
    eng = SSBEngine(dict(tables), mode="jspim")
    eng.warm_cache()
    return eng


def _same(a, b):
    return int(a[0]) == int(b[0]) and np.array_equal(np.asarray(a[1]),
                                                     np.asarray(b[1]))


def _params(name, n, seed):
    rng = np.random.default_rng(seed)
    return [PARAM_QUERIES[name].sample(rng) for _ in range(n)]


# -- column set ---------------------------------------------------------------

class _Recorder:
    """A table that records every column a predicate reads."""

    def __init__(self):
        self.read = set()

    def __getitem__(self, col):
        self.read.add(col)
        return np.arange(8, dtype=np.int32)


def _reads(dim_filter, *args):
    t = _Recorder()
    dim_filter(t, *args)
    return t.read


def test_ssb_queries_read_exactly_the_cached_columns():
    read = {d: set() for d in ATTR_COLUMNS}
    for spec in SSB_QUERIES.values():
        for dim, f in spec.dim_filters.items():
            read[dim] |= _reads(f)
        for dim, col, _ in spec.group_by:
            read[dim].add(col)
    assert read == {d: set(c) for d, c in ATTR_COLUMNS.items()}


@pytest.mark.parametrize("name", QUERIES)
def test_param_queries_read_only_cached_columns(name):
    pq = PARAM_QUERIES[name]
    vectors = [pq.defaults] + _params(name, 8, seed=5)
    for p in vectors:
        for dim, f in pq.dim_filters.items():
            assert _reads(f, p) <= set(ATTR_COLUMNS[dim]), (dim, p)
    for dim, col, _ in SSB_QUERIES[name].group_by:
        assert col in ATTR_COLUMNS[dim]


# -- parity ---------------------------------------------------------------------

@pytest.mark.parametrize("name", QUERIES)
def test_run_matches_in_program_gather(engine, name):
    got = engine.run(name)
    assert _same(got, engine.run_eager(name))
    assert _same(got, engine.run(name, use_cache=False))
    assert _same(got, engine.run(name, fusion="mega"))
    assert _same(got, engine.run(name, fusion="composed"))


def test_run_all_matches_in_program_gather(engine):
    suite = engine.run_all(fusion="mega")            # cached: one program
    cold = engine.run_all(fusion="mega", use_cache=False)
    composed = engine.run_all(fusion="composed")
    for name in QUERIES:
        want = engine.run_eager(name)
        for got in (suite, cold, composed):
            assert _same(got[name], want), name


@pytest.mark.parametrize("name", QUERIES)
def test_serving_flavors_match_in_program_gather(engine, name):
    """The batch flavor at widths 1, 2 and 4 and the composed flavor read
    the cached columns; the mega flavor and a plain jit of the tail with
    dimension columns gather in the program.  All agree bit for bit."""
    pq = PARAM_QUERIES[name]
    spec = SSB_QUERIES[name]
    fact = dict(engine.tables["lineorder"].columns)
    dims = {d: dict(engine.tables[d].columns) for d in spec.joined_dims()}
    probes = {d: engine.probe_dim(d) for d in spec.joined_dims()}
    gather_in_program = jax.jit(
        lambda p: _filter_aggregate(pq.bind(p), fact, dims, probes))
    params = _params(name, 4, seed=QUERIES.index(name))
    want = [gather_in_program(jnp.asarray(p, jnp.int32)) for p in params]
    runner = BatchRunner()
    for n in (1, 2, 4):
        got = runner.run_batch(engine, name, params[:n])
        assert all(_same(g, w) for g, w in zip(got, want[:n])), n
    for flavor in ("composed", "mega"):
        got = runner.run_batch(engine, name, params, flavor=flavor)
        assert all(_same(g, w) for g, w in zip(got, want)), flavor


# -- lifetime -------------------------------------------------------------------

NAMES = ("Q1.2", "Q2.1", "Q3.1", "Q4.3")   # every dimension joined


def _check_oracle(eng, model):
    for name in NAMES:
        assert _same(eng.run(name), model.query(name)), name


def _gathers(eng):
    return eng.cache_info()["attr_gathers"]


def test_upsert_regathers_only_that_dimension(tables):
    eng = SSBEngine(dict(tables), mode="jspim")
    model = LogicalModel(eng.tables)
    eng.warm_cache()
    n0 = _gathers(eng)
    keys = np.asarray(tables["supplier"]["suppkey"])[:6]
    rows = np.arange(6, dtype=np.int32)[::-1]
    with eng.snapshot() as snap:
        before = {n: snap.run(n) for n in NAMES}
        eng.ingest("supplier", keys, rows, op="upsert", auto_compact=False)
        for k, r in zip(keys, rows):
            model.repoint("supplier", int(k), int(r))
        assert "supplier" not in eng._attr_cache
        _check_oracle(eng, model)
        assert _gathers(eng) == n0 + len(ATTR_COLUMNS["supplier"])
        # the snapshot keeps its own epoch's columns, bit for bit
        assert all(_same(snap.run(n), before[n]) for n in NAMES)
    eng.compact("supplier")
    _check_oracle(eng, model)
    assert _gathers(eng) == n0 + 2 * len(ATTR_COLUMNS["supplier"])


def test_invalidate_regathers(tables):
    eng = SSBEngine(dict(tables), mode="jspim")
    eng.warm_cache()
    n0 = _gathers(eng)
    eng.invalidate_probe_cache("part")
    assert sorted(eng._attr_cache) == ["customer", "date", "supplier"]
    eng.run("Q2.1")
    assert _gathers(eng) == n0 + len(ATTR_COLUMNS["part"])
    eng.invalidate_probe_cache()
    assert eng._attr_cache == {}
    eng.warm_cache()
    assert _gathers(eng) == n0 + 15


def test_fact_append_splices_the_tail(tables):
    """Appends gather only the tail: the cached columns equal a full
    gather through the extended probe, capacity growth included, and the
    answers match the oracle; a snapshot from before keeps its answers."""
    eng = SSBEngine(dict(tables), mode="jspim")
    model = LogicalModel(eng.tables)
    eng.warm_cache()
    n0 = _gathers(eng)
    rng = np.random.default_rng(3)
    snap = eng.snapshot()
    before = {n: snap.run(n) for n in NAMES}
    grew = False
    for _ in range(6):
        cols = generate_fact_batch(eng.tables, 700, rng)
        grew |= eng.append_fact_rows(cols)["capacity_grew"]
        model.append_fact(cols)
    assert grew
    assert _gathers(eng) == n0
    for dim, (probe, table, attrs) in eng._attr_cache.items():
        assert probe is eng._probe_cache[dim] and table is eng.tables[dim]
        full = _gather_attrs({c: table[c] for c in attrs}, probe[1])
        for c in attrs:
            assert np.array_equal(np.asarray(attrs[c]), np.asarray(full[c]))
    _check_oracle(eng, model)
    assert all(_same(snap.run(n), before[n]) for n in NAMES)
    snap.release()


def test_warm_snapshot_serves_from_cache(tables):
    """After ``warm_cache`` a snapshot and the serving batch path gather
    nothing: every read is a hit, counted in the engine's counters."""
    eng = SSBEngine(dict(tables), mode="jspim")
    eng.warm_cache()
    info0 = eng.cache_info()
    runner = BatchRunner()
    with eng.snapshot() as snap:
        assert snap.cache_info()["attr_dims"] == sorted(ATTR_COLUMNS)
        for name in NAMES:
            runner.run_batch(snap, name, _params(name, 2, seed=1))
    info1 = eng.cache_info()
    assert info1["attr_gathers"] == info0["attr_gathers"]
    assert info1["attr_hits"] == info0["attr_hits"] + sum(
        len(SSB_QUERIES[n].joined_dims()) for n in NAMES)


def test_snapshot_miss_gathers_into_its_own_memo(tables):
    eng = SSBEngine(dict(tables), mode="jspim")
    with eng.snapshot() as snap:
        snap.dim_attrs("date")
        assert snap.cache_info()["attr_dims"] == ["date"]
        snap.dim_attrs("date")
        info = eng.cache_info()
        assert (info["attr_gathers"], info["attr_hits"]) == (3, 1)
    assert eng._attr_cache == {}
    with pytest.raises(RuntimeError, match="released"):
        snap.dim_attrs("date")


def test_uncached_probe_is_gathered_not_kept(tables):
    eng = SSBEngine(dict(tables), mode="jspim")
    found, row = eng.probe_dim("date")
    other = (found, row + 0)
    attrs = eng.dim_attrs("date", other)
    assert "date" not in eng._attr_cache
    assert set(attrs) == set(ATTR_COLUMNS["date"])
    assert eng.cache_info()["attr_gathers"] == 3


def test_attr_counters_lose_no_update_under_threads(tables):
    """Snapshots count gathers and hits from serving threads into the
    engine's counters, under the engine's lock."""
    import sys
    import threading

    eng = SSBEngine(dict(tables), mode="jspim")
    n_threads, per = 16, 1000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda: [eng._count_attrs(gathers=1, hits=2)
                            for _ in range(per)])
            for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    info = eng.cache_info()
    assert info["attr_gathers"] == n_threads * per
    assert info["attr_hits"] == 2 * n_threads * per
