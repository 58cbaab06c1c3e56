"""The benchmark's reference agrees with the program's numpy oracle
(``LogicalModel``) on all 13 queries, default and sampled parameters, on the
uniform and the Zipf data, and tracks a churn sequence epoch by epoch."""
import numpy as np
import pytest

from benchkit import ssbgen
from benchkit.reference import Reference
from benchkit.ssbq import FACT_FK, QUERIES, QUERY_IDS

SF = 0.005
ZIPF = {"custkey": 1.5, "partkey": 1.5, "suppkey": 1.5}


def _data(seed, zipf):
    rng = np.random.default_rng(seed)
    return ssbgen.dims(rng, SF), ssbgen.fact(rng, SF, zipf)


def _model(dims, fact):
    from repro.engine import Table
    from repro.serving import LogicalModel

    tables = {"lineorder": Table.from_numpy(fact)}
    tables.update({d: Table.from_numpy(c) for d, c in dims.items()})
    return LogicalModel(tables)


def _same(a, b):
    return int(a[0]) == int(b[0]) and np.array_equal(
        np.asarray(a[1]).astype(np.int64), np.asarray(b[1]).astype(np.int64))


@pytest.mark.parametrize("zipf", [{}, ZIPF], ids=["uniform", "zipf"])
def test_reference_matches_logical_model(zipf):
    dims, fact = _data(7, zipf)
    ref = Reference(fact, dims, fact["orderkey"].shape[0])
    model = _model(dims, fact)
    rng = np.random.default_rng(11)
    for name in QUERY_IDS:
        for p in [QUERIES[name].defaults] + [QUERIES[name].sample(rng)
                                             for _ in range(3)]:
            got = ref.answer(name, p, 0)
            want = model.param_query(name, p)
            assert _same(got, want), (name, p, got[0], want[0])


def test_zipf_keys_are_skewed_and_permuted():
    _, fact = _data(3, ZIPF)
    counts = np.bincount(fact["custkey"])
    assert counts.max() > 0.3 * counts.sum()       # s=1.5: a hot head
    # hotness does not follow key order
    assert np.any(np.diff(counts) > 0)
    _, uni = _data(3, {})
    c = np.bincount(uni["custkey"])
    assert c.max() < 2 * c.mean()


def test_reference_tracks_churn_epoch_by_epoch():
    dims, fact = _data(5, ZIPF)
    n0 = fact["orderkey"].shape[0]
    batches, live = [], n0
    buf = {k: np.concatenate([v, np.zeros(6 * 50, np.int32)])
           for k, v in fact.items()}
    rng = np.random.default_rng(9)
    for _ in range(6):
        b = ssbgen.fact_batch(rng, buf, live, 50)
        for k, v in b.items():
            buf[k][live:live + 50] = v
        batches.append(b)
        live += 50
    ref = Reference(buf, dims, n0)
    model = _model(dims, fact)
    frozen = {0: model.freeze()}
    epoch = 0
    sizes = ssbgen.sizes(SF)
    for i, b in enumerate(batches):
        epoch += 1
        model.append_fact(b)
        ref.append(epoch, 50)
        frozen[epoch] = model.freeze()
        if i % 2 == 1:
            dim = ("customer", "supplier", "part", "date")[i // 2 % 4]
            keys = np.unique(buf[FACT_FK[dim]][rng.integers(0, n0, 6)])
            rows = rng.integers(0, sizes[dim], keys.shape[0])
            # an upsert whose compaction publishes a second epoch in the
            # same mutation: the state is recorded at the last one, and
            # the one between, never visible to a reader, maps to the old
            epoch += 2
            frozen[epoch - 1] = frozen[epoch - 2]
            for k, r in zip(keys, rows):
                model.repoint(dim, int(k), int(r))
            ref.upsert(epoch, dim, keys, rows)
            frozen[epoch] = model.freeze()
    qrng = np.random.default_rng(13)
    for e, m in frozen.items():
        for name in QUERY_IDS:
            p = QUERIES[name].sample(qrng)
            assert _same(ref.answer(name, p, e), m.param_query(name, p)), \
                (e, name, p)
