"""The 13 SSB queries as the benchmark defines them, apart from the program.

Each query is a parameter vector's meaning: which dimensions it joins, the
predicates over dimension attributes and fact columns, the measure, the
dense group key, and how traffic draws a random parameter vector.  The
plain reference (``reference.py``) evaluates these definitions with numpy;
the scan byte count (``scan_bytes``) reads the column lists.

Answer format, shared with the system under test: ``(total, groups)`` where
``total`` is the int32 (wrapping) sum of the measure over qualifying rows and
``groups`` an int32 vector over the composite key
``((v0 % c0) * c1 + v1 % c1) * ...`` of the group-by attributes (a query
without group-by returns ``[total]``).  A fact row qualifies when every
joined dimension key resolves to a dimension row, every dimension predicate
holds on that row, and the fact predicate holds.

Parameter vectors and their sampling rules follow SSB rev. 3's query
templates (O'Neil et al.) as parameterized by the serving tier's request
format: one int32 vector per request.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

REGIONS, NATIONS, CITIES = 5, 25, 250
MFGRS, CATEGORIES, BRANDS = 5, 25, 1000
YEARS = (1992, 1998)

FACT_FK = {"customer": "custkey", "supplier": "suppkey",
           "part": "partkey", "date": "orderdate"}


@dataclasses.dataclass(frozen=True)
class Query:
    name: str
    n_params: int
    defaults: tuple[int, ...]
    # dim -> (attribute columns read, predicate(cols, p) -> bool mask)
    dim_filters: dict[str, tuple[tuple[str, ...], Callable]]
    fact_cols: tuple[str, ...]          # fact columns the predicate reads
    fact_filter: Callable | None        # (cols, p) -> bool mask
    measure_cols: tuple[str, ...]
    measure: Callable                   # cols -> int64 values
    group_by: tuple[tuple[str, str, int], ...]
    sampler: Callable[[np.random.Generator], tuple[int, ...]]

    def joined_dims(self) -> tuple[str, ...]:
        return tuple(sorted(set(self.dim_filters)
                            | {d for d, _, _ in self.group_by}))

    def dim_columns(self) -> dict[str, set[str]]:
        """Dimension attribute columns the query reads, per dimension."""
        out: dict[str, set[str]] = {d: set() for d in self.joined_dims()}
        for d, (cols, _) in self.dim_filters.items():
            out[d].update(cols)
        for d, c, _ in self.group_by:
            out[d].add(c)
        return out

    def fact_columns(self) -> tuple[str, ...]:
        """Fact columns the query must read: joined keys, predicate
        columns and measure columns, each once."""
        cols = [FACT_FK[d] for d in self.joined_dims()]
        cols += list(self.fact_cols) + list(self.measure_cols)
        return tuple(dict.fromkeys(cols))

    def sample(self, rng: np.random.Generator) -> tuple[int, ...]:
        p = tuple(int(v) for v in self.sampler(rng))
        if len(p) != self.n_params:
            raise ValueError(f"{self.name}: sampler gave {len(p)} params")
        return p


def _year(rng):
    return int(rng.integers(YEARS[0], YEARS[1] + 1))


def _ym(rng):
    return _year(rng) * 100 + int(rng.integers(1, 13))


def _year_range(rng):
    lo = _year(rng)
    return lo, int(rng.integers(lo, YEARS[1] + 1))


def _disc_qty_range(rng):
    d = int(rng.integers(0, 9))
    q = int(rng.integers(1, 41))
    return d, d + 2, q, q + 9


def _discounted(c):
    return c["extendedprice"].astype(np.int64) * c["discount"]


def _revenue(c):
    return c["revenue"].astype(np.int64)


def _profit(c):
    return c["revenue"].astype(np.int64) - c["supplycost"]


_DQ = ("discount", "quantity")
QUERIES: dict[str, Query] = {}


def _add(q: Query) -> None:
    QUERIES[q.name] = q


# Q1.x: one date join, fact-side discount and quantity predicates
_add(Query(
    "Q1.1", 4, (1993, 1, 3, 25),
    {"date": (("year",), lambda c, p: c["year"] == p[0])},
    _DQ, lambda c, p: ((c["discount"] >= p[1]) & (c["discount"] <= p[2])
                       & (c["quantity"] < p[3])),
    ("extendedprice", "discount"), _discounted, (),
    lambda rng: (_year(rng), (d := int(rng.integers(0, 9))), d + 2,
                 int(rng.integers(10, 51)))))
_add(Query(
    "Q1.2", 5, (199401, 4, 6, 26, 35),
    {"date": (("yearmonthnum",), lambda c, p: c["yearmonthnum"] == p[0])},
    _DQ, lambda c, p: ((c["discount"] >= p[1]) & (c["discount"] <= p[2])
                       & (c["quantity"] >= p[3]) & (c["quantity"] <= p[4])),
    ("extendedprice", "discount"), _discounted, (),
    lambda rng: (_ym(rng), *_disc_qty_range(rng))))
_add(Query(
    "Q1.3", 6, (6, 1994, 5, 7, 26, 35),
    {"date": (("weeknuminyear", "year"),
              lambda c, p: ((c["weeknuminyear"] == p[0])
                            & (c["year"] == p[1])))},
    _DQ, lambda c, p: ((c["discount"] >= p[2]) & (c["discount"] <= p[3])
                       & (c["quantity"] >= p[4]) & (c["quantity"] <= p[5])),
    ("extendedprice", "discount"), _discounted, (),
    lambda rng: (int(rng.integers(1, 53)), _year(rng), *_disc_qty_range(rng))))

# Q2.x: part, supplier and date, grouped by year and brand
_Q2_GROUPS = (("date", "year", 7), ("part", "brand", BRANDS))
_add(Query(
    "Q2.1", 2, (12, 1),
    {"part": (("category",), lambda c, p: c["category"] == p[0]),
     "supplier": (("region",), lambda c, p: c["region"] == p[1])},
    (), None, ("revenue",), _revenue, _Q2_GROUPS,
    lambda rng: (int(rng.integers(0, CATEGORIES)),
                 int(rng.integers(0, REGIONS)))))
_add(Query(
    "Q2.2", 3, (260, 267, 2),
    {"part": (("brand",), lambda c, p: (c["brand"] >= p[0])
              & (c["brand"] <= p[1])),
     "supplier": (("region",), lambda c, p: c["region"] == p[2])},
    (), None, ("revenue",), _revenue, _Q2_GROUPS,
    lambda rng: ((b := int(rng.integers(0, BRANDS - 7))), b + 7,
                 int(rng.integers(0, REGIONS)))))
_add(Query(
    "Q2.3", 2, (260, 3),
    {"part": (("brand",), lambda c, p: c["brand"] == p[0]),
     "supplier": (("region",), lambda c, p: c["region"] == p[1])},
    (), None, ("revenue",), _revenue, _Q2_GROUPS,
    lambda rng: (int(rng.integers(0, BRANDS)),
                 int(rng.integers(0, REGIONS)))))


# Q3.x: customer, supplier and date
def _city_pair(c, p):
    return (c["city"] == p[0]) | (c["city"] == p[1])


def _years(c, p, i=2):
    return (c["year"] >= p[i]) & (c["year"] <= p[i + 1])


_add(Query(
    "Q3.1", 4, (2, 2, 1992, 1997),
    {"customer": (("region",), lambda c, p: c["region"] == p[0]),
     "supplier": (("region",), lambda c, p: c["region"] == p[1]),
     "date": (("year",), _years)},
    (), None, ("revenue",), _revenue,
    (("customer", "nation", NATIONS), ("supplier", "nation", NATIONS),
     ("date", "year", 7)),
    lambda rng: (int(rng.integers(0, REGIONS)), int(rng.integers(0, REGIONS)),
                 *_year_range(rng))))
_add(Query(
    "Q3.2", 4, (14, 14, 1992, 1997),
    {"customer": (("nation",), lambda c, p: c["nation"] == p[0]),
     "supplier": (("nation",), lambda c, p: c["nation"] == p[1]),
     "date": (("year",), _years)},
    (), None, ("revenue",), _revenue,
    (("customer", "city", CITIES), ("supplier", "city", CITIES),
     ("date", "year", 7)),
    lambda rng: (int(rng.integers(0, NATIONS)), int(rng.integers(0, NATIONS)),
                 *_year_range(rng))))
_add(Query(
    "Q3.3", 4, (141, 145, 1992, 1997),
    {"customer": (("city",), _city_pair),
     "supplier": (("city",), _city_pair),
     "date": (("year",), _years)},
    (), None, ("revenue",), _revenue,
    (("customer", "city", CITIES), ("supplier", "city", CITIES),
     ("date", "year", 7)),
    lambda rng: (int(rng.integers(0, CITIES)), int(rng.integers(0, CITIES)),
                 *_year_range(rng))))
_add(Query(
    "Q3.4", 3, (141, 145, 199712),
    {"customer": (("city",), _city_pair),
     "supplier": (("city",), _city_pair),
     "date": (("yearmonthnum",), lambda c, p: c["yearmonthnum"] == p[2])},
    (), None, ("revenue",), _revenue,
    (("customer", "city", CITIES), ("supplier", "city", CITIES),
     ("date", "year", 7)),
    lambda rng: (int(rng.integers(0, CITIES)), int(rng.integers(0, CITIES)),
                 _ym(rng))))


# Q4.x: all four dimensions, profit
def _mfgr_pair(c, p, i=2):
    return (c["mfgr"] == p[i]) | (c["mfgr"] == p[i + 1])


def _year_pair(c, p, i):
    return (c["year"] == p[i]) | (c["year"] == p[i + 1])


def _two_years(rng):
    y = _year(rng)
    return y, min(y + 1, YEARS[1])


_add(Query(
    "Q4.1", 4, (1, 1, 0, 1),
    {"customer": (("region",), lambda c, p: c["region"] == p[0]),
     "supplier": (("region",), lambda c, p: c["region"] == p[1]),
     "part": (("mfgr",), _mfgr_pair)},
    (), None, ("revenue", "supplycost"), _profit,
    (("date", "year", 7), ("customer", "nation", NATIONS)),
    lambda rng: (int(rng.integers(0, REGIONS)), int(rng.integers(0, REGIONS)),
                 int(rng.integers(0, MFGRS)), int(rng.integers(0, MFGRS)))))
_add(Query(
    "Q4.2", 6, (1, 1, 0, 1, 1997, 1998),
    {"customer": (("region",), lambda c, p: c["region"] == p[0]),
     "supplier": (("region",), lambda c, p: c["region"] == p[1]),
     "part": (("mfgr",), _mfgr_pair),
     "date": (("year",), lambda c, p: _year_pair(c, p, 4))},
    (), None, ("revenue", "supplycost"), _profit,
    (("date", "year", 7), ("supplier", "nation", NATIONS),
     ("part", "category", CATEGORIES)),
    lambda rng: (int(rng.integers(0, REGIONS)), int(rng.integers(0, REGIONS)),
                 int(rng.integers(0, MFGRS)), int(rng.integers(0, MFGRS)),
                 *_two_years(rng))))
_add(Query(
    "Q4.3", 5, (1, 6, 3, 1997, 1998),
    {"customer": (("region",), lambda c, p: c["region"] == p[0]),
     "supplier": (("nation",), lambda c, p: c["nation"] == p[1]),
     "part": (("category",), lambda c, p: c["category"] == p[2]),
     "date": (("year",), lambda c, p: _year_pair(c, p, 3))},
    (), None, ("revenue", "supplycost"), _profit,
    (("date", "year", 7), ("supplier", "city", CITIES),
     ("part", "brand", BRANDS)),
    lambda rng: (int(rng.integers(0, REGIONS)), int(rng.integers(0, NATIONS)),
                 int(rng.integers(0, CATEGORIES)), *_two_years(rng))))

QUERY_IDS = tuple(sorted(QUERIES))


def scan_bytes(name: str, n_fact: int, dim_rows: dict[str, int]) -> int:
    """Bytes one evaluation of query ``name`` must read: 4 B per live row
    for each fact column it reads, plus 4 B per row for each dimension
    attribute column it reads.  The same for any implementation."""
    q = QUERIES[name]
    b = 4 * n_fact * len(q.fact_columns())
    for d, cols in q.dim_columns().items():
        b += 4 * dim_rows[d] * len(cols)
    return b
