"""SSB data, generated by the benchmark from a seed (written from SSB rev. 3's
schema and dbgen's distributions; the program's generator is not used).

Every column is int32; strings are dictionary codes.  Every table keeps all
of SSB's columns: lineorder 17, customer 8, supplier 7, part 9, date 17.
Row counts follow the linear scaling: lineorder 6,000,000 x SF, customer
30,000 x SF, supplier 2,000 x SF, part 200,000 x SF, date 2,556 days
(1992-1998).  A dimension's key is its row number, so key k names row k
until an upsert re-points it.

Lineorder is a sequence of orders of 1 to 7 lines (the last one cut to the
row count).  Custkey, orderdate, orderpriority and shippriority belong to
the order; partkey, suppkey and the measures to the line.  Extendedprice
is quantity x the part's retail price, revenue extendedprice x (100 -
discount) / 100, supplycost 6 x retail price / 10, and ordtotalprice the
order's sum of extendedprice x (100 + tax) x (100 - discount) / 10,000.

Foreign keys are drawn uniformly, or Zipf(s) with the exact rank counts
``n x p(rank)`` (largest remainders), placed on keys by a seeded
permutation and shuffled: every seed gives the same multiset of key
frequencies, so the skew-aware probe's shapes do not depend on the seed,
and hotness does not follow key order.  Appended batches re-sample their
foreign keys from live fact rows (an order's from one row, a line's from
another), so the skew holds.
"""
from __future__ import annotations

import numpy as np

from benchkit.ssbq import (BRANDS, CATEGORIES, CITIES, MFGRS, NATIONS,
                           REGIONS, YEARS)

DIMS = ("customer", "supplier", "part", "date")
FACT_COLUMNS = ("orderkey", "linenumber", "custkey", "partkey", "suppkey",
                "orderdate", "orderpriority", "shippriority", "quantity",
                "extendedprice", "ordtotalprice", "discount", "revenue",
                "supplycost", "tax", "commitdate", "shipmode")
N_DATES = 2556
MAX_LINES = 7                 # lines per order, uniform 1..7
PRIORITIES, SHIPMODES = 5, 7
SEGMENTS, COLORS, TYPES, CONTAINERS = 5, 92, 150, 40


def sizes(sf: float) -> dict[str, int]:
    return {"lineorder": int(6_000_000 * sf), "customer": int(30_000 * sf),
            "supplier": int(2_000 * sf), "part": int(200_000 * sf),
            "date": N_DATES}


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """dbgen's p_retailprice in cents, for 0-based part keys."""
    k = partkey.astype(np.int64) + 1
    return (90_000 + (k // 10) % 20_001 + 100 * (k % 1_000)).astype(np.int32)


def _dates() -> dict[str, np.ndarray]:
    k = np.arange(N_DATES, dtype=np.int32)
    year = np.minimum(YEARS[0] + k // 365, YEARS[1]).astype(np.int32)
    dayn = (k % 365 + 1).astype(np.int32)
    month = np.minimum((dayn - 1) // 31 + 1, 12).astype(np.int32)
    dom = ((dayn - 1) % 31 + 1).astype(np.int32)
    dow = ((k + 2) % 7 + 1).astype(np.int32)   # 1992-01-01 was a Wednesday
    return {"datekey": k, "date": k, "dayofweek": dow, "month": month,
            "year": year,
            "yearmonthnum": (year * 100 + month).astype(np.int32),
            "yearmonth": ((year - YEARS[0]) * 12 + month - 1).astype(
                np.int32),
            "daynuminweek": dow, "daynuminmonth": dom, "daynuminyear": dayn,
            "monthnuminyear": month,
            "weeknuminyear": ((k % 365) // 7 + 1).astype(np.int32),
            "sellingseason": ((month % 12) // 3).astype(np.int32),
            "lastdayinweekfl": (dow == 7).astype(np.int32),
            "lastdayinmonthfl": (dom == 31).astype(np.int32),
            "holidayfl": np.isin(dayn, (1, 185, 359, 360)).astype(np.int32),
            "weekdayfl": (dow < 6).astype(np.int32)}


def _geo(rng, n, key):
    region = rng.integers(0, REGIONS, n, dtype=np.int32)
    nation = region * (NATIONS // REGIONS) + rng.integers(
        0, NATIONS // REGIONS, n, dtype=np.int32)
    city = nation * (CITIES // NATIONS) + rng.integers(
        0, CITIES // NATIONS, n, dtype=np.int32)
    keys = np.arange(n, dtype=np.int32)
    return {key: keys, "name": keys.copy(),
            "address": rng.integers(0, 1 << 30, n, dtype=np.int32),
            "city": city, "nation": nation, "region": region,
            "phone": rng.integers(0, 1 << 30, n, dtype=np.int32)}


def dims(rng: np.random.Generator, sf: float) -> dict[str, dict]:
    n = sizes(sf)
    mfgr = rng.integers(0, MFGRS, n["part"], dtype=np.int32)
    category = mfgr * (CATEGORIES // MFGRS) + rng.integers(
        0, CATEGORIES // MFGRS, n["part"], dtype=np.int32)
    brand = category * (BRANDS // CATEGORIES) + rng.integers(
        0, BRANDS // CATEGORIES, n["part"], dtype=np.int32)
    customer = _geo(rng, n["customer"], "custkey")
    customer["mktsegment"] = rng.integers(0, SEGMENTS, n["customer"],
                                          dtype=np.int32)
    supplier = _geo(rng, n["supplier"], "suppkey")
    pk = np.arange(n["part"], dtype=np.int32)
    part = {"partkey": pk, "name": pk.copy(), "mfgr": mfgr,
            "category": category, "brand": brand,
            "color": rng.integers(0, COLORS, n["part"], dtype=np.int32),
            "type": rng.integers(0, TYPES, n["part"], dtype=np.int32),
            "size": rng.integers(1, 51, n["part"], dtype=np.int32),
            "container": rng.integers(0, CONTAINERS, n["part"],
                                      dtype=np.int32)}
    return {"customer": customer, "supplier": supplier, "part": part,
            "date": _dates()}


def _keys(rng, n_keys: int, n: int, zipf_s: float) -> np.ndarray:
    if zipf_s == 0:
        return rng.integers(0, n_keys, n, dtype=np.int32)
    p = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** zipf_s
    p /= p.sum()
    counts = np.floor(n * p).astype(np.int64)
    short = n - int(counts.sum())
    # largest remainders, ties to the lower rank: the same counts for
    # every seed
    order = np.lexsort((np.arange(n_keys), -(n * p - counts)))
    counts[order[:short]] += 1
    placement = rng.permutation(n_keys).astype(np.int32)
    return rng.permutation(np.repeat(placement, counts))


def _orders(rng, n_rows: int) -> np.ndarray:
    """Lines per order, uniform 1..7, the last order cut to ``n_rows``."""
    lines = rng.integers(1, MAX_LINES + 1, n_rows)
    end = np.cumsum(lines)
    k = int(np.searchsorted(end, n_rows))
    lines = lines[:k + 1].copy()
    lines[k] -= int(end[k]) - n_rows
    return lines


def _lines(rng, lines: np.ndarray, first_order: int, order_cols: dict,
           partkey: np.ndarray, suppkey: np.ndarray) -> dict:
    """The lineorder rows of orders with ``lines`` lines each: the order's
    columns repeated over its lines, fresh line measures."""
    n = int(lines.sum())
    n_orders = lines.shape[0]
    order_of = np.repeat(np.arange(n_orders), lines)
    start = np.cumsum(lines) - lines
    quantity = rng.integers(1, 51, n, dtype=np.int32)
    discount = rng.integers(0, 11, n, dtype=np.int32)
    tax = rng.integers(0, 9, n, dtype=np.int32)
    price = retail_price(partkey)
    ext = quantity * price
    charge = (ext.astype(np.int64) * (100 + tax) * (100 - discount)
              // 10_000)
    total = np.bincount(order_of, weights=charge, minlength=n_orders)
    orderdate = order_cols["orderdate"][order_of]
    cols = {"orderkey": (first_order + order_of).astype(np.int32),
            "linenumber": (np.arange(n) - start[order_of] + 1).astype(
                np.int32),
            "custkey": order_cols["custkey"][order_of],
            "partkey": partkey, "suppkey": suppkey,
            "orderdate": orderdate,
            "orderpriority": order_cols["orderpriority"][order_of],
            "shippriority": np.zeros(n, np.int32),
            "quantity": quantity, "extendedprice": ext,
            "ordtotalprice": np.rint(total[order_of]).astype(np.int32),
            "discount": discount,
            "revenue": (ext.astype(np.int64) * (100 - discount)
                        // 100).astype(np.int32),
            "supplycost": (price * 6 // 10).astype(np.int32),
            "tax": tax,
            "commitdate": (orderdate + rng.integers(30, 91, n)).astype(
                np.int32),
            "shipmode": rng.integers(0, SHIPMODES, n, dtype=np.int32)}
    return {k: cols[k] for k in FACT_COLUMNS}


def fact(rng: np.random.Generator, sf: float,
         zipf_s: dict[str, float]) -> dict[str, np.ndarray]:
    """The lineorder table; ``zipf_s`` maps a fact key column to its Zipf
    exponent (0 or absent: uniform)."""
    n = sizes(sf)
    lines = _orders(rng, n["lineorder"])
    rows, n_orders = n["lineorder"], lines.shape[0]
    order_cols = {
        "custkey": _keys(rng, n["customer"], n_orders,
                         float(zipf_s.get("custkey", 0.0))),
        "orderdate": rng.integers(0, n["date"], n_orders, dtype=np.int32),
        "orderpriority": rng.integers(0, PRIORITIES, n_orders,
                                      dtype=np.int32)}
    partkey = _keys(rng, n["part"], rows, float(zipf_s.get("partkey", 0.0)))
    suppkey = _keys(rng, n["supplier"], rows,
                    float(zipf_s.get("suppkey", 0.0)))
    return _lines(rng, lines, 0, order_cols, partkey, suppkey)


def fact_batch(rng: np.random.Generator, live: dict[str, np.ndarray],
               n_live: int, n: int) -> dict[str, np.ndarray]:
    """``n`` appended rows, in new orders whose keys continue the
    sequence: an order's custkey and orderdate re-sampled from one live
    fact row (of the first ``n_live``), each line's partkey and suppkey
    from another, fresh measures."""
    lines = _orders(rng, n)
    src = rng.integers(0, n_live, lines.shape[0])
    order_cols = {"custkey": live["custkey"][src],
                  "orderdate": live["orderdate"][src],
                  "orderpriority": rng.integers(0, PRIORITIES,
                                                lines.shape[0],
                                                dtype=np.int32)}
    idx = rng.integers(0, n_live, n)
    first = int(live["orderkey"][n_live - 1]) + 1
    return _lines(rng, lines, first, order_cols, live["partkey"][idx],
                  live["suppkey"][idx])
