"""The 13 SSB queries (Q1.1–Q4.3), spec-driven, with pluggable join engine.

Modes:
  * "jspim"     — joins offloaded to the JSPIM path (prebuilt DimIndex probe);
                  dimension predicates applied while streaming results back
                  (§4.1.5: filter-on-the-fly during PIM→CPU streaming).
  * "baseline"  — compiled sort-merge joins (DuckDB-stand-in on this host).
  * "pid"       — partitioned-hash joins (PID-Join-style partition passes).

Every query returns (total, groups) where ``groups`` is a dense vector over a
small composite group-key space (segment-summed revenue), so baseline/jspim
agreement is exact and testable.

Execution pipeline (DESIGN.md §4):

  * **Cross-query probe cache** — fact FK columns are query-independent, so
    each dimension is probed once per engine and the (found, dim_row) pair
    is reused by every query that touches the dimension.  The §3.2.3 update
    commands (``entry_update`` / ``index_update`` / ``table_update``) go
    through the engine and invalidate the affected dimension's cache entry.
  * **Fact-aligned column cache** — the dimension columns the templates
    read (``ATTR_COLUMNS``: 12 int32 columns) are gathered once through
    each cached ``dim_row`` (``dim_attrs``), so the query programs compare
    and combine fact-length columns elementwise instead of gathering a
    dimension-sized mask or group column through the rows per query.  An
    entry is keyed on the probe entry it was gathered from and on the
    dimension ``Table`` it read, lives and dies with that probe entry
    (invalidation drops it, a fact append splices the gathered tail in,
    snapshots freeze it), and costs 4 B per fact row per column.
  * **Fused per-query programs** — each ``QuerySpec`` compiles (once) into a
    single jitted filter→mask→measure→segment-sum program consuming the
    cached probes, so a warm query is one XLA dispatch.  A second "full"
    flavor folds the probe itself (and, on the Pallas path, the fused
    probe+predicate kernel) into the same program for cache-cold runs.
  * **Skew-adaptive probe scheduling** (DESIGN.md §6) — at engine build,
    ``build_dim_index`` records the fact FK column's skew on
    ``BuildStats.fact_skew`` and ``core.planner.plan_probe`` picks a probe
    schedule per dimension (gathered / stream / deduped / hot_cold) from
    the cost model; both ``probe_dim`` and the cache-cold fused programs
    execute the planned schedule.  ``schedule=`` forces one everywhere.
  * **Streaming ingest** (DESIGN.md §7) — ``append_rows`` / ``ingest``
    absorb dimension inserts/deletes/upserts into a per-dimension delta
    buffer (``core/delta.py``) instead of rebuilding; every probe path
    overlays the delta, the affected dimension's cached probes drop, and
    ``core.planner.plan_compaction`` prices the overlay tax against a
    bucket-local merge to decide when the delta folds back into the main
    table.
  * **Fact-side streaming append** (DESIGN.md §8) — ``append_fact_rows``
    lands new lineorder rows in a pow2-bucketed capacity tail
    (``table.append_tail``) and *extends* the probe cache instead of
    invalidating it: only the padded tail is probed, under each
    dimension's already-planned schedule with the delta overlay included,
    and spliced into the cached ``(found, dim_row)`` arrays
    (``join.extend_cached_probe`` — one dispatch per dimension).  A
    monotone ``fact_epoch`` stamps every cache entry so consumers always
    see a consistent snapshot; after heavy append the fact-side skew is
    re-measured and drifted dimensions re-planned
    (``planner.skew_drift`` — the ROADMAP skew-drift item).
  * **run_all** — the batched entry point: probes each dimension at most
    once and executes all 13 compiled programs against the shared cache.
  * **MVCC epoch snapshots** (DESIGN.md §9) — ``snapshot()`` freezes one
    consistent image (tables + indexes + deltas + plans + probe cache) as
    an ``EpochSnapshot`` that answers queries through the same compiled
    machinery (``_QueryRunner``) while ingest advances the engine's head
    image and publishes each step with an atomic epoch bump.  Donation
    (the in-place fact-table write, probe-cache splice and compaction
    merge) is gated on buffer-generation refcounts: a generation pinned
    by a live snapshot is never donated, so stale snapshots stay valid
    and bit-identical until released.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import weakref
from functools import partial
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import hash_table as _ht
from repro.core.delta import TOMBSTONE, delta_is_empty, delta_stats
from repro.core.dictionary import encode
from repro.core.lookup import build_hot_table, hot_hit_count
from repro.core.planner import (FACT_REMEASURE_FRAC, TOP_SHARE_DRIFT,
                                CompactionPlan, FactAppendPlan, SchedulePlan,
                                plan_compaction, plan_fact_append,
                                plan_probe, plan_query, refine_plan,
                                skew_drift)
from repro.core.policy import ExecutionPolicy, resolve_policy
from repro.core.skew import measure_skew, top_keys
from repro.engine import baselines
from repro.engine.join import (DimIndex, build_dim_index, compact_index,
                               effective_index, extend_cached_probe,
                               extend_cached_probe_donated, ingest_index,
                               lookup, lookup_filtered)
from repro.kernels import fused_query
from repro.engine.table import Table, pad_batch, tail_bucket

FACT_FK = {"customer": "custkey", "supplier": "suppkey",
           "part": "partkey", "date": "orderdate"}
DIM_PK = {"customer": "custkey", "supplier": "suppkey",
          "part": "partkey", "date": "datekey"}


def _check_batch_col(arg: str, values, *,
                     expect_len: int | None = None) -> np.ndarray:
    """API-boundary validation of one host batch column.

    Raises ``ValueError`` naming the offending argument — mis-shaped or
    wrong-dtype batches must die here with a readable error, not deep
    inside a jitted program with an opaque shape message.  This is also a
    durability requirement: WAL replay trusts recorded batches, so only
    batches that passed this gate may ever be logged.
    """
    a = np.asarray(values)
    if a.dtype.kind not in "iu":
        raise ValueError(f"{arg}: expected an integer array, got dtype "
                         f"{a.dtype}")
    if a.ndim != 1:
        raise ValueError(f"{arg}: expected a 1-D array, got shape "
                         f"{tuple(a.shape)}")
    if a.size and (int(a.min()) < -(2 ** 31)
                   or int(a.max()) > 2 ** 31 - 1):
        raise ValueError(f"{arg}: values exceed the engine's int32 key "
                         "space")
    if expect_len is not None and a.shape[0] != expect_len:
        raise ValueError(f"{arg}: length {a.shape[0]} != {expect_len} "
                         "(ragged batch)")
    return a.astype(np.int32, copy=False)


@dataclasses.dataclass(frozen=True)
class MutationEvent:
    """One published mutation, as delivered to registered hooks.

    ``kind`` is the WAL record kind (``ingest`` / ``append_rows`` /
    ``append_fact_rows`` / ``compact`` / ``raw_update``), ``meta`` and
    ``arrays`` the validated batch exactly as the WAL would log it, and
    ``epoch`` / ``fact_epoch`` the engine counters at delivery — i.e.
    *after* the mutation published, so a hook that finishes processing
    the event is exactly as fresh as the engine.  Delivery happens under
    the engine's mutation lock, at the same call sites as the WAL's
    post-publish hook (``_wal_publish``), in mutation order.
    """

    kind: str
    meta: dict
    arrays: dict
    epoch: int
    fact_epoch: int


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    name: str
    dim_filters: dict[str, Callable[[Table], jax.Array]]
    fact_filter: Callable[[Table], jax.Array] | None
    measure: Callable[[Table], jax.Array]
    group_by: tuple[tuple[str, str, int], ...] = ()  # (dim, col, cardinality)

    def joined_dims(self) -> tuple[str, ...]:
        return tuple(sorted(set(self.dim_filters)
                            | {d for d, _, _ in self.group_by}))


def _between(col, lo, hi):
    return lambda t: (t[col] >= lo) & (t[col] <= hi)


def _eq(col, v):
    return lambda t: t[col] == v


def _in(col, vals):
    def f(t):
        m = jnp.zeros_like(t[col], bool)
        for v in vals:
            m = m | (t[col] == v)
        return m
    return f


def _rev(t):
    return t["revenue"]


def _profit(t):
    return t["revenue"] - t["supplycost"]


def _discounted(t):
    return t["extendedprice"] * t["discount"]


SSB_QUERIES: dict[str, QuerySpec] = {}


def _q(name, dim_filters, fact_filter, measure, group_by=()):
    SSB_QUERIES[name] = QuerySpec(name, dim_filters, fact_filter, measure,
                                  tuple(group_by))


# --- Q1.x: filter-heavy, single date join -------------------------------
_q("Q1.1", {"date": _eq("year", 1993)},
   lambda t: (t["discount"] >= 1) & (t["discount"] <= 3) & (t["quantity"] < 25),
   _discounted)
_q("Q1.2", {"date": _eq("yearmonthnum", 199401)},
   lambda t: (t["discount"] >= 4) & (t["discount"] <= 6)
   & (t["quantity"] >= 26) & (t["quantity"] <= 35),
   _discounted)
_q("Q1.3", {"date": lambda t: (t["weeknuminyear"] == 6) & (t["year"] == 1994)},
   lambda t: (t["discount"] >= 5) & (t["discount"] <= 7)
   & (t["quantity"] >= 26) & (t["quantity"] <= 35),
   _discounted)
# --- Q2.x: part ⋈ supplier ⋈ date ----------------------------------------
_q("Q2.1", {"part": _eq("category", 12), "supplier": _eq("region", 1)},
   None, _rev, [("date", "year", 7), ("part", "brand", 1000)])
_q("Q2.2", {"part": _between("brand", 260, 267), "supplier": _eq("region", 2)},
   None, _rev, [("date", "year", 7), ("part", "brand", 1000)])
_q("Q2.3", {"part": _eq("brand", 260), "supplier": _eq("region", 3)},
   None, _rev, [("date", "year", 7), ("part", "brand", 1000)])
# --- Q3.x: customer ⋈ supplier ⋈ date -------------------------------------
_q("Q3.1", {"customer": _eq("region", 2), "supplier": _eq("region", 2),
            "date": _between("year", 1992, 1997)},
   None, _rev, [("customer", "nation", 25), ("supplier", "nation", 25),
                ("date", "year", 7)])
_q("Q3.2", {"customer": _eq("nation", 14), "supplier": _eq("nation", 14),
            "date": _between("year", 1992, 1997)},
   None, _rev, [("customer", "city", 250), ("supplier", "city", 250),
                ("date", "year", 7)])
_q("Q3.3", {"customer": _in("city", (141, 145)), "supplier": _in("city", (141, 145)),
            "date": _between("year", 1992, 1997)},
   None, _rev, [("customer", "city", 250), ("supplier", "city", 250),
                ("date", "year", 7)])
_q("Q3.4", {"customer": _in("city", (141, 145)), "supplier": _in("city", (141, 145)),
            "date": _eq("yearmonthnum", 199712)},
   None, _rev, [("customer", "city", 250), ("supplier", "city", 250),
                ("date", "year", 7)])
# --- Q4.x: all four dims ----------------------------------------------------
_q("Q4.1", {"customer": _eq("region", 1), "supplier": _eq("region", 1),
            "part": _in("mfgr", (0, 1))},
   None, _profit, [("date", "year", 7), ("customer", "nation", 25)])
_q("Q4.2", {"customer": _eq("region", 1), "supplier": _eq("region", 1),
            "part": _in("mfgr", (0, 1)), "date": _in("year", (1997, 1998))},
   None, _profit, [("date", "year", 7), ("supplier", "nation", 25),
                   ("part", "category", 25)])
_q("Q4.3", {"customer": _eq("region", 1), "supplier": _eq("nation", 6),
            "part": _eq("category", 3), "date": _in("year", (1997, 1998))},
   None, _profit, [("date", "year", 7), ("supplier", "city", 250),
                   ("part", "brand", 1000)])

# The dimension columns the templates read, in their predicates (the
# parameterized variants of ``serving/params.py`` included) and group
# keys: the fact-aligned column cache gathers exactly these per dimension.
ATTR_COLUMNS: dict[str, tuple[str, ...]] = {
    "customer": ("region", "nation", "city"),
    "supplier": ("region", "nation", "city"),
    "part": ("mfgr", "category", "brand"),
    "date": ("year", "yearmonthnum", "weeknuminyear"),
}


# ---------------------------------------------------------------------------
# jitted probe primitives (shared across engines; cached by jax by shapes)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("impl", "plan"))
def _jspim_probe(index: DimIndex, fk: jax.Array,
                 hot_codes: jax.Array | None = None, *,
                 impl: str = "xla", plan: SchedulePlan | None = None):
    pr = lookup(index, fk, impl=impl, plan=plan, hot_codes=hot_codes)
    return pr.found, jnp.where(pr.found, pr.payload, -1)


@jax.jit
def _sort_merge_probe(fk: jax.Array, dk: jax.Array):
    return baselines.sort_merge_join_unique(fk, dk)


@jax.jit
def _pid_probe(fk: jax.Array, dk: jax.Array):
    return baselines.partitioned_hash_join_unique(fk, dk)


@jax.jit
def _gather_attrs(cols: dict, row: jax.Array) -> dict:
    """Each dimension column gathered through a probe's ``dim_row``: the
    fact-aligned columns.  A miss (``dim_row`` -1) reads row 0's value,
    as the query programs' clipped gather does, and ``found`` masks it."""
    n = next(iter(cols.values())).shape[0]
    r = jnp.clip(row, 0, n - 1)
    return {k: c[r] for k, c in cols.items()}


@partial(jax.jit, static_argnames=("n_tail",))
def _extend_attrs(attrs: dict, cols: dict, row: jax.Array,
                  start: jax.Array, *, n_tail: int) -> dict:
    """Fact-aligned columns after a fact append: ``row`` is the extended
    probe, whose ``n_tail`` rows at ``start`` are gathered and spliced in
    (a copy, never a donation: snapshots and callers alias the columns).
    When the fact capacity grew, the new padding reads row 0's value,
    exactly what a full re-gather of its -1 rows would read."""
    n = next(iter(cols.values())).shape[0]
    r = jnp.clip(jax.lax.dynamic_slice(row, (start,), (n_tail,)), 0, n - 1)
    out = {}
    for k, a in attrs.items():
        c = cols[k]
        if a.shape[0] != row.shape[0]:
            a = jnp.concatenate(
                [a, jnp.full((row.shape[0] - a.shape[0],), c[0], a.dtype)])
        out[k] = jax.lax.dynamic_update_slice(a, c[r], (start,))
    return out


def _filter_aggregate(spec: QuerySpec, fact_cols, dim_cols, probes,
                      attrs=None):
    """Shared tail of every query program: filter-on-the-fly → mask →
    measure → segment-sum.  ``probes[dim] = (found, dim_row)``.

    With ``attrs`` (``attrs[dim][col]``: the fact-aligned columns of
    :meth:`_QueryRunner.dim_attrs`) predicates and group keys read those
    columns elementwise and ``dim_cols`` is not read; without, each
    predicate runs on the dimension table and its mask, like each group
    column, is gathered through the probe's rows.  Both give the same
    bits: the predicates are elementwise and read the same values.

    Its ops carry the named scopes ``filter`` (join mask, dimension
    predicates, fact filter), ``group_key`` (the group keys) and
    ``aggregate`` (measure, total, segment sum), so a device trace splits
    the query's time by stage."""
    fact = Table(fact_cols)
    n_rows = fact.n_rows
    rows: dict[str, jax.Array] = {}
    with jax.named_scope("filter"):
        mask = jnp.ones((n_rows,), bool)
        for dim in spec.joined_dims():
            found, r = probes[dim]
            rows[dim] = r
            mask = mask & found
            if dim not in spec.dim_filters:
                continue
            if attrs is not None:
                mask = mask & spec.dim_filters[dim](Table(attrs[dim]))
            else:
                dmask = spec.dim_filters[dim](Table(dim_cols[dim]))
                # filter-on-the-fly while streaming results (paper §4.1.5)
                mask = mask & dmask[jnp.clip(r, 0, dmask.shape[0] - 1)]
        if spec.fact_filter is not None:
            mask = mask & spec.fact_filter(fact)
    with jax.named_scope("aggregate"):
        measure = spec.measure(fact)
        total = jnp.sum(jnp.where(mask, measure.astype(jnp.int32), 0))
    if not spec.group_by:
        return total, total[None]
    # dense composite group key (small spaces by construction)
    with jax.named_scope("group_key"):
        gk = jnp.zeros((n_rows,), jnp.int32)
        size = 1
        for dim, col, card in spec.group_by:
            if attrs is not None:
                v = attrs[dim][col] % card
            else:
                c = dim_cols[dim][col]
                v = c[jnp.clip(rows[dim], 0, c.shape[0] - 1)] % card
            gk = gk * card + v
            size *= card
    with jax.named_scope("aggregate"):
        groups = jax.ops.segment_sum(
            jnp.where(mask, measure.astype(jnp.int32), 0),
            jnp.where(mask, gk, 0), num_segments=size)
    return total, groups


def _mega_operands(spec: QuerySpec, fact_cols, dim_cols, indexes):
    """Build the ``fused_query`` operands for one SSB query.

    Per joined dimension: the per-slot *attribute plane* —
    ``(group_key*stride << 1) | pred_bit`` for unique in-range payloads,
    -1 for dup/invalid slots — over the hash table (and, when a delta is
    live, over the delta's word plane with tombstones as -1), gathered by
    the probe bucket ids so the kernel sees aligned comparator rows.  The
    encoding makes the composite group key a plain sum across dimensions
    (strides = suffix products of the group cardinalities), bit-identical
    to ``_filter_aggregate``'s ``gk = gk*card + v`` accumulation.
    """
    fact = Table(fact_cols)
    measure = spec.measure(fact).astype(jnp.int32)
    if spec.fact_filter is not None:
        measure = jnp.where(spec.fact_filter(fact), measure, 0)
    size = 1
    for _, _, card in spec.group_by:
        size *= card
    strides: dict[str, tuple[str, int, int]] = {}
    rem = size
    for dim, col, card in spec.group_by:
        rem //= card
        strides[dim] = (col, card, rem)
    dim_ops = []
    for dim in spec.joined_dims():
        idx = indexes[dim]
        dt = Table(dim_cols[dim])
        n = dt.n_rows
        pred = spec.dim_filters[dim](dt) if dim in spec.dim_filters else None
        col_card_stride = strides.get(dim)

        def attr_of(payload, invalid):
            clip = jnp.clip(payload, 0, n - 1)
            ok = (payload >= 0) & (payload < n) & ~invalid
            p = pred[clip].astype(jnp.int32) if pred is not None \
                else jnp.ones_like(clip)
            if col_card_stride is None:
                g = jnp.zeros_like(clip)
            else:
                col, card, stride = col_card_stride
                g = (dim_cols[dim][col][clip].astype(jnp.int32)
                     % card) * stride
            return jnp.where(ok, (g << 1) | p, jnp.int32(-1))

        table = idx.table
        attr = attr_of(table.values >> 1, (table.values & 1) == 1)
        fk = fact_cols[FACT_FK[dim]]
        codes = encode(idx.dictionary, fk)
        bids = _ht.hash_bucket(codes, table.num_buckets, table.hash_mode)
        ops = (codes, table.keys[bids], attr[bids])
        if idx.delta is not None:
            d = idx.delta
            dattr = attr_of(d.words >> 1, d.words == TOMBSTONE)
            raw = fk.astype(jnp.int32)
            dbids = _ht.hash_bucket(raw, d.num_buckets, d.hash_mode)
            ops = ops + (raw, d.keys[dbids], dattr[dbids])
        dim_ops.append(ops)
    return tuple(dim_ops), measure, size if spec.group_by else 1


class _QueryRunner:
    """Shared query-execution surface of the live engine and its snapshots.

    Subclasses provide the state (``tables`` / ``indexes`` / ``plans`` /
    ``_hot_codes`` / ``mode`` / ``probe_impl``, the probe and column
    caches, the program caches) and ``probe_dim`` / ``_count_attrs``
    implementations; everything from the join
    primitive to ``run_all`` lives here, identical between the mutable
    ``SSBEngine`` and a frozen ``EpochSnapshot``.  That sharing is the
    MVCC serving contract (DESIGN.md §9): a snapshot answers queries
    through the *same compiled programs* as the head engine — same
    shapes, same plans-as-static-keys — so serving from an old epoch
    costs no retrace and can never diverge behaviorally from the code
    path the head runs.
    """

    policy: ExecutionPolicy
    tables: dict[str, Table]
    indexes: dict[str, DimIndex]
    plans: dict[str, SchedulePlan]
    _hot_codes: dict[str, jax.Array]
    _probe_cache: dict[str, tuple[jax.Array, jax.Array]]
    # dim -> (probe entry, dimension Table, {col: fact-aligned column})
    _attr_cache: dict[str, tuple[tuple, Table, dict]]
    _cached_programs: dict[str, Callable]
    _full_programs: dict[str, Callable]
    _suite_programs: dict[tuple, Callable]
    _mega_programs: dict[str, Callable]

    # legacy knob surface: read-only views of the ExecutionPolicy so every
    # pre-PR-8 call site (and test) keeps working unchanged
    @property
    def mode(self) -> str:
        return self.policy.mode

    @property
    def probe_impl(self) -> str:
        return self.policy.kernel

    @property
    def schedule(self) -> str:
        return self.policy.schedule

    def probe_dim(self, dim: str) -> tuple[jax.Array, jax.Array]:
        raise NotImplementedError

    def _count_attrs(self, gathers: int = 0, hits: int = 0) -> None:
        raise NotImplementedError

    def dim_attrs(self, dim: str, probe=None) -> dict[str, jax.Array]:
        """``ATTR_COLUMNS[dim]`` gathered through the cached ``dim_row``:
        one fact-aligned int32 column per name, gathered once per probe
        cache entry and reused by every query.

        ``probe`` is the ``probe_dim(dim)`` result the caller already
        holds (read here when omitted).  An entry serves only the probe
        entry and dimension ``Table`` it was gathered from; a probe that
        is not the cached entry (or a traced one) is gathered and not
        kept.  Gathers and hits count in the engine's ``cache_info()``,
        a snapshot's included."""
        if probe is None:
            probe = self.probe_dim(dim)
        table = self.tables[dim]
        hit = self._attr_cache.get(dim)
        if hit is not None and hit[0] is probe and hit[1] is table:
            self._count_attrs(hits=1)
            return hit[2]
        cols = ATTR_COLUMNS[dim]
        with TraceAnnotation("engine.attr_gather", dim=dim):
            attrs = _gather_attrs({c: table[c] for c in cols}, probe[1])
        if not isinstance(probe[1], jax.core.Tracer):
            self._count_attrs(gathers=len(cols))
            if probe is self._probe_cache.get(dim):
                self._attr_cache[dim] = (probe, table, attrs)
        return attrs

    def _cached_operands(self, dims) -> tuple[dict, dict]:
        """Cached probes and fact-aligned columns of ``dims``."""
        probes = {d: self.probe_dim(d) for d in dims}
        return probes, {d: self.dim_attrs(d, probes[d]) for d in dims}

    # -- join primitive: (found, dim_row) per fact row ---------------------
    def _probe(self, dim: str) -> tuple[jax.Array, jax.Array]:
        """``_join`` under an ``engine.probe`` host span; ``probe_dim``'s
        misses, on the engine and on its snapshots, all come through
        here."""
        with TraceAnnotation("engine.probe", dim=dim):
            return self._join(dim)

    def _join(self, dim: str) -> tuple[jax.Array, jax.Array]:
        fact = self.tables["lineorder"]
        fk = fact[FACT_FK[dim]]
        if self.mode == "jspim":
            # empty-delta strip at the host/program boundary: keys the
            # trace onto the fused no-delta structure (satellite fix —
            # mirror of the PR 5 empty-compact no-op)
            return _jspim_probe(effective_index(self.indexes[dim]), fk,
                                self._hot_codes.get(dim),
                                impl=self.probe_impl,
                                plan=self.plans.get(dim))
        dk = self.tables[dim][DIM_PK[dim]]
        if self.mode == "baseline":
            return _sort_merge_probe(fk, dk)
        if self.mode == "pid":
            return _pid_probe(fk, dk)
        raise ValueError(self.mode)

    # -- compiled query programs ------------------------------------------
    def _cached_program(self, name: str) -> Callable:
        """Jitted filter→mask→aggregate consuming cached probes and
        fact-aligned columns."""
        prog = self._cached_programs.get(name)
        if prog is None:
            spec = SSB_QUERIES[name]

            def program(fact_cols, probes, attrs):
                return _filter_aggregate(spec, fact_cols, None, probes,
                                         attrs)

            prog = jax.jit(program)
            self._cached_programs[name] = prog
        return prog

    def _full_program(self, name: str) -> Callable:
        """One jitted probe→filter→mask→aggregate program (cache-cold path).

        In jspim mode with a Pallas impl, dimensions that carry a predicate
        probe through the fused probe+filter kernel — compare, tag-decode,
        and dimension-filter in a single VMEM pass.
        """
        prog = self._full_programs.get(name)
        if prog is not None:
            return prog
        spec = SSB_QUERIES[name]
        mode, impl = self.mode, self.probe_impl
        plans = dict(self.plans)  # fixed per runner: safe static closure
        fuse_filter = mode == "jspim" and impl.startswith("pallas")

        def program(fact_cols, dim_cols, indexes, hots):
            probes: dict[str, tuple[jax.Array, jax.Array]] = {}
            with jax.named_scope("probe"):
                for dim in spec.joined_dims():
                    fk = fact_cols[FACT_FK[dim]]
                    if mode == "jspim":
                        if fuse_filter and dim in spec.dim_filters:
                            dmask = spec.dim_filters[dim](
                                Table(dim_cols[dim]))
                            pr = lookup_filtered(indexes[dim], fk, dmask,
                                                 impl=impl)
                        else:
                            pr = lookup(indexes[dim], fk, impl=impl,
                                        plan=plans.get(dim),
                                        hot_codes=hots.get(dim))
                        probes[dim] = (pr.found,
                                       jnp.where(pr.found, pr.payload, -1))
                    elif mode == "baseline":
                        probes[dim] = baselines.sort_merge_join_unique(
                            fk, dim_cols[dim][DIM_PK[dim]])
                    else:
                        probes[dim] = baselines.partitioned_hash_join_unique(
                            fk, dim_cols[dim][DIM_PK[dim]])
            return _filter_aggregate(spec, fact_cols, dim_cols, probes)

        prog = jax.jit(program)
        self._full_programs[name] = prog
        return prog

    def _suite_program(self, names: tuple[str, ...]) -> Callable:
        """ONE jitted program executing every named query's filter→mask→
        aggregate tail against the shared cached probes and fact-aligned
        columns — a single dispatch replaces ``len(names)``, and the
        compiler shares the subexpressions the flights repeat (identical
        group-key construction across Q2.x / Q3.2–3.4, the revenue and
        profit measures).  On CPU the per-dispatch overhead this removes is
        small next to the per-query tails; the measured mega win lives in
        :meth:`_mega_suite_program`, which also folds the *probes* in.
        """
        prog = self._suite_programs.get(names)
        if prog is None:
            specs = [SSB_QUERIES[n] for n in names]

            def program(fact_cols, probes, attrs):
                return {s.name: _filter_aggregate(s, fact_cols, None,
                                                  probes, attrs)
                        for s in specs}

            prog = jax.jit(program)
            self._suite_programs[names] = prog
        return prog

    def _mega_suite_program(self, names: tuple[str, ...]) -> Callable:
        """ONE jitted launch for the whole suite: probe→filter→aggregate.

        Each joined dimension is probed exactly once *inside* the program
        (planned schedule, delta overlay included) and every query tail
        consumes the shared probes — this is the one-launch execution the
        mega path exists for, and the flavor measured against the composed
        per-query pipeline (which re-probes its dimensions per query) in
        ``BENCH_ssb.json``.  Keyed separately from the cached-probe suite
        program because the operand structure differs (indexes and hot
        codes ride in, probes do not).
        """
        key = ("one_launch",) + names
        prog = self._suite_programs.get(key)
        if prog is None:
            specs = [SSB_QUERIES[n] for n in names]
            mode, impl = self.mode, self.probe_impl
            plans = dict(self.plans)  # fixed per runner: safe static closure
            dims = sorted({d for s in specs for d in s.joined_dims()})

            def program(fact_cols, dim_cols, indexes, hots):
                probes: dict[str, tuple[jax.Array, jax.Array]] = {}
                for dim in dims:
                    fk = fact_cols[FACT_FK[dim]]
                    if mode == "jspim":
                        pr = lookup(indexes[dim], fk, impl=impl,
                                    plan=plans.get(dim),
                                    hot_codes=hots.get(dim))
                        probes[dim] = (pr.found,
                                       jnp.where(pr.found, pr.payload, -1))
                    elif mode == "baseline":
                        probes[dim] = baselines.sort_merge_join_unique(
                            fk, dim_cols[dim][DIM_PK[dim]])
                    else:
                        probes[dim] = baselines.partitioned_hash_join_unique(
                            fk, dim_cols[dim][DIM_PK[dim]])
                return {s.name: _filter_aggregate(s, fact_cols, dim_cols,
                                                  probes)
                        for s in specs}

            prog = jax.jit(program)
            self._suite_programs[key] = prog
        return prog

    def _mega_program(self, name: str) -> Callable:
        """One-launch Pallas mega-kernel program for a single query.

        Probe, predicate filter, delta overlay, and segment-sum aggregate
        run in one ``fused_query`` kernel launch (DESIGN.md §12): the
        per-slot attribute planes are built in the same jitted program and
        the kernel consumes the gathered comparator rows directly.  Delta
        presence is pytree structure, so live-ingest engines trace the
        delta-folded grid with no fallback.
        """
        prog = self._mega_programs.get(name)
        if prog is None:
            spec = SSB_QUERIES[name]
            interpret = self.policy.interpret

            def program(fact_cols, dim_cols, indexes):
                dim_ops, fmeasure, size = _mega_operands(spec, fact_cols,
                                                         dim_cols, indexes)
                return fused_query(dim_ops, fmeasure, num_segments=size,
                                   interpret=interpret)

            prog = jax.jit(program)
            self._mega_programs[name] = prog
        return prog

    # -- execution ---------------------------------------------------------
    def _dim_cols(self, spec: QuerySpec) -> dict:
        return {d: dict(self.tables[d].columns) for d in spec.joined_dims()}

    def run(self, name: str, *, use_cache: bool | None = None,
            fusion: str | None = None) -> tuple[jax.Array, jax.Array]:
        """Execute one query as a single compiled program.

        ``use_cache=True`` (policy default) consumes the cross-query probe
        cache; ``use_cache=False`` runs the fully fused probe→…→aggregate
        program without touching the cache (cold-path benchmark flavor).
        ``fusion="mega"`` (or an ``ExecutionPolicy(fusion="mega")``) routes
        a jspim query through the one-launch Pallas mega-kernel instead.
        """
        spec = SSB_QUERIES[name]
        use_cache = self.policy.use_cache if use_cache is None else use_cache
        fusion = self.policy.fusion if fusion is None else fusion
        fact_cols = dict(self.tables["lineorder"].columns)
        if use_cache and not (fusion == "mega" and self.mode == "jspim"):
            probes, attrs = self._cached_operands(spec.joined_dims())
            return self._cached_program(name)(fact_cols, probes, attrs)
        dim_cols = self._dim_cols(spec)
        if fusion == "mega" and self.mode == "jspim":
            idx = {d: effective_index(self.indexes[d])
                   for d in spec.joined_dims()}
            return self._mega_program(name)(fact_cols, dim_cols, idx)
        if self.mode == "jspim":
            idx = {d: effective_index(self.indexes[d])
                   for d in spec.joined_dims()}
            hots = {d: self._hot_codes[d] for d in spec.joined_dims()
                    if d in self._hot_codes}
        else:
            idx, hots = {}, {}
        return self._full_program(name)(fact_cols, dim_cols, idx, hots)

    def _plan_fusion(self, n_queries: int) -> str:
        """Consult the planner for the run_all program shape.  The suite
        tail is XLA regardless of the probe kernel, so the decision models
        the one-dispatch/shared-subexpression win, not the Pallas path."""
        return plan_query(self.tables["lineorder"].n_rows, n_queries,
                          backend=jax.default_backend(),
                          kernel="xla").fusion

    def run_all(self, names=None, *, use_cache: bool | None = None,
                fusion: str | None = None
                ) -> dict[str, tuple[jax.Array, jax.Array]]:
        """Batched entry point: all queries against the shared probe cache.

        Probes each dimension at most once.  ``fusion`` picks the program
        shape: "mega" is ONE compiled dispatch for the whole suite —
        against the host-side probe cache when ``use_cache`` (tails
        only), or the full one-launch probe→filter→aggregate program
        when cache-cold (each dimension probed once *inside* the launch,
        vs the composed flavor re-probing per query); "composed" loops
        the per-query programs; "auto" (policy default) asks
        ``planner.plan_query``.
        """
        names = list(names) if names is not None else sorted(SSB_QUERIES)
        use_cache = self.policy.use_cache if use_cache is None else use_cache
        fusion = self.policy.fusion if fusion is None else fusion
        if fusion == "auto":
            fusion = self._plan_fusion(len(names)) if use_cache \
                else "composed"
        if fusion == "mega":
            dims = sorted({d for n in names
                           for d in SSB_QUERIES[n].joined_dims()})
            fact_cols = dict(self.tables["lineorder"].columns)
            if use_cache:
                probes, attrs = self._cached_operands(dims)
                return self._suite_program(tuple(names))(
                    fact_cols, probes, attrs)
            dim_cols = {d: dict(self.tables[d].columns) for d in dims}
            idx = {d: effective_index(self.indexes[d]) for d in dims} \
                if self.mode == "jspim" else {}
            hots = {d: self._hot_codes[d] for d in dims
                    if d in self._hot_codes}
            return self._mega_suite_program(tuple(names))(
                fact_cols, dim_cols, idx, hots)
        out: dict[str, tuple[jax.Array, jax.Array]] = {}
        for name in names:
            out[name] = self.run(name, use_cache=use_cache,
                                 fusion="composed")
        return out


def _mutates(fn):
    """Mutation-method guard: engine lock + closed check.

    Serialized under the engine's reentrant lock so a serving tier's
    snapshot refresh / background compaction publish can never observe a
    torn mutation; reentrant because mutations compose (``append_rows``
    drives ``ingest``, ``ingest`` may drive ``compact``).  The closed
    check makes post-``close()`` mutations a clear ``RuntimeError``
    instead of a write to a closed WAL handle.
    """
    @functools.wraps(fn)
    def wrapper(self, *a, **k):
        with self._mu:
            self._check_open()
            try:
                return fn(self, *a, **k)
            except BaseException:
                # a torn mutation must not leave staged-but-unpublished
                # events behind: a later publish would deliver a phantom
                # batch the engine never applied
                self._pending_events.clear()
                raise
    return wrapper


class SSBEngine(_QueryRunner):
    """Executes SSB queries with joins delegated to the selected engine.

    Execution knobs live on one frozen :class:`ExecutionPolicy`
    (``policy=``).  The positional ``mode`` / ``probe_impl`` /
    ``schedule`` kwargs are deprecation shims resolved into the policy
    (``core.policy.resolve_policy``); passing both a policy and a
    conflicting legacy kwarg raises.

    ``probe_impl`` (policy.kernel): "xla" | "pallas" | "pallas_stream"
    (jspim mode only).  ``schedule``: "auto" lets the planner pick a probe
    schedule per dimension from the fact-side skew stats recorded at index
    build; "gathered" | "stream" | "deduped" | "hot_cold" force one
    everywhere (benchmark override).
    """

    def __init__(self, tables: dict[str, Table], mode: str | None = None,
                 probe_impl: str | None = None, schedule: str | None = None,
                 *, indexes: dict[str, DimIndex] | None = None,
                 policy: ExecutionPolicy | None = None):
        self.policy = resolve_policy(policy, mode=mode,
                                     probe_impl=probe_impl,
                                     schedule=schedule)
        mode = self.policy.mode
        self.tables = tables
        self.indexes: dict[str, DimIndex] = {}
        self.plans: dict[str, SchedulePlan] = {}
        self._hot_codes: dict[str, jax.Array] = {}
        # durability tier (DESIGN.md §10): attached by
        # DurabilityManager.create / SSBEngine.open; None = volatile engine
        self._durability = None
        # mutation-hook fan-out (DESIGN.md §13): observers (the IVM tier)
        # ride the same call sites as the WAL — ``_wal_log`` stages the
        # validated batch, ``_wal_publish`` delivers it after the epoch
        # publishes.  ``_view_suites`` is the registry ``snapshot()``
        # consults to freeze maintained answers into the epoch image.
        self._mutation_hooks: list[Callable] = []
        self._pending_events: list[tuple] = []
        self._view_suites: list = []
        # serving-tier contract (DESIGN.md §11): mutations serialize under
        # one reentrant lock (queries and snapshots stay lock-free), and a
        # closed engine refuses them with a clear error
        self._mu = threading.RLock()
        self._closed = False
        if mode == "jspim":
            if indexes is not None:
                # durability restore path: adopt the checkpointed index
                # state verbatim (deltas included — it is NOT derivable
                # from the dimension tables) and only re-derive plans
                self.indexes = dict(indexes)
                for dim in self.indexes:
                    self._plan_dim(dim)
            else:
                # built once, reused across queries (§3.2.3 persistence);
                # the fact FK column rides along so BuildStats records its
                # skew (sliced to logical rows — capacity padding is not
                # data)
                n_fact = tables["lineorder"].n_rows
                for dim, pk in DIM_PK.items():
                    self.indexes[dim] = build_dim_index(
                        tables[dim][pk],
                        fact_keys=np.asarray(
                            tables["lineorder"][FACT_FK[dim]])[:n_fact])
                    self._plan_dim(dim)
        # cross-query probe cache: dim -> (found, dim_row) over fact rows,
        # each entry stamped with the fact epoch it is consistent with
        self._probe_cache: dict[str, tuple[jax.Array, jax.Array]] = {}
        self._probe_epoch: dict[str, int] = {}
        # fact-aligned column cache (``dim_attrs``): per dim, the probe
        # entry and dimension Table it was gathered from, and the columns
        self._attr_cache: dict[str, tuple[tuple, Table, dict]] = {}
        # dims whose cached arrays were (re)built by the extension path —
        # nothing external can alias those, so the next tail splice may
        # donate them and update in place (O(tail) instead of O(stream))
        self._cache_owned: set[str] = set()
        # -- MVCC epoch serving (DESIGN.md §9) ----------------------------
        # Global state epoch: bumped by every mutation that advances the
        # head image (fact append, dim ingest/delete, §3.2.3 updates,
        # compaction).  Lives in host state only — it must NEVER become a
        # jit-static argument, or every epoch swap would retrace.
        self._epoch = 0
        # Live snapshots (weak: an unreferenced snapshot stops pinning
        # even without an explicit release) and buffer generations.  A
        # generation counts fresh buffer *families*: it bumps whenever the
        # engine creates new physical buffers for that piece of state, so
        # "snapshot pins generation g" + "current generation is still g"
        # ⟺ donating now would delete arrays the snapshot reads.
        self._snapshots: "weakref.WeakSet" = weakref.WeakSet()
        self._snapshots_taken = 0
        self._pin_copies = 0          # donations refused because of a pin
        self._fact_gen = 0            # lineorder capacity-buffer family
        self._cache_gens: dict[str, int] = {}   # per-dim probe-cache family
        self._index_gens: dict[str, int] = {}   # per-dim main-table family
        self._fact_epoch = 0
        self._fact_appends = 0
        self._fact_rows_appended = 0
        self._tail_extensions = 0
        self._tail_reprobes = 0
        self._skew_replans = 0
        self._skew_measured_rows = tables["lineorder"].n_rows
        self._hits = 0
        self._misses = 0
        self._invalidations = 0
        self._probes_run = 0
        self._attr_gathers = 0
        self._attr_hits = 0
        self._probes_mu = threading.Lock()
        self._ingest_batches = 0
        self._compactions = 0
        # compiled per-query programs, keyed by query name
        self._cached_programs: dict[str, Callable] = {}
        self._full_programs: dict[str, Callable] = {}
        # one-launch programs (PR 8): the run_all suite program keyed by
        # the query-name tuple, and the per-query Pallas mega-kernel
        # programs.  Both consume their operands as pytree args (nothing
        # index- or plan-static closed over), so they survive epoch
        # swaps, appends, re-plans and compactions without clearing.
        self._suite_programs: dict[tuple, Callable] = {}
        self._mega_programs: dict[str, Callable] = {}

    # -- skew-adaptive probe planning (§3.3) -------------------------------
    def _plan_dim(self, dim: str) -> None:
        """Plan the probe schedule for one dimension and stage its hot
        codes (hottest-first, or the full code range for a full map)."""
        idx = self.indexes[dim]
        st = idx.stats
        force = None if self.schedule == "auto" else self.schedule
        if st is None or st.fact_skew is None:
            self.plans[dim] = SchedulePlan(schedule=force or "gathered")
            return
        # the code space is the dictionary's, not n_unique: deleted keys'
        # codes stay allocated until dictionary GC, so a full map sized by
        # n_unique would drop live keys whose codes sit past it
        plan = plan_probe(st.fact_skew, bucket_width=st.bucket_width,
                          backend=jax.default_backend(),
                          impl=self.probe_impl,
                          code_space=int(idx.dictionary.n),
                          hash_mode=idx.table.hash_mode,
                          delta_slots=(0 if idx.delta is None
                                       else idx.delta.num_slots),
                          force=force)
        if plan.schedule == "hot_cold":
            fk = self.tables["lineorder"][FACT_FK[dim]]
            if plan.full_map:
                hot = jnp.arange(plan.hot_entries, dtype=jnp.int32)
            else:
                # rank hot keys over the logical rows only (capacity
                # padding would rank EMPTY_KEY as a hot key); the cold
                # capacity below stays sized to the physical stream the
                # probes actually run over, so padding rows that fall
                # cold can never overflow it
                valid = np.asarray(fk)[:self.tables["lineorder"].n_rows]
                hot = encode(idx.dictionary, jnp.asarray(
                    top_keys(valid, plan.hot_entries)))
                # tighten the cold capacity to the exact measured count
                ht = build_hot_table(idx.table, hot, plan.hot_slots)
                codes = encode(idx.dictionary, fk)
                cold = int(fk.shape[0]
                           - hot_hit_count(idx.table, ht, codes))
                plan = refine_plan(plan, cold, int(fk.shape[0]))
            self._hot_codes[dim] = hot
        self.plans[dim] = plan

    @property
    def build_stats(self):
        """Final index geometry per dimension (jspim mode)."""
        return {d: ix.stats for d, ix in self.indexes.items()}

    # -- cross-query probe cache ------------------------------------------
    def probe_dim(self, dim: str) -> tuple[jax.Array, jax.Array]:
        """Cached (found, dim_row) for one dimension (probe once, reuse).

        Entries are stamped with the fact epoch they were probed (or
        tail-extended) at; a stale stamp — possible only if an append path
        failed to extend or invalidate — reads as a miss, so consumers can
        never mix probe snapshots across fact epochs.
        """
        hit = self._probe_cache.get(dim)
        if hit is not None:
            if self._probe_epoch.get(dim) == self._fact_epoch:
                self._hits += 1
                # the caller now aliases the arrays: the next extension
                # must copy, not donate, so this reference stays live
                self._cache_owned.discard(dim)
                return hit
            self.invalidate_probe_cache(dim)  # stale epoch: defensive drop
        self._misses += 1
        out = self._probe(dim)
        # never capture tracers (engine used under an outer jit trace)
        if not isinstance(out[0], jax.core.Tracer):
            self._count_probe()
            self._probe_cache[dim] = out
            self._probe_epoch[dim] = self._fact_epoch
            # fresh probe output: a new buffer generation (no snapshot
            # can pin it yet), but the caller holds the same tuple, so
            # it is not donation-safe until the first copying extension
            # rebuilds it privately
            self._cache_gens[dim] = self._cache_gens.get(dim, 0) + 1
            self._cache_owned.discard(dim)
        return out

    def warm_cache(self, dims=None) -> None:
        """Probe every (or the given) dimension into the cache up front,
        and gather its fact-aligned columns."""
        for dim in (dims if dims is not None else DIM_PK):
            self.dim_attrs(dim)

    def invalidate_probe_cache(self, dim: str | None = None) -> None:
        """Drop cached probes — all dims, or one (after an index update) —
        with the fact-aligned columns gathered from them."""
        if dim is None:
            self._invalidations += len(self._probe_cache)
            self._probe_cache.clear()
            self._cache_owned.clear()
            self._attr_cache.clear()
        elif dim in self._probe_cache:
            self._invalidations += 1
            del self._probe_cache[dim]
            self._cache_owned.discard(dim)
            self._attr_cache.pop(dim, None)

    def _count_probe(self) -> None:
        with self._probes_mu:   # snapshots probe from serving threads
            self._probes_run += 1

    def _count_attrs(self, gathers: int = 0, hits: int = 0) -> None:
        with self._probes_mu:
            self._attr_gathers += gathers
            self._attr_hits += hits

    def _extend_attr_cache(self, dim: str, start: jax.Array,
                           n_tail: int) -> None:
        """Carry ``dim``'s fact-aligned columns over a fact append whose
        probe extension just replaced the cache entry: gather the tail
        only and splice it in (a copy of each column)."""
        entry = self._attr_cache.pop(dim, None)
        table = self.tables[dim]
        if entry is None or entry[1] is not table:
            return
        probe = self._probe_cache[dim]
        cols = {c: table[c] for c in entry[2]}
        self._attr_cache[dim] = (probe, table, _extend_attrs(
            entry[2], cols, probe[1], start, n_tail=n_tail))

    def cache_info(self) -> dict:
        """Probe-cache counters.  ``probes_run`` counts every probe run
        (``_join``) by the engine *and by its snapshots*, whose misses
        probe into their own memo and never touch ``hits`` / ``misses``.
        ``attr_gathers`` counts fact-aligned columns gathered in full
        (``dim_attrs`` misses), ``attr_hits`` the ``dim_attrs`` reads
        served from a cache, engine and snapshots alike."""
        return {"hits": self._hits, "misses": self._misses,
                "invalidations": self._invalidations,
                "probes_run": self._probes_run,
                "attr_gathers": self._attr_gathers,
                "attr_hits": self._attr_hits,
                "cached_dims": sorted(self._probe_cache),
                "fact_epoch": self._fact_epoch}

    # -- MVCC epoch snapshots (DESIGN.md §9) -------------------------------
    @property
    def epoch(self) -> int:
        """Monotone global state epoch (every mutation publishes one)."""
        return self._epoch

    def snapshot(self) -> "EpochSnapshot":
        """Freeze the current image as a lock-free query snapshot.

        The returned ``EpochSnapshot`` shares this engine's buffers
        (zero-copy) and compiled programs; it keeps answering queries
        bit-identically at this epoch while ``append_fact_rows`` /
        ``ingest`` / ``compact`` advance the engine.  The engine's
        donation fast paths (in-place fact writes, probe-cache splices,
        in-place compaction merges) refuse to touch any buffer
        generation a live snapshot pins — the first mutation after a
        snapshot copies into a fresh generation instead, after which
        donation re-arms.  Release the snapshot (``release()`` / context
        manager / letting it be garbage collected) to retire its pins.
        """
        with self._mu:  # freeze can't interleave with a mutation
            snap = self._make_snapshot()
            self._snapshots.add(snap)
            self._snapshots_taken += 1
        return snap

    def _make_snapshot(self):
        """Construct the frozen image (under ``_mu``).  Subclasses freeze
        richer images — the sharded engine verifies the collective epoch
        stamps and returns a mesh-aware snapshot here."""
        from repro.engine.snapshot import EpochSnapshot

        return EpochSnapshot(self)

    def _live_snapshots(self) -> list:
        return [s for s in self._snapshots if not s.released]

    def _fact_pinned(self) -> bool:
        """Does a live snapshot pin the current fact capacity buffers?"""
        return any(s._pin_fact_gen == self._fact_gen
                   for s in self._live_snapshots())

    def _cache_pinned(self, dim: str) -> bool:
        """Does a live snapshot pin ``dim``'s current cached probe arrays?"""
        g = self._cache_gens.get(dim, 0)
        return any(s._pin_cache_gens.get(dim) == g
                   for s in self._live_snapshots())

    def _index_pinned(self, dim: str) -> bool:
        """Does a live snapshot pin ``dim``'s current main-table buffers?"""
        g = self._index_gens.get(dim, 0)
        return any(s._pin_index_gens.get(dim) == g
                   for s in self._live_snapshots())

    def snapshot_info(self) -> dict:
        """Epoch / snapshot / pin counters (serving observability)."""
        return {"epoch": self._epoch,
                "live_snapshots": len(self._live_snapshots()),
                "snapshots_taken": self._snapshots_taken,
                "pin_copies": self._pin_copies,
                "fact_gen": self._fact_gen}

    # -- durability tier (WAL + checkpoints, DESIGN.md §10) ----------------
    def _wal_log(self, kind: str, meta: dict | None = None,
                 arrays=None) -> None:
        """Write-ahead hook: make the mutation durable *before* applying.

        Called by every mutation method after validation but before any
        state changes; the manager fsyncs the record stamped with the
        epoch the mutation is about to publish.  No-op on a volatile
        engine and during recovery replay (replay re-drives the mutation
        API from the log — logging it again would double every record).
        """
        d = self._durability
        if d is not None and not d.replaying:
            d.log_mutation(self, kind, meta, arrays)
        if self._mutation_hooks:
            # stage the validated batch for the mutation-hook fan-out; it
            # is delivered by _wal_publish once the epoch publishes, so
            # observers only ever see batches the engine actually applied
            self._pending_events.append(
                (kind, dict(meta or {}), dict(arrays or {})))

    def _wal_publish(self) -> None:
        """Post-publish hook: let the durability tier weigh a checkpoint
        (cost-model trigger — replay debt vs state write)."""
        d = self._durability
        if d is not None and not d.replaying:
            d.on_publish(self)
        self._notify_hooks()

    def _notify_hooks(self) -> None:
        """Deliver staged mutation batches to registered observers.

        Runs under the engine lock at the ``_wal_publish`` call sites, in
        mutation order.  Nested mutations (auto-compact inside ingest,
        ingest inside append_rows) stage multiple events that all drain
        at the outermost publish, stamped with the final epoch — which is
        exactly the epoch their combined effect is visible at.
        """
        if not self._pending_events:
            return
        pending, self._pending_events = self._pending_events, []
        for kind, meta, arrays in pending:
            ev = MutationEvent(kind=kind, meta=meta, arrays=arrays,
                               epoch=self._epoch,
                               fact_epoch=self._fact_epoch)
            for hook in list(self._mutation_hooks):
                hook(ev)

    # -- mutation-hook / view-suite registry (DESIGN.md §13) ---------------
    def add_mutation_hook(self, fn: Callable) -> None:
        """Subscribe ``fn(event: MutationEvent)`` to mutation batches.

        Hooks run under the engine lock, post-publish, in mutation order
        (the same call sites the WAL uses).  Keep them cheap and never
        call back into engine mutation methods from a hook."""
        with self._mu:
            self._mutation_hooks.append(fn)

    def remove_mutation_hook(self, fn: Callable) -> None:
        """Unsubscribe a hook added with ``add_mutation_hook``."""
        with self._mu:
            self._mutation_hooks.remove(fn)
            if not self._mutation_hooks:
                self._pending_events.clear()

    def register_view_suite(self, suite) -> None:
        """Attach a maintained-view suite (``repro.ivm.MaintainedSuite``).

        The suite's event hook subscribes to mutations, and
        ``snapshot()`` freezes its answers into the epoch image whenever
        the suite is fresh at the frozen epoch."""
        with self._mu:
            self._view_suites.append(suite)
            self._mutation_hooks.append(suite._on_event)

    def unregister_view_suite(self, suite) -> None:
        """Detach a suite registered with ``register_view_suite``."""
        with self._mu:
            self._view_suites.remove(suite)
            self._mutation_hooks.remove(suite._on_event)
            if not self._mutation_hooks:
                self._pending_events.clear()

    def persist(self, root: str, **kw) -> "object":
        """Start durability for this engine at a fresh ``root``.

        Writes a genesis checkpoint of the current epoch, opens the WAL,
        and attaches the manager: from here every mutation batch is
        logged-and-fsynced before its epoch publishes, and checkpoints
        are taken on the cost-model trigger.  Recover later with
        ``SSBEngine.open(root)``.  Keyword args pass through to
        ``DurabilityManager`` (``fs``, ``keep``, ``min_log_bytes``,
        ``safety``, ``auto_checkpoint``).
        """
        from repro.durability.manager import DurabilityManager

        return DurabilityManager.create(root, self, **kw)

    @classmethod
    def open(cls, root: str, **kw) -> "SSBEngine":
        """Recover an engine from a durability root (DESIGN.md §10).

        Restores the newest checkpoint whose leaves verify (falling back
        to older steps on corruption), truncates the WAL's torn tail,
        replays the log suffix through the normal mutation API, and
        returns the engine with the log open for new mutations.
        """
        from repro.durability.manager import open_engine

        return open_engine(root, **kw)

    @property
    def durability(self):
        """The attached DurabilityManager, or None (volatile engine)."""
        return self._durability

    def close(self) -> None:
        """Close the engine: detach durability and refuse further mutations.

        Idempotent.  A closed engine (and its live snapshots) keeps
        serving queries — a serving tier drains in-flight reads during
        shutdown/recovery — but every mutation raises a clear
        ``RuntimeError``.  (Previously a closed durable engine silently
        reverted to volatile: a post-close ``ingest`` either vanished
        from the durable image or died deep in the manager on the closed
        WAL handle.)"""
        with self._mu:
            if self._closed:
                return
            self._closed = True
            if self._durability is not None:
                self._durability.close()
                self._durability = None

    @property
    def closed(self) -> bool:
        return self._closed

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError(
                "engine is closed: mutations are refused (queries and "
                "held snapshots keep working; reopen the durability root "
                "with SSBEngine.open, or build a new engine, to mutate)")

    # -- §3.2.3 update commands (invalidate the affected dim's probes) -----
    def _replace_table(self, dim: str, table) -> None:
        if self._durability is not None:
            raise RuntimeError(
                "entry_update/index_update/table_update are raw §3.2.3 "
                "cell writes outside the WAL mandate — a durable engine "
                "would silently lose them on recovery; use ingest / "
                "append_rows, or close() durability first")
        self.indexes[dim] = dataclasses.replace(self.indexes[dim],
                                                table=table)
        # the functional update minted fresh table buffers: new generation
        # (snapshots keep the old table object), new published epoch
        self._index_gens[dim] = self._index_gens.get(dim, 0) + 1
        self._epoch += 1
        self.invalidate_probe_cache(dim)
        if self._mutation_hooks:
            # raw cell writes bypass the WAL (volatile-only), so stage +
            # deliver here; observers can't incrementalize an arbitrary
            # cell edit and are expected to invalidate on this kind
            self._pending_events.append(("raw_update", {"dim": dim}, {}))
            self._notify_hooks()

    @_mutates
    def entry_update(self, dim: str, bucket, slot, key, value_word) -> None:
        """Entry Update: overwrite one (bucket, slot) cell of ``dim``.

        This is the paper's raw DRAM-cell write: ``key`` is a stored
        dictionary *code* (or EMPTY_KEY), not a raw dimension key."""
        self._replace_table(dim, _ht.entry_update(
            self.indexes[dim].table, bucket, slot, key, value_word))

    @_mutates
    def index_update(self, dim: str, key, new_payload) -> None:
        """Index Update: search raw ``key`` in ``dim``; update its payload.

        The table is keyed by dictionary codes, so the raw key is encoded
        first; an absent key encodes to NO_CODE and the update no-ops."""
        code = encode(self.indexes[dim].dictionary,
                      jnp.asarray(key, jnp.int32))
        self._replace_table(dim, _ht.index_update(
            self.indexes[dim].table, code, new_payload))

    @_mutates
    def table_update(self, dim: str, bucket_ids, new_keys,
                     new_values) -> None:
        """Table Update: burst-write whole buckets of ``dim``."""
        self._replace_table(dim, _ht.table_update(
            self.indexes[dim].table, bucket_ids, new_keys, new_values))

    # -- streaming ingest: delta buffer + cost-model-driven compaction -----
    @_mutates
    def ingest(self, dim: str, keys, payloads=None, *, op: str = "upsert",
               auto_compact: bool = True,
               _wal: bool = True) -> CompactionPlan:
        """Absorb a batch of index ops into ``dim``'s delta buffer.

        ``keys`` are raw dimension keys; ``op`` is "insert" / "upsert"
        (``payloads`` = dimension-row indices) or "delete" (tombstones).
        Invalidates the dimension's cached probes, then consults the
        planner: when the modeled delta-overlay tax or occupancy says so
        (and ``auto_compact``), the delta folds into the main table.
        Returns the compaction decision either way.

        Batches are validated at this boundary (1-D integer arrays,
        int32-range values, matching lengths) and rejected with a
        ``ValueError`` naming the argument.  ``_wal`` is internal: it
        suppresses this batch's own WAL record when the caller
        (``append_rows``) already logged a composite record covering it.
        """
        if self.mode != "jspim":
            raise ValueError("ingest requires jspim mode (no index to "
                             f"maintain in mode={self.mode!r})")
        if dim not in self.indexes:
            raise ValueError(f"dim: unknown dimension {dim!r} (have "
                             f"{sorted(self.indexes)})")
        if op not in ("insert", "upsert", "delete"):
            raise ValueError(f"op: expected insert/upsert/delete, "
                             f"got {op!r}")
        keys = _check_batch_col("keys", keys)
        if np.any(keys == int(_ht.EMPTY_KEY)):
            # EMPTY_KEY is the delta's empty-slot sentinel: apply_batch
            # would silently drop such ops, minting a hollow delta (no
            # live entries) that still publishes an epoch and pays the
            # overlay tax on every probe until compaction
            raise ValueError("keys: EMPTY_KEY is reserved as the hash "
                             "slot sentinel and cannot be ingested")
        if op == "delete":
            payloads = None
        else:
            if payloads is None:
                raise ValueError(f"payloads: required for op={op!r} "
                                 "(the new dimension-row indices)")
            payloads = _check_batch_col("payloads", payloads,
                                        expect_len=keys.shape[0])
        if keys.shape[0] == 0:
            # strict no-op (mirror of the empty-append fix): zero ops can
            # change no state, so publishing an epoch, dropping probes,
            # re-planning, or minting an empty delta would be pure loss
            return self.compaction_plan(dim)
        if _wal:
            arrays = {"keys": keys}
            if payloads is not None:
                arrays["payloads"] = payloads
            self._wal_log("ingest", {"dim": dim, "op": op}, arrays)
        before = self.indexes[dim].delta
        self.indexes[dim] = ingest_index(self.indexes[dim], keys, payloads,
                                         op=op)
        self._ingest_batches += 1
        # delta buffers are fresh but the main table's are shared with the
        # previous index object, so the table generation does NOT bump —
        # a pre-ingest snapshot still pins them against donated merges
        self._epoch += 1
        self.invalidate_probe_cache(dim)
        after = self.indexes[dim].delta
        if before is None or before.num_slots != after.num_slots:
            # the delta appeared (or grew): re-plan so the schedule
            # estimates price the live overlay occupancy.  The overlay tax
            # is schedule-independent (added uniformly by the cost model),
            # so the *decision* cannot change — compiled full programs
            # that closed over the old plan stay behaviorally identical
            # and are deliberately kept.
            self._plan_dim(dim)
        plan = self.compaction_plan(dim)
        if auto_compact and plan.compact:
            self.compact(dim)
        if _wal:
            self._wal_publish()
        return plan

    @_mutates
    def append_rows(self, dim: str, rows, *,
                    auto_compact: bool = True) -> None:
        """Append new rows to a dimension table and index them.

        ``rows`` maps every column of ``dim`` to a 1-D array of new
        values (validated here: integer, 1-D, equal lengths — a bad
        column raises ``ValueError`` naming it).  The dimension table
        grows in place; in jspim mode the new PK -> row-index mappings
        stream into the delta buffer (no index rebuild), and in every
        mode the dimension's cached probes drop.  A zero-row append is a
        strict no-op.  ``auto_compact`` passes through to the internal
        ``ingest`` (recovery replays with it off so logged ``compact``
        records reproduce the original fold points).
        """
        if dim not in DIM_PK:
            raise ValueError(f"dim: unknown dimension {dim!r} (have "
                             f"{sorted(DIM_PK)})")
        t = self.tables[dim]
        missing = set(t.names()) ^ set(rows)
        if missing:
            raise ValueError(f"append_rows({dim!r}) column mismatch: "
                             f"{sorted(missing)}")
        cols_np: dict[str, np.ndarray] = {}
        n_new: int | None = None
        for k in t.names():
            cols_np[k] = _check_batch_col(f"rows[{k!r}]", rows[k],
                                          expect_len=n_new)
            if n_new is None:
                n_new = cols_np[k].shape[0]
        if n_new == 0:
            return
        if self.mode == "jspim" and \
                np.any(cols_np[DIM_PK[dim]] == int(_ht.EMPTY_KEY)):
            # reject before any state changes: the internal ingest would
            # raise on this PK *after* the table grew, tearing the append
            raise ValueError(f"rows[{DIM_PK[dim]!r}]: EMPTY_KEY is "
                             "reserved as the hash slot sentinel and "
                             "cannot be a dimension primary key")
        self._wal_log("append_rows", {"dim": dim}, cols_np)
        n0 = t.n_rows
        self.tables[dim] = t.append(
            {k: jnp.asarray(v) for k, v in cols_np.items()})
        if self.mode == "jspim":
            self.ingest(dim, cols_np[DIM_PK[dim]],
                        np.arange(n0, n0 + n_new, dtype=np.int32),
                        op="insert", auto_compact=auto_compact, _wal=False)
        else:
            self._epoch += 1
            self.invalidate_probe_cache(dim)
        self._wal_publish()

    # -- fact-side streaming append: probe-cache tail extension ------------
    @_mutates
    def append_fact_rows(self, rows, *, extend_cache: bool = True) -> dict:
        """Append new lineorder rows; extend cached probes over the tail.

        ``rows`` maps every lineorder column to a 1-D array of new values.
        The fact table grows through the pow2-bucketed capacity tail
        (``Table.append_tail`` — steady-state appends at a fixed batch
        size change no array shapes, so every compiled program is reused),
        with FK columns padded by ``EMPTY_KEY`` so capacity padding can
        never join.  Each cached dimension probe is then *extended*, not
        invalidated: ``plan_fact_append`` prices a tail-only probe (under
        the planned schedule, delta overlay included) + splice against a
        cold re-probe of the grown stream and almost always extends; a
        dimension whose extension loses (or ``extend_cache=False``, the
        benchmark baseline) is invalidated instead.  A zero-row append is
        a strict no-op: no epoch bump, no invalidation, no compilation.

        Steady-state appends DONATE the capacity-padded buffers (table
        columns and cached probes) so both updates happen in place —
        O(tail batch), not O(table).  Consequences: fact column arrays
        taken from the engine before an append are invalidated by it
        (jax raises "Array has been deleted" on use, never silent
        corruption); probe tuples from ``probe_dim`` survive the first
        subsequent append (reading a cache entry relinquishes ownership,
        so that extension copies) but not further appends without a
        re-read — ``np.asarray`` them to keep a snapshot.  Externally
        shared *base* tables are never donated: the first append always
        copies into fresh capacity buffers.

        Returns a report: rows appended, the new fact epoch, the per-dim
        decision, and which dimensions were re-planned for skew drift.
        """
        fact = self.tables["lineorder"]
        missing = set(fact.names()) ^ set(rows)
        if missing:
            raise ValueError(f"append_fact_rows column mismatch: "
                             f"{sorted(missing)}")
        # host-side staging: padding happens in numpy (table.pad_batch),
        # so ragged batch sizes reach every jitted program bucket-shaped;
        # validation at this boundary names the bad column (and is what
        # lets WAL replay trust recorded batches)
        new_cols: dict[str, np.ndarray] = {}
        n_new: int | None = None
        for k in fact.names():
            new_cols[k] = _check_batch_col(f"rows[{k!r}]", rows[k],
                                           expect_len=n_new)
            if n_new is None:
                n_new = new_cols[k].shape[0]
        if n_new == 0:  # strict no-op: nothing moved, nothing invalidates
            return {"appended": 0, "epoch": self._fact_epoch, "dims": {},
                    "capacity_grew": False, "skew_replanned": []}
        self._wal_log("append_fact_rows", {}, new_cols)
        n0 = fact.n_rows
        pad_values = {FACT_FK[d]: int(_ht.EMPTY_KEY) for d in FACT_FK}
        # one bucket for both write windows (table tail AND cache splice)
        bp = tail_bucket(n_new)
        will_grow = n0 + bp > fact.n_physical
        if fact.tail_owned and not will_grow and self._fact_pinned():
            # a live snapshot pins the current capacity buffers: this
            # append must copy into a fresh generation (the snapshot's
            # readers keep the old one, bit-identical forever); donation
            # re-arms on the new buffers for the next append.  A growing
            # append writes fresh concat buffers regardless, so pins
            # change (and therefore count) nothing there.
            fact = dataclasses.replace(fact, tail_owned=False)
            self._pin_copies += 1
        grown = fact.append_tail(new_cols, pad_values, bucket=bp)
        capacity_grew = grown.n_physical != fact.n_physical
        if capacity_grew or not fact.tail_owned:
            self._fact_gen += 1  # fresh buffers: no snapshot pins them yet
        self.tables["lineorder"] = grown
        self._epoch += 1
        self._fact_epoch += 1
        self._fact_appends += 1
        self._fact_rows_appended += int(n_new)
        report = {"appended": int(n_new), "epoch": self._fact_epoch,
                  "capacity_grew": capacity_grew, "dims": {}}
        if self.mode != "jspim":  # no index: probes must rerun from cold
            self.invalidate_probe_cache()
            report["skew_replanned"] = []
            self._wal_publish()
            return report
        start = jnp.int32(n0)
        for dim in sorted(self._probe_cache):
            ap = self._fact_append_plan(dim, bp, n0)
            if not (extend_cache and ap.extend):
                self.invalidate_probe_cache(dim)
                self._tail_reprobes += 1
                report["dims"][dim] = ap.reason if extend_cache \
                    else "invalidated"
                continue
            found, row = self._probe_cache[dim]
            owned = dim in self._cache_owned
            pinned_copy = False
            if owned and self._cache_pinned(dim):
                # a live snapshot pins these probe arrays: splice into a
                # fresh copy instead of donating them out from under it
                owned = False
                pinned_copy = True
            fresh = not owned  # a copying splice mints a new generation
            if found.shape[0] != grown.n_physical:  # capacity grew: re-pad
                pad = grown.n_physical - found.shape[0]
                found = jnp.concatenate([found, jnp.zeros((pad,), bool)])
                row = jnp.concatenate([row, jnp.full((pad,), -1, jnp.int32)])
                owned, fresh = True, True  # fresh concats: donation-safe
                pinned_copy = False  # the concat copied regardless of pins
            if pinned_copy:
                self._pin_copies += 1
            fk_tail = pad_batch(new_cols[FACT_FK[dim]], bp,
                                int(_ht.EMPTY_KEY))
            extend = (extend_cached_probe_donated if owned
                      else extend_cached_probe)
            self._probe_cache[dim] = extend(
                effective_index(self.indexes[dim]), found, row, fk_tail,
                start,
                self._hot_codes.get(dim), impl=self.probe_impl,
                plan=self.plans.get(dim))
            self._extend_attr_cache(dim, start, bp)
            self._probe_epoch[dim] = self._fact_epoch
            self._cache_owned.add(dim)
            if fresh:
                self._cache_gens[dim] = self._cache_gens.get(dim, 0) + 1
            self._tail_extensions += 1
            report["dims"][dim] = "extended"
        report["skew_replanned"] = self._maybe_replan_fact_skew()
        self._wal_publish()
        return report

    def _fact_append_plan(self, dim: str, n_tail: int,
                          n_cached: int) -> FactAppendPlan:
        """The planner's extend-or-reprobe decision for one cached dim."""
        idx = self.indexes[dim]
        st = idx.stats
        sk = st.fact_skew if st is not None else None
        return plan_fact_append(
            self.plans.get(dim) or SchedulePlan(schedule="gathered"),
            n_tail=n_tail, n_cached=n_cached,
            distinct=(sk.distinct if sk is not None
                      else int(idx.table.n_unique)),
            bucket_width=idx.table.bucket_width,
            delta_slots=0 if idx.delta is None else idx.delta.num_slots,
            backend=jax.default_backend())

    def _maybe_replan_fact_skew(self, force: bool = False) -> list[str]:
        """Re-measure fact-side skew after heavy append; re-plan drifters.

        ``BuildStats.fact_skew`` was measured at index build; a long
        append stream can move the top-share curve until the planned
        schedules are wrong (the ROADMAP skew-drift item).  Once the
        logical stream has grown ``FACT_REMEASURE_FRAC`` past the last
        measurement (or on ``force``), each dimension's FK column is
        re-measured over the logical rows; dimensions whose curve moved
        ``TOP_SHARE_DRIFT`` get fresh stats and a fresh plan.  Compiled
        full programs drop only when a plan's schedule or geometry
        actually changed (they close over plans statically); cached
        probes stay — every schedule is bit-identical by contract.
        """
        if self.mode != "jspim":
            return []
        n_valid = self.tables["lineorder"].n_rows
        base = max(1, self._skew_measured_rows)
        if not force and (n_valid - base) / base < FACT_REMEASURE_FRAC:
            return []
        self._skew_measured_rows = n_valid
        replanned: list[str] = []
        for dim in DIM_PK:
            idx = self.indexes[dim]
            st = idx.stats
            if st is None:
                continue
            fresh = measure_skew(
                np.asarray(self.tables["lineorder"][FACT_FK[dim]])[:n_valid])
            if (st.fact_skew is not None
                    and skew_drift(st.fact_skew, fresh) < TOP_SHARE_DRIFT):
                continue
            self.indexes[dim] = dataclasses.replace(
                idx, stats=dataclasses.replace(st, fact_skew=fresh))
            old = self.plans.get(dim)
            self._plan_dim(dim)
            new = self.plans.get(dim)
            if old is not None and (
                    old.schedule, old.hot_entries, old.hot_slots,
                    old.cold_capacity, old.full_map) == (
                    new.schedule, new.hot_entries, new.hot_slots,
                    new.cold_capacity, new.full_map):
                # same decision, fresher estimates: keep the old plan
                # object AND the old index metadata — both are static
                # jit keys (DimIndex.stats included), so replacing either
                # would retrace every probe/extension program for a
                # re-plan that changed nothing.  The stale fact_skew
                # baseline only means the drift trigger re-evaluates on
                # the next re-measure, which costs a plan, not a trace.
                self.plans[dim] = old
                self.indexes[dim] = idx
            else:
                self._full_programs.clear()  # they close over plans
            self._skew_replans += 1
            replanned.append(dim)
        return replanned

    @property
    def fact_epoch(self) -> int:
        """Monotone fact-snapshot counter (bumped per non-empty append).

        Every probe-cache entry carries the epoch it is consistent with,
        so sharded probes and fused query programs built from one epoch's
        tables never silently consume another epoch's probes — the
        snapshot half of the MVCC serving story (ROADMAP)."""
        return self._fact_epoch

    def fact_append_info(self) -> dict:
        """Fact-side append/extension counters + tail geometry."""
        fact = self.tables["lineorder"]
        return {"fact_epoch": self._fact_epoch,
                "appends": self._fact_appends,
                "rows_appended": self._fact_rows_appended,
                "tail_extensions": self._tail_extensions,
                "tail_reprobes": self._tail_reprobes,
                "skew_replans": self._skew_replans,
                "n_valid": fact.n_rows,
                "n_physical": fact.n_physical}

    def compaction_plan(self, dim: str) -> CompactionPlan:
        """The planner's compact-or-defer decision for ``dim`` right now."""
        idx = self.indexes[dim]
        st = idx.stats
        ds = delta_stats(idx.delta) if idx.delta is not None else None
        return plan_compaction(
            delta_entries=0 if ds is None else ds.n_entries,
            delta_slots=0 if ds is None else ds.num_slots,
            fill_frac=0.0 if ds is None else ds.fill_frac,
            worst_bucket_frac=0.0 if ds is None else ds.worst_bucket_frac,
            n_build=(st.n_build if st is not None
                     else int(idx.table.n_build)),
            n_dict=int(idx.dictionary.n),
            bucket_width=idx.table.bucket_width,
            expected_probes=self.tables["lineorder"].n_rows,
            backend=jax.default_backend(),
            pinned=self._index_pinned(dim))

    @_mutates
    def compact(self, dim: str) -> None:
        """Fold ``dim``'s delta into its main table and re-plan probes.

        With no buffered ops (no delta, or an all-empty one) this is a
        strict no-op — no cache invalidation, no re-plan, no compiled
        programs dropped, no epoch published, nothing compiled (the
        mirror of the empty-append fix): there is no state a merge of
        zero ops could change, so thrashing compiled programs for it
        would be pure loss.

        When a live snapshot pins the main-table buffers the merge runs
        in its **swap** flavor (fresh buffer pair, old table intact for
        the snapshot's readers, one atomic reference publish); unpinned,
        it donates the buffers and merges in place (O(delta)).
        """
        idx = self.indexes[dim]
        if delta_is_empty(idx.delta):
            if idx.delta is not None:
                # hollow delta (allocated but zero live entries — e.g. a
                # restored image): strip it so no future program boundary
                # ever sees the overlay shape.  Bit-identical state, so no
                # epoch publishes and no caches drop.
                self.indexes[dim] = dataclasses.replace(idx, delta=None)
            return
        # logged like every other mutation batch (after the empty check:
        # an empty compact publishes nothing, so it must log nothing) so
        # recovery replays the exact live fold points — auto-compactions
        # included, since they arrive here too
        self._wal_log("compact", {"dim": dim})
        pinned = self._index_pinned(dim)
        if pinned:
            self._pin_copies += 1
        self.indexes[dim] = compact_index(idx, donate=not pinned)
        # either flavor publishes a fresh table generation: the swap built
        # a new pair, and the donated merge's buffers were never pinned
        self._index_gens[dim] = self._index_gens.get(dim, 0) + 1
        self._epoch += 1
        self._compactions += 1
        self.invalidate_probe_cache(dim)
        # the code space / geometry changed: re-plan, and drop compiled
        # full programs (they close over the old plans statically)
        self._plan_dim(dim)
        self._full_programs.clear()
        self._wal_publish()

    # -- background compaction (off the serving path, DESIGN.md §11) -------
    def prepare_compact(self, dim: str):
        """Stage ``dim``'s delta merge without blocking queries or ingest.

        Runs ``compact_index``'s **swap** flavor (fresh buffer pair; the
        live table, every snapshot, and every cached probe stay
        untouched) with the engine lock released during the heavy merge,
        so a background worker can do the folding while the serving path
        keeps answering.  Returns an opaque staging token for
        :meth:`publish_compact`, or ``None`` when there is nothing to
        fold.
        """
        with self._mu:
            self._check_open()
            if dim not in self.indexes:
                raise ValueError(f"dim: unknown dimension {dim!r} (have "
                                 f"{sorted(self.indexes)})")
            idx = self.indexes[dim]
        if delta_is_empty(idx.delta):
            return None
        # off-lock: O(delta) merge against an immutable index image
        return (dim, idx, compact_index(idx, donate=False))

    def publish_compact(self, prepared) -> bool:
        """Publish a staged merge like any other epoch (atomic swap).

        Returns ``False`` (merge discarded, state untouched) when a
        mutation landed on the dimension after ``prepare_compact`` read
        it — the delta the merge folded is no longer the live delta, so
        publishing would lose the newer ops.  The caller (the serving
        tier's maintenance loop) simply re-stages.
        """
        if prepared is None:
            return False
        dim, source, merged = prepared
        with self._mu:
            self._check_open()
            if self.indexes[dim] is not source:
                return False
            self._wal_log("compact", {"dim": dim})
            self.indexes[dim] = merged
            self._index_gens[dim] = self._index_gens.get(dim, 0) + 1
            self._epoch += 1
            self._compactions += 1
            self.invalidate_probe_cache(dim)
            self._plan_dim(dim)
            self._full_programs.clear()
            self._wal_publish()
            return True

    def ingest_info(self) -> dict:
        """Ingest/compaction counters + per-dim delta occupancy."""
        deltas = {d: dataclasses.asdict(delta_stats(ix.delta))
                  for d, ix in self.indexes.items() if ix.delta is not None}
        return {"ingest_batches": self._ingest_batches,
                "compactions": self._compactions, "deltas": deltas}

    def _join_eager(self, dim: str) -> tuple[jax.Array, jax.Array]:
        """Un-jitted flavor of ``_join`` (op-by-op dispatch, no caching)."""
        fact = self.tables["lineorder"]
        fk = fact[FACT_FK[dim]]
        if self.mode == "jspim":
            # deliberately schedule-oblivious: this is the seed reference
            # the planned/fused paths are measured and tested against
            pr = lookup(self.indexes[dim], fk, impl=self.probe_impl)
            return pr.found, jnp.where(pr.found, pr.payload, -1)
        dk = self.tables[dim][DIM_PK[dim]]
        if self.mode == "baseline":
            return baselines.sort_merge_join_unique(fk, dk)
        if self.mode == "pid":
            return baselines.partitioned_hash_join_unique(fk, dk)
        raise ValueError(self.mode)

    def run_eager(self, name: str) -> tuple[jax.Array, jax.Array]:
        """The seed per-query loop: un-jitted op-by-op dispatch, no cache.

        Kept as the reference implementation (jit-vs-eager equality tests)
        and as the benchmark baseline the fused pipeline is measured
        against."""
        spec = SSB_QUERIES[name]
        probes = {d: self._join_eager(d) for d in spec.joined_dims()}
        return _filter_aggregate(spec, dict(self.tables["lineorder"].columns),
                                 self._dim_cols(spec), probes)
