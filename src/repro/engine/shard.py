"""Shard-axis-first-class fact engine: rank-parallel mutation + query.

The software analogue of JSPIM's rank-level parallelism (§3.3): every
device ("rank") holds the replicated dimension indexes — dictionary,
hash table, delta buffer, all tiny next to the fact table — and owns one
contiguous shard of every fact column, so probes, tail extensions and
appends run with **zero cross-device traffic**.  DESIGN.md §14.

:class:`ShardedSSBEngine` subclasses :class:`SSBEngine` and keeps its
entire contract — probe cache with epoch stamps, MVCC generation pins and
donation gating, WAL/mutation-hook staging, dimension ingest/compaction —
while re-implementing the fact-side physical layout:

* **Per-shard capacity tails.**  Each fact column is ONE device array of
  ``ndev × shard_cap`` rows sharded ``P(axis)``, organized as ``ndev``
  uniform per-shard regions that each behave exactly like
  ``Table.append_tail``'s pow2-bucketed tail.  ``append_fact_rows``
  splits a batch into ``ndev`` contiguous sub-batches; a short last
  sub-batch is padded with *dead rows* (every FK = ``EMPTY_KEY``,
  measures 0) so the per-shard write windows stay uniform.  Dead rows
  miss every probe and every SSB query joins at least one dimension, so
  they fall out of every aggregate — bit-identity with the single-device
  engine holds because int32 modular addition is associative and
  commutative across any row partition.
* **Cached shard programs.**  Probes, tail writes, capacity growth, and
  probe-cache tail extension each run through one jitted
  ``shard_map`` program cached per (mesh, axis, plan/geometry) —
  ``engine/join.py:sharded_probe_program`` and friends — so steady-state
  sharded operation compiles nothing (the ``count_lowerings == 0``
  regression in tests/test_sharded_engine.py).
* **Collective epoch publication.**  Every mutation publish stamps the
  new epoch onto all shards through a tiny shard_map broadcast
  (``_epoch_stamps``, one int32 per shard).  ``snapshot()`` asserts the
  stamps are uniform and equal to the engine epoch before freezing — a
  shard still serving an older epoch (a torn publish) fails loudly
  instead of freezing a mixed-epoch image.
* **Re-sharding** (``reshard``) re-opens the logical image on a
  different mesh via ``launch/elastic.py:shard_fact_columns`` — fact
  columns pad to the new shard multiple (never silently dropping the
  axis), dimension state carries over verbatim, results stay
  bit-identical across 1→4→2 device moves.
* **Streamed open at scale** (``from_streamed``): dimensions generate
  host-side (small), fact rows arrive in shard-sized chunks
  (``engine/ssb.py:stream_ssb_fact``) appended straight into the
  sharded tails — the full fact table never materializes on one host.

Caveats vs the parent: ``mode="jspim"`` / ``kernel="xla"`` only and no
``hot_cold``/``stream`` schedules (``core.policy.validate_sharded``);
``Table.trimmed()`` on the sharded fact table is meaningless (live rows
are not a physical prefix — use ``logical_fact_columns``).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import hash_table as _ht
from repro.core.planner import SchedulePlan
from repro.core.policy import ExecutionPolicy, resolve_policy, \
    validate_sharded
from repro.engine.join import (DimIndex, build_dim_index, effective_index,
                               sharded_extend_program)
from repro.engine.queries import (DIM_PK, FACT_FK, SSBEngine,
                                  _check_batch_col, _mutates)
from repro.engine.snapshot import ShardedEpochSnapshot, sharded_join
from repro.engine.table import (TAIL_GROWTH_BATCHES, TAIL_MIN_BUCKET,
                                TAIL_RESERVE_FRAC, Table, round_up,
                                tail_bucket)
from repro.launch import elastic
from repro.launch.mesh import make_data_mesh

_FK_COLS = frozenset(FACT_FK.values())

# Compiled shard-side mutation programs, keyed by (kind, mesh, axis, ...).
# Same discipline as join._SHARDED_PROGRAMS: steady-state appends at a
# fixed batch bucket re-dispatch cached executables, no re-traces.
_PROGRAMS: dict = {}


def _write_program(mesh, axis: str, donate: bool):
    """Per-shard fused tail write (dynamic-slice every column at the
    replicated shard-local ``start``).  ``donate=True`` updates the
    capacity buffers in place — O(tail) per shard, not O(capacity)."""
    key = ("write", mesh, axis, donate)
    prog = _PROGRAMS.get(key)
    if prog is None:
        from repro.launch import compat

        def write_shard(cols, tails, start):
            return {k: jax.lax.dynamic_update_slice(cols[k], tails[k],
                                                    (start,))
                    for k in cols}

        sm = compat.shard_map(write_shard, mesh=mesh,
                              in_specs=(P(axis), P(axis), P()),
                              out_specs=P(axis))
        prog = jax.jit(sm, donate_argnums=(0,)) if donate else jax.jit(sm)
        _PROGRAMS[key] = prog
    return prog


def _grow_program(mesh, axis: str, extra: int, fills: tuple):
    """Per-shard capacity growth: concat ``extra`` fill rows onto every
    column shard (``fills`` = sorted (name, fill) pairs, static)."""
    key = ("grow", mesh, axis, extra, fills)
    prog = _PROGRAMS.get(key)
    if prog is None:
        from repro.launch import compat

        def grow_shard(cols):
            return {k: jnp.concatenate(
                [cols[k], jnp.full((extra,), f, jnp.int32)])
                for k, f in fills}

        prog = jax.jit(compat.shard_map(
            grow_shard, mesh=mesh, in_specs=(P(axis),),
            out_specs=P(axis)))
        _PROGRAMS[key] = prog
    return prog


def _grow_probe_program(mesh, axis: str, extra: int):
    """Per-shard probe-cache growth: pad (found, dim_row) with miss
    lanes (False / -1) to the grown shard capacity."""
    key = ("grow_probe", mesh, axis, extra)
    prog = _PROGRAMS.get(key)
    if prog is None:
        from repro.launch import compat

        def grow_shard(found, row):
            return (jnp.concatenate([found, jnp.zeros((extra,), bool)]),
                    jnp.concatenate([row,
                                     jnp.full((extra,), -1, jnp.int32)]))

        prog = jax.jit(compat.shard_map(
            grow_shard, mesh=mesh, in_specs=(P(axis), P(axis)),
            out_specs=(P(axis), P(axis))))
        _PROGRAMS[key] = prog
    return prog


def _stamp_program(mesh, axis: str):
    """Collective epoch publication: broadcast the (traced) epoch scalar
    so every shard holds its own stamp — the artifact ``snapshot()``
    checks for epoch uniformity across the mesh."""
    key = ("stamp", mesh, axis)
    prog = _PROGRAMS.get(key)
    if prog is None:
        from repro.launch import compat

        prog = jax.jit(compat.shard_map(
            lambda e: jnp.reshape(e, (1,)), mesh=mesh, in_specs=(P(),),
            out_specs=P(axis)))
        _PROGRAMS[key] = prog
    return prog


class ShardedSSBEngine(SSBEngine):
    """:class:`SSBEngine` with the fact table sharded across a mesh.

    ``mesh`` defaults to a 1-D data mesh over every local device;
    ``axis`` names the shard axis.  Everything the parent serves —
    ``run`` / ``run_all`` / ``probe_dim`` / ``snapshot`` / ``ingest`` /
    ``append_rows`` / ``compact`` — works unchanged; fact appends and
    probes run rank-parallel through cached shard_map programs.  Results
    are bit-identical to a single-device :class:`SSBEngine` over the
    same logical rows (the differential suite's contract).
    """

    def __init__(self, tables: dict[str, Table], *,
                 mesh: jax.sharding.Mesh | None = None, axis: str = "data",
                 indexes: dict[str, DimIndex] | None = None,
                 policy: ExecutionPolicy | None = None,
                 min_bucket: int = TAIL_MIN_BUCKET):
        pol = validate_sharded(resolve_policy(policy))
        if mesh is None:
            mesh = make_data_mesh(axis=axis)
        self.mesh = mesh
        self.axis = axis
        self._ndev = int(mesh.shape[axis])
        self._min_bucket = int(min_bucket)
        fact = tables["lineorder"]
        n0 = fact.n_rows
        self._fills = {k: (int(_ht.EMPTY_KEY) if k in _FK_COLS else 0)
                       for k in fact.names()}
        cols_np = {k: np.asarray(fact[k])[:n0] for k in fact.names()}
        for col in sorted(_FK_COLS):
            if n0 and (cols_np[col] == int(_ht.EMPTY_KEY)).any():
                raise ValueError(
                    f"lineorder[{col!r}] contains EMPTY_KEY — the "
                    "sentinel marks dead shard-filler rows and cannot "
                    "appear in live fact rows")
        # initial per-shard capacity mirrors append_tail's reserve policy
        per = elastic.shard_multiple(n0, self._ndev) // self._ndev
        if n0:
            reserve = max(TAIL_GROWTH_BATCHES * self._min_bucket,
                          int(per * TAIL_RESERVE_FRAC))
            cap = round_up(per + reserve, self._min_bucket)
        else:
            # an empty engine still reserves one tail bucket per shard:
            # Shardy refuses a shard_map whose operands are zero-size
            # (the empty manual-computation operand folds to a constant)
            cap = self._min_bucket
        sharded, cap, per = elastic.shard_fact_columns(
            cols_np, mesh, axis=axis, fills=self._fills,
            cap_per_shard=cap)
        tables = dict(tables)
        tables["lineorder"] = Table(sharded, valid_rows=n0)
        if indexes is None and pol.mode == "jspim":
            # replicated index build from the (small) dimension tables
            # only: no host pull of the sharded fact FK column, so
            # fact_skew stays unmeasured and planning is shard-local
            indexes = {dim: build_dim_index(tables[dim][pk])
                       for dim, pk in DIM_PK.items()}
        super().__init__(tables, indexes=indexes, policy=pol)
        self._shard_cap = cap      # physical rows per shard
        self._shard_valid = per    # written rows per shard (live + dead)
        self._n_live = n0          # true live rows across the mesh
        self._shard_owned = False  # buffers donatable by the next write
        # (start, per, n_live) per append window: the layout record that
        # reassembles logical row order from the per-shard regions
        self._windows: list[tuple[int, int, int]] = \
            [(0, per, n0)] if n0 else []
        self._epoch_stamps = _stamp_program(mesh, axis)(
            jnp.int32(self._epoch))

    # -- streamed open at scale -------------------------------------------
    @classmethod
    def from_streamed(cls, sf: float, seed: int = 0, *,
                      mesh: jax.sharding.Mesh | None = None,
                      axis: str = "data", chunk_rows: int = 1 << 20,
                      policy: ExecutionPolicy | None = None,
                      min_bucket: int = TAIL_MIN_BUCKET
                      ) -> "ShardedSSBEngine":
        """Open SSB at scale factor ``sf`` without ever materializing the
        fact table on one host: dimensions generate host-side, fact rows
        stream in ``chunk_rows``-sized append batches straight into the
        per-shard capacity tails."""
        from repro.engine.ssb import (LINEORDER_COLUMNS, generate_ssb_dims,
                                      stream_ssb_fact)

        tables = generate_ssb_dims(sf, seed)
        tables["lineorder"] = Table(
            {k: np.zeros((0,), np.int32) for k in LINEORDER_COLUMNS})
        eng = cls(tables, mesh=mesh, axis=axis, policy=policy,
                  min_bucket=min_bucket)
        for chunk in stream_ssb_fact(sf, seed, chunk_rows=chunk_rows):
            eng.append_fact_rows(chunk)
        return eng

    # -- shard-local planning ---------------------------------------------
    def _plan_dim(self, dim: str) -> None:
        """Shard-local probe planning: no host pull of the sharded FK
        column for hot-key ranking (``validate_sharded`` already rejected
        the schedules that would need one).  Every schedule is
        bit-identical by contract, so the restriction affects cost, not
        answers."""
        force = None if self.schedule == "auto" else self.schedule
        self.plans[dim] = SchedulePlan(schedule=force or "gathered")

    def _maybe_replan_fact_skew(self, force: bool = False) -> list[str]:
        """Skew re-measurement reads the whole FK column host-side —
        a single-host assumption.  Shard-local plans are static."""
        return []

    # -- rank-parallel join primitive -------------------------------------
    def _join(self, dim: str):
        return sharded_join(self, dim, self.mesh, self.axis)

    # -- sharded fact append ----------------------------------------------
    @_mutates
    def append_fact_rows(self, rows, *, extend_cache: bool = True) -> dict:
        """Append lineorder rows: every shard takes its own tail slice.

        The batch splits into ``ndev`` contiguous sub-batches (the last
        one dead-row-padded to keep per-shard windows uniform), writes
        land through one cached shard_map dynamic-slice program, and each
        cached dimension probe extends per shard — probe the pow2-padded
        per-shard tail, splice at the shard-local offset — through the
        cached :func:`~repro.engine.join.sharded_extend_program`.
        Donation, MVCC pins, WAL staging and the epoch publish mirror the
        parent exactly; the publish additionally stamps the new epoch on
        every shard (the collective ``snapshot()`` verifies).

        Live rows must not carry ``EMPTY_KEY`` in any FK column: the
        sentinel is reserved for dead filler rows at the shard boundary.
        """
        fact = self.tables["lineorder"]
        missing = set(fact.names()) ^ set(rows)
        if missing:
            raise ValueError(f"append_fact_rows column mismatch: "
                             f"{sorted(missing)}")
        new_cols: dict[str, np.ndarray] = {}
        n_new: int | None = None
        for k in fact.names():
            new_cols[k] = _check_batch_col(f"rows[{k!r}]", rows[k],
                                           expect_len=n_new)
            if n_new is None:
                n_new = new_cols[k].shape[0]
        if n_new == 0:  # strict no-op, like the parent
            return {"appended": 0, "epoch": self._fact_epoch, "dims": {},
                    "capacity_grew": False, "skew_replanned": []}
        for col in sorted(_FK_COLS):
            if (new_cols[col] == int(_ht.EMPTY_KEY)).any():
                raise ValueError(
                    f"rows[{col!r}] contains EMPTY_KEY — reserved for "
                    "dead shard-filler rows; live fact rows cannot "
                    "carry the sentinel")
        self._wal_log("append_fact_rows", {}, new_cols)
        ndev = self._ndev
        per = -(-n_new // ndev)           # live+dead rows per shard
        bp = tail_bucket(per, self._min_bucket)
        sharding = NamedSharding(self.mesh, P(self.axis))
        tails: dict[str, jax.Array] = {}
        for k, v in new_cols.items():
            fill = self._fills[k]
            buf = np.full((ndev, bp), fill, np.int32)
            flat = np.full((ndev * per,), fill, np.int32)
            flat[:n_new] = v
            buf[:, :per] = flat.reshape(ndev, per)
            tails[k] = jax.device_put(buf.reshape(-1), sharding)
        start = self._shard_valid
        grow = start + bp > self._shard_cap
        pinned = self._fact_pinned()
        if self._shard_owned and not grow and pinned:
            self._pin_copies += 1
        cols = dict(fact.columns)
        capacity_grew = False
        if grow:
            reserve = max(TAIL_GROWTH_BATCHES * bp,
                          int(self._shard_cap * TAIL_RESERVE_FRAC))
            new_cap = round_up(start + bp + reserve, bp)
            fills = tuple(sorted((k, self._fills[k]) for k in cols))
            cols = _grow_program(self.mesh, self.axis,
                                 new_cap - self._shard_cap, fills)(cols)
            self._shard_cap = new_cap
            capacity_grew = True
        if grow or not self._shard_owned or pinned:
            self._fact_gen += 1  # fresh buffers: no snapshot pins them
        donate = grow or (self._shard_owned and not pinned)
        cols = _write_program(self.mesh, self.axis, donate)(
            cols, tails, jnp.int32(start))
        self._shard_valid = start + per
        self._n_live += int(n_new)
        self._windows.append((start, per, int(n_new)))
        self.tables["lineorder"] = Table(cols, valid_rows=self._n_live)
        self._shard_owned = True
        self._epoch += 1
        self._fact_epoch += 1
        self._fact_appends += 1
        self._fact_rows_appended += int(n_new)
        report = {"appended": int(n_new), "epoch": self._fact_epoch,
                  "capacity_grew": capacity_grew, "dims": {}}
        start_t = jnp.int32(start)
        for dim in sorted(self._probe_cache):
            ap = self._fact_append_plan(dim, bp, start)
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
                owned = False
                pinned_copy = True
            fresh = not owned
            if found.shape[0] != ndev * self._shard_cap:  # capacity grew
                extra = self._shard_cap - found.shape[0] // ndev
                found, row = _grow_probe_program(
                    self.mesh, self.axis, extra)(found, row)
                owned, fresh = True, True
                pinned_copy = False
            if pinned_copy:
                self._pin_copies += 1
            plan = self.plans.get(dim)
            key_plan = plan if plan is not None and \
                plan.schedule == "deduped" else None
            extend = sharded_extend_program(self.mesh, self.axis,
                                            self.probe_impl, key_plan,
                                            donate=owned)
            self._probe_cache[dim] = extend(
                effective_index(self.indexes[dim]), None, found, row,
                tails[FACT_FK[dim]], start_t)
            # the fact-aligned columns are gathered again, lazily, from
            # the extended probe (no per-shard splice program)
            self._attr_cache.pop(dim, None)
            self._probe_epoch[dim] = self._fact_epoch
            self._cache_owned.add(dim)
            if fresh:
                self._cache_gens[dim] = self._cache_gens.get(dim, 0) + 1
            self._tail_extensions += 1
            report["dims"][dim] = "extended"
        report["skew_replanned"] = self._maybe_replan_fact_skew()
        self._wal_publish()
        return report

    # -- collective epoch publication -------------------------------------
    def _wal_publish(self) -> None:
        # stamp BEFORE observers run: a hook (or a snapshot taken from
        # one) must already see a mesh uniformly at the new epoch
        self._epoch_stamps = _stamp_program(self.mesh, self.axis)(
            jnp.int32(self._epoch))
        super()._wal_publish()

    def _replace_table(self, dim: str, table) -> None:
        # raw §3.2.3 cell writes bypass _wal_publish; re-stamp here so
        # the collective epoch can never fall behind the host epoch
        super()._replace_table(dim, table)
        self._epoch_stamps = _stamp_program(self.mesh, self.axis)(
            jnp.int32(self._epoch))

    def _make_snapshot(self) -> ShardedEpochSnapshot:
        stamps = np.asarray(self._epoch_stamps)
        if stamps.size and not (stamps == self._epoch).all():
            raise RuntimeError(
                f"mixed-epoch shard image: per-shard epoch stamps "
                f"{stamps.tolist()} != engine epoch {self._epoch}; a "
                "mutation path failed to publish collectively")
        return ShardedEpochSnapshot(self)

    # -- logical view + re-sharding ---------------------------------------
    def logical_fact_columns(self) -> dict[str, np.ndarray]:
        """The live fact rows in original append order (host pull).

        Reassembles the logical stream from the per-shard regions via the
        append-window record, dropping dead filler rows.  This is the
        mesh-agnostic image ``reshard`` (and any oracle) consumes — the
        sharded analogue of ``Table.trimmed()``, which is meaningless on
        the sharded layout (live rows are not a physical prefix).
        """
        fact = self.tables["lineorder"]
        cols = {k: np.asarray(v).reshape(self._ndev, self._shard_cap)
                for k, v in fact.columns.items()}
        out: dict[str, list] = {k: [] for k in cols}
        for (start, per, n) in self._windows:
            for k, v in cols.items():
                out[k].append(v[:, start:start + per].reshape(-1)[:n])
        return {k: (np.concatenate(v) if v
                    else np.zeros((0,), np.int32))
                for k, v in out.items()}

    def shard_info(self) -> dict:
        """Mesh + per-shard layout counters (observability)."""
        return {"devices": self._ndev, "axis": self.axis,
                "shard_capacity": self._shard_cap,
                "shard_valid": self._shard_valid,
                "live_rows": self._n_live,
                "dead_rows": self._shard_valid * self._ndev
                - self._n_live,
                "windows": len(self._windows)}

    def reshard(self, new_mesh: jax.sharding.Mesh, *,
                axis: str | None = None) -> "ShardedSSBEngine":
        """Re-open this engine's logical image on a different mesh.

        The elastic-restart path (device count changed between open and
        serve): fact columns are reassembled mesh-agnostically and
        re-padded to the new shard multiple (``shard_fact_columns`` —
        never silently dropping the shard axis); dimension tables,
        indexes and deltas carry over verbatim; plans re-derive.  The new
        engine is volatile (re-attach durability explicitly) and answers
        bit-identically to this one.
        """
        axis = axis or self.axis
        tables = dict(self.tables)
        tables["lineorder"] = Table(self.logical_fact_columns())
        return type(self)(tables, mesh=new_mesh, axis=axis,
                          indexes=dict(self.indexes), policy=self.policy,
                          min_bucket=self._min_bucket)
