"""MVCC epoch-snapshot serving: lock-free query/ingest separation.

JSPIM's rank-level design assumes queries stream against a stable index
image while updates land elsewhere (paper §3.2.3); this module is the
write-side half of that story (DESIGN.md §9).  ``SSBEngine.snapshot()``
freezes one consistent image — dimension tables, dictionaries, hash
tables, delta buffers, fact table, probe cache, fact-aligned column cache
and plan set, all at the engine's current epoch — as an
:class:`EpochSnapshot`.  The engine then keeps advancing its private head
image (``append_fact_rows`` / ``ingest`` / ``compact``) and publishes every
step with an atomic epoch bump, while the snapshot keeps serving queries
at its epoch:

* **Zero-copy freeze** — jax arrays are immutable values, so the snapshot
  simply aliases the engine's buffers.  The only mutation in the system
  is *buffer donation* (the engine's in-place fact-tail write, probe-cache
  splice and compaction merge), and the engine refuses to donate any
  buffer generation a live snapshot pins — the first mutation after a
  snapshot copies into a fresh generation, after which donation re-arms.
  Pin accounting is refcount-by-liveness: the engine holds snapshots in a
  ``WeakSet`` and a generation retires when every snapshot pinning it has
  been released (or garbage collected).
* **No invalidation path** — a snapshot never invalidates anything.  Its
  probe cache only grows (lazy probes of dimensions the engine had not
  cached at freeze time), its plans never re-plan, its programs never
  retrace: the epoch lives in host state, not in any jit-static argument.
* **Shared compiled programs** — the snapshot executes through the same
  ``_QueryRunner`` surface and the same compiled per-query programs as
  the head engine (shapes and plans are jit keys; the epoch is not), so
  serving an old epoch costs no compilation and cannot diverge
  behaviorally from the head's code path.
"""
from __future__ import annotations

import jax

from repro.engine.queries import DIM_PK, FACT_FK, SSBEngine, _QueryRunner


class EpochSnapshot(_QueryRunner):
    """One consistent, immutable image of an :class:`SSBEngine` at epoch E.

    Obtained from ``SSBEngine.snapshot()``.  Supports the engine's whole
    read surface — ``probe_dim`` / ``warm_cache`` / ``run`` / ``run_all``
    (cached and fused flavors) — and stays bit-identical to the freeze
    instant no matter how far the engine advances.  Release it when done
    (``release()``, or use it as a context manager) so the engine's
    donation fast paths re-arm; queries on a released snapshot raise.
    """

    def __init__(self, engine: SSBEngine):
        self.engine: SSBEngine | None = engine
        self.epoch = engine.epoch
        self.fact_epoch = engine.fact_epoch
        # the frozen ExecutionPolicy is immutable — aliasing it IS the
        # freeze (mode/probe_impl/schedule are _QueryRunner views of it)
        self.policy = engine.policy
        # the image: shallow copies of the engine's state dicts — the
        # values (Tables, DimIndex pytrees, plans, probe tuples) are
        # immutable, so aliasing them IS the freeze.  The fact table gets
        # an unowned view so not even a direct append on the snapshot's
        # table object could donate the shared capacity buffers.
        tables = dict(engine.tables)
        tables["lineorder"] = tables["lineorder"].pinned_view()
        self.tables = tables
        self.indexes = dict(engine.indexes)
        self.plans = dict(engine.plans)
        self._hot_codes = dict(engine._hot_codes)
        # freeze only probe entries consistent with the fact epoch (stale
        # stamps — possible only after a bug — read as misses everywhere)
        self._probe_cache = {
            d: e for d, e in engine._probe_cache.items()
            if engine._probe_epoch.get(d) == engine._fact_epoch}
        # fact-aligned columns frozen with the probe entries they were
        # gathered from; a miss gathers into this snapshot's own memo
        self._attr_cache = {
            d: e for d, e in engine._attr_cache.items()
            if e[0] is self._probe_cache.get(d) and e[1] is tables[d]}
        # compiled programs: the cached-probe programs are epoch-oblivious
        # (keyed by query + shapes) and shared with the engine outright;
        # the fused full programs close over plans statically, so the
        # snapshot takes a private copy the engine's re-plans cannot clear
        self._cached_programs = engine._cached_programs
        self._full_programs = dict(engine._full_programs)
        # one-launch programs are epoch- and plan-oblivious (operands are
        # pytree args), so they share outright like the cached programs
        self._suite_programs = engine._suite_programs
        self._mega_programs = engine._mega_programs
        # pin records: the buffer generations this snapshot aliases.  The
        # engine's donation sites check these against their *current*
        # generations — matching means "donating now would delete arrays
        # this snapshot reads", so they copy instead.
        self._pin_fact_gen = engine._fact_gen
        self._pin_cache_gens = {d: engine._cache_gens.get(d, 0)
                                for d in self._probe_cache}
        self._pin_index_gens = {d: engine._index_gens.get(d, 0)
                                for d in self.indexes}
        # maintained-view freeze (DESIGN.md §13): if a registered suite is
        # fresh at this exact epoch, its answers ARE this image's answers
        # — copy them out (host ints/arrays, O(views)) so the serving tier
        # can answer canonical queries without touching the fact table.
        # A stale or invalidated suite contributes nothing: queries fall
        # back to this snapshot's compiled recompute paths.
        self.maintained = None
        for suite in getattr(engine, "_view_suites", ()):
            if suite.fresh_at(engine.epoch):
                self.maintained = suite.results()
                break
        self._released = False

    # -- lifecycle ---------------------------------------------------------
    @property
    def released(self) -> bool:
        return self._released

    def release(self) -> None:
        """Retire this snapshot's pins (idempotent).

        Drops every buffer reference and unregisters from the engine, so
        the engine's next mutation may donate again if no *other* live
        snapshot pins the same generations — the refcounted retirement
        half of the MVCC story.  After release the snapshot refuses to
        run queries (its buffers may be donated away at any time).
        """
        if self._released:
            return
        self._released = True
        if self.engine is not None:
            self.engine._snapshots.discard(self)
        self.engine = None
        self.tables = {}
        self.indexes = {}
        self.plans = {}
        self._hot_codes = {}
        self._probe_cache = {}
        self._attr_cache = {}
        self._full_programs = {}
        self.maintained = None
        # rebind (not clear!) the shared one-launch program dicts
        self._suite_programs = {}
        self._mega_programs = {}

    def epoch_lag(self) -> int:
        """How many epochs the head engine has advanced past this image.

        0 ⟺ this snapshot is fresh.  The serving tier reports this per
        response as the staleness measure (DESIGN.md §11): a scheduler
        in degraded mode keeps answering from its last pinned snapshot
        and clients see exactly how stale the answer is.
        """
        self._check_live()
        return max(0, self.engine.epoch - self.epoch)

    def __enter__(self) -> "EpochSnapshot":
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def _check_live(self) -> None:
        if self._released:
            raise RuntimeError(
                "EpochSnapshot was released: its buffer pins are retired "
                "and the engine may have donated the arrays it aliased")

    # -- read surface ------------------------------------------------------
    def probe_dim(self, dim: str) -> tuple[jax.Array, jax.Array]:
        """(found, dim_row) for one dimension at this snapshot's epoch.

        Entries frozen from the engine are served as-is; a dimension the
        engine had not cached at freeze time is probed lazily against the
        snapshot's own (immutable) index image and memoized locally —
        the engine's cache is never touched, but the probe counts in its
        ``cache_info()["probes_run"]``.
        """
        self._check_live()
        hit = self._probe_cache.get(dim)
        if hit is not None:
            return hit
        out = self._probe(dim)
        if not isinstance(out[0], jax.core.Tracer):
            self.engine._count_probe()
            self._probe_cache[dim] = out
        return out

    def dim_attrs(self, dim: str, probe=None) -> dict[str, jax.Array]:
        """Fact-aligned columns at this snapshot's epoch: entries frozen
        from the engine are served as-is, a miss is gathered into the
        snapshot's own memo (the engine's cache is never touched)."""
        self._check_live()
        return super().dim_attrs(dim, probe)

    def _count_attrs(self, gathers: int = 0, hits: int = 0) -> None:
        self.engine._count_attrs(gathers=gathers, hits=hits)

    def warm_cache(self, dims=None) -> None:
        """Probe every (or the given) dimension into the snapshot cache,
        and gather its fact-aligned columns."""
        for dim in (dims if dims is not None else DIM_PK):
            self.dim_attrs(dim)

    def run(self, name: str, *, use_cache: bool | None = None,
            fusion: str | None = None):
        self._check_live()
        return super().run(name, use_cache=use_cache, fusion=fusion)

    def cache_info(self) -> dict:
        return {"epoch": self.epoch, "fact_epoch": self.fact_epoch,
                "cached_dims": sorted(self._probe_cache),
                "attr_dims": sorted(self._attr_cache),
                "released": self._released}


def sharded_join(runner: _QueryRunner, dim: str, mesh, axis: str):
    """The sharded engine's join primitive: cached shard_map probe over
    the mesh-sharded fact FK column (index and delta replicated ``P()``).

    Shared by :class:`~repro.engine.shard.ShardedSSBEngine` and
    :class:`ShardedEpochSnapshot` so head and snapshot execute the same
    compiled program — the program cache in ``engine/join.py`` is keyed
    by (mesh, axis, plan), and the probe's delta structure and batch
    shape key the inner jit, exactly the ``probe_dim`` discipline.
    Misses carry ``dim_row == -1`` (the cached-probe representation).
    """
    from repro.engine.join import effective_index, sharded_probe_program

    plan = runner.plans.get(dim)
    key_plan = plan if plan is not None and \
        plan.schedule == "deduped" else None
    prog = sharded_probe_program(mesh, axis, key_plan, 0)
    fk = runner.tables["lineorder"][FACT_FK[dim]]
    pr = prog(effective_index(runner.indexes[dim]), None, fk)
    return pr.found, pr.payload


class ShardedEpochSnapshot(EpochSnapshot):
    """An :class:`EpochSnapshot` of a mesh-sharded engine.

    The freeze is the same zero-copy aliasing — sharded arrays are
    immutable jax values like any other — plus the mesh geometry and the
    engine's collective epoch stamps, captured *after* the engine
    asserted they are uniform (``ShardedSSBEngine.snapshot``): no shard
    of this image can serve a mixed epoch.  Lazy probes of dimensions
    the engine had not cached run through the same cached shard_map
    programs as the head, so they come back sharded ``P(axis)`` and
    bit-identical to what the engine would have served at this epoch.
    """

    def __init__(self, engine):
        super().__init__(engine)
        self.mesh = engine.mesh
        self.axis = engine.axis
        # the per-shard epoch stamps at freeze (device array, one per
        # shard) — uniformity was asserted by the engine under its lock
        self.epoch_stamps = engine._epoch_stamps

    def _join(self, dim: str):
        return sharded_join(self, dim, self.mesh, self.axis)

    def cache_info(self) -> dict:
        info = super().cache_info()
        info["shards"] = int(self.mesh.shape[self.axis])
        return info
