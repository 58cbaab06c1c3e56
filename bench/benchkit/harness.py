"""One run of one cell: set-up, the measured window, the check.

The window drives the program's serving entry (``QueryScheduler.submit``
over an ``SSBEngine``) from closed-loop query streams, and, where the
traffic mix has a writer, ``SSBEngine.append_fact_rows`` and
``SSBEngine.ingest`` on an open-loop schedule.  Every request and write is
drawn from the seed; the reference mirrors every write with the epoch the
engine published it at, and after the window a seeded sample of the answers
is compared with the reference at the epoch each answer reports.
"""
from __future__ import annotations

import collections
import dataclasses
import sys
import threading
import time

import numpy as np

from benchkit import reference as reference_mod
from benchkit import ssbgen
from benchkit.ssbq import FACT_FK, QUERIES, QUERY_IDS

# seed streams (SeedSequence words after the run's seed)
DATA, WRITER, CHECK, STREAM0 = 0, 1, 2, 100
DRAIN_S = 60.0          # how long an answer due in the window may be late


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileClock:
    """JAX's trace, lowering and backend-compile durations and counts, and
    persistent-cache hits (jax.monitoring)."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.counts = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event in (self.TRACE, self.LOWER, self.COMPILE):
            self.seconds += duration
            self.counts[event.rsplit("/", 1)[-1]] += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, **self.counts}


@dataclasses.dataclass
class Request:
    stream: int
    seq: int
    name: str
    params: tuple[int, ...]
    ticket: object


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def _warm_widths(n_streams: int) -> list[int]:
    """Batch widths the window can produce: at most one request per
    stream is queued, and the scheduler pads a batch to a power of two."""
    out = [1]
    while out[-1] < n_streams:
        out.append(2 * out[-1])
    return out


class Writer:
    """The open-loop writer: fact appends due at a fixed rate, and after
    every ``upsert_every``-th append a re-point of ``upsert_keys`` keys of
    one dimension (the dimensions in turn).  Every batch is drawn from the
    seed in set-up.  An append's latency runs from when it was due to when
    the engine has published its epoch; one that falls behind starts as
    soon as the write before it is done.  Every write due in the window is
    applied, late or not, unless ``DRAIN_S`` past the close has gone by."""

    def __init__(self, traffic: dict, data, ref):
        self.t = traffic
        self.ref = ref
        self.data = data
        self.n_done = 0            # appends applied, set-up included
        self.latency_s: list[float] = []    # due -> published
        self.service_s: list[float] = []    # started -> published
        self.lateness_s: list[float] = []
        self.failures: list[str] = []
        self.n_due = 0             # appends due in the window
        self.n_upserts_due = 0
        self.n_unapplied = 0       # writes due that never started
        self.n_upserts = 0

    def _batch(self, i: int) -> dict[str, np.ndarray]:
        n = self.t["append_rows"]
        lo = self.data.n_base + i * n
        return {k: v[lo:lo + n] for k, v in self.data.fact.items()}

    def append(self, engine) -> None:
        from jax.profiler import TraceAnnotation

        rows = self._batch(self.n_done)
        with TraceAnnotation("append"):
            engine.append_fact_rows(rows)
        self.ref.append(engine.epoch, self.t["append_rows"])
        self.n_done += 1

    def upsert(self, engine, dim: str) -> None:
        from jax.profiler import TraceAnnotation

        keys, rows = self.data.upserts[self.n_upserts]
        keys, rows = keys[dim], rows[dim]
        with TraceAnnotation("upsert"):
            engine.ingest(dim, keys, rows, op="upsert")
        self.ref.upsert(engine.epoch, dim, keys, rows)
        self.n_upserts += 1

    def warm(self, engine) -> None:
        """Set-up: an append (which moves the fact table into its capacity
        buffers, the shapes every later write sees), an upsert of each
        dimension, then appends, every other one under a live snapshot (the
        copying write path the serving tier's pinned snapshots force), so
        every program the window's writes use has run.  The upserts leave
        the engine's probe cache empty, as the window's upserts would: the
        serving tier never fills it again."""
        self.append(engine)
        for dim in self.t["upsert_dims"]:
            self.upsert(engine, dim)
        for i in range(1, self.t["warmup_appends"]):
            snap = engine.snapshot() if i % 2 else None
            self.append(engine)
            if snap is not None:
                snap.release()

    def run(self, engine, t0: float, t_end: float) -> None:
        """Apply the writes due in the window, in order."""
        rate = float(self.t["appends_per_s"])
        every, dims = self.t["upsert_every"], self.t["upsert_dims"]
        self.n_due = int(np.ceil((t_end - t0) * rate))
        self.n_upserts_due = self.n_due // every
        deadline = t_end + DRAIN_S
        for j in range(self.n_due):
            due = t0 + j / rate
            now = time.monotonic()
            if now >= deadline:
                self.n_unapplied = (self.n_due - j
                                    + self.n_upserts_due - j // every)
                log(f"writer: {self.n_unapplied} writes due in the window "
                    f"never started")
                return
            if due > now:
                time.sleep(due - now)
            start = time.monotonic()
            self.lateness_s.append(max(0.0, start - due))
            ops = [self.append]
            if (j + 1) % every == 0:
                dim = dims[((j + 1) // every - 1) % len(dims)]
                ops.append(lambda engine, dim=dim: self.upsert(engine, dim))
            for op in ops:
                try:
                    op(engine)
                except Exception as e:   # a failed write is counted
                    self.failures.append(repr(e))
                    log(f"writer: write {j} failed: {e!r}")
                    continue
                if op == self.append:
                    done = time.monotonic()
                    self.latency_s.append(done - due)
                    self.service_s.append(done - start)


@dataclasses.dataclass
class Data:
    fact: dict[str, np.ndarray]     # base rows, then every appended batch
    dims: dict[str, dict[str, np.ndarray]]
    n_base: int
    upserts: list                   # per upsert: ({dim: keys}, {dim: rows})


def generate(seed: int, config: dict, traffic: dict, seconds: float) -> Data:
    """The cell's data and writes, all from the seed."""
    rng = _rng(seed, DATA)
    sf = float(config["sf"])
    dims = ssbgen.dims(rng, sf)
    base = ssbgen.fact(rng, sf, config.get("fk_zipf_s", {}))
    n_base = base["orderkey"].shape[0]
    if not traffic.get("appends_per_s"):
        return Data(base, dims, n_base, [])
    wrng = _rng(seed, WRITER)
    n_window = int(np.ceil(traffic["appends_per_s"] * seconds))
    n_app = traffic["warmup_appends"] + n_window
    n = traffic["append_rows"]
    fact = {k: np.empty(n_base + n_app * n, np.int32) for k in base}
    for k, v in base.items():
        fact[k][:n_base] = v
    live = n_base
    for _ in range(n_app):
        b = ssbgen.fact_batch(wrng, fact, live, n)
        for k, v in b.items():
            fact[k][live:live + n] = v
        live += n
    # re-points: keys drawn as the fact rows reference them (hot keys are
    # updated in proportion), each to a uniformly drawn dimension row
    n_up = len(traffic["upsert_dims"]) + n_window // traffic["upsert_every"]
    upserts = []
    sizes = ssbgen.sizes(sf)
    for _ in range(n_up):
        keys, rows = {}, {}
        for dim in ssbgen.DIMS:
            fk = fact[FACT_FK[dim]]
            picked: list[int] = []
            while len(picked) < traffic["upsert_keys"]:
                for v in fk[wrng.integers(0, n_base, 16)].tolist():
                    if v not in picked and len(picked) < traffic[
                            "upsert_keys"]:
                        picked.append(v)
            picked = np.asarray(picked)
            keys[dim] = picked.astype(np.int32)
            rows[dim] = wrng.integers(0, sizes[dim], picked.shape[0],
                                      dtype=np.int32)
        upserts.append((keys, rows))
    return Data(fact, dims, n_base, upserts)


def _percentile(xs, q) -> float | None:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def build(data: Data):
    """The engine over the base rows, its probe cache warmed."""
    import jax
    from repro.engine import SSBEngine, Table

    tables = {"lineorder": Table.from_numpy(
        {k: v[:data.n_base] for k, v in data.fact.items()})}
    for d, cols in data.dims.items():
        tables[d] = Table.from_numpy(cols)
    engine = SSBEngine(tables)
    engine.warm_cache()
    jax.block_until_ready([engine.probe_dim(d) for d in ssbgen.DIMS])
    return engine


def run(cell, seed: int, seconds: float, trace_dir: str | None,
        t_process: float, control: bool = False) -> dict:
    """Set up, measure, check.  Returns everything the harness prints.
    With ``control``, also ``control_check``: the same check with the
    float32 reference's answers put in the program's place."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.serving import QueryScheduler, ServeConfig

    clock = CompileClock()
    traffic, config = cell.traffic, cell.config
    split = {"tpu_init_s": time.monotonic() - t_process}

    t = time.monotonic()
    data = generate(seed, config, traffic, seconds)
    split["datagen_s"] = time.monotonic() - t

    t = time.monotonic()
    engine = build(data)
    split["engine_build_s"] = time.monotonic() - t
    log("plans: " + " ".join(f"{d}={p.schedule}"
                             + ("(full_map)" if p.full_map else "")
                             for d, p in sorted(engine.plans.items())))

    t = time.monotonic()
    ref = reference_mod.Reference(data.fact, data.dims, data.n_base,
                                  epoch=engine.epoch)
    sched = QueryScheduler(engine, ServeConfig())
    writer = None
    if traffic.get("appends_per_s"):
        writer = Writer(traffic, data, ref)
        writer.warm(engine)
    runner = sched.runner
    plain = runner.run_batch
    snap = engine.snapshot()
    widths = _warm_widths(traffic["query_streams"])
    for name in QUERY_IDS:
        for w in widths:
            plain(snap, name, [QUERIES[name].defaults] * w)
    snap.release()
    split["warmup_s"] = time.monotonic() - t
    setup_compile = clock.snapshot()

    dispatches: list[tuple[float, str, int, int]] = []
    dim_rows = {d: int(next(iter(c.values())).shape[0])
                for d, c in data.dims.items()}

    def observed(rn, name, params_list, **kw):
        with TraceAnnotation(f"dispatch {name} x{len(params_list)}"):
            out = plain(rn, name, params_list, **kw)
        dispatches.append((time.monotonic(), name, len(params_list),
                           rn.tables["lineorder"].n_rows))
        return out

    runner.run_batch = observed

    requests: list[Request] = []
    n_streams = traffic["query_streams"]

    def stream(i: int, t_end: float) -> None:
        rng = _rng(seed, STREAM0 + i)
        seq = 0
        while time.monotonic() < t_end:
            for k in rng.permutation(len(QUERY_IDS)):
                if time.monotonic() >= t_end:
                    return
                name = QUERY_IDS[int(k)]
                p = QUERIES[name].sample(rng)
                tk = sched.submit(name, p)
                requests.append(Request(i, seq, name, p, tk))
                seq += 1
                tk.wait(max(0.0, t_end + DRAIN_S - time.monotonic()))
                if not tk.done:
                    return

    info0 = sched.info()
    cache0, fact0 = engine.cache_info(), engine.fact_append_info()
    n_phys0 = fact0["n_physical"]
    comp0 = clock.snapshot()
    window = {}
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.monotonic()
    split["setup_s"] = t0 - t_process
    t_end = t0 + seconds
    sched.start(n_dispatchers=traffic["dispatchers"])
    threads = [threading.Thread(target=stream, args=(i, t_end),
                                name=f"stream-{i}")
               for i in range(n_streams)]
    if writer is not None:
        threads.append(threading.Thread(target=writer.run,
                                        args=(engine, t0, t_end),
                                        name="writer"))
    for th in threads:
        th.start()
    time.sleep(max(0.0, t_end - time.monotonic()))
    if trace_dir is not None:
        t_stop = time.monotonic()
        jax.profiler.stop_trace()
        window["traced_window_s"] = t_stop - t0
    for th in threads:
        th.join(timeout=t_end + DRAIN_S + 5 - time.monotonic())
    hung = [th.name for th in threads if th.is_alive()]
    info1 = sched.info()
    cache1, fact1 = engine.cache_info(), engine.fact_append_info()
    comp1 = clock.snapshot()
    sched.stop()
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    plans_after = {d: p.schedule for d, p in engine.plans.items()}
    sched.close()
    del sched, engine, runner, plain, observed
    jax.clear_caches()

    # -- end-to-end readings -------------------------------------------------
    in_window = [r for r in requests if r.ticket.submitted_at < t_end]
    ok = [r for r in in_window if r.ticket.done and r.ticket.response.ok]
    missing = [r for r in in_window if not r.ticket.done]
    not_ok = [r for r in in_window if r.ticket.done
              and not r.ticket.response.ok]
    done_in_window = [r for r in ok if r.ticket.resolved_at <= t_end]
    lat = [r.ticket.latency_s for r in in_window if r.ticket.done]
    e2e = {"queries_per_s": len(done_in_window) / seconds,
           "query_p95_ms": (_percentile(lat, 95) or 0.0) * 1e3,
           "setup_s": split["setup_s"]}
    w_disp = [d for d in dispatches if t0 <= d[0] <= t_end]
    attempted = len(in_window)
    failed = len(not_ok) + len(missing)
    if writer is not None:
        e2e["append_p95_ms"] = (_percentile(writer.latency_s, 95)
                                or 0.0) * 1e3
        attempted += writer.n_due + writer.n_upserts_due
        failed += len(writer.failures) + writer.n_unapplied

    # -- what the per-layer readers see ---------------------------------------
    ctx = {
        "seconds": seconds,
        "queries_done": len(done_in_window),
        "sched": (info0, info1),
        "cache": (cache0, cache1),
        "fact_append": (fact0, fact1),
        "dispatches": w_disp,
        "dim_rows": dim_rows,
        "setup_compile": setup_compile,
        "window": window,
    }

    log(f"setup split: " + " ".join(f"{k}={v:.3f}" for k, v in split.items())
        + f" setup_compile_s={setup_compile['seconds']:.3f}"
        + f" setup_cache_hits={setup_compile.get('cache_hits', 0)}")
    comp_in = {k: comp1.get(k, 0) - comp0.get(k, 0) for k in comp1
               if k != "seconds"}
    log(f"compiles in window: {comp_in} "
        f"({comp1['seconds'] - comp0['seconds']:.3f} s)")
    widths = collections.Counter(d[2] for d in w_disp)
    log(f"batch widths in window: {dict(sorted(widths.items()))}; "
        f"scheduler {_delta(info0, info1)}")
    log(f"probe cache: {_delta(cache0, cache1)}; "
        f"fact appends: {_delta(fact0, fact1)}")
    log(f"capacity: n_physical {n_phys0} -> {fact1['n_physical']}"
        f" (grew in window: {fact1['n_physical'] != n_phys0}); "
        f"plans after window: {plans_after}")
    if writer is not None:
        log(f"writer: appends due={writer.n_due} "
            f"applied={len(writer.latency_s)} "
            f"upserts due={writer.n_upserts_due} "
            f"failed={len(writer.failures)} "
            f"unapplied={writer.n_unapplied} "
            f"append_p50_ms={1e3 * (_percentile(writer.latency_s, 50) or 0):.3f} "
            f"append_max_ms={1e3 * max(writer.latency_s, default=0):.3f} "
            f"service_p95_ms="
            f"{1e3 * (_percentile(writer.service_s, 95) or 0):.3f} "
            f"lateness_max_s={max(writer.lateness_s, default=0):.4f} "
            f"lateness_p95_s={_percentile(writer.lateness_s, 95) or 0:.4f} "
            f"upserts={writer.n_upserts}")
    log(f"requests: submitted={len(in_window)} ok={len(ok)} "
        f"not_ok={len(not_ok)} missing={len(missing)} hung={hung} "
        f"completed_in_window={len(done_in_window)}")

    # -- the check ------------------------------------------------------------
    check = compare(ok, ref, seed, traffic["check_sample"])
    check["missing_answers"] = len(missing)
    out = {"e2e": e2e, "ctx": ctx, "attempted": attempted,
           "failed": failed, "memory_peak_bytes": int(mem or 0),
           "check": check, "device": dev, "split": split}
    if control:
        out["control_check"] = compare(ok, ref, seed,
                                       traffic["check_sample"],
                                       accumulate="float32")
        out["control_check"]["missing_answers"] = len(missing)
    return out


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b
            if isinstance(b[k], (int, float)) and not isinstance(b[k], bool)
            and k in a and b[k] != a[k]}


def compare(ok: list[Request], ref, seed: int, n_sample: int,
            accumulate: str | None = None) -> dict:
    """Compare a seeded sample of the ok answers with the reference at the
    epoch each reports.  With ``accumulate``, the reference computed so
    stands in for the program's answers on the same sample (the control)."""
    ok = sorted(ok, key=lambda r: (r.stream, r.seq))
    rng = _rng(seed, CHECK)
    k = min(n_sample, len(ok))
    pick = sorted(rng.choice(len(ok), size=k, replace=False).tolist(),
                  key=lambda i: ok[i].ticket.response.epoch) if k else []
    wrong = 0
    who = "control" if accumulate else "check"
    for i in pick:
        r = ok[i]
        resp = r.ticket.response
        if accumulate:
            got = ref.answer(r.name, r.params, resp.epoch,
                             accumulate=accumulate)
        else:
            got = (int(resp.total), np.asarray(resp.groups).astype(np.int64))
        want = ref.answer(r.name, r.params, resp.epoch)
        if not _same(got, want):
            wrong += 1
            if wrong <= 3:
                log(f"{who}: {r.name}{r.params} at epoch {resp.epoch}: "
                    f"total {got[0]} != reference {want[0]}")
    return {"sampled": k, "wrong_answers": wrong,
            "epochs": sorted({ok[i].ticket.response.epoch for i in pick})}


def _same(a, b) -> bool:
    return int(a[0]) == int(b[0]) and np.array_equal(
        np.asarray(a[1]).astype(np.int64), np.asarray(b[1]).astype(np.int64))
