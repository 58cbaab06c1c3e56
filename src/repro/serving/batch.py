"""Batched dispatch: many parameter vectors through one compiled program.

The fused path vmaps the engine's shared ``_filter_aggregate`` tail over a
``(B, P)`` int32 parameter array — the fact columns, cached probes and
fact-aligned dimension columns (``dim_attrs``) are closed over as
non-mapped operands, so a batch of B compatible requests costs one
dispatch instead of B, and no dimension column is gathered per query.
Batch width is bucketed to powers of two (replicating the last row) so
the number of distinct traces per query id is logarithmic in the largest
batch ever served.

The composed path is the degraded flavor the circuit breaker falls back
to: one request at a time through a plain (non-vmapped) jit of the same
tail.  It is deliberately a *different* compiled program — a poisoned
fused kernel (the chaos harness injects faults per code path) must not be
re-entered by its own fallback.

PR 8 adds the **mega** flavor: one dispatch folds the delta-aware probe
*into* the batched program (no cached-probe dependency), the serving
analogue of the engine's one-launch fused path.  The fallback ladder is
mega → composed: a breaker opened by mega faults serves composed
directly, never re-entering the poisoned one-launch program.

All flavors read only :class:`~repro.engine.queries._QueryRunner` surface
(``probe_dim`` / ``dim_attrs`` / ``tables`` / ``indexes``), so a
:class:`~repro.engine.snapshot.EpochSnapshot` serves batches exactly like
the head engine would.

Each program is named ``serve_<flavor>_<query>`` (``serve_batch_Q2_1``), so
a profiler trace names its device time by flavor and query.  Each launch
runs under a ``serve.run`` host span (on the batched flavors from operand
gathering on) and each wait for its answers under ``serve.fetch``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.policy import ExecutionPolicy
from repro.durability.faults import NULL_FAULTS
from repro.engine.join import effective_index, lookup
from repro.engine.queries import FACT_FK, SSB_QUERIES, _filter_aggregate
from repro.serving.params import PARAM_QUERIES


def _bucket(n: int) -> int:
    """Smallest power of two ≥ n (trace-count bound per query id)."""
    b = 1
    while b < n:
        b *= 2
    return b


def _named(program, flavor: str, name: str):
    """Jit ``program`` as ``serve_<flavor>_<query>``: the compiled module
    is then ``jit_serve_<flavor>_<query>`` in HLO and in a trace."""
    program.__name__ = f"serve_{flavor}_{name.replace('.', '_')}"
    return jax.jit(program)


class BatchRunner:
    """Per-query-id compiled programs over a ``_QueryRunner``'s state.

    Programs are keyed by query id only — parameters are *operands*, so
    refreshing to a newer snapshot reuses every compiled program (shapes
    and plans are unchanged; the epoch is never a jit key).
    """

    def __init__(self, policy: ExecutionPolicy | None = None):
        # the serving tier's ExecutionPolicy: fusion="mega" makes the
        # one-launch flavor the default dispatch (the breaker still
        # ladders down to composed); None keeps the pre-PR-8 batch default
        self.policy = policy
        self._batch_programs: dict[str, object] = {}
        self._single_programs: dict[str, object] = {}
        self._mega_programs: dict[str, object] = {}

    # -- compiled programs -------------------------------------------------
    def _batch_program(self, name: str):
        prog = self._batch_programs.get(name)
        if prog is None:
            pq = PARAM_QUERIES[name]

            def program(fact_cols, attrs, probes, params):
                def one(p):
                    return _filter_aggregate(pq.bind(p), fact_cols, None,
                                             probes, attrs)
                return jax.vmap(one)(params)

            prog = _named(program, "batch", name)
            self._batch_programs[name] = prog
        return prog

    def _mega_program(self, name: str):
        """One-launch batched program: delta-aware probe folded into the
        dispatch.  Probes are parameter-independent (parameters bind only
        filters and group keys), so they compute once per dispatch and the
        vmapped tails share them — one launch serves the whole batch even
        probe-cache-cold, and live deltas resolve inside the program."""
        prog = self._mega_programs.get(name)
        if prog is None:
            pq = PARAM_QUERIES[name]
            spec = SSB_QUERIES[name]

            def program(fact_cols, dim_cols, indexes, params):
                probes = {}
                with jax.named_scope("probe"):
                    for dim in spec.joined_dims():
                        pr = lookup(indexes[dim], fact_cols[FACT_FK[dim]])
                        probes[dim] = (pr.found,
                                       jnp.where(pr.found, pr.payload, -1))

                def one(p):
                    return _filter_aggregate(pq.bind(p), fact_cols,
                                             dim_cols, probes)
                return jax.vmap(one)(params)

            prog = _named(program, "mega", name)
            self._mega_programs[name] = prog
        return prog

    def _single_program(self, name: str):
        prog = self._single_programs.get(name)
        if prog is None:
            pq = PARAM_QUERIES[name]

            def program(fact_cols, attrs, probes, p):
                return _filter_aggregate(pq.bind(p), fact_cols, None,
                                         probes, attrs)

            prog = _named(program, "composed", name)
            self._single_programs[name] = prog
        return prog

    # -- inputs ------------------------------------------------------------
    @staticmethod
    def _operands(runner, name: str):
        """Fact columns, fact-aligned dimension columns and cached probes
        of the batch and composed programs."""
        fact_cols = dict(runner.tables["lineorder"].columns)
        probes, attrs = runner._cached_operands(
            SSB_QUERIES[name].joined_dims())
        return fact_cols, attrs, probes

    # -- execution ---------------------------------------------------------
    def _resolve_flavor(self, runner, flavor: str | None,
                        composed: bool) -> str:
        if flavor is None:
            if composed:
                return "composed"
            if (self.policy is not None and self.policy.fusion == "mega"
                    and getattr(runner, "mode", None) == "jspim"):
                return "mega"
            return "batch"
        if flavor not in ("mega", "batch", "composed"):
            raise ValueError(f"unknown serve flavor {flavor!r}")
        if flavor == "mega" and getattr(runner, "mode", None) != "jspim":
            return "batch"     # no indexes to fold the probe over
        return flavor

    def run_batch(self, runner, name: str, params_list, *,
                  composed: bool = False, flavor: str | None = None,
                  faults=NULL_FAULTS) -> list[tuple[int, np.ndarray]]:
        """Serve ``params_list`` against ``runner``; one (total, groups)
        per request, as host numpy.

        ``flavor`` picks the dispatch shape: "mega" (one launch, probe
        folded in), "batch" (vmapped tail over cached probes), "composed"
        (per-request fallback programs).  ``composed=True`` is the legacy
        shim for flavor="composed"; with neither, the runner policy
        decides.  ``faults`` sees ``kernel_mega:{name}`` /
        ``kernel_batch:{name}`` / ``kernel_composed:{name}`` once per
        dispatch, *before* the kernel runs — an injected crash poisons
        the whole batch, like a real device fault would.
        """
        if not params_list:
            return []
        pq = PARAM_QUERIES[name]
        for p in params_list:
            if len(p) != pq.n_params:
                raise ValueError(
                    f"{name} takes {pq.n_params} params {pq.params}, "
                    f"got {len(p)}: {tuple(p)!r}")
        flavor = self._resolve_flavor(runner, flavor, composed)
        if flavor == "composed":
            operands = self._operands(runner, name)
            prog = self._single_program(name)
            out = []
            for p in params_list:
                faults.hit(f"kernel_composed:{name}")
                with TraceAnnotation("serve.run", flavor=flavor, width=1):
                    total, groups = prog(*operands,
                                         jnp.asarray(p, jnp.int32))
                with TraceAnnotation("serve.fetch"):
                    out.append((int(total), np.asarray(groups)))
            return out
        b = len(params_list)
        width = _bucket(b)
        with TraceAnnotation("serve.run", flavor=flavor, width=width):
            padded = list(params_list) + [params_list[-1]] * (width - b)
            params = jnp.asarray(np.asarray(padded, np.int32))
            if flavor == "mega":
                spec = SSB_QUERIES[name]
                fact_cols = dict(runner.tables["lineorder"].columns)
                dim_cols = {d: dict(runner.tables[d].columns)
                            for d in spec.joined_dims()}
                idx = {d: effective_index(runner.indexes[d])
                       for d in spec.joined_dims()}
                faults.hit(f"kernel_mega:{name}")
                totals, groups = self._mega_program(name)(
                    fact_cols, dim_cols, idx, params)
            else:
                fact_cols, attrs, probes = self._operands(runner, name)
                faults.hit(f"kernel_batch:{name}")
                totals, groups = self._batch_program(name)(
                    fact_cols, attrs, probes, params)
        with TraceAnnotation("serve.fetch"):
            totals = np.asarray(totals)
            groups = np.asarray(groups)
        return [(int(totals[i]), groups[i]) for i in range(b)]
