"""The plain reference: SSB answers with numpy over the benchmark's own data.

It imports nothing of the program.  It holds the fact rows the benchmark
generated (base rows and every appended batch, in order), the dimension
tables, and the log of key re-points (upserts), and answers a query as of
any recorded state version: the first ``n_fact`` fact rows and the first
``n_upserts`` upserts.  ``accumulate="float32"`` sums the measure in float32,
the lower-precision control the comparison must catch.
"""
from __future__ import annotations

import bisect

import numpy as np

from benchkit.ssbgen import DIMS
from benchkit.ssbq import FACT_FK, QUERIES


def _wrap32(x) -> np.ndarray:
    return np.asarray(x).astype(np.int64).astype(np.int32)


class Reference:
    def __init__(self, fact: dict[str, np.ndarray],
                 dims: dict[str, dict[str, np.ndarray]], n_fact: int,
                 epoch: int = 0):
        self.fact = fact            # buffers; the live prefix grows
        self.dims = dims
        self.n_dim = {d: next(iter(dims[d].values())).shape[0] for d in DIMS}
        self.upserts: list[tuple[str, np.ndarray, np.ndarray]] = []
        self.epochs: list[int] = [epoch]
        self.states: list[tuple[int, int]] = [(n_fact, 0)]
        self._rows_cache: dict[str, tuple[int, np.ndarray]] = {}

    # -- state log ---------------------------------------------------------
    @property
    def n_fact(self) -> int:
        return self.states[-1][0]

    def append(self, epoch: int, n_new: int) -> None:
        """The next ``n_new`` rows of the fact buffers became visible at
        ``epoch``."""
        n, u = self.states[-1]
        self._record(epoch, (n + n_new, u))

    def upsert(self, epoch: int, dim: str, keys, rows) -> None:
        """Keys of ``dim`` re-pointed to dimension rows, visible at
        ``epoch``."""
        self.upserts.append((dim, np.asarray(keys, np.int64),
                             np.asarray(rows, np.int64)))
        n, _ = self.states[-1]
        self._record(epoch, (n, len(self.upserts)))

    def _record(self, epoch: int, state: tuple[int, int]) -> None:
        if epoch < self.epochs[-1]:
            raise ValueError(f"epoch went back: {epoch} < {self.epochs[-1]}")
        if epoch == self.epochs[-1]:
            self.states[-1] = state
        else:
            self.epochs.append(epoch)
            self.states.append(state)

    def state_at(self, epoch: int) -> tuple[int, int]:
        i = bisect.bisect_right(self.epochs, epoch) - 1
        if i < 0:
            raise ValueError(f"epoch {epoch} precedes the first state")
        return self.states[i]

    # -- evaluation ----------------------------------------------------------
    def _key_map(self, dim: str, n_upserts: int) -> np.ndarray:
        mp = np.arange(self.n_dim[dim], dtype=np.int64)
        for d, keys, rows in self.upserts[:n_upserts]:
            if d == dim:
                mp[keys] = rows
        return mp

    def _rows(self, dim: str, n_upserts: int, n_fact: int) -> np.ndarray:
        k = sum(1 for d, _, _ in self.upserts[:n_upserts] if d == dim)
        hit = self._rows_cache.get(dim)
        if hit is None or hit[0] != k or hit[1].shape[0] < n_fact:
            fk = self.fact[FACT_FK[dim]][:self.n_fact].astype(np.int64)
            mp = self._key_map(dim, n_upserts)
            ok = (fk >= 0) & (fk < mp.shape[0])
            rows = np.where(ok, mp[np.clip(fk, 0, mp.shape[0] - 1)], -1)
            hit = (k, rows)
            self._rows_cache[dim] = hit
        return hit[1][:n_fact]

    def answer(self, name: str, params, epoch: int, *,
               accumulate: str = "exact") -> tuple[int, np.ndarray]:
        n, u = self.state_at(epoch)
        return self.answer_state(name, params, n, u, accumulate=accumulate)

    def answer_state(self, name: str, params, n_fact: int, n_upserts: int, *,
                     accumulate: str = "exact") -> tuple[int, np.ndarray]:
        q = QUERIES[name]
        p = tuple(int(x) for x in params)
        fact = {c: self.fact[c][:n_fact] for c in q.fact_columns()}
        mask = np.ones(n_fact, bool)
        rows = {}
        for dim in q.joined_dims():
            r = self._rows(dim, n_upserts, n_fact)
            rows[dim] = r
            mask &= r >= 0
            if dim in q.dim_filters:
                dmask = np.asarray(q.dim_filters[dim][1](self.dims[dim], p))
                mask &= dmask[np.clip(r, 0, dmask.shape[0] - 1)]
        if q.fact_filter is not None:
            mask &= q.fact_filter(fact, p)
        m = q.measure(fact)[mask]
        if q.group_by:
            gk = np.zeros(int(mask.sum()), np.int64)
            size = 1
            for dim, col, card in q.group_by:
                c = self.dims[dim][col]
                r = rows[dim][mask]
                gk = gk * card + c[np.clip(r, 0, c.shape[0] - 1)] % card
                size *= card
        if accumulate == "exact":
            total = int(_wrap32(m.sum()))
            if not q.group_by:
                return total, np.asarray([total], np.int32)
            groups = np.bincount(gk, weights=m, minlength=size)
            return total, _wrap32(np.rint(groups))
        if accumulate != "float32":
            raise ValueError(f"unknown accumulation {accumulate!r}")
        m32 = m.astype(np.float32)
        total = int(_wrap32(np.float64(np.sum(m32, dtype=np.float32))))
        if not q.group_by:
            return total, np.asarray([total], np.int32)
        groups = np.zeros(size, np.float32)
        np.add.at(groups, gk, m32)
        return total, _wrap32(groups.astype(np.float64))
