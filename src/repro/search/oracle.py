"""The memoized, parallel, persistently-cached engine cost oracle.

A placement search evaluates thousands of candidate virtual->global PE
maps; :class:`PlacementOracle` makes each evaluation as cheap as possible
while keeping one invariant absolute: **every makespan it returns is a
full discrete-event engine result** (:func:`repro.core.engine
.oracle_makespan`).  The layers, from cheapest to costliest:

1. **in-memory memo** — candidates are keyed by the SHA-256 digest of
   their map; a digest seen before in this process returns instantly.
2. **persistent cache** — an :class:`~repro.search.cache.OracleCache`
   keyed ``fingerprint/geometry/interconnect/digest`` (the graph
   fingerprint is :func:`repro.obs.trace.graph_fingerprint` of the
   materialized base).  Warm re-runs, CI smoke, and the autotuner hit
   this layer and issue zero engine evals.
3. **surrogate prune** — the admissible
   :class:`~repro.search.surrogate.LowerBoundModel`: candidates whose
   lower bound already meets the best engine-verified makespan can never
   improve on it and are discarded *unevaluated* (the surrogate prunes;
   it never produces a returned makespan).
4. **engine evaluation** — remap the one materialized base graph
   (:func:`repro.device.partition._remap_ir`, an int-gather) and run the
   engine.  The base is materialized once, the
   :class:`~repro.device.resources.DeviceModel` (and its memoized
   cross-bank plan prices) is shared across every candidate, and the
   event loop is chosen per graph size: the scalar loop for small oracle
   cells, the vectorized loop at scale — both bit-identical by the
   engine's core invariant, so the choice is pure speed.
5. **process pool** — only when asked for with ``n_workers > 1`` (the
   default is 1: forking a process that holds an accelerator is unsafe),
   cache-missed candidates fan out over a forked worker pool (workers
   inherit the base graph, model, and warm move-cache by fork, sharing
   every structural memo).  Results
   are merged in input order keyed by candidate digest, so a search is
   seed-reproducible regardless of worker count (asserted by
   ``tests/test_search.py``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import weakref

import numpy as np

from repro.core import engine, ir
from repro.core.ir import TaskGraph
from repro.core.pluto import Interconnect
from repro.device.geometry import DeviceGeometry
from repro.device.resources import DeviceModel
from repro.search.cache import OracleCache
from repro.search.surrogate import LowerBoundModel

#: graphs at or below this task count evaluate on the scalar event loop —
#: at oracle-cell sizes its per-call overhead beats the vectorized loop's
#: batch setup (PR7 measured the crossover; both loops are bit-identical)
SCALAR_ORACLE_CUTOVER = 4096

#: live oracles, for :func:`clear_caches` teardown
_ORACLES: "weakref.WeakSet[PlacementOracle]" = weakref.WeakSet()

#: fork-inherited registry the pool workers resolve their oracle from
_FORK_REGISTRY: dict[int, "PlacementOracle"] = {}


def _pool_eval(payload):
    """Worker-side entry: evaluate one candidate map in a forked child."""
    oid, buf = payload
    o = _FORK_REGISTRY[oid]
    m = np.frombuffer(buf, dtype=np.int64)
    return o._engine_eval(m)


def placement_digest(m: np.ndarray) -> str:
    """SHA-256 digest (16 hex chars) of a virtual->global PE map."""
    a = np.ascontiguousarray(np.asarray(m, dtype=np.int64))
    return hashlib.sha256(a.tobytes()).hexdigest()[:16]


def geometry_key(geom: DeviceGeometry) -> str:
    """Stable cache-key component naming every geometry field."""
    return (f"{geom.devices}d{geom.channels}c{geom.bank_groups_per_channel}"
            f"g{geom.banks_per_channel}b{geom.pes_per_bank}p")


@dataclasses.dataclass
class OracleStats:
    """Counters over one oracle's lifetime (mirrors the profile hooks)."""

    engine_evals: int = 0
    memo_hits: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    surrogate_prunes: int = 0
    batches: int = 0
    n_workers: int = 1

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class PlacementOracle:
    """Layered makespan oracle over placements of one graph (module doc)."""

    def __init__(self, struct: TaskGraph, mode: Interconnect,
                 geom: DeviceGeometry, *,
                 cache: OracleCache | None = None,
                 model: DeviceModel | None = None,
                 n_workers: int = 1,
                 profile=None, engine_kind: str | None = None):
        self.mode, self.geom = mode, geom
        self.base = ir.materialize(struct, mode)
        if model is None:
            model = DeviceModel(mode, geom)
        elif model.mode is not mode or model.geom != geom:
            raise ValueError(
                f"model is for ({model.mode}, {model.geom.describe()}), "
                f"not ({mode}, {geom.describe()})")
        self.model = model
        self.engine_kind = engine_kind or (
            "scalar" if self.base.n <= SCALAR_ORACLE_CUTOVER else "vector")
        self.lb_model = LowerBoundModel(self.base, geom)
        self.cache = cache
        self.profile = profile
        self.n_workers = max(1, int(n_workers))
        self.stats = OracleStats(n_workers=self.n_workers)
        from repro.obs.trace import graph_fingerprint
        self.key_prefix = (f"{graph_fingerprint(self.base)}/"
                           f"{geometry_key(geom)}/{mode.value}")
        self._memo: dict[str, float] = {}
        self._lb_memo: dict[str, float] = {}
        self._pool = None
        _ORACLES.add(self)

    # --- keys -------------------------------------------------------------------

    def cache_key(self, digest: str) -> str:
        return f"{self.key_prefix}/{digest}"

    # --- the layers -------------------------------------------------------------

    def _engine_eval(self, m: np.ndarray) -> float:
        from repro.device import partition
        g = partition._remap_ir(self.base, np.asarray(m, dtype=np.int64))
        return engine.oracle_makespan(g, self.model,
                                      engine=self.engine_kind)

    def lower_bound(self, m: np.ndarray, digest: str | None = None) -> float:
        if digest is None:
            digest = placement_digest(m)
        lb = self._lb_memo.get(digest)
        if lb is None:
            lb = self._lb_memo[digest] = self.lb_model.lower_bound(
                np.asarray(m, dtype=np.int64))
        return lb

    def _pool_map(self, maps: list[np.ndarray]) -> list[float]:
        if self._pool is None:
            import multiprocessing as mp
            try:
                ctx = mp.get_context("fork")
            except ValueError:        # no fork on this platform: stay serial
                self.n_workers = self.stats.n_workers = 1
                return [self._engine_eval(m) for m in maps]
            _FORK_REGISTRY[id(self)] = self
            self._pool = ctx.Pool(self.n_workers)
        payloads = [(id(self), np.ascontiguousarray(
            np.asarray(m, dtype=np.int64)).tobytes()) for m in maps]
        return self._pool.map(_pool_eval, payloads)

    # --- public evaluation ------------------------------------------------------

    def evaluate(self, maps, *, prune_at: float | None = None
                 ) -> list[float | None]:
        """Makespans aligned with ``maps``; ``None`` marks a pruned entry.

        Candidates whose memo/cache layer already holds a verdict return it
        (no pruning — known values are free).  Remaining candidates with
        ``lower_bound >= prune_at`` are discarded: they provably cannot
        *improve* on an engine-verified ``prune_at``, so the search never
        needs their exact cost.  Everything else is engine-evaluated (in
        the worker pool when configured), merged back in input order by
        digest, and written through to the memo and the persistent cache.
        """
        digests = [placement_digest(m) for m in maps]
        out: list[float | None] = [None] * len(maps)
        todo: dict[str, np.ndarray] = {}
        memo_hits = cache_hits = prunes = 0
        for i, (d, m) in enumerate(zip(digests, maps)):
            v = self._memo.get(d)
            if v is not None:
                out[i] = v
                memo_hits += 1
                continue
            if self.cache is not None:
                v = self.cache.get(self.cache_key(d))
                if isinstance(v, (int, float)):
                    out[i] = self._memo[d] = float(v)
                    cache_hits += 1
                    continue
            if prune_at is not None and d not in todo \
                    and self.lower_bound(m, d) >= prune_at:
                prunes += 1
                continue
            todo.setdefault(d, np.asarray(m, dtype=np.int64))
        fresh = list(todo.items())
        if fresh:
            if self.n_workers > 1 and len(fresh) > 1:
                values = self._pool_map([m for _, m in fresh])
            else:
                values = [self._engine_eval(m) for _, m in fresh]
            for (d, _), v in zip(fresh, values):
                self._memo[d] = v
                if self.cache is not None:
                    self.cache.put(self.cache_key(d), v)
            for i, d in enumerate(digests):
                if out[i] is None and d in self._memo:
                    out[i] = self._memo[d]
        self.stats.engine_evals += len(fresh)
        self.stats.memo_hits += memo_hits
        self.stats.cache_hits += cache_hits
        self.stats.cache_misses += len(fresh)
        self.stats.surrogate_prunes += prunes
        self.stats.batches += 1
        if self.profile is not None:
            self.profile.record_oracle(
                evals=len(fresh), memo_hits=memo_hits,
                cache_hits=cache_hits, cache_misses=len(fresh),
                prunes=prunes, workers=self.n_workers)
        return out

    def evaluate_one(self, m) -> float:
        """Unpruned single-candidate evaluation (always returns a float)."""
        return self.evaluate([m])[0]

    # --- teardown ---------------------------------------------------------------

    def forget(self) -> None:
        """Drop the in-memory memo layers (persistent cache untouched)."""
        self._memo.clear()
        self._lb_memo.clear()

    def close(self) -> None:
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None
        _FORK_REGISTRY.pop(id(self), None)

    def __del__(self):  # pragma: no cover - interpreter-shutdown best effort
        try:
            self.close()
        except Exception:
            pass


def clear_caches() -> None:
    """Teardown hook for :func:`repro.device.batch.clear_caches`.

    Forgets every live oracle's memo and surrogate layers and every
    :class:`OracleCache`'s in-memory state.  On-disk cache *files* are kept
    — they are the persistent layer; deleting them is the owner's call
    (:meth:`OracleCache.clear`).
    """
    from repro.search import cache as _cache
    for o in list(_ORACLES):
        o.forget()
    _cache.clear_loaded()
