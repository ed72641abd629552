"""Cost-driven placement search on the engine oracle.

This package closes the ROADMAP's search-based-placement loop: instead of
trusting one greedy heuristic, the place stage can *search* the space of
virtual->global PE maps with the discrete-event engine as its makespan
oracle — the compiler-directed data placement the PIM-adoption literature
names as the adoption gap.  The one invariant everything here preserves:

    **the oracle is the engine; the surrogate only prunes, never decides.**

Layout:

* :mod:`repro.search.oracle`    — :class:`PlacementOracle`: memoized,
  persistently cached, optionally process-pool-parallel engine evals;
* :mod:`repro.search.surrogate` — :class:`LowerBoundModel`: the admissible
  makespan lower bound used only to discard can't-win candidates;
* :mod:`repro.search.cache`     — :class:`OracleCache`: append-only JSONL
  store keyed (fingerprint, geometry, interconnect, placement digest),
  tolerant of corrupt/truncated entries;
* :mod:`repro.search.place`     — :func:`search_pe_map`: seeded beam
  search + simulated-annealing refinement, deterministic by seed at any
  worker count;
* :mod:`repro.search.autotune`  — :class:`Autotuner`: per-graph-family
  pipeline choice (search vs winning greedy policy), cached by
  fingerprint.

Pipeline integration lives in :class:`repro.passes.SearchPlacePass`
(``validate -> search-place -> optimize -> legalize``); the serving
runtime opts in with ``ServingRuntime(..., placement="search")``.
"""

from __future__ import annotations

from repro.search.autotune import Autotuner, TunedChoice  # noqa: F401
from repro.search.cache import OracleCache  # noqa: F401
from repro.search.oracle import (SCALAR_ORACLE_CUTOVER,  # noqa: F401
                                 OracleStats, PlacementOracle,
                                 geometry_key, placement_digest)
from repro.search.oracle import clear_caches  # noqa: F401
from repro.search.place import (SearchConfig, SearchResult,  # noqa: F401
                                search_pe_map)
from repro.search.surrogate import LowerBoundModel  # noqa: F401
