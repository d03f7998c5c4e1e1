"""Staged host-planning pipeline (DESIGN.md §3).

Public surface:

* :func:`plan_cannon` / :func:`plan_summa` / :func:`plan_oned` — cached
  pipeline drivers (ingest → relabel → decompose → pack → stage)
  returning a :class:`PlanArtifact`.
* :class:`PlanCache` / :func:`graph_digest` / :func:`default_cache` —
  the content-addressed plan cache (§10.5); :func:`digest_stats` counts
  digests computed and kept digests returned.
* :func:`count_triangles_many` — batched front-end: many graphs, one
  compiled engine call.
* :mod:`.stages` — the individual stage functions (vectorized packers,
  relabel composition) for callers assembling their own pipelines.
"""
from .artifact import PlanArtifact  # noqa: F401
from .batch import ManyResult, count_triangles_many  # noqa: F401
from .cache import (  # noqa: F401
    PlanCache,
    default_cache,
    digest_stats,
    graph_digest,
    set_default_cache,
)
from .delta import EdgeDelta, apply_delta  # noqa: F401
from .planner import plan_cannon, plan_oned, plan_summa  # noqa: F401
from .rebalance import (  # noqa: F401
    masked_critical_path,
    rebalance_stage,
    rebalance_trial_perm,
)
from .stages import relabel_stage  # noqa: F401

__all__ = [
    "EdgeDelta",
    "apply_delta",
    "relabel_stage",
    "rebalance_stage",
    "rebalance_trial_perm",
    "masked_critical_path",
    "PlanArtifact",
    "PlanCache",
    "ManyResult",
    "count_triangles_many",
    "default_cache",
    "set_default_cache",
    "graph_digest",
    "digest_stats",
    "plan_cannon",
    "plan_summa",
    "plan_oned",
]
