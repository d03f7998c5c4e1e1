"""The single product of the host planning pipeline (DESIGN.md §3).

A :class:`PlanArtifact` bundles everything one planned graph needs to be
counted repeatedly: the relabeled host graph, the composed relabeling
permutation, the device-ready plan (``TCPlan`` / ``SummaPlan`` /
``OneDPlan``), per-stage wall times, and a memo space where the runners
park derived state (staged ``jnp`` arrays, compiled engine fns, tile
plans) so a cache hit skips *all* per-call host work — planning, host→
device staging, and retracing.

Artifacts are what the schedule runners and engine builders consume;
``repro.core.plan.as_plan`` coerces an artifact (or a raw plan) to its
plan object, so every ``build_*_fn`` accepts either.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from ..core.graph import Graph

__all__ = ["PlanArtifact"]


@dataclasses.dataclass
class PlanArtifact:
    """One planned graph, ready for repeated counting.

    ``kind`` names the plan family ("cannon" | "summa" | "oned");
    ``digest`` is the content digest of the *input* graph (pre-relabel),
    ``key`` the full cache key this artifact is stored under.
    """

    kind: str
    digest: str
    key: Tuple
    graph: Graph  # relabeled graph actually planned
    perm: Optional[np.ndarray]  # composed relabeling, old id -> new id
    plan: Any  # TCPlan | SummaPlan | OneDPlan
    stage_seconds: Dict[str, float] = dataclasses.field(default_factory=dict)
    cache_hit: bool = False
    # skip-aware rebalance search report (DESIGN.md §4.3): trial history,
    # winning seed, baseline/best masked critical path, skipped steps;
    # None when the plan was not rebalanced.  The trials knob is part of
    # ``key``, so rebalanced and plain artifacts never collide.
    rebalance: Optional[dict] = None
    # planner knobs this artifact was built with, recorded so the delta
    # path (DESIGN.md §4.7) can re-pack stages or rebase with identical
    # flags; None on artifacts from pre-delta code paths.
    config: Optional[dict] = None
    # delta lineage: dict(root_digest, chain, depth) joining the cache
    # key for incrementally-derived artifacts; None for cold plans.
    lineage: Optional[dict] = None
    # per-delta report (dirty blocks/cells, replanned stages, rebased,
    # level) attached by ``apply_delta``; None for cold plans.
    delta_report: Optional[dict] = None
    # re-stage handoff: (prev host arrays, prev staged jnp arrays) from
    # the parent artifact, consumed lazily by ``staged()`` so clean
    # device buffers are reused instead of re-uploaded.
    restage_from: Optional[Tuple[Dict, Dict]] = dataclasses.field(
        default=None, repr=False
    )
    # device buffers the re-stage kept from the parent artifact (0 on a
    # fresh upload)
    reused_buffers: int = 0
    _memo: Dict = dataclasses.field(default_factory=dict, repr=False)
    _memo_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False
    )

    # ------------------------------------------------------------------
    def device_arrays(self) -> Dict[str, np.ndarray]:
        return self.plan.device_arrays()

    @property
    def compact(self):
        """The plan's staged :class:`~repro.core.plan.CompactSchedule`
        (globally-live steps + fused hop vector), or ``None`` when the
        compaction stage was off or had no mask to work from."""
        return getattr(self.plan, "compact", None)

    @property
    def autotune(self) -> Optional[dict]:
        """The deterministic kernel-shape autotune report (chunk,
        ``d_small``/``n_long`` split, ``tail_heavy``), or ``None``."""
        return getattr(self.plan, "autotune", None)

    @property
    def hubsplit(self) -> Optional[dict]:
        """The hub-split stage report (``h0``, ``hub_rows``,
        ``hub_nnz_frac``, … — DESIGN.md §4.8), or ``None`` when the
        stage was off or no row crossed the threshold."""
        hub = getattr(self.plan, "hub", None)
        return None if hub is None else hub.report()

    def memo(self, key, build: Callable):
        """Build-once storage for derived per-artifact state.

        Used by the runners for staged arrays, compiled engine fns (keyed
        by mesh/method/dtype), tile plans, and dense blocks — everything
        that would otherwise be recomputed or retraced on every count of
        an already-planned graph.  Locked, so serving threads sharing a
        cached artifact build (and trace/compile) each entry once.
        """
        with self._memo_lock:
            if key not in self._memo:
                self._memo[key] = build()
            return self._memo[key]

    def staged(self, shardings: Optional[Dict] = None) -> Dict:
        """Device-staged (``jnp``) plan arrays, memoized (the pipeline's
        ``stage`` step); its first call is the ``tc.stage`` span, whose
        wall time lands in ``stage_seconds["stage"]``.

        ``shardings`` (input name -> ``jax.sharding.Sharding``, e.g. an
        engine fn's ``.shardings``) stages exactly those arrays, each
        straight onto its shards of a multi-device mesh; memoized per
        placement.  Without it every array goes to the default device.

        Delta-derived artifacts carry ``restage_from`` — the parent's
        host/staged array pairs — and go through the engine re-stage
        path, which keeps the parent's device buffer for every array the
        splice left unchanged (DESIGN.md §4.7).  Placed stagings upload
        afresh."""
        import jax
        import jax.numpy as jnp

        from ..core.spans import span

        def build():
            with span("tc.stage", self.stage_seconds, "stage"):
                handoff = self.restage_from
                host = self.device_arrays()
                if shardings is not None:
                    return {
                        k: jax.device_put(host[k], s)
                        for k, s in shardings.items()
                    }
                if handoff is not None:
                    from ..core.engine import restage_device_arrays

                    out, self.reused_buffers = restage_device_arrays(
                        handoff[0], handoff[1], host
                    )
                    return out
                return {k: jnp.asarray(v) for k, v in host.items()}

        if shardings is None:
            return self.memo("staged_arrays", build)
        key = ("staged_arrays", tuple(sorted(shardings.items())))
        return self.memo(key, build)

    def release(self) -> None:
        """Drop memoized device state (staged buffers, compiled fns, tile
        plans) and the re-stage handoff.  Called by ``PlanCache`` on LRU
        eviction so pinned device memory does not outlive the cache entry
        while serving threads still hold the artifact; the next use
        simply rebuilds the memo entries."""
        with self._memo_lock:
            self._memo.clear()
            self.restage_from = None
