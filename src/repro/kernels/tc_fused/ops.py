"""Fused device-step dispatcher: short panel + long-row fallback.

``count_pair_fused`` implements the :mod:`repro.core.engine` CSR-kernel
contract on top of the planner's two-sided maxfrag split: the first
``n_long`` tasks (either fragment > ``d_small``) run the chunked
two-level global-search path at ``dpad_long``; everything after runs
the fused equality panel at ``d_small``.  Unlike ``search2`` the long
bucket is *skipped entirely* when ``n_long == 0`` — no always-on long
chunk, no aug-key traffic on panel-only steps.

VMEM budget (DESIGN.md §5.1): the CSR index arrays stay in HBM; the
Pallas kernel holds two per-task DMA windows, two ``(tile, dp)`` panels
and the equality accumulator in VMEM.  ``fused_vmem_bytes`` accounts
for all of it; ``fused_gate`` is the one decision point: when the total
exceeds
``VMEM_BUDGET_BYTES`` an ``impl="auto"`` call falls back to the lax
reference **with a warning** while an explicit ``impl="pallas"`` fails
loudly — and both diagnose a *hub-driven* overflow (``dmax`` dwarfing
``d_small``, the heavy-tail signature that ``hub_split=True`` planning
removes) so the report no longer blames the panel for a handful of hub
rows.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.count import (
    build_aug_keys,
    count_pair_search,
    count_pair_search_global,
)
from .ref import fused_short_ref
from .tc_fused import LANES, SUBLANES, fused_short_counts, fused_window_rows

__all__ = [
    "VMEM_BUDGET_BYTES",
    "count_pair_fused",
    "fused_gate",
    "fused_tile_for",
    "fused_vmem_bytes",
    "resolve_fused_impl",
]

# leave ~4 MiB of a v5e core's ~16 MiB VMEM for double-buffering slack
VMEM_BUDGET_BYTES = 12 * (1 << 20)
# per-tile compare work cap: tile * d * d int32 compares
_PANEL_BUDGET_ELEMS = 1 << 20
_TILE_MIN, _TILE_MAX = 8, 256


def fused_tile_for(d: int, budget_elems: int = _PANEL_BUDGET_ELEMS) -> int:
    """Largest power-of-two tile keeping the tile's ``tile * d * d``
    compares in budget, clamped to [8, 256]."""
    cap = budget_elems // max(1, d * d)
    t = _TILE_MIN
    while t * 2 <= min(cap, _TILE_MAX):
        t <<= 1
    return t


def fused_vmem_bytes(tile: int, d: int) -> int:
    """Whole-kernel VMEM estimate in bytes: two ``(tile, nr, 128)`` DMA
    windows (``nr`` padded to a sublane group), two ``(tile, dp)``
    panels and the ``(tile, dp)`` accumulator, all int32."""
    nr = fused_window_rows(d)
    nr_pad = -(-nr // SUBLANES) * SUBLANES
    dp = (nr - 1) * LANES
    return 4 * tile * (2 * nr_pad * LANES + 3 * dp)


# a long-bucket dmax this far past the panel depth is the heavy-tail
# signature: a handful of hub rows, not a uniformly deep plan
_HUB_DMAX_RATIO = 4


def fused_gate(
    tile: int,
    d: int,
    *,
    dmax: Optional[int] = None,
    d_small: Optional[int] = None,
) -> dict:
    """The fused kernel's VMEM admission decision, as data.

    Returns ``need_bytes`` / ``budget_bytes`` / ``fits`` plus
    ``hub_driven``: True when the plan's long-bucket ``dmax`` exceeds
    ``d_small`` by the heavy-tail ratio, i.e. the padded shapes (and any
    overflow) are driven by a few hub rows that hub-split planning
    (``hub_split=True``, DESIGN.md §4.8) would take off the panel's
    plate — rather than by a uniformly deep graph where only a smaller
    ``d_small``/``tile`` helps.
    """
    need = fused_vmem_bytes(tile, d)
    hub_driven = (
        dmax is not None
        and d_small is not None
        and int(dmax) > _HUB_DMAX_RATIO * max(1, int(d_small))
    )
    return dict(
        need_bytes=int(need),
        budget_bytes=int(VMEM_BUDGET_BYTES),
        fits=bool(need <= VMEM_BUDGET_BYTES),
        hub_driven=bool(hub_driven),
    )


def resolve_fused_impl(impl: str) -> str:
    """``auto`` → Pallas on TPU, the lax reference elsewhere (the panel
    math is identical; on CPU the reference IS the fast path)."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "lax"
    if impl not in ("pallas", "pallas-interpret", "lax"):
        raise ValueError(
            f"unknown fused impl {impl!r}: expected auto | pallas | "
            "pallas-interpret | lax"
        )
    return impl


def count_pair_fused(
    a_indptr,
    a_indices,
    b_indptr,
    b_indices,
    ti,
    tj,
    tcount,
    *,
    n_long: int,
    d_small: int,
    dpad_long: int,
    chunk: int,
    tile: Optional[int] = None,
    count_dtype=jnp.int32,
    impl: str = "auto",
    long_fallback: str = "global",
    probe_shorter: bool = True,
    sentinel: Optional[int] = None,
    aug_b=None,
):
    """Device-step count under the maxfrag split (DESIGN.md §5.1).

    ``long_fallback`` picks the long-bucket path: ``"global"`` (the
    two-level row-encoded key search; Cannon/SUMMA block-local ids) or
    ``"search"`` (padded binary search; the 1D ring's global ids, where
    row-encoded keys don't apply).  The short bucket always runs the
    equality panel — raw column ids, valid on every schedule.
    """
    tmax = ti.shape[0]
    n_long = int(n_long)
    n_long_c = 0
    chunk_l = int(chunk)
    if n_long > 0:
        # round the long bucket at fine granularity, NOT at the search
        # path's autotuned chunk: with e.g. chunk=4096 and n_long=522,
        # chunk-rounding would shove 4096 tasks through the fallback and
        # starve the panel of the very tasks it exists for.  The
        # fallback's internal chunk shrinks to match so its padding
        # stays aligned.
        chunk_l = min(chunk_l, max(64, -(-n_long // 64) * 64))
        n_long_c = min(-(-n_long // chunk_l) * chunk_l, tmax)

    d = int(max(1, min(d_small, a_indices.shape[0], b_indices.shape[0])))
    tile = int(tile) if tile else fused_tile_for(d)

    resolved = resolve_fused_impl(impl)
    if resolved == "pallas":
        gate = fused_gate(tile, d, dmax=dpad_long, d_small=d_small)
        if not gate["fits"]:
            hint = (
                "the overflow is hub-driven (dmax "
                f"{dpad_long} >> d_small {d_small}): plan with "
                "hub_split=True to count the hub rows off-panel"
                if gate["hub_driven"]
                else "shrink the plan's d_small/tile"
            )
            if impl == "auto":
                # the old gate demoted silently and the report then
                # blamed the panel for a handful of hub rows — say what
                # happened and why; supervised runs additionally audit
                # the demotion on TCResult.supervision (DESIGN.md §8)
                reason = (
                    "fused panel kernel demoted to the lax reference: "
                    f"needs ~{gate['need_bytes'] / 2**20:.1f} MiB VMEM > "
                    f"budget {gate['budget_bytes'] / 2**20:.0f} MiB; "
                    + hint
                )
                warnings.warn(reason, RuntimeWarning, stacklevel=2)
                from ...runtime.supervisor import note_demotion

                note_demotion("fused_impl", "pallas", "lax", reason=reason)
                resolved = "lax"
            else:
                raise ValueError(
                    "fused panel kernel needs "
                    f"~{gate['need_bytes'] / 2**20:.1f} MiB VMEM "
                    f"(tile={tile}, d={d}) "
                    f"> budget {gate['budget_bytes'] / 2**20:.0f} MiB; "
                    "use impl='lax' or " + hint
                )

    acc = jnp.zeros((), dtype=count_dtype)
    if n_long_c:
        long_count = jnp.minimum(tcount, n_long_c)
        if long_fallback == "global":
            if aug_b is None:
                aug_b = build_aug_keys(b_indptr, b_indices)
            acc = acc + count_pair_search_global(
                a_indptr, a_indices, b_indptr, b_indices,
                ti[:n_long_c], tj[:n_long_c], long_count,
                dpad=dpad_long, chunk=chunk_l, count_dtype=count_dtype,
                aug_b=aug_b,
            )
        elif long_fallback == "search":
            acc = acc + count_pair_search(
                a_indptr, a_indices, b_indptr, b_indices,
                ti[:n_long_c], tj[:n_long_c], long_count,
                dpad=dpad_long, chunk=chunk_l, probe_shorter=probe_shorter,
                count_dtype=count_dtype, sentinel=sentinel,
            )
        else:
            raise ValueError(
                f"unknown long_fallback {long_fallback!r}: "
                "expected global | search"
            )

    if n_long_c >= tmax:
        return acc

    short_count = jnp.maximum(tcount - n_long_c, 0)
    ti_s = ti[n_long_c:]
    tj_s = tj[n_long_c:]
    if resolved == "lax":
        acc_short = fused_short_ref(
            a_indptr, a_indices, b_indptr, b_indices,
            ti_s, tj_s, short_count,
            d=d, tile=tile, count_dtype=count_dtype,
        )
    else:
        per_tile = fused_short_counts(
            a_indptr, a_indices, b_indptr, b_indices,
            ti_s, tj_s, short_count,
            tile=tile, d=d, interpret=(resolved == "pallas-interpret"),
        )
        acc_short = jnp.sum(per_tile, dtype=count_dtype)
    return acc + acc_short
