"""Fused device-step intersection kernel (DESIGN.md §5.1).

``tc_fused`` — probe-gather + sorted-intersection + count-accumulate for
an entire device-step in one Pallas kernel, tiled over the autotuner's
``d_small``/``n_long`` maxfrag split: short tasks run through a dense
equality panel held in VMEM (fragments DMA'd from HBM-resident
indices), long rows fall back to the chunked
two-level global-search path.  A pure-lax reference with identical
masking semantics backs CPU CI (and is the fast path on CPU backends).

``autotune`` — the measured-roofline table (DESIGN.md §4.6): time
candidate (tile, chunk, d_small) shapes once per (backend, dtype,
shape-bucket), check them against ``launch/roofline.py`` bandwidth
ceilings, and persist the verdict so ``method="auto"`` can resolve to
the fused kernel only where measurement says it wins.
"""
from .ops import (  # noqa: F401
    VMEM_BUDGET_BYTES,
    count_pair_fused,
    fused_gate,
    fused_tile_for,
    fused_vmem_bytes,
    resolve_fused_impl,
)
from .ref import fused_short_ref  # noqa: F401
from .tc_fused import fused_short_counts  # noqa: F401
from .autotune import (  # noqa: F401
    default_table_dir,
    measured_entry,
    measured_table_key,
    predict_fused_wins,
)
