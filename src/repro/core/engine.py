"""Unified schedule engine: one pluggable runtime for every distributed
count (DESIGN.md §4-§6).

A distributed triangle count is expressed as the composition

    (OperandStore, ShiftSchedule, CountKernel, Reduction)

and this module generates the jitted ``shard_map`` SPMD function from the
parts — the scan/ppermute schedule bodies that used to be quadruplicated
across ``cannon.py`` / ``summa.py`` / ``onedim.py`` live here exactly once.

* :class:`OperandStore` subclasses encapsulate *payload representation*:
  how per-device blocks are packed for shifting (single-blob CSR with
  optional uint16 length compression, dense 0/1 blocks, bit-packed
  128x128 tiles) and how a payload is unpacked back into count-kernel
  arguments.
* :class:`ShiftSchedule` subclasses encapsulate *permutation structure*:
  Cannon's q-step left/up rotation with 2.5D pod striding, SUMMA's
  one-hot-psum broadcast rounds, and the 1D ring rotation.  Each yields a
  ``(carry0, body, nsteps)`` triple for one shared ``lax.scan`` driver;
  the same body also powers the host-driven stepper used for fault
  tolerance (:func:`build_engine_stepper`).
* CountKernels are the existing :mod:`repro.core.count` paths behind one
  signature ``kernel(a_ptr, a_idx, b_ptr, b_idx, ti, tj, cnt) -> scalar``
  (see :func:`make_csr_kernel` / :data:`CSR_KERNELS`); dense and tile
  stores carry their own kernels behind the store-level ``count`` hook.
* :class:`Reduction` turns per-device per-step partials into the global
  scalar (psum over every mesh axis) or per-device outputs.

Mesh and SPMD calls go through the thin helpers of :mod:`repro.compat`,
which hold the repo's conventions (all-Auto axes, ``check_vma`` off) for
the one supported jax (0.9.0).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import compat
from . import count as count_mod
from .blob import blob_layout, pack_blob, unpack_blob

__all__ = [
    "GridAxes",
    "RingAxes",
    "OperandStore",
    "CSRStore",
    "DenseStore",
    "TileStore",
    "SummaCSRStore",
    "OneDCSRStore",
    "ShiftSchedule",
    "CannonSchedule",
    "SummaSchedule",
    "RingSchedule",
    "Reduction",
    "HubCount",
    "CSR_KERNELS",
    "MASK_NAME",
    "register_csr_kernel",
    "make_csr_kernel",
    "masked_count",
    "build_engine_fn",
    "build_engine_stepper",
    "restage_device_arrays",
    "shift_perm",
    "tree_ppermute",
    "pod_tree_allreduce",
    "chain_broadcast",
]


# ======================================================================
# mesh axes
# ======================================================================
@dataclasses.dataclass(frozen=True)
class GridAxes:
    """Named mesh axes of a 2D (optionally 2.5D) grid."""

    row: str = "data"
    col: str = "model"
    pod: Optional[str] = None

    @property
    def all(self) -> Tuple[str, ...]:
        return (self.pod, self.row, self.col) if self.pod else (self.row, self.col)


@dataclasses.dataclass(frozen=True)
class RingAxes:
    """A single mesh axis forming the 1D ring."""

    axis: str = "flat"

    @property
    def all(self) -> Tuple[str, ...]:
        return (self.axis,)


# ======================================================================
# shared shift helpers
# ======================================================================
def shift_perm(size: int, k: int):
    """ppermute pairs shifting *towards lower index* by ``k`` (left/up)."""
    return [(s, (s - k) % size) for s in range(size)]


def tree_ppermute(tree, axis: str, perm):
    """Shift every leaf of a payload pytree along one mesh axis."""
    return jax.tree.map(lambda a: compat.ppermute(a, axis, perm), tree)


def _squeeze(a, lead: int):
    return a.reshape(a.shape[lead:])


# ======================================================================
# delta re-stage path (DESIGN.md §4.7)
# ======================================================================
def restage_device_arrays(
    prev_host: Dict[str, "jnp.ndarray"],
    prev_staged: Dict[str, "jnp.ndarray"],
    new_host: Dict[str, "jnp.ndarray"],
) -> Tuple[Dict[str, "jnp.ndarray"], int]:
    """Stage ``new_host`` arrays, reusing the parent's device buffers for
    every array an edge delta left unchanged.

    The splice in ``apply_delta`` copies only arrays it touches, so a
    clean array is often the *same object* as the parent's (identity
    fast path); otherwise a value comparison against the parent's host
    array decides — e.g. ``step_keep`` frequently survives a delta
    byte-identical even though it was recomputed.  Returns the staged
    dict and how many device buffers were reused (skipped uploads).
    """
    import numpy as np

    out: Dict[str, jnp.ndarray] = {}
    reused = 0
    for name, host in new_host.items():
        prev = prev_host.get(name)
        staged = prev_staged.get(name)
        same = (
            staged is not None
            and prev is not None
            and prev.shape == host.shape
            and prev.dtype == host.dtype
            and (prev is host or np.array_equal(prev, host))
        )
        if same:
            out[name] = staged
            reused += 1
        else:
            out[name] = jnp.asarray(host)
    return out, reused


# ======================================================================
# CSR count-kernel registry — "behind one signature"
# ======================================================================
# Every CSR kernel factory returns
#   kernel(a_ptr, a_idx, b_ptr, b_idx, ti, tj, cnt) -> scalar count
# with all plan-derived padding/chunk parameters bound at build time.
CSR_KERNELS: Dict[str, Callable] = {}


def register_csr_kernel(name: str, factory: Callable) -> None:
    """Register a CSR count-kernel factory under ``name``.

    ``factory(dpad=..., chunk=..., probe_shorter=..., count_dtype=...,
    sentinel=..., n_long=..., d_small=..., **extra) -> kernel``.
    ``extra`` carries method-specific knobs (the fused kernel's
    ``fused_tile``/``fused_impl``/``fused_long_fallback``); factories
    must tolerate and ignore keys they don't own.
    """
    CSR_KERNELS[name] = factory


def _search_factory(*, dpad, chunk, probe_shorter, count_dtype, sentinel,
                    n_long, d_small, **extra):
    del n_long, d_small, extra
    return functools.partial(
        count_mod.count_pair_search,
        dpad=dpad,
        chunk=chunk,
        probe_shorter=probe_shorter,
        count_dtype=count_dtype,
        sentinel=sentinel,
    )


def _search2_factory(*, dpad, chunk, probe_shorter, count_dtype, sentinel,
                     n_long, d_small, **extra):
    # sentinel is plan-derived: builders pass it unconditionally with no
    # user intent behind it, so drop it here and spare engine users the
    # one-time ignored-kwarg warning inside count_pair_search_two_level.
    # probe_shorter is deliberately forwarded: a non-default value only
    # ever comes from an explicit user request (count_triangles(
    # probe_shorter=False)) — exactly the search-to-search2 porting
    # mistake the warning exists to surface.
    del sentinel, extra
    if n_long is None or d_small is None:
        raise ValueError(
            "method 'search2' needs a bucketized plan (bucketize_plan) "
            "providing n_long/d_small"
        )

    def kernel(a_ptr, a_idx, b_ptr, b_idx, ti, tj, cnt, aug_b=None):
        return count_mod.count_pair_search_two_level(
            a_ptr, a_idx, b_ptr, b_idx, ti, tj, cnt, n_long,
            dpad_long=dpad,
            dpad_short=d_small,
            chunk=chunk,
            probe_shorter=probe_shorter,
            count_dtype=count_dtype,
            aug_b=aug_b,
        )

    return kernel


def _global_factory(*, dpad, chunk, probe_shorter, count_dtype, sentinel,
                    n_long, d_small, **extra):
    del probe_shorter, sentinel, n_long, d_small, extra
    return functools.partial(
        count_mod.count_pair_search_global,
        dpad=dpad,
        chunk=chunk,
        count_dtype=count_dtype,
    )


def _fused_factory(*, dpad, chunk, probe_shorter, count_dtype, sentinel,
                   n_long, d_small, **extra):
    """Fused panel kernel + long-row fallback (DESIGN.md §5.1).

    Needs the *two-sided* (maxfrag) split: under the probe-only split a
    B fragment longer than ``d_small`` would be silently truncated by
    the equality panel — builders enforce the split provenance, this
    factory only enforces that a split exists at all.
    """
    if n_long is None or d_small is None:
        raise ValueError(
            "method 'fused' needs a maxfrag-split plan: re-plan with "
            "autotune='fused' providing n_long/d_small"
        )
    from ..kernels.tc_fused import count_pair_fused
    from ..runtime import faultinject

    faultinject.fire("fused")

    tile = extra.get("fused_tile")
    impl = extra.get("fused_impl", "auto")
    long_fallback = extra.get("fused_long_fallback", "global")

    def kernel(a_ptr, a_idx, b_ptr, b_idx, ti, tj, cnt, aug_b=None):
        return count_pair_fused(
            a_ptr, a_idx, b_ptr, b_idx, ti, tj, cnt,
            n_long=n_long,
            d_small=d_small,
            dpad_long=dpad,
            chunk=chunk,
            tile=tile,
            count_dtype=count_dtype,
            impl=impl,
            long_fallback=long_fallback,
            probe_shorter=probe_shorter,
            sentinel=sentinel,
            aug_b=aug_b,
        )

    return kernel


register_csr_kernel("search", _search_factory)
register_csr_kernel("search2", _search2_factory)
register_csr_kernel("global", _global_factory)
register_csr_kernel("fused", _fused_factory)


def check_fused_split(plan) -> None:
    """Refuse ``method='fused'`` on plans without the two-sided split.

    ``bucketize_plan`` and the default autotune stage classify tasks by
    the PROBE fragment only — sound for the global-search paths (keys
    are searched unpadded) but NOT for the fused panel, which gathers
    both fragments at ``d_small`` and would silently truncate a long B
    row into a wrong count.  Only plans whose autotune report carries
    ``split='maxfrag'`` (planner ``autotune='fused'``) are accepted.
    """
    report = getattr(plan, "autotune", None) or {}
    if report.get("split") != "maxfrag":
        raise ValueError(
            "method 'fused' requires a plan with the two-sided maxfrag "
            "split (plan with autotune='fused'); got "
            f"split={report.get('split')!r} — a probe-only split would "
            "truncate long B fragments and miscount"
        )


def make_csr_kernel(
    method: str,
    *,
    dpad: int,
    chunk: int,
    probe_shorter: bool = True,
    count_dtype=jnp.int32,
    sentinel: Optional[int] = None,
    n_long: Optional[int] = None,
    d_small: Optional[int] = None,
    **extra,
) -> Callable:
    """Build a registered CSR kernel with plan parameters bound."""
    try:
        factory = CSR_KERNELS[method]
    except KeyError:
        raise ValueError(
            f"unknown CSR count method {method!r}; "
            f"registered: {sorted(CSR_KERNELS)}"
        ) from None
    return factory(
        dpad=dpad,
        chunk=chunk,
        probe_shorter=probe_shorter,
        count_dtype=count_dtype,
        sentinel=sentinel,
        n_long=n_long,
        d_small=d_small,
        **extra,
    )


# ======================================================================
# operand stores
# ======================================================================
class OperandStore:
    """Payload representation: pack/unpack + kernel-argument extraction.

    Contract (all methods trace inside ``shard_map``):

    * ``operand_names`` / ``static_names`` — plan device-array names, in
      call order (operands travel; statics stay put).
    * ``in_specs(axes)``  — PartitionSpec per array name.
    * ``lead(name, axes)`` — number of leading mesh block-dims shard_map
      prefixes onto that array (stripped by ``localize``).
    * ``payload(local)``  — packed shiftable state (a pytree; schedules
      treat it opaquely and shift it with :func:`tree_ppermute`).
    * ``count(state, local, step, ctx)`` — unpack ``state`` and run the
      bound count kernel for one schedule step.
    """

    operand_names: Sequence[str] = ()
    static_names: Sequence[str] = ()

    @property
    def names(self):
        return tuple(self.operand_names) + tuple(self.static_names)

    def in_specs(self, axes) -> Dict[str, P]:
        raise NotImplementedError

    def lead(self, name: str, axes) -> int:
        raise NotImplementedError

    def localize(self, named: Dict, axes) -> Dict:
        return {k: _squeeze(v, self.lead(k, axes)) for k, v in named.items()}

    def payload(self, local: Dict):
        raise NotImplementedError

    def count(self, state, local: Dict, step, ctx):
        raise NotImplementedError


class CSRStore(OperandStore):
    """CSR-block operands shifted as single int32 blobs (paper's
    serialization optimization), with optional uint16 length compression
    (§Perf H1b: ship row-length *pairs* instead of the int32 indptr and
    rebuild the indptr with one cumsum after each receive).

    ``with_aug=True`` adds the planner-staged row-encoded intersection
    keys (``b_aug``, DESIGN.md §5) as an extra payload leaf travelling
    with the B operand: the keys shift with the blocks, so the
    ``global``/``search2`` kernels never rebuild them on device.  The
    aug leaf stays outside the int32 blob — its dtype is plan-chosen
    (``aug_key_dtype``) and may be int64.
    """

    operand_names = ("a_indptr", "a_indices", "b_indptr", "b_indices")
    static_names = ("m_ti", "m_tj", "m_cnt")

    def __init__(self, kernel, *, use_blob: bool = True,
                 compress_lengths: bool = False, dmax: Optional[int] = None,
                 with_aug: bool = False):
        if compress_lengths:
            assert use_blob, "length compression only applies to blob shifts"
            assert dmax is not None and dmax < 65536, (
                "uint16 length compression needs d < 2^16"
            )
        self.kernel = kernel
        self.use_blob = use_blob
        self.compress_lengths = compress_lengths
        self.with_aug = with_aug
        if with_aug:
            self.operand_names = self.operand_names + ("b_aug",)
        self._layouts = {}

    def in_specs(self, axes):
        ab = P(*axes.all)
        m = P(axes.row, axes.col)
        specs = dict(
            a_indptr=ab, a_indices=ab, b_indptr=ab, b_indices=ab,
            m_ti=m, m_tj=m, m_cnt=m,
        )
        if self.with_aug:
            specs["b_aug"] = ab
        return specs

    def lead(self, name, axes):
        return len(axes.all) if name in self.operand_names else 2

    # -- uint16 length compression ------------------------------------
    @staticmethod
    def _pack_lengths(ptr):
        """(nb+1,) indptr -> (ceil(nb/2),) int32 of uint16 length pairs."""
        lens = jnp.diff(ptr).astype(jnp.int32)
        if lens.shape[0] % 2:
            lens = jnp.concatenate([lens, jnp.zeros((1,), jnp.int32)])
        return lens[0::2] | (lens[1::2] << 16)

    @staticmethod
    def _unpack_lengths(packed, nb):
        lo = packed & 0xFFFF
        hi = (packed >> 16) & 0xFFFF
        lens = jnp.stack([lo, hi], axis=1).reshape(-1)[:nb]
        return jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(lens, dtype=jnp.int32)]
        )

    # -- pack / unpack -------------------------------------------------
    def payload(self, local):
        a_ptr, a_idx = local["a_indptr"], local["a_indices"]
        b_ptr, b_idx = local["b_indptr"], local["b_indices"]
        aug = local["b_aug"] if self.with_aug else None
        if not self.use_blob:
            b_state = (b_ptr, b_idx) if aug is None else (b_ptr, b_idx, aug)
            return ((a_ptr, a_idx), b_state)
        self._nb = a_ptr.shape[0] - 1
        if self.compress_lengths:
            a_head, b_head = self._pack_lengths(a_ptr), self._pack_lengths(b_ptr)
        else:
            a_head, b_head = a_ptr, b_ptr
        self._layouts["a"], _ = blob_layout([a_head.shape, a_idx.shape])
        self._layouts["b"], _ = blob_layout([b_head.shape, b_idx.shape])
        b_blob = pack_blob([b_head, b_idx])
        b_state = b_blob if aug is None else (b_blob, aug)
        return (pack_blob([a_head, a_idx]), b_state)

    def _unpack(self, blob, side):
        head, idx = unpack_blob(blob, self._layouts[side])
        if self.compress_lengths:
            head = self._unpack_lengths(head, self._nb)
        return head, idx

    def count(self, state, local, step, ctx):
        del step, ctx
        a_state, b_state = state
        aug = None
        if self.use_blob:
            a_ptr, a_idx = self._unpack(a_state, "a")
            if self.with_aug:
                b_blob, aug = b_state
            else:
                b_blob = b_state
            b_ptr, b_idx = self._unpack(b_blob, "b")
        else:
            a_ptr, a_idx = a_state
            if self.with_aug:
                b_ptr, b_idx, aug = b_state
            else:
                b_ptr, b_idx = b_state
        extra = {} if aug is None else dict(aug_b=aug)
        return self.kernel(
            a_ptr, a_idx, b_ptr, b_idx,
            local["m_ti"], local["m_tj"], local["m_cnt"],
            **extra,
        )


class DenseStore(OperandStore):
    """Dense 0/1 block operands (oracle path): count = sum((A@Bᵀ)⊙M)."""

    operand_names = ("a_dense", "b_dense")
    static_names = ("m_dense",)

    def __init__(self, *, acc_dtype=jnp.float32):
        self.acc_dtype = acc_dtype

    def in_specs(self, axes):
        ab = P(*axes.all)
        return dict(a_dense=ab, b_dense=ab, m_dense=P(axes.row, axes.col))

    def lead(self, name, axes):
        return len(axes.all) if name in self.operand_names else 2

    def payload(self, local):
        return (local["a_dense"], local["b_dense"])

    def count(self, state, local, step, ctx):
        del step, ctx
        a, b = state
        return count_mod.count_pair_dense(
            a, b, local["m_dense"], acc_dtype=self.acc_dtype
        )


class TileStore(OperandStore):
    """Bit-packed 128x128 tile operands driving the Pallas kernel.

    Tile stores shift exactly like CSR blobs; the per-(device, shift)
    active-triple lists are static (planner-joined) and selected by the
    schedule's step index.
    """

    operand_names = ("a_tiles", "b_tiles")
    static_names = ("m_tiles", "triples")

    def __init__(self, *, mode: str = "popcount", interpret: bool,
                 count_dtype=jnp.int32):
        self.mode = mode
        self.interpret = interpret
        self.count_dtype = count_dtype

    def in_specs(self, axes):
        spec = P(axes.row, axes.col)
        return {k: spec for k in self.names}

    def lead(self, name, axes):
        del name
        return 2

    def payload(self, local):
        return (local["a_tiles"], local["b_tiles"])

    def count(self, state, local, step, ctx):
        del ctx
        from ..kernels.tc_tile.tc_tile import tile_triple_counts

        a_cur, b_cur = state
        per = tile_triple_counts(
            local["triples"][step], a_cur, b_cur, local["m_tiles"],
            mode=self.mode, interpret=self.interpret,
        )
        return jnp.sum(per, dtype=self.count_dtype)


class SummaCSRStore(OperandStore):
    """CSR operands for SUMMA broadcast rounds.

    Nothing is carried between steps; instead the B operand holds
    ``npan = ceil(c/r)`` panels per device and :meth:`select` realizes
    step ``z``'s (A, B) panel pair per the ``broadcast`` strategy:

    * ``"onehot"`` — masked psums (XLA lowers each to an all-reduce
      moving ``2·S·(n-1)/n`` bytes — strictly more than a broadcast);
    * ``"chain"`` — masked ppermute doubling chains
      (:func:`chain_broadcast`, ``S·(n-1)/n`` bytes — half the psum).
      Chain rounds need *static* round indices (the ppermute pairs are
      trace constants), so the schedule must run its unrolled body —
      :func:`~repro.core.summa.build_summa_fn` arranges this.

    ``elide_broadcast=True`` is the count-only timing probe (mirroring
    Cannon's ``elide_shifts``): every device counts its *local* panel
    pair, no collectives — counts are wrong for grids > 1x1.
    """

    operand_names = ("a_indptr", "a_indices", "b_indptr", "b_indices")
    static_names = ("m_ti", "m_tj", "m_cnt")

    def __init__(self, kernel, *, r: int, c: int, broadcast: str = "onehot",
                 elide_broadcast: bool = False):
        if broadcast not in ("onehot", "chain"):
            raise ValueError(
                f"unknown broadcast strategy {broadcast!r}; "
                "expected 'onehot' or 'chain'"
            )
        self.kernel = kernel
        self.r = r
        self.c = c
        self.broadcast = broadcast
        self.elide_broadcast = elide_broadcast

    def in_specs(self, axes):
        spec = P(axes.row, axes.col)
        return {k: spec for k in self.names}

    def lead(self, name, axes):
        del name, axes
        return 2

    def payload(self, local):  # SUMMA carries no shift state
        del local
        return ()

    def select(self, local, z, ctx):
        """Broadcast of step ``z``'s A panel (along the grid row, from
        owner column ``z % c``) and B panel (along the grid column, from
        owner row ``z % r``, local slot ``z // r``)."""
        a_ptr, a_idx = local["a_indptr"], local["a_indices"]
        b_ptr, b_idx = local["b_indptr"], local["b_indices"]
        if self.elide_broadcast:
            return ((a_ptr, a_idx), (b_ptr[z // self.r], b_idx[z // self.r]))
        with jax.named_scope("tc_broadcast"):
            if self.broadcast == "chain":
                if isinstance(z, jax.core.Tracer):
                    raise ValueError(
                        "chain broadcast needs static round indices "
                        "(ppermute pairs are trace constants): run the "
                        "unrolled schedule body (live_steps set)"
                    )
                z = int(z)
                pa_ptr = chain_broadcast(
                    a_ptr, ctx.axes.col, self.c, z % self.c
                )
                pa_idx = chain_broadcast(
                    a_idx, ctx.axes.col, self.c, z % self.c
                )
                slot = z // self.r
                pb_ptr = chain_broadcast(
                    b_ptr[slot], ctx.axes.row, self.r, z % self.r
                )
                pb_idx = chain_broadcast(
                    b_idx[slot], ctx.axes.row, self.r, z % self.r
                )
                return ((pa_ptr, pa_idx), (pb_ptr, pb_idx))
            owna = (
                ctx.axis_index(ctx.axes.col) == z % self.c
            ).astype(a_ptr.dtype)
            pa_ptr = jax.lax.psum(a_ptr * owna, ctx.axes.col)
            pa_idx = jax.lax.psum(a_idx * owna, ctx.axes.col)
            slot = z // self.r
            ownb = (
                ctx.axis_index(ctx.axes.row) == z % self.r
            ).astype(b_ptr.dtype)
            pb_ptr = jax.lax.psum(b_ptr[slot] * ownb, ctx.axes.row)
            pb_idx = jax.lax.psum(b_idx[slot] * ownb, ctx.axes.row)
            return ((pa_ptr, pa_idx), (pb_ptr, pb_idx))

    def count(self, state, local, step, ctx):
        del step, ctx
        (a_ptr, a_idx), (b_ptr, b_idx) = state
        return self.kernel(
            a_ptr, a_idx, b_ptr, b_idx,
            local["m_ti"], local["m_tj"], local["m_cnt"],
        )


class OneDCSRStore(OperandStore):
    """1D-ring operands: each device's own row-block CSR rotates as one
    blob; tasks are grouped by owner-of-j and the group matching the
    currently-held block is selected each step."""

    operand_names = ("indptr", "indices")
    static_names = ("t_i", "t_j", "t_cnt")

    def __init__(self, kernel, *, p: int):
        self.kernel = kernel
        self.p = p
        self._layout = None

    def in_specs(self, axes):
        return {k: P(axes.axis) for k in self.names}

    def lead(self, name, axes):
        del name, axes
        return 1

    def payload(self, local):
        own_ptr, own_idx = local["indptr"], local["indices"]
        self._layout, _ = blob_layout([own_ptr.shape, own_idx.shape])
        return pack_blob([own_ptr, own_idx])

    def count(self, state, local, step, ctx):
        b_ptr, b_idx = unpack_blob(state, self._layout)
        d = ctx.axis_index(ctx.axes.axis)
        o = (d + step) % self.p
        return self.kernel(
            local["indptr"], local["indices"], b_ptr, b_idx,
            jnp.take(local["t_i"], o, axis=0),
            jnp.take(local["t_j"], o, axis=0),
            jnp.take(local["t_cnt"], o, axis=0),
        )


# ======================================================================
# shift schedules
# ======================================================================
@dataclasses.dataclass
class _Ctx:
    """Per-trace context handed to stores (axis introspection)."""

    axes: object

    @staticmethod
    def axis_index(name: str):
        return jax.lax.axis_index(name)


MASK_NAME = "step_keep"


def masked_count(store, state, local, step, ctx, step_keep, count_dtype):
    """One schedule step's count, short-circuited by the planner's mask.

    ``step_keep`` is the device-local ``(nsteps,)`` bool vector staged by
    the planner (True = this step's incoming block pair can contribute);
    ``lax.cond`` with the traced predicate skips the whole count kernel
    on masked-off steps.  Collectives (ppermute shifts, SUMMA's psum
    broadcasts) must stay *outside* — every device participates in the
    exchange even when its own count is skipped, so the SPMD program
    stays uniform.
    """

    def count():
        with jax.named_scope("tc_count"):
            return store.count(state, local, step, ctx)

    if step_keep is None:
        return count()
    return jax.lax.cond(
        step_keep[step],
        count,
        lambda: jnp.zeros((), jnp.dtype(count_dtype)),
    )


class ShiftSchedule:
    """Permutation structure for the shared ``lax.scan`` driver.

    Split into three hooks so the full-scan engine and the host-driven
    fault-tolerance stepper share one body:

    * ``init_carry(store, local, ctx)`` — the scan carry at step 0
      (may issue prologue collectives, e.g. Cannon's first in-flight
      shift when double-buffered);
    * ``carry_template(payload)`` — the carry's pytree *structure* only
      (no computation; the stepper uses it to rebuild the carry from
      host-checkpointed leaves);
    * ``make_body(store, local, ctx, step_keep=..., count_dtype=...,
      hop=1)`` — ``body(carry, step) -> (carry', count)``, consuming the
      planner's per-step skip mask via :func:`masked_count`; ``hop`` is
      the static shift distance in schedule steps (the stepper compiles
      one body per distinct hop of a compacted schedule).

    ``make_scan`` composes them into the ``(carry0, body, nsteps)``
    triple the engine's scan driver consumes.  ``run`` executes the
    whole schedule: the scan driver normally, or — when ``live_steps``
    is set (a compacted schedule, DESIGN.md §4.4) — an *unrolled* body
    over only the globally-live steps, with the elided unit shifts fused
    into multi-hop ``ppermute``\\ s.  Step indices stay in the original
    numbering, so per-device conds index the staged ``step_keep`` mask
    unremapped and step-selected statics (tile triples, ring task
    groups) keep working.
    """

    live_steps: Optional[Tuple[int, ...]] = None

    def init_carry(self, store: OperandStore, local: Dict, ctx: _Ctx):
        return store.payload(local)

    def carry_template(self, payload):
        return payload

    def make_body(self, store: OperandStore, local: Dict, ctx: _Ctx, *,
                  step_keep=None, count_dtype=jnp.int32, hop: int = 1):
        raise NotImplementedError

    def make_scan(self, store: OperandStore, local: Dict, ctx: _Ctx, *,
                  step_keep=None, count_dtype=jnp.int32):
        body = self.make_body(
            store, local, ctx, step_keep=step_keep, count_dtype=count_dtype
        )
        return self.init_carry(store, local, ctx), body, self.nsteps

    def run_compacted(self, store: OperandStore, local: Dict, ctx: _Ctx, *,
                      step_keep=None, count_dtype=jnp.int32):
        raise NotImplementedError

    def run(self, store: OperandStore, local: Dict, ctx: _Ctx, *,
            step_keep=None, count_dtype=jnp.int32):
        """Execute the whole schedule, returning the device's total."""
        if self.live_steps is not None:
            return self.run_compacted(
                store, local, ctx, step_keep=step_keep,
                count_dtype=count_dtype,
            )
        carry0, body, nsteps = self.make_scan(
            store, local, ctx, step_keep=step_keep, count_dtype=count_dtype
        )
        _, per_step = jax.lax.scan(body, carry0, jnp.arange(nsteps))
        return jnp.sum(per_step, dtype=count_dtype)


@dataclasses.dataclass
class CannonSchedule(ShiftSchedule):
    """Cannon's q-step {count, shift-A-left, shift-B-up} rotation.

    ``double_buffer=True`` (default) runs the communication-overlapped
    body: the carry holds *two* payload generations ``(cur, inflight)``
    — ``cur`` is counted at step ``s`` while ``inflight`` (step s+1's
    blocks, requested one step earlier) is already being shifted toward
    step s+2.  Count and collective touch disjoint buffers, so the
    overlap is structural, not a scheduling hope.  Costs one extra
    (discarded) shift at the end of the rotation.

    Multi-pod (2.5D): blocks are replicated over the pod axis, pod ``t``
    starts at skew offset ``t`` (see ``pod_stack_arrays``) and executes
    every ``npods``-th shift — memory ×npods, shift traffic ÷npods.
    """

    q: int
    axes: GridAxes
    npods: int = 1
    double_buffer: bool = True
    # compacted schedule: original indices of the globally-live steps
    # (strictly increasing).  ``run`` then unrolls over them with fused
    # multi-hop shifts; the stepper compiles one body per distinct hop.
    live_steps: Optional[Tuple[int, ...]] = None
    # timing probe: elide every shift (counts are wrong for q > 1 — used
    # only by the benchmark's count-only attribution run)
    elide_shifts: bool = False

    @property
    def nsteps(self) -> int:
        assert self.q % self.npods == 0, "pods must divide the grid dimension"
        return self.q // self.npods

    def _shift_k(self, payload, hop: int):
        """Fused shift of ``hop`` schedule steps (one ppermute per
        operand regardless of hop — the multi-hop fusion)."""
        k = (hop * self.npods) % self.q
        if k == 0 or self.elide_shifts:
            return payload
        perm = shift_perm(self.q, k)
        a_state, b_state = payload
        with jax.named_scope("tc_shift"):
            return (
                tree_ppermute(a_state, self.axes.col, perm),
                tree_ppermute(b_state, self.axes.row, perm),
            )

    def _shift(self, payload):
        return self._shift_k(payload, 1)

    def init_carry(self, store, local, ctx):
        payload = store.payload(local)
        if self.live_steps is not None:
            # compacted stepper: single-generation carry pre-shifted to
            # the first live step (the prologue hop)
            assert not self.double_buffer, (
                "the compacted stepper runs single-buffered"
            )
            if self.live_steps:
                payload = self._shift_k(payload, self.live_steps[0])
            return payload
        if not self.double_buffer:
            return payload
        # prologue: put step 1's blocks in flight before step 0 counts
        return (payload, self._shift(payload))

    def carry_template(self, payload):
        if self.live_steps is not None:
            return payload
        return (payload, payload) if self.double_buffer else payload

    def make_body(self, store, local, ctx, *, step_keep=None,
                  count_dtype=jnp.int32, hop: int = 1):
        if self.double_buffer:

            def body(carry, s):
                cur, inflight = carry
                # issue step s+2's shift from the independent buffer
                # BEFORE counting step s — collective ∥ intersection.
                nxt = self._shift_k(inflight, hop)
                c = masked_count(
                    store, cur, local, s, ctx, step_keep, count_dtype
                )
                return (inflight, nxt), c

        else:

            def body(carry, s):
                nxt = self._shift_k(carry, hop)
                c = masked_count(
                    store, carry, local, s, ctx, step_keep, count_dtype
                )
                return nxt, c

        return body

    def run_compacted(self, store, local, ctx, *, step_keep=None,
                      count_dtype=jnp.int32):
        """Unrolled kept-step body: count only the live steps, reach
        each via one fused multi-hop ppermute.  In straight-line code
        the shift for the next live step and the current count touch
        independent values, so the communication/compute overlap of the
        double-buffered scan body is structural here without a second
        payload generation (``double_buffer`` is a scan-body knob and is
        ignored)."""
        live = self.live_steps
        total = jnp.zeros((), jnp.dtype(count_dtype))
        if not live:
            return total  # everything elided: no shifts, no counts
        payload = store.payload(local)
        payload = self._shift_k(payload, live[0])
        for i, s in enumerate(live):
            nxt = (
                self._shift_k(payload, live[i + 1] - s)
                if i + 1 < len(live)
                else None
            )
            total = total + masked_count(
                store, payload, local, s, ctx, step_keep, count_dtype
            )
            if nxt is not None:
                payload = nxt
        return total


@dataclasses.dataclass
class SummaSchedule(ShiftSchedule):
    """SUMMA broadcast rounds on an ``r x c`` grid: ``c`` steps, each a
    one-hot-psum panel broadcast realized by the store's ``select``.

    The broadcast itself is unconditional (every device contributes to
    the psum); only the count is skip-masked.
    """

    r: int
    c: int
    axes: GridAxes
    live_steps: Optional[Tuple[int, ...]] = None

    @property
    def nsteps(self) -> int:
        return self.c

    def make_body(self, store, local, ctx, *, step_keep=None,
                  count_dtype=jnp.int32, hop: int = 1):
        del hop  # broadcast rounds carry no shift state

        def body(carry, z):
            state = store.select(local, z, ctx)
            c = masked_count(
                store, state, local, z, ctx, step_keep, count_dtype
            )
            return carry, c

        return body

    def run_compacted(self, store, local, ctx, *, step_keep=None,
                      count_dtype=jnp.int32):
        """Elide whole broadcast rounds: a globally-dead round's one-hot
        psum pair disappears with its count (SUMMA is stateless between
        rounds, so no hop fusion is needed)."""
        total = jnp.zeros((), jnp.dtype(count_dtype))
        for z in self.live_steps:
            state = store.select(local, z, ctx)
            total = total + masked_count(
                store, state, local, z, ctx, step_keep, count_dtype
            )
        return total


@dataclasses.dataclass
class RingSchedule(ShiftSchedule):
    """1D ring rotation over ``p`` devices: the whole payload passes
    through every device once (the baseline's (p-1)/p·nnz volume)."""

    p: int
    axes: RingAxes
    live_steps: Optional[Tuple[int, ...]] = None
    # timing probe: elide every rotation (counts are wrong for p > 1 —
    # used only by the benchmark's count-only attribution run)
    elide_shifts: bool = False

    @property
    def nsteps(self) -> int:
        return self.p

    def _shift_k(self, payload, hop: int):
        k = hop % self.p
        if k == 0 or self.elide_shifts:
            return payload
        with jax.named_scope("tc_shift"):
            return tree_ppermute(
                payload, self.axes.axis, shift_perm(self.p, k)
            )

    def make_body(self, store, local, ctx, *, step_keep=None,
                  count_dtype=jnp.int32, hop: int = 1):
        def body(carry, t):
            nxt = self._shift_k(carry, hop)
            c = masked_count(
                store, carry, local, t, ctx, step_keep, count_dtype
            )
            return nxt, c

        return body

    def run_compacted(self, store, local, ctx, *, step_keep=None,
                      count_dtype=jnp.int32):
        """Unrolled ring: rotate straight to each live step with one
        fused multi-hop ppermute (the elided steps' blob passes are
        gone, cutting the baseline's (p-1)/p·nnz shifted volume to the
        live fraction)."""
        live = self.live_steps
        total = jnp.zeros((), jnp.dtype(count_dtype))
        if not live:
            return total
        payload = store.payload(local)
        payload = self._shift_k(payload, live[0])
        for i, t in enumerate(live):
            nxt = (
                self._shift_k(payload, live[i + 1] - t)
                if i + 1 < len(live)
                else None
            )
            total = total + masked_count(
                store, payload, local, t, ctx, step_keep, count_dtype
            )
            if nxt is not None:
                payload = nxt
        return total


# ======================================================================
# reduction
# ======================================================================
def pod_tree_allreduce(x, axis: str, n: int):
    """Binomial-tree all-reduce over one mesh axis of size ``n`` (a
    power of two): log2(n) masked ppermute rounds funnel partials to
    position 0, log2(n) more broadcast the sum back.

    ``ppermute`` delivers zeros to devices outside a round's receiver
    set, so the reduce rounds add unconditionally; the broadcast rounds
    select receivers by axis index.  Round ``k`` involves ``n / 2k`` of
    the ``n`` positions as senders, so with pairs-aware accounting the
    total moved is ``2·S·(n-1)/n`` — a psum's ring cost, but reached in
    2·log2(n) latency hops instead of 2(n-1), and composable with a
    *joint* grid psum so the 2.5D reduce never all-reduces over the pod
    axis times the grid (see :class:`Reduction`).
    """
    if n == 1:
        return x
    assert n & (n - 1) == 0, "tree reduce needs a power-of-two axis size"
    idx = jax.lax.axis_index(axis)
    rounds = []
    k = 1
    while k < n:
        rounds.append(k)
        k *= 2
    # reduce: round k's senders (t % 2k == k) funnel into t - k
    for k in rounds:
        pairs = [(t, t - k) for t in range(n) if t % (2 * k) == k]
        x = x + compat.ppermute(x, axis, pairs)
    # broadcast back: reversed rounds, receivers replace their stale
    # partials (senders' values pass through ``x`` unchanged)
    for k in reversed(rounds):
        pairs = [(t, t + k) for t in range(n) if t % (2 * k) == 0]
        recv = compat.ppermute(x, axis, pairs)
        x = jnp.where(idx % (2 * k) == k, recv, x)
    return x


def chain_broadcast(x, axis: str, n: int, owner: int):
    """Broadcast ``owner``'s value along one mesh axis of size ``n`` via
    a masked ppermute doubling chain (emulating collective-broadcast
    until jax exposes one).

    Round ``d`` has every already-covered position forward to distance
    ``d`` ahead (mod ``n``, never wrapping past the owner), doubling
    coverage; ``n - 1`` pairs total across all rounds, so the moved
    bytes are ``S·(n-1)/n`` — exactly *half* the one-hot psum's
    all-reduce cost ``2·S·(n-1)/n``, in ceil(log2(n)) hops.  Positions
    outside the covered prefix never send, so their stale values are
    harmless and are replaced on receipt.
    """
    if n == 1:
        return x
    owner = int(owner) % n
    rel = (jax.lax.axis_index(axis) - owner) % n
    cover = 1
    while cover < n:
        pairs = [
            (t, (t + cover) % n)
            for t in range(n)
            if (t - owner) % n < cover and (t - owner) % n + cover < n
        ]
        recv = compat.ppermute(x, axis, pairs)
        x = jnp.where((rel >= cover) & (rel < 2 * cover), recv, x)
        cover *= 2
    return x


@dataclasses.dataclass(frozen=True)
class Reduction:
    """Global sum of the per-device partials, or per-device outputs.

    ``strategy`` selects how the global sum is realized:

    * ``"flat"`` — one psum per mesh axis (the original path; the only
      choice on single-pod grids and rings);
    * ``"tree"`` — the 2.5D staged reduce: one *joint* psum over the
      grid axes (a single all-reduce over the q² group, strictly fewer
      bytes than the per-axis pair), then one cross-pod binomial tree
      via log₂(npods) masked ppermute rounds each way
      (:func:`pod_tree_allreduce`).  Needs a pod axis with a
      power-of-two size > 1 — :meth:`resolve` enforces this;
    * ``"auto"`` — ``tree`` whenever it is applicable, else ``flat``.

    Builders pass the unresolved knob; :func:`build_engine_fn` binds it
    against the mesh via :meth:`resolve`.  An unresolved ``"auto"``
    applies as ``flat`` (the safe default for direct ``apply`` callers).
    """

    global_sum: bool = True
    strategy: str = "auto"  # "flat" | "tree" | "auto"
    npods: int = 1  # pod-axis size, bound by resolve()

    def resolve(self, mesh, axes) -> "Reduction":
        """Bind ``strategy`` and the pod-axis size against the mesh."""
        pod = getattr(axes, "pod", None)
        npods = int(mesh.shape[pod]) if pod else 1
        pow2 = npods > 1 and (npods & (npods - 1)) == 0
        strategy = self.strategy
        if strategy == "auto":
            strategy = "tree" if (pod and pow2) else "flat"
        elif strategy == "tree":
            if not pod or npods <= 1:
                raise ValueError(
                    "reduce strategy 'tree' needs a pod axis with "
                    "npods > 1; use 'flat' (or 'auto') on single-pod "
                    "grids and rings"
                )
            if not pow2:
                raise ValueError(
                    f"reduce strategy 'tree' needs a power-of-two pod "
                    f"count, got npods={npods}"
                )
        elif strategy != "flat":
            raise ValueError(
                f"unknown reduce strategy {strategy!r}; "
                "expected 'flat', 'tree', or 'auto'"
            )
        return dataclasses.replace(self, strategy=strategy, npods=npods)

    def apply(self, total, axes):
        if not self.global_sum:
            return total.reshape((1,) * len(axes.all))
        with jax.named_scope("tc_reduce"):
            if self.strategy == "tree":
                total = jax.lax.psum(total, (axes.row, axes.col))
                return pod_tree_allreduce(total, axes.pod, self.npods)
            for ax in axes.all:
                total = jax.lax.psum(total, ax)
            return total

    def out_specs(self, axes):
        return P() if self.global_sum else P(*axes.all)


# ======================================================================
# hub-split partial count (DESIGN.md §4.8)
# ======================================================================
class HubCount:
    """The replicated hub-fragment partial sum of a hub-split plan.

    Runs *outside* the schedule loop: the planner's hub-split stage
    (:mod:`repro.pipeline.hubsplit`) stages column-strided fragment
    CSRs + task lists per device, each device counts its slice with the
    plain pair-search kernel once, and the partial folds into the same
    :class:`Reduction` as the schedule total — so flat and tree
    reductions, skip masks, and schedule compaction all compose
    untouched (hub work can never revive an elided step).

    Hub arrays ride the *static* partition specs — ``P(row, col)`` on
    grids, ``P(axis)`` on rings — so multi-pod meshes replicate them
    across the pod axis; :meth:`count` zeroes the partial on every pod
    but pod 0 to keep the global sum exact.
    """

    names = ("hub_indptr", "hub_indices", "hub_ti", "hub_tj", "hub_cnt")

    def __init__(self, *, dpad: int, chunk: int, sentinel: int,
                 probe_shorter: bool = True):
        self.dpad = int(dpad)
        self.chunk = int(chunk)
        self.sentinel = int(sentinel)
        self.probe_shorter = probe_shorter

    @classmethod
    def from_plan(cls, plan, *, probe_shorter: bool = True):
        h = getattr(plan, "hub", None)
        if h is None:
            return None
        return cls(
            dpad=h.dpad, chunk=h.chunk, sentinel=h.sentinel,
            probe_shorter=probe_shorter,
        )

    def in_specs(self, axes):
        if getattr(axes, "axis", None) is not None:  # ring
            spec = P(axes.axis)
        else:
            spec = P(axes.row, axes.col)
        return {k: spec for k in self.names}

    def count(self, local, ctx, count_dtype):
        with jax.named_scope("tc_hub"):
            c = count_mod.count_pair_search(
                local["hub_indptr"], local["hub_indices"],
                local["hub_indptr"], local["hub_indices"],
                local["hub_ti"], local["hub_tj"], local["hub_cnt"],
                dpad=self.dpad, chunk=self.chunk,
                probe_shorter=self.probe_shorter,
                count_dtype=count_dtype, sentinel=self.sentinel,
            )
            pod = getattr(ctx.axes, "pod", None)
            if pod is not None:
                c = c * (jax.lax.axis_index(pod) == 0).astype(c.dtype)
            return c


# ======================================================================
# engine builders
# ======================================================================
def _make_call(fn, ordered, in_specs, shardings):
    """Keyword/positional call wrapper with ``.lower`` for dry runs.

    ``call.shardings`` maps each input name to the ``NamedSharding`` the
    program takes it with — what :meth:`PlanArtifact.staged` places
    arrays by, so a multi-device mesh never stages everything on its
    first device."""

    def call(*pos, **arrays):
        if pos:
            return fn(*pos)
        return fn(*(arrays[k] for k in ordered))

    def lower(*pos, **arrays):
        if pos:
            return fn.lower(*pos)
        return fn.lower(*(arrays[k] for k in ordered))

    call.lower = lower
    call.in_specs = in_specs
    call.shardings = shardings
    call.ordered = list(ordered)
    return call


def build_engine_fn(
    mesh,
    axes,
    store: OperandStore,
    schedule: ShiftSchedule,
    *,
    count_dtype=jnp.int32,
    reduction: Optional[Reduction] = None,
    batched: bool = False,
    use_step_mask: bool = False,
    hub: Optional[HubCount] = None,
):
    """Generate the jitted SPMD counting function for one composition.

    Returns ``call(**device_arrays)`` (also accepts positional arrays in
    ``call.ordered`` order) yielding the global count scalar, or
    per-device counts with ``Reduction(global_sum=False)``.

    ``use_step_mask=True`` adds a ``step_keep`` device array to the call
    (the planner's per-device per-step skip mask, sharded like the grid:
    ``(..., nsteps)`` bools behind ``P(*axes.all)``); the schedule body
    then short-circuits the count kernel on masked-off steps via
    ``lax.cond`` while still performing every exchange collectively.

    ``batched=True`` builds the multi-graph variant: every device array
    carries an unsharded leading batch axis (graphs padded to shared
    maxima and stacked by :mod:`repro.pipeline.batch`), the schedule
    runs per graph under one ``lax.map`` inside the same ``shard_map``,
    and the call returns the ``(batch,)`` vector of global counts — one
    compiled executable and one dispatch for the whole batch.
    """
    reduction = (reduction or Reduction()).resolve(mesh, axes)
    count_dtype = compat.canonical_count_dtype(count_dtype)
    ordered = list(store.names)
    if hub is not None:
        ordered += list(hub.names)
    if use_step_mask:
        ordered.append(MASK_NAME)
    specs = store.in_specs(axes)
    mask_lead = len(axes.all)
    if hub is not None:
        specs = dict(specs, **hub.in_specs(axes))
    if use_step_mask:
        specs = dict(specs, **{MASK_NAME: P(*axes.all)})
    ctx = _Ctx(axes)

    def core(local):
        local = dict(local)
        keep = local.pop(MASK_NAME, None)
        hub_local = (
            {k: local.pop(k) for k in hub.names} if hub is not None else None
        )
        total = schedule.run(
            store, local, ctx, step_keep=keep, count_dtype=count_dtype
        )
        if hub is not None:
            total = total + hub.count(hub_local, ctx, count_dtype)
        return reduction.apply(total, axes)

    if batched:
        assert hub is None, (
            "batched engines do not take hub-split plans (per-graph hub "
            "sides differ; plan with hub_split=False)"
        )
        assert reduction.global_sum, (
            "batched engine returns per-graph global counts"
        )
        assert schedule.live_steps is None, (
            "batched engines use the scan body (per-graph masks differ; "
            "compaction would need their union)"
        )

        def tc_engine_many(*args):
            named = dict(zip(ordered, args))
            keep = named.pop(MASK_NAME, None)
            # strip the size-1 mesh block dims that follow the batch axis
            local = {
                k: v.reshape((v.shape[0],) + v.shape[1 + store.lead(k, axes):])
                for k, v in named.items()
            }
            if keep is not None:
                local[MASK_NAME] = keep.reshape(
                    (keep.shape[0],) + keep.shape[1 + mask_lead:]
                )
            return jax.lax.map(core, local)

        body = tc_engine_many
        in_specs = tuple(P(None, *specs[k]) for k in ordered)
        out_specs = P(None)
    else:

        def tc_engine(*args):
            named = dict(zip(ordered, args))
            keep = named.pop(MASK_NAME, None)
            local = store.localize(named, axes)
            if keep is not None:
                local[MASK_NAME] = _squeeze(keep, mask_lead)
            return core(local)

        body = tc_engine
        in_specs = tuple(specs[k] for k in ordered)
        out_specs = reduction.out_specs(axes)

    # the function's name names the device program: jit_tc_engine...
    fn = jax.jit(
        compat.shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
    )
    shardings = {
        k: jax.sharding.NamedSharding(mesh, spec)
        for k, spec in zip(ordered, in_specs)
    }
    return _make_call(fn, ordered, specs, shardings)


def build_engine_stepper(
    mesh,
    axes,
    store: OperandStore,
    schedule: ShiftSchedule,
    *,
    count_dtype=jnp.int32,
    use_step_mask: bool = False,
):
    """One-schedule-step-at-a-time variant for fault-tolerant runs.

    Reuses the exact scan body of ``schedule`` (``make_body``) but
    executes a single step per call with the scan *carry* held by the
    host as explicit arrays, so the host loop owns the shift index and
    can checkpoint state between shifts (a restarted job resumes
    mid-loop).  With a double-buffered :class:`CannonSchedule` the carry
    is two payload generations — both buffers checkpoint and round-trip
    exactly like any other state arrays.

    Requires a store whose payload is identity-structured (raw arrays,
    e.g. ``CSRStore(use_blob=False)``) so checkpointed state round-trips
    exactly.  Returns ``one_shift(state, statics, step=0) -> state``
    where ``state = (*carry_arrays, acc)`` and ``statics`` maps the
    store's static names (plus ``"step_keep"`` when ``use_step_mask``).
    ``one_shift.prime(operand_arrays) -> carry_arrays`` builds the
    step-0 carry (including any prologue shift the schedule issues);
    ``one_shift.n_carry`` is the number of carry arrays.

    With a *compacted* schedule (``schedule.live_steps`` set) the host
    loop iterates ``one_shift.live_steps`` only, still passing the
    **original** step index — mask lookups need no remapping, and a
    checkpointed step index round-trips unchanged (the resume loop just
    filters the live list to ``>= saved``).  Each call shifts by the
    fused hop to the *next* live step; one executable is compiled per
    distinct hop (a handful at most).
    """
    import numpy as np

    count_dtype = compat.canonical_count_dtype(count_dtype)
    ordered_statics = list(store.static_names)
    specs = store.in_specs(axes)
    ctx = _Ctx(axes)
    op_names = list(store.operand_names)
    op_spec = specs[op_names[0]]
    lead = store.lead(op_names[0], axes)
    mask_lead = len(axes.all)
    live = schedule.live_steps

    # carry pytree *structure* from a computation-free dummy payload —
    # only identity-structured stores qualify (same restriction as the
    # checkpoint round-trip itself).
    try:
        dummy = store.payload({k: np.zeros((), np.int32) for k in op_names})
        treedef = jax.tree.structure(schedule.carry_template(dummy))
    except Exception as e:  # noqa: BLE001
        raise ValueError(
            "stepper requires an identity-structured payload "
            "(e.g. CSRStore(use_blob=False))"
        ) from e
    n_state = treedef.num_leaves

    one = lambda a: a.reshape((1,) * lead + a.shape)
    static_specs = tuple(specs[k] for k in ordered_statics)
    mask_specs = (P(*axes.all),) if use_step_mask else ()

    def _make_fn(hop: int):
        def tc_engine_step(*args):
            carry_leaves = [_squeeze(a, lead) for a in args[:n_state]]
            pos = n_state
            statics = dict(
                zip(ordered_statics, args[pos:pos + len(ordered_statics)])
            )
            pos += len(ordered_statics)
            keep = None
            if use_step_mask:
                keep = _squeeze(args[pos], mask_lead)
                pos += 1
            acc = _squeeze(args[pos], lead)
            step = args[pos + 1]
            local = store.localize(statics, axes)
            carry = jax.tree.unflatten(treedef, carry_leaves)
            body = schedule.make_body(
                store, local, ctx, step_keep=keep, count_dtype=count_dtype,
                hop=hop,
            )
            carry_next, c = body(carry, step)
            leaves = jax.tree.flatten(carry_next)[0]
            return tuple(one(x) for x in leaves) + (one(acc + c),)

        return jax.jit(
            compat.shard_map(
                tc_engine_step,
                mesh=mesh,
                in_specs=(op_spec,) * n_state + static_specs + mask_specs
                + (op_spec, P()),
                out_specs=(op_spec,) * (n_state + 1),
                check_vma=False,
            )
        )

    fns: Dict[int, Callable] = {}

    def _fn_for(hop: int):
        if hop not in fns:
            fns[hop] = _make_fn(hop)
        return fns[hop]

    def tc_engine_step_prime(*args):
        local = store.localize(dict(zip(op_names, args)), axes)
        carry0 = schedule.init_carry(store, local, ctx)
        leaves = jax.tree.flatten(carry0)[0]
        assert len(leaves) == n_state, (
            "stepper requires an identity-structured payload "
            "(e.g. CSRStore(use_blob=False))"
        )
        return tuple(one(x) for x in leaves)

    prime_fn = jax.jit(
        compat.shard_map(
            tc_engine_step_prime,
            mesh=mesh,
            in_specs=tuple(specs[k] for k in op_names),
            out_specs=(op_spec,) * n_state,
            check_vma=False,
        )
    )

    def one_shift(state, statics, step=0):
        *carry, acc = state
        args = list(carry) + [statics[k] for k in ordered_statics]
        if use_step_mask:
            args.append(statics[MASK_NAME])
        args += [acc, jnp.asarray(step, jnp.int32)]
        hop = 1
        if live is not None:
            i = live.index(int(step))  # host loop must pass a live step
            hop = live[i + 1] - live[i] if i + 1 < len(live) else 0
        return _fn_for(hop)(*args)

    one_shift.prime = lambda operands: prime_fn(
        *(operands[k] for k in op_names)
    )
    one_shift.n_carry = n_state
    one_shift.live_steps = live
    return one_shift
