"""Pallas TPU kernels: PIM gate-program executors.

TPU adaptation of the paper's core insight (DESIGN.md §2): a PIM column of r
row-bits is a dense bitvector, and an arithmetic algorithm is a straight-line
NOR program over columns.  Executing the *entire program* while a row-tile's
cells are resident in VMEM pays HBM traffic once per tile instead of once per
gate, lifting arithmetic intensity from ~1 bit-op/byte to ~program-length
bit-ops/byte -- the memory-wall argument of the paper, restated for the
TPU memory hierarchy (HBM -> VMEM -> VREG).

Layout: ``state[cell, word]`` (uint32), 32 rows packed per word along the
lane dimension; one grid step owns a ``(n_cells, TILE_W)`` VMEM block.

Two executors (DESIGN.md §5):

  * :func:`pim_exec_padded` -- gate-serial.  The lowered program (ops/a/b/out
    int32 arrays, ops in {INIT0=0, INIT1=1, NOT=2, NOR=3}) arrives via scalar
    prefetch and drives a ``fori_loop``; NOT is NOR with b==a, so the compute
    is a single branchless select per gate.  One dynamic row slice per gate:
    this lowers on real TPU hardware today.
  * :func:`pim_exec_level_padded` -- levelized, dense ("scan"-alloc)
    schedules.  The LevelSchedule's (n_levels, width) index matrices drive
    a ``fori_loop`` over *levels*; each iteration gathers the level's
    operand rows, NORs them as one (width, TILE_W) block and scatters the
    results.  The gather/scatter use vector indices, which Mosaic does not
    lower for uint32 row gathers, so this legacy path runs only in
    interpret mode.

Slot-schedule kernels (DESIGN.md §9), consuming ``alloc="slots"``
schedules from ``core.gates.levelize``:

  * :func:`pim_exec_slots_fused` / :func:`pim_exec_slots_io` -- the fused
    fast path: the kernel assembles the state from the input port rows
    (one slice update; inputs are a contiguous run by construction), runs a
    ``lax.scan`` over levels whose *write* side is a contiguous band
    ``dynamic_update_slice`` (the scatter is gone), and emits the output
    band as one slice.  The remaining vector gather on the operand read
    side keeps this kernel interpret-only, but it is the structurally
    leanest form and beats the jnp reference on the tracked benchmark row.
  * :func:`pim_exec_slots_static` -- the rewritten levelized kernel
    (:func:`_pim_level_kernel`): the straight-line static-slice emission
    shared with ``kernels.slots``.  The level loop is unrolled at trace
    time, every read is a ``lax.slice`` at a Python-constant offset (merged
    into maximal runs), every band is an SSA value, and the output block is
    a static concatenation -- **zero dynamic indexing**, so the kernel body
    is Mosaic-lowerable on hardware.  On CPU the unrolled form trades the
    loop for per-op interpret overhead, which is why the scan kernel above
    is the CPU benchmark path.

Every entry point takes ``interpret=None``: :func:`interpret_mode` resolves
it from the platform (compiled through Mosaic on a TPU, interpreted
elsewhere).  ``kernels.ops`` refuses, on a TPU, every plan that would reach
one of the interpret-only kernels above.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .plan import TILE_W            # lane-dim words per block (re-export)
from .slots import (SLOT_UNROLL, at_cells, band_slice, band_update,
                    build_init_block, emit_levels, pack_values, plane_shape,
                    read_concat, static_plan, take_cells, unpack_values)

_FULL = 0xFFFFFFFF


def interpret_mode(interpret=None) -> bool:
    """Whether a ``pallas_call`` runs in interpret mode: never on a TPU,
    always on every other backend (which has no Mosaic compiler).  The
    single place the mode is decided; an explicit bool wins (tests compile
    for a described TPU from a CPU process with ``interpret=False``)."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def _check_state_shape(where: str, state, n_cells: int) -> None:
    """Trace-time shape validation.  Explicit raises, not ``assert``: these
    guard grid construction and block specs, and must survive ``python -O``
    (asserts are stripped there, turning shape bugs into silent garbage).
    State is (n_cells, n_words) under rows32 or (planes, n_cells, n_words)
    under the paired rows64 layout."""
    if state.ndim not in (2, 3) or state.shape[-2] != n_cells:
        raise ValueError(
            f"{where}: state must be ([planes,] n_cells={n_cells}, "
            f"n_words), got shape {tuple(state.shape)}")
    if state.shape[-1] % TILE_W != 0:
        raise ValueError(
            f"{where}: n_words={state.shape[-1]} must be a multiple of "
            f"TILE_W={TILE_W}")


def _state_block(state, n_cells: int):
    """(BlockSpec shape, index_map) tiling the trailing word axis of a 2-D
    or planes-leading 3-D state."""
    if state.ndim == 2:
        return (n_cells, TILE_W), lambda i, *_: (0, i)
    return (state.shape[0], n_cells, TILE_W), lambda i, *_: (0, 0, i)


def _pim_kernel(ops_ref, a_ref, b_ref, o_ref, state_ref, out_ref):
    # bring the tile into the output buffer once; all gates run in-place
    out_ref[...] = state_ref[...]
    n = ops_ref.shape[0]

    def body(i, carry):
        op = ops_ref[i]
        av = out_ref[pl.ds(a_ref[i], 1), :]
        bv = out_ref[pl.ds(b_ref[i], 1), :]
        nor = ~(av | bv)                      # NOT == NOR with b == a
        init = jnp.where(op == 1, jnp.uint32(_FULL), jnp.uint32(0))
        res = jnp.where(op >= 2, nor, jnp.broadcast_to(init, nor.shape))
        out_ref[pl.ds(o_ref[i], 1), :] = res
        return carry

    jax.lax.fori_loop(0, n, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("n_cells", "interpret"),
                   donate_argnums=(0,))
def pim_exec_padded(state, ops, a, b, o, *, n_cells, interpret=None):
    """Run a lowered NOR program over ``state`` (uint32[n_cells, n_words]),
    n_words a multiple of TILE_W.  Returns the final state.  ``state`` is
    donated (single-use staging buffer on the gate-serial path)."""
    n_words = state.shape[1]
    _check_state_shape("pim_exec_padded", state, n_cells)
    grid = (n_words // TILE_W,)
    return pl.pallas_call(
        _pim_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[pl.BlockSpec((n_cells, TILE_W), lambda i, *_: (0, i))],
            out_specs=pl.BlockSpec((n_cells, TILE_W), lambda i, *_: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct(state.shape, jnp.uint32),
        interpret=interpret_mode(interpret),
    )(ops, a, b, o, state)


def _pim_level_gather_kernel(la_ref, lb_ref, lo_ref, state_ref, out_ref):
    """Legacy levelized kernel for dense ("scan"-alloc) schedules: vector
    gathers and scatters per level, which Mosaic does not lower -- retained
    for ``schedule="dense"`` compatibility, interpret mode only.  Any
    leading plane axis (rows64) batches through the gather/scatter."""
    n_levels = la_ref.shape[0]
    st0 = state_ref[...]
    if n_levels == 0:           # gate-free (passthrough) program
        out_ref[...] = st0
        return

    def body(l, st):
        av = take_cells(st, la_ref[l])            # (..., width, TILE_W)
        bv = take_cells(st, lb_ref[l])
        return at_cells(st, lo_ref[l]).set(
            ~(av | bv), mode="promise_in_bounds", unique_indices=True)

    out_ref[...] = jax.lax.fori_loop(0, n_levels, body, st0)


@functools.partial(jax.jit, static_argnames=("n_cells", "interpret"),
                   donate_argnums=(0,))
def pim_exec_level_padded(state, la, lb, lo, out_idx=None, *, n_cells,
                          interpret=None):
    """Run a levelized NOR schedule over ``state`` (uint32[n_cells,
    n_words] or the planes-leading rows64 form), n_words a multiple of
    TILE_W.  ``la``/``lb``/``lo`` are the LevelSchedule's dense
    int32[n_levels, width] index matrices (padding lanes write distinct
    sink cells, keeping scatter indices unique).  Returns the final state,
    or only the rows in ``out_idx`` (the port cells) when given.
    ``state`` is donated: the caller's buffer is consumed (the padded
    paths materialize it purely as kernel input, so the donation kills the
    defensive copy)."""
    n_words = state.shape[-1]
    _check_state_shape("pim_exec_level_padded", state, n_cells)
    grid = (n_words // TILE_W,)
    block, index_map = _state_block(state, n_cells)
    final = pl.pallas_call(
        _pim_level_gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[pl.BlockSpec(block, index_map)],
            out_specs=pl.BlockSpec(block, index_map),
        ),
        out_shape=jax.ShapeDtypeStruct(state.shape, jnp.uint32),
        interpret=interpret_mode(interpret),
    )(la, lb, lo, state)
    return final if out_idx is None else take_cells(final, out_idx)


@functools.partial(jax.jit, static_argnames=(
    "n_cells", "one_cell", "in_widths", "out_widths", "interpret",
    "planes"))
def pim_exec_level_fused(in_vals, in_idx, la, lb, lo, out_idx, *,
                         n_cells, one_cell, in_widths, out_widths,
                         interpret=None, planes=1):
    """Fully fused levelized Pallas executor (ports of <= 32 cells): the
    row-major <-> column-major bit transposes run on device around the
    kernel, so only (n_ports, n_rows) uint32 values cross the boundary.
    ``planes`` selects the word layout (kernels.plan)."""
    from .ref import assemble_state, pack_columns, unpack_columns
    st = assemble_state(pack_columns(in_vals, in_widths, planes), in_idx,
                        in_vals.shape[1] // (32 * planes),
                        n_cells=n_cells, one_cell=one_cell)
    final = pim_exec_level_padded(st, la, lb, lo, n_cells=n_cells,
                                  interpret=interpret)
    return unpack_columns(take_cells(final, out_idx), out_widths, planes)


@functools.partial(jax.jit,
                   static_argnames=("n_cells", "one_cell", "interpret"))
def pim_exec_level_padded_io(in_rows, in_idx, la, lb, lo, out_idx, *,
                             n_cells, one_cell=None, interpret=None):
    """Levelized Pallas executor with on-device state assembly: ships in
    only the input port rows (uint32[k_in, n_words], planes-leading under
    rows64), materializes the zero state and the folded INIT1 constant
    device-side, and returns only the output port rows."""
    from .ref import assemble_state
    st = assemble_state(in_rows, in_idx, in_rows.shape[-1],
                        n_cells=n_cells, one_cell=one_cell)
    final = pim_exec_level_padded(st, la, lb, lo, n_cells=n_cells,
                                  interpret=interpret)
    return take_cells(final, out_idx)


# --------------------------------------------------------------------------
# slot-schedule kernels (DESIGN.md §9)
# --------------------------------------------------------------------------

def _slot_scan_kernel(la_ref, lb_ref, lo_ref, in_ref, out_ref, *,
                      n_cells, one_cell, k_in, in_base, out_base, k_out,
                      unroll, has_levels=True, planes=1):
    """Scan-form slot kernel: state assembly, the level loop and the output
    band extraction all happen on kernel-resident values.  Writes are
    contiguous band slice updates (no scatter); the operand read remains a
    vector gather, so this kernel is the interpret-mode fast path while
    :func:`_pim_level_kernel` is the hardware-legal form.  ``has_levels``
    is False for gate-free (passthrough) programs, whose index operands are
    dummy 1x1 blocks (gridless pallas rejects 0-sized blocks).  ``planes``
    is the word layout: the rows64 state keeps its leading pair axis as a
    batch dim through every op."""
    n_words = in_ref.shape[-1]
    st = jnp.zeros(plane_shape(planes, n_cells, n_words), jnp.uint32)
    if k_in:                    # inputs are the leading contiguous run
        st = band_update(st, in_ref[...][..., :k_in, :], in_base)
    if one_cell is not None:
        st = at_cells(st, one_cell).set(jnp.uint32(_FULL))
    if has_levels:
        W = la_ref.shape[1]
        lab = jnp.concatenate([la_ref[...], lb_ref[...]], axis=1)
        off = lo_ref[...][:, 0]

        def body(s, idx):
            ab, o = idx
            g = take_cells(s, ab)
            return band_update(s, ~(g[..., :W, :] | g[..., W:, :]), o), None

        st, _ = lax.scan(body, st, (lab, off), unroll=unroll)
    out_ref[...] = band_slice(st, out_base, out_ref.shape[-2])


def _nonempty_levels(la, lb, lo):
    """Replace 0-sized schedule operands (gate-free programs) with dummy
    1x1 blocks; returns (la, lb, lo, has_levels)."""
    if la.shape[0] and la.shape[1]:
        return la, lb, lo, True
    dummy = jnp.zeros((1, 1), jnp.int32)
    return dummy, dummy, dummy, False


def _slots_call(kernel, k_out, n_words, interpret, la, lb, lo,
                in_rows, planes=1):
    """Single whole-array ``pallas_call`` for the scan-form slot kernel.

    Gridless on purpose: the kernel is interpret-only (its operand read is
    a vector gather), and under interpretation every block boundary is a
    real buffer copy -- a word-tiled grid would re-copy the schedule
    operands per tile for no benefit.  The hardware-shaped, word-tiled
    TILE_W grid lives on the static-slice kernel
    (:func:`make_slots_static`), which is the Mosaic-lowerable form."""
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            plane_shape(planes, max(k_out, 1), n_words), jnp.uint32),
        interpret=interpret_mode(interpret),
    )(la, lb, lo, in_rows)


@functools.partial(jax.jit, static_argnames=(
    "n_cells", "one_cell", "in_widths", "out_widths", "in_base", "out_base",
    "unroll", "interpret", "planes"))
def pim_exec_slots_fused(in_vals, in_idx, la, lb, lo, out_idx, *,
                         n_cells, one_cell, in_widths, out_widths,
                         in_base, out_base, unroll=SLOT_UNROLL,
                         interpret=None, planes=1):
    """Fused slot executor, Pallas backend: butterfly bit transposes wrap a
    single scan-form kernel; only (n_ports, n_rows) uint32 values cross the
    host/device boundary.  Requires the slot layout's contiguous input and
    output runs (``in_base``/``out_base``)."""
    n_words = in_vals.shape[1] // (32 * planes)
    packed = pack_values(in_vals, in_widths, planes)
    k_in, k_out = packed.shape[-2], sum(out_widths)
    if not k_in:        # constant-generator program: dummy zero block
        packed = jnp.zeros(plane_shape(planes, 1, n_words), jnp.uint32)
    la, lb, lo, has_levels = _nonempty_levels(la, lb, lo)
    kern = functools.partial(
        _slot_scan_kernel, n_cells=n_cells, one_cell=one_cell,
        k_in=k_in, in_base=in_base if k_in else 0, out_base=out_base,
        k_out=k_out, unroll=unroll, has_levels=has_levels, planes=planes)
    sub = _slots_call(kern, k_out, n_words, interpret, la, lb, lo,
                      packed, planes)
    return unpack_values(sub[..., :k_out, :], out_widths, planes)


@functools.partial(jax.jit, static_argnames=(
    "n_cells", "one_cell", "k_out", "in_base", "out_base", "unroll",
    "interpret"))
def pim_exec_slots_io(in_rows, in_idx, la, lb, lo, out_idx, *,
                      n_cells, one_cell, k_out, in_base, out_base,
                      unroll=SLOT_UNROLL, interpret=None):
    """Slot executor over pre-packed port rows, Pallas backend (arbitrary
    port widths; the word layout is inferred from the input rank)."""
    planes = 1 if in_rows.ndim == 2 else in_rows.shape[0]
    n_words = in_rows.shape[-1]
    k_in = in_rows.shape[-2]
    if not k_in:
        in_rows = jnp.zeros(plane_shape(planes, 1, n_words), jnp.uint32)
    la, lb, lo, has_levels = _nonempty_levels(la, lb, lo)
    kern = functools.partial(
        _slot_scan_kernel, n_cells=n_cells, one_cell=one_cell,
        k_in=k_in, in_base=in_base if k_in else 0, out_base=out_base,
        k_out=k_out, unroll=unroll, has_levels=has_levels, planes=planes)
    sub = _slots_call(kern, k_out, n_words, interpret, la, lb, lo,
                      in_rows, planes)
    return sub[..., :k_out, :]


def _pim_level_kernel(sched, in_widths, out_names):
    """The rewritten levelized kernel: build the static-slice straight-line
    body for a slot schedule.  The returned kernel reads the packed input
    block, reconstructs the initial region by concatenation (inputs are the
    leading run; constants are broadcast rows), unrolls every level into
    ``band = ~(A | B)`` with A/B as static-offset slice concatenations, and
    stores the contiguous output band.  No gather, no scatter, no dynamic
    offset anywhere: every index is a Python constant at trace time, which
    is what makes the body Mosaic-lowerable."""
    reads, out_srcs, n_init = static_plan(sched)
    one_cell = None if sched.one_cell is None else int(sched.one_cell)
    stacked_out = [s for name in out_names for s in out_srcs[name]]

    def kernel(in_ref, out_ref):
        packed = in_ref[...][..., :sum(in_widths), :]
        init_block = build_init_block(packed, n_init, one_cell)
        bands = emit_levels(reads, 0, sched.n_levels, init_block, {})
        sub = read_concat(init_block, bands, stacked_out)
        if sub.shape[-2] < out_ref.shape[-2]:   # k_out == 0 pad block
            pad_shape = sub.shape[:-2] + (
                out_ref.shape[-2] - sub.shape[-2], out_ref.shape[-1])
            sub = jnp.concatenate([sub, jnp.zeros(pad_shape, jnp.uint32)],
                                  axis=-2)
        out_ref[...] = sub

    return kernel


def make_slots_static(sched, in_widths, out_widths, out_names,
                      interpret=None, planes=1):
    """Hardware-legal levelized Pallas executor factory: returns a jitted
    ``run(in_vals) -> out_vals`` wrapping one ``pallas_call`` whose body is
    the fully static-slice form of ``sched`` (see
    :func:`_pim_level_kernel`).  Fused bridges; ports of <= 32 cells.
    Interpret mode pays per-op cost for the unrolled body on CPU -- this
    entry exists for hardware lowering and bit-exactness testing, and is
    benchmarked as its own row.  Callers cache the returned function (the
    kernel closure embeds the whole unrolled program; rebuilding it per
    call would retrace).  ``planes`` is the word layout: under rows64 the
    blocks grow the leading pair axis (still zero dynamic indexing)."""
    kernel = _pim_level_kernel(sched, in_widths, out_names)
    k_out = sum(out_widths)

    def block(k):
        index_map = (lambda i: (0, i)) if planes == 1 else \
            (lambda i: (0, 0, i))
        return pl.BlockSpec(plane_shape(planes, max(k, 1), TILE_W),
                            index_map)

    @jax.jit
    def run(in_vals):
        n_words = in_vals.shape[1] // (32 * planes)
        packed = pack_values(in_vals, in_widths, planes)
        k_in = packed.shape[-2]
        if not k_in:
            packed = jnp.zeros(plane_shape(planes, 1, n_words), jnp.uint32)
        sub = pl.pallas_call(
            kernel,
            grid=(n_words // TILE_W,),
            in_specs=[block(k_in)],
            out_specs=block(k_out),
            out_shape=jax.ShapeDtypeStruct(
                plane_shape(planes, max(k_out, 1), n_words), jnp.uint32),
            interpret=interpret_mode(interpret),
        )(packed)
        return unpack_values(sub[..., :k_out, :], out_widths, planes)

    return run


# --------------------------------------------------------------------------
# verified execution: device-side check-word generation (DESIGN.md §12)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("axis",))
def check_words(block, axis: int):
    """Per-word XOR check fold of an output block over its cell (or port)
    axis -- verified execution's "on-device ECC generation": the fold is
    computed while the result is still device-resident, *before* the
    fault-prone readback, so a host-side refold of the transferred data
    detects any single corrupted bit per word position (two corruptions of
    the same bit position in different cells cancel -- the classic parity
    limit; the sampled oracle spot checks in ``kernels.ops`` backstop it).
    Works on both output representations: fused per-port row values
    ``(n_ports, rows)`` with ``axis=0`` and packed word blocks
    ``(..., k, n_words)`` with ``axis=ndim-2``."""
    return lax.reduce(block, jnp.uint32(0), lax.bitwise_xor, (axis,))
