"""jit'd wrappers around the PIM executor kernels: the compile->execute
pipeline behind every entry point.

Pipeline (DESIGN.md §5, §11): ``Program`` -> :func:`levelize` schedule ->
:class:`~repro.kernels.plan.ExecPlan` (schedule kind x backend x
:class:`~repro.kernels.plan.WordLayout` x mesh/chunking) -> resolved
executor + packed bridges -> kernel -> unpack.  All host-side bridging is
fully vectorized: packing and unpacking move whole ports per numpy call
(one 32-bit limb loop for arbitrarily wide ports), never per cell or per
row.

Execution configuration is an :class:`ExecPlan` (``kernels.plan``):
every public entry point here accepts either a plan or the historical
convenience strings, normalizes them **once** via :func:`plan.as_plan`,
and threads only the plan below that point.  The compiled-program cache,
the pin API and the resolved-executor memo all key on the plan, and the
dense-fallback decision for degenerate slot layouts happens once at plan
resolution -- not per call site.

Scale layer (DESIGN.md §8): :func:`run_program_streaming` tiles arbitrary
row counts into fixed-shape word-aligned chunks and overlaps host packing of
chunk ``k+1`` with device execution of chunk ``k`` (JAX async dispatch);
:func:`row_mesh` + the plan's ``mesh`` shard the packed word axis over
multiple devices with ``jax.shard_map`` (the level loop is elementwise along
words, so sharding needs no communication).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import time
import weakref
from typing import Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.gates import LevelSchedule, levelize
from ..runtime import telemetry
from ..runtime.faults import (DeadlineExceeded, FaultError,  # noqa: F401
                              FaultModel, VerifyPolicy, note_quarantine,
                              record_wear)
from . import slots as kslots
from .plan import (BACKENDS, DEFAULT_LAYOUT, DEFAULT_PLAN, DEFAULT_SCHEDULE,
                   LAYOUTS, ROWS32, ROWS64, SCHEDULES, TILE_W, Backend,
                   ExecPlan, WordLayout, as_plan)
from .pim_exec import (check_words, interpret_mode, make_slots_static,
                       pim_exec_level_fused, pim_exec_level_padded_io,
                       pim_exec_padded, pim_exec_slots_fused,
                       pim_exec_slots_io)
from .ref import (pim_exec_ref, pim_exec_ref_level_fused,
                  pim_exec_ref_level_io)
from .slots import (as_run, pim_exec_ref_slots_fused, pim_exec_ref_slots_io)

_FULL = np.uint32(0xFFFFFFFF)

# Historical tunable names, re-exported from their canonical home on
# kernels.plan (the Backend descriptors read the same values) for callers
# that import them from here.
from .plan import (DEFAULT_CHUNK_ROWS, LEVEL_MAX_WIDTH,  # noqa: F401
                   SLOT_WIDTH)


def make_plan(**kw) -> ExecPlan:
    """Build an :class:`ExecPlan` from convenience keywords
    (``backend=``, ``schedule=``, ``layout=``, ``mesh=``, ``chunk_rows=``,
    or a ready plan via ``plan=``).  The exemplary entry for callers that
    want to name their execution config once and reuse it."""
    return as_plan(kw.pop("plan", None), **kw)


# --------------------------------------------------------------------------
# plan-keyed compiled-program cache (bounded LRU)
# --------------------------------------------------------------------------
#
# Programs are compiled (NOR-lowered to dense arrays, levelized, shipped to
# the device) once per (*structure*, *plan*): the cache key pairs a content
# hash of the instruction stream + ports with the plan's ``compile_key`` --
# the plan fields that determine compiled artifacts (schedule kind, word
# layout, allocator widths, static segmentation).  Structurally identical
# programs under the same plan share compiled artifacts, and -- unlike an
# id()-keyed cache -- a dead program's recycled id can never poison the
# entry of a new one.  Content keys are memoized per live instance via a
# WeakKeyDictionary.
#
# The cache is a bounded LRU: each entry pins device buffers (schedule index
# matrices, port gather vectors), so an unbounded dict would leak device
# memory under long-running serving that keeps minting new program
# structures.  Eviction is safe -- an evicted structure is simply recompiled
# on next use, bit-identically (compilation is a pure function of the key).

_COMPILED_CAP = 64

# Fused compound programs (expression chains, GEMV stages) are often orders
# of magnitude larger than single-op programs, so the cache is bounded by
# total *schedule weight* -- sum over entries of levels x slot width, a
# proxy for the device buffers an entry pins -- as well as by entry count.
# Without the weight bound, one fused GEMV whose entry counts as "1" could
# silently displace the entire hot set of small programs.
_COMPILED_WEIGHT_CAP = 8 << 20

# Weight-triggered eviction never shrinks the cache below this many
# unpinned entries: when a single entry's weight exceeds the whole cap, the
# most recently used entries (including that entry) stay resident instead
# of thrashing on every call.
_COMPILED_MIN_RESIDENT = 4

_key_memo: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_compiled: "collections.OrderedDict[tuple, _Compiled]" = \
    collections.OrderedDict()

# Serial-order modeled costs for paths that never build a compiled entry
# (the numpy oracle).  Weak-keyed so it does not pin programs, and kept
# out of ``_compiled`` so oracle runs cannot churn the weighted LRU.
_serial_model_memo: "weakref.WeakKeyDictionary" = \
    weakref.WeakKeyDictionary()


def _serial_model(program) -> "telemetry.ModeledCost":
    m = _serial_model_memo.get(program)
    if m is None:
        m = telemetry.COST_MODEL.program_cost(program.cost())
        _serial_model_memo[program] = m
    return m

#: Compiled-program LRU lifecycle counters (``pim.cache.hits`` /
#: ``misses`` / ``evictions`` on the global registry) -- what serving's
#: periodic stats lines derive the cache hit rate from.  The disk tier
#: (``runtime.artifact_cache``) adds ``disk_hits``/``disk_misses``/
#: ``disk_writes``/``disk_errors``/``disk_evictions`` to the same group,
#: ``levelized`` below counts *fresh* levelizations -- the signal a
#: warm-started replica drives to zero -- and ``aot_failed`` counts the
#: AOT-tier fallbacks to plain jit in :func:`_aot_call`.
_CACHE = telemetry.REGISTRY.group("pim.cache")

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _count_compile(event: str, secs: float, **_kw) -> None:
    """``pim.jit.compiles`` / ``pim.jit.compile_s``: every XLA backend
    compile in the process (a persistent-cache hit compiles nothing), so a
    compile inside a timed window shows in that window's counter change."""
    if event == _BACKEND_COMPILE_EVENT:
        telemetry.REGISTRY.add_many({"pim.jit.compiles": 1,
                                     "pim.jit.compile_s": secs})


jax.monitoring.register_event_duration_secs_listener(_count_compile)

# --------------------------------------------------------------------------
# optional on-disk artifact tier (DESIGN.md §16)
# --------------------------------------------------------------------------
#
# When installed, the disk cache sits *below* the in-memory LRU: an
# in-memory schedule miss first tries ``load_schedule`` before paying
# levelize, every fresh levelize writes through, and the levelized-
# executor dispatcher AOT-compiles + serializes XLA executables per call
# signature so a later process deserializes (~20ms) instead of tracing and
# compiling (~700ms on the tracked fp16-add row).

_artifacts = None       # Optional[runtime.artifact_cache.ArtifactCache]

# Program build provenance -- how ``core.pim_numerics`` constructed each
# program (the ``program_for``/``fused_program_for`` argument triple).
# Written into on-disk schedule headers so ``ArtifactCache.warm()`` can
# rebuild the program in a fresh process and verify its content hash.
# Weak-keyed: provenance never pins a program alive.
_provenance: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def set_artifact_cache(cache) -> None:
    """Install (or, with None, remove) the process-wide on-disk artifact
    cache consulted by the compiled-program machinery.

    Schedules the in-memory LRU already holds were levelized before this
    tier existed and would never pass through it (later calls hit memory),
    so installing a cache writes them through at once; otherwise a cache
    directory installed mid-process silently lacks every program the
    process had already run."""
    global _artifacts
    _artifacts = cache
    if cache is None:
        return
    programs = {key: prog for prog, key in list(_key_memo.items())}
    for (content, ck), entry in list(_compiled.items()):
        prog = programs.get(content)
        for alloc, sched in entry.scheds.items():
            cache.store_schedule(
                content, ck, alloc, sched,
                provenance=None if prog is None else provenance_of(prog))


def artifact_cache():
    """The installed on-disk artifact tier, or None."""
    return _artifacts


def note_provenance(program, tag: tuple) -> None:
    """Record how ``program`` was built (a plain-data tag the artifact
    cache persists and ``warm()`` replays)."""
    try:
        _provenance.setdefault(program, tag)
    except TypeError:
        pass


def provenance_of(program):
    return _provenance.get(program)


def clear_compiled_cache() -> int:
    """Drop every *unpinned* compiled-program entry (tests use this to
    force cold in-memory state against a warm disk cache); returns the
    number dropped."""
    victims = [k for k in _compiled if k not in _pinned]
    for k in victims:
        del _compiled[k]
    return len(victims)

# Pinned entries (cache key -> pin refcount) are exempt from LRU
# eviction: the batched serving runtime pins its hot working set so mixed
# traffic that keeps minting cold program structures can never churn a hot
# program's schedule + device buffers out of the cache.  Pins are
# refcounted (several pin caches may share a program); a fully pinned
# cache may transiently exceed the cap -- unpinned entries still evict.
_pinned: Dict[tuple, int] = {}


def _evict_over_cap(protect: Optional[tuple] = None) -> None:
    """Drop least-recently-used *unpinned* entries while over either cap:
    entry count (``_COMPILED_CAP``) or total schedule weight
    (``_COMPILED_WEIGHT_CAP``, sum of per-entry levels x slot width).

    ``protect`` exempts one key -- the entry a caller just created or
    touched.  Without it, a cache whose cap is saturated by pinned entries
    would evict the entry it is in the middle of handing out: the caller
    would keep building artifacts on an orphaned object that the next
    lookup (or a later ``pin_program``) silently replaces, so the work is
    lost and a pin can land on an empty twin.  (The pinned-vs-cap audit of
    ISSUE 5; regression-tested in tests/test_plan.py.)

    Weight-only pressure (count under cap, weight over) stops once at most
    ``_COMPILED_MIN_RESIDENT`` unpinned entries would remain, so a single
    oversized fused program can never purge the whole hot set -- and stays
    resident itself rather than recompiling on every call."""
    weight = sum(e.weight for e in _compiled.values())
    for key in list(_compiled):
        over_n = len(_compiled) > _COMPILED_CAP
        over_w = weight > _COMPILED_WEIGHT_CAP
        if not (over_n or over_w):
            break
        if key in _pinned or key == protect:
            continue
        if not over_n:      # weight pressure only: respect the floor
            unpinned = sum(1 for k in _compiled
                           if k not in _pinned and k != protect)
            if unpinned <= _COMPILED_MIN_RESIDENT:
                break
        weight -= _compiled[key].weight
        del _compiled[key]
        _CACHE.add("evictions")


def set_compiled_cache_cap(cap: int, weight_cap: Optional[int] = None) -> int:
    """Set the compiled-program LRU capacity (entries) and, optionally, the
    total schedule-weight cap (levels x slots summed over entries); returns
    the old entry cap.  Shrinking evicts least-recently-used unpinned
    entries immediately; pinned entries always survive, even when the new
    cap is smaller than the pinned count (the cache then runs over cap
    until pins release)."""
    global _COMPILED_CAP, _COMPILED_WEIGHT_CAP
    if cap < 1:
        raise ValueError(f"cache cap must be >= 1, got {cap}")
    old, _COMPILED_CAP = _COMPILED_CAP, cap
    if weight_cap is not None:
        if weight_cap < 1:
            raise ValueError(f"weight cap must be >= 1, got {weight_cap}")
        _COMPILED_WEIGHT_CAP = weight_cap
    _evict_over_cap()
    return old


def cache_key(program, plan: Optional[ExecPlan] = None) -> tuple:
    """The compiled-program cache key: (program content hash,
    plan.compile_key).  The plan defaults to :data:`plan.DEFAULT_PLAN`."""
    plan = DEFAULT_PLAN if plan is None else plan
    return (content_key(program), plan.compile_key)


def pin_program(program, plan: Optional[ExecPlan] = None) -> tuple:
    """Pin ``program``'s compiled-cache entry (under ``plan``, default the
    default plan) against LRU eviction; returns the cache key (the token
    :func:`unpin_program` takes).  Creates the entry if the program was
    never compiled, so artifacts built later land in the pinned slot.
    Pins nest (refcounted)."""
    key = cache_key(program, plan)
    if key not in _compiled:
        _compiled[key] = _Compiled()
        _CACHE.add("misses")     # a pin-created entry is a cold program
        _evict_over_cap(protect=key)
    _pinned[key] = _pinned.get(key, 0) + 1
    return key


def unpin_program(key: tuple) -> bool:
    """Release one pin on ``key``; returns True while pins remain.  The
    entry stays cached but becomes evictable again once fully unpinned."""
    n = _pinned.get(key, 0)
    if n > 1:
        _pinned[key] = n - 1
        return True
    _pinned.pop(key, None)
    _evict_over_cap()
    return False


def content_key(program) -> bytes:
    """Structural hash of a Program (instrs, ports, cells, schedule hints)."""
    try:
        return _key_memo[program]
    except (KeyError, TypeError):
        pass
    h = hashlib.blake2b(digest_size=16)
    h.update(int(program.n_cells).to_bytes(8, "little"))
    flat = []
    for ins in program.instrs:
        flat.extend((int(ins.op), len(ins.ins)))
        flat.extend(int(c) for c in ins.ins)
        flat.extend(int(c) for c in ins.outs)
        flat.append(-1)
    h.update(np.asarray(flat, np.int64).tobytes())
    for name in sorted(program.ports):
        h.update(name.encode())
        h.update(b"\x00i" if name in program.in_ports else b"\x00o")
        h.update(np.asarray(program.ports[name], np.int64).tobytes())
    if program.parallel_steps is not None:
        for idxs in program.parallel_steps:
            h.update(np.asarray(list(idxs) + [-1], np.int64).tobytes())
    key = h.digest()
    try:
        _key_memo[program] = key
    except TypeError:
        pass
    return key


def _stacked_cells(cell_lists) -> np.ndarray:
    """Concatenate per-port cell lists into one int32 index vector."""
    if not cell_lists:
        return np.zeros(0, np.int32)
    return np.concatenate(
        [np.asarray(c, np.int64) for c in cell_lists]).astype(np.int32)


def output_names(ports_owner) -> list:
    """The port names ``run_program`` returns, sorted: the declared output
    ports, falling back to *every* port for direction-less programs.

    Works on anything with ``ports`` and (optionally) ``out_ports`` --
    ``Program``, ``LevelSchedule`` -- and is the single source of truth for
    that fallback, so all executor backends agree.
    """
    return sorted(getattr(ports_owner, "out_ports", None)
                  or ports_owner.ports)


# --------------------------------------------------------------------------
# per-(structure, plan) compilation artifacts
# --------------------------------------------------------------------------

@dataclasses.dataclass
class _Resolved:
    """One plan+program+input-set binding, resolved exactly once: the
    *effective* schedule kind (the dense fallback for degenerate slot
    layouts is decided here, not per call site), the device-resident
    schedule operands, the bridge index vectors, and the static widths the
    executors take as compile-time constants."""
    kind: str                        # effective schedule after fallback
    sched: LevelSchedule
    la: object
    lb: object
    lo: object
    out_idx: object
    names: list
    out_base: Optional[int]
    in_idx: object
    in_base: Optional[int]
    one_cell: Optional[int]
    in_widths: tuple
    out_widths: tuple
    k_out: int
    fused_ok: bool                   # every port fits a 32-bit transpose
    use_static: bool                 # the straight-line emission applies
    model: Optional["telemetry.ModeledCost"] = None  # analytical cost gauge


@dataclasses.dataclass
class _Compiled:
    """Lazily-populated compilation artifacts for one (program structure,
    plan compile-key) cache entry: the plan's own levelized schedule, the
    dense-fallback artifacts for degenerate slot layouts, device index
    buffers, resolved executor bindings and the static straight-line
    chains."""
    arrays: Optional[tuple] = None              # (ops, a, b, o, n_cells)
    scheds: Dict[str, LevelSchedule] = dataclasses.field(default_factory=dict)
    devs: Dict[str, tuple] = dataclasses.field(default_factory=dict)
    in_idx: Dict[tuple, tuple] = dataclasses.field(default_factory=dict)
    resolved: Dict[tuple, _Resolved] = dataclasses.field(default_factory=dict)
    static_chain: Dict[tuple, Callable] = dataclasses.field(
        default_factory=dict)
    serial_model: Optional["telemetry.ModeledCost"] = None
    # AOT-compiled executables keyed by call-signature memo string
    # (executor name + arg shapes/dtypes + static kwargs).  Populated from
    # the disk tier (deserialize) or by lower().compile() write-through;
    # ``aot_failed`` remembers signatures XLA could not AOT so the jit
    # path is used without re-attempting every call.
    aot: Dict[str, Callable] = dataclasses.field(default_factory=dict)
    aot_failed: set = dataclasses.field(default_factory=set)

    @property
    def weight(self) -> int:
        """Schedule size this entry holds resident: levels x slot width,
        summed over its levelized allocations -- the proxy the LRU's
        weight cap (``_COMPILED_WEIGHT_CAP``) bounds."""
        return sum(int(s.n_levels) * int(s.width)
                   for s in self.scheds.values())

    def get_arrays(self, program):
        if self.arrays is None:
            self.arrays = program.to_arrays()
        return self.arrays

    def get_serial_model(self, program) -> "telemetry.ModeledCost":
        """Modeled cost of the *gate-serial* execution order (numpy oracle
        and un-levelized executors), memoized per cache entry."""
        if self.serial_model is None:
            self.serial_model = telemetry.COST_MODEL.program_cost(
                program.cost())
        return self.serial_model

    def get_schedule(self, program, plan: ExecPlan, kind: Optional[str] = None
                     ) -> LevelSchedule:
        kind = plan.schedule if kind is None else kind
        alloc = "dense" if kind == "dense" else "slots"
        s = self.scheds.get(alloc)
        if s is None:
            content = content_key(program)
            if _artifacts is not None:
                s = _artifacts.load_schedule(content, plan, alloc)
                if s is not None and \
                        set(s.ports) != set(program.ports):
                    # key-collision / stale-entry guard: never trust a
                    # disk schedule whose ports disagree with the program
                    _CACHE.add("disk_errors")
                    s = None
            if s is None:
                if alloc == "dense":
                    s = levelize(program,
                                 max_width=plan.backend.level_max_width)
                else:
                    s = levelize(program, alloc="slots",
                                 max_width=plan.backend.slot_width)
                _CACHE.add("levelized")
                if _artifacts is not None:
                    _artifacts.store_schedule(
                        content, plan, alloc, s,
                        provenance=provenance_of(program))
            self.scheds[alloc] = s
        return s

    def get_sched_dev(self, program, plan: ExecPlan, kind: str):
        alloc = "dense" if kind == "dense" else "slots"
        dev = self.devs.get(alloc)
        if dev is None:
            s = self.get_schedule(program, plan, kind)
            names = output_names(s)
            cells = _stacked_cells([s.ports[n] for n in names])
            dev = (jnp.asarray(s.a), jnp.asarray(s.b), jnp.asarray(s.out),
                   jnp.asarray(cells), names,
                   as_run(cells) if alloc == "slots" else None)
            self.devs[alloc] = dev
        return dev

    def get_in_idx(self, program, plan: ExecPlan, kind: str, in_names):
        alloc = "dense" if kind == "dense" else "slots"
        key = (alloc, tuple(in_names))
        if key not in self.in_idx:
            s = self.get_schedule(program, plan, kind)
            cells = _stacked_cells([s.pack_cells(n) for n in in_names])
            self.in_idx[key] = (jnp.asarray(cells), as_run(cells))
        return self.in_idx[key]

    def resolve(self, program, plan: ExecPlan, in_names: tuple) -> _Resolved:
        """Bind ``plan`` to this program for one input-name set: pick the
        effective schedule (dense fallback for layouts the slot executors
        cannot assemble), materialize the device operands, and freeze the
        static widths.  Memoized -- the per-call dispatcher only reads."""
        memo_key = (plan.schedule, plan.backend.name, plan.mesh is None,
                    in_names)
        r = self.resolved.get(memo_key)
        if r is not None:
            return r
        kind = plan.schedule
        sched = self.get_schedule(program, plan, kind)
        la, lb, lo, out_idx, names, out_base = \
            self.get_sched_dev(program, plan, kind)
        in_idx, in_base = self.get_in_idx(program, plan, kind, in_names)
        k_out = sum(len(sched.ports[n]) for n in names)
        slots_ok = (kind != "dense" and out_base is not None and k_out > 0)
        if plan.backend.name == "pallas" and slots_ok and in_base is None:
            slots_ok = False    # aliased input ports: slice assembly
            #                     impossible, use the dense kernels
        if not slots_ok and kind != "dense":
            # degenerate program for the slot layout: dense executors,
            # which handle every schedule shape
            kind = "dense"
            sched = self.get_schedule(program, plan, kind)
            la, lb, lo, out_idx, names, out_base = \
                self.get_sched_dev(program, plan, kind)
            in_idx, in_base = self.get_in_idx(program, plan, kind, in_names)
        in_widths = tuple(len(sched.pack_cells(n)) for n in in_names)
        out_widths = tuple(len(sched.ports[n]) for n in names)
        r = _Resolved(
            kind=kind, sched=sched, la=la, lb=lb, lo=lo, out_idx=out_idx,
            names=names, out_base=out_base, in_idx=in_idx, in_base=in_base,
            one_cell=None if sched.one_cell is None else int(sched.one_cell),
            in_widths=in_widths, out_widths=out_widths,
            k_out=sum(out_widths),
            fused_ok=bool(in_names) and
            max(in_widths + out_widths, default=0) <= 32,
            use_static=(plan.schedule == "slots-static" and slots_ok
                        and plan.mesh is None),
            model=telemetry.COST_MODEL.schedule_cost(sched))
        self.resolved[memo_key] = r
        return r

    def get_static_chain(self, program, plan: ExecPlan, in_names, fused,
                         in_widths, out_widths):
        key = (tuple(in_names), fused, in_widths, out_widths,
               plan.layout.planes)
        if key not in self.static_chain:
            s = self.get_schedule(program, plan, "slots")
            cells = _stacked_cells([s.pack_cells(n) for n in in_names])
            self.static_chain[key] = kslots.build_static_chain(
                s, in_widths, out_widths, output_names(s), cells,
                seg_levels=plan.backend.seg_levels, fused=fused,
                planes=plan.layout.planes)
        return self.static_chain[key]

    def get_static_pallas(self, program, plan: ExecPlan, in_names,
                          in_widths, out_widths):
        key = ("pallas", tuple(in_names), in_widths, out_widths,
               plan.layout.planes)
        if key not in self.static_chain:
            s = self.get_schedule(program, plan, "slots")
            self.static_chain[key] = make_slots_static(
                s, in_widths, out_widths, output_names(s),
                planes=plan.layout.planes)
        return self.static_chain[key]


def compiled(program, plan: Optional[ExecPlan] = None) -> _Compiled:
    key = cache_key(program, plan)
    entry = _compiled.get(key)
    if entry is None:
        entry = _compiled[key] = _Compiled()
        _CACHE.add("misses")
    else:
        _compiled.move_to_end(key)
        _CACHE.add("hits")
    _evict_over_cap(protect=key)
    return entry


def is_compiled(program, plan=None) -> bool:
    """True when the compiled-program cache already holds ``program``'s
    lowered schedule artifacts for ``plan`` -- i.e. the next execution
    pays no levelize/lowering cost.  ``plan`` accepts an ExecPlan or a
    schedule-name string (the historical signature).  A pure query: it
    never creates an entry and never touches LRU order (serving uses it to
    report honest ``cached`` flags without perturbing eviction)."""
    if isinstance(plan, str):
        plan = as_plan(schedule=plan)
    entry = _compiled.get(cache_key(program, plan))
    if entry is None:
        return False
    kind = (plan or DEFAULT_PLAN).schedule
    return ("dense" if kind == "dense" else "slots") in entry.devs


def program_arrays(program):
    """(ops, a, b, out, n_cells) of the NOR-lowered program, cached by
    structural content hash (under the default plan's cache entry)."""
    return compiled(program).get_arrays(program)


def program_schedule(program, plan=None) -> LevelSchedule:
    """The levelized execution schedule of ``program`` (slot or dense
    layout per the plan's schedule kind), cached per (structure, plan).
    ``plan`` accepts an ExecPlan or a schedule-name string."""
    if isinstance(plan, str):
        plan = as_plan(schedule=plan)
    plan = DEFAULT_PLAN if plan is None else plan
    return compiled(program, plan).get_schedule(program, plan)


# --------------------------------------------------------------------------
# row-major <-> packed-column bridges (fully vectorized)
# --------------------------------------------------------------------------

def _ports_of(ports_or_program) -> Dict[str, list]:
    return getattr(ports_or_program, "ports", ports_or_program)


def _value_limbs(vals, n_limbs: int, pad_rows: int) -> np.ndarray:
    """uint32[pad_rows, n_limbs] little-endian 32-bit limbs of per-row
    integers.  Wide ports (> 64 bits) go through an object-dtype array so
    arbitrary-precision values split without any per-row Python loop."""
    vals = np.asarray(vals)
    n = len(vals)
    limbs = np.zeros((pad_rows, n_limbs), np.uint32)
    if n_limbs <= 2 and vals.dtype != object:
        v = np.zeros(pad_rows, np.uint64)
        v[:n] = vals.astype(np.uint64)
        for j in range(n_limbs):
            limbs[:, j] = ((v >> np.uint64(32 * j))
                           & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    else:
        v = np.zeros(pad_rows, object)
        v[:n] = vals.astype(object)
        for j in range(n_limbs):
            limbs[:, j] = ((v >> (32 * j)) & 0xFFFFFFFF).astype(np.uint32)
    return limbs


def _le_bytes(arr: np.ndarray) -> np.ndarray:
    """Little-endian uint8 view of an integer array (copy only on BE hosts),
    so bit k of element e is bit k%8 of byte e*itemsize + k//8."""
    return np.ascontiguousarray(arr).astype(
        arr.dtype.newbyteorder("<"), copy=False).view(np.uint8)


def _pack_port_words(vals, nc: int, n_words: int,
                     layout: WordLayout = ROWS32) -> np.ndarray:
    """Packed words of one port's per-row integers: uint32[nc, n_words]
    under rows32 (bit w of word i is row 32*i + w), or the planes-leading
    uint32[planes, nc, n_words] under rows64 (plane h of word i covers
    rows ``64*i + 32*h + w`` -- the little-endian uint64 halves)."""
    n_limbs = (nc + 31) // 32
    n32 = n_words * layout.planes
    limbs = _value_limbs(vals, n_limbs, n32 * 32)
    # [pad_rows, 32 * n_limbs] -> cell-major [nc, pad_rows] bit matrix
    bits = np.unpackbits(_le_bytes(limbs), axis=1, bitorder="little")
    cols = np.ascontiguousarray(bits.T[:nc])
    words = np.packbits(cols.reshape(nc, n32, 32), axis=2,
                        bitorder="little")                    # [nc, n32, 4]
    w32 = words.reshape(nc, -1).view("<u4")
    if layout.planes == 1:
        return w32
    # uint32 word 2i+h of rows32 is plane h of logical word i
    return np.ascontiguousarray(
        np.moveaxis(w32.reshape(nc, n_words, layout.planes), -1, 0))


def _sub_to_rows32(sub: np.ndarray) -> np.ndarray:
    """Collapse a planes-leading packed block back to the rows32 word
    order: (planes, k, n_words) -> (k, n_words * planes)."""
    if sub.ndim == 2:
        return sub
    planes, k, n_words = sub.shape
    return np.ascontiguousarray(
        np.moveaxis(sub, 0, -1).reshape(k, n_words * planes))


def pack_rows(values: Dict[str, np.ndarray], ports, n_rows: int,
              n_cells: int, one_cell: Optional[int] = None,
              pad_to: int = TILE_W,
              layout: WordLayout = ROWS32) -> np.ndarray:
    """Pack per-row port integers into column-major word state --
    uint32[n_cells, n_words] (rows32; bit w of state[c, i] = cell c of row
    32*i + w) or the planes-leading uint32[planes, n_cells, n_words]
    (rows64).  ``ports`` is a name -> cell-list mapping (or any object with
    a ``.ports`` attribute).  ``one_cell``, when given, is filled with ones
    (the LevelSchedule's folded INIT1 constant).

    Bit transposition runs entirely in C (unpackbits/packbits on
    little-endian byte views); the only Python loop is over 32-bit limbs of
    arbitrarily wide ports.
    """
    ports = _ports_of(ports)
    n_words = layout.n_words(n_rows, pad_to)
    state = np.zeros(layout.state_shape(n_cells, n_words), np.uint32)
    if one_cell is not None:
        state[..., one_cell, :] = _FULL
    for name, vals in values.items():
        cells = np.asarray(ports[name], np.int64)
        state[..., cells, :] = _pack_port_words(vals, len(cells), n_words,
                                                layout)
    return state


def unpack_rows(state: np.ndarray, ports, n_rows: int,
                names: Optional[Iterable[str]] = None
                ) -> Dict[str, np.ndarray]:
    """Inverse of :func:`pack_rows` (row-major ints); ``names`` restricts
    which ports are unpacked (default: all).  The word layout is inferred
    from the state rank.  Ports wider than 63 cells come back as object
    arrays of Python ints.

    ``state`` may be a device (jnp) array: the port rows are gathered with
    one indexed read and transferred once.
    """
    ports = _ports_of(ports)
    names = list(ports if names is None else names)
    all_cells = np.concatenate(
        [np.asarray(ports[n], np.int64) for n in names]) if names else \
        np.zeros(0, np.int64)
    sub = np.asarray(state[all_cells] if state.ndim == 2
                     else state[:, all_cells])   # one gather + host transfer
    return _unpack_sub(sub, [(n, len(ports[n])) for n in names], n_rows)


def _unpack_sub(sub: np.ndarray, name_widths, n_rows: int
                ) -> Dict[str, np.ndarray]:
    """Unpack pre-gathered port rows (stacked in ``name_widths`` order;
    rows32 2-D or planes-leading 3-D)."""
    sub = _sub_to_rows32(np.asarray(sub))
    out = {}
    off = 0
    for name, nc in name_widths:
        w = sub[off:off + nc]                                  # [nc, n_words]
        off += nc
        n_limbs = (nc + 31) // 32
        # word bits -> row-major bit matrix [n_rows, nc] -> limb matrix
        bits = np.unpackbits(_le_bytes(w), axis=1,
                             bitorder="little")[:, :n_rows]
        by = np.packbits(np.ascontiguousarray(bits.T), axis=1,
                         bitorder="little")                # [n_rows, ceil/8]
        if by.shape[1] != 4 * n_limbs:
            pad = np.zeros((n_rows, 4 * n_limbs), np.uint8)
            pad[:, :by.shape[1]] = by
            by = pad
        limbs = by.view("<u4")                             # [n_rows, n_limbs]
        if nc > 63:
            acc = np.zeros(n_rows, object)
            for j in range(n_limbs):
                acc |= limbs[:, j].astype(object) << (32 * j)
            out[name] = acc
        else:
            acc = limbs[:, 0].astype(np.uint64)
            if n_limbs > 1:
                acc |= limbs[:, 1].astype(np.uint64) << np.uint64(32)
            out[name] = acc
    return out


# --------------------------------------------------------------------------
# multi-device row sharding (word axis)
# --------------------------------------------------------------------------
#
# The packed word axis is embarrassingly parallel: every level executes
# ``out[cells] <- ~(a[cells] | b[cells])`` elementwise along words, and the
# schedule's index operands are word-invariant.  Sharding is therefore pure
# data parallelism -- input port rows split along words, index matrices
# replicate, output port rows split along words; no collective ever runs.

@functools.lru_cache(maxsize=None)
def row_mesh(n_devices: Optional[int] = None) -> Optional[Mesh]:
    """1-D device mesh over the packed word (row-block) axis, or ``None``
    when only one device is available / requested (the unsharded path).
    Run CPU hosts with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    to exercise N-way sharding without accelerators."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else min(n_devices, len(devs))
    if n <= 1:
        return None
    return Mesh(np.array(devs[:n]), ("rows",))


# Every levelized executor entry point shares one signature --
# (in_block, in_idx, la, lb, lo, out_idx) -- with the data block sharded
# along its trailing word/row axis and the schedule operands replicated.
# The data block is rank 2 (fused values, rows32 port rows) or rank 3
# (rows64 port rows with the leading plane axis); specs follow the rank.

def _shard_specs(data_rank: int) -> Tuple[tuple, P]:
    data = P(*([None] * (data_rank - 1) + ["rows"]))
    return ((data, P(None), P(None, None), P(None, None), P(None, None),
             P(None)), data)


# Bounded like _compiled, and for the same reason: each wrapper pins
# compiled XLA executables keyed by per-program statics, so long-running
# serving that keeps minting program structures must evict here too.
_SHARD_CACHE_CAP = 64
_shard_cache: "collections.OrderedDict[tuple, Callable]" = \
    collections.OrderedDict()


def _sharded_exec(fn, mesh: Mesh, check_vma: bool, data_rank: int = 2,
                  **static) -> Callable:
    """``jax.jit(jax.shard_map(fn))`` over the rank-matched specs, cached
    per (executor, mesh, statics) so each chunk shape compiles once.
    Pallas calls have no varying-axes rule, hence ``check_vma=False``
    there."""
    key = (fn, mesh, check_vma, data_rank, tuple(sorted(static.items())))
    wrapped = _shard_cache.get(key)
    if wrapped is None:
        inner = functools.partial(fn, **static)
        in_specs, out_spec = _shard_specs(data_rank)
        wrapped = jax.jit(jax.shard_map(
            inner, mesh=mesh, in_specs=in_specs,
            out_specs=out_spec, check_vma=check_vma))
        _shard_cache[key] = wrapped
        while len(_shard_cache) > _SHARD_CACHE_CAP:
            _shard_cache.popitem(last=False)
    else:
        _shard_cache.move_to_end(key)
    return wrapped


def _place_rows(block: np.ndarray, mesh: Mesh):
    """Put a host data block straight onto the row mesh, split along its
    trailing word/row axis: each device receives only its own shard
    (``jnp.asarray`` would land the whole operand on one device first)."""
    return jax.device_put(block,
                          NamedSharding(mesh, _shard_specs(block.ndim)[1]))


def _refuse_interpret_only(kernel: str) -> None:
    """On a TPU, refuse a plan whose Pallas kernel runs only in interpret
    mode (its operand read is a vector gather that Mosaic does not lower):
    such a plan raises instead of running interpreted without saying so."""
    if not interpret_mode():
        raise ValueError(
            f"backend='pallas' reaches the {kernel} kernel, which runs only "
            "in Pallas interpret mode, never on a TPU; use "
            "schedule='slots-static' (unsharded, ports of <= 32 cells, "
            "inputs from cell 0), levelized=False, or backend='ref'")


# --------------------------------------------------------------------------
# fault-tolerant execution: inject -> detect -> retry -> remap (DESIGN §12,
# §14)
# --------------------------------------------------------------------------
#
# The plan's FaultModel corrupts each chunk's *output readback* (the
# layout-polymorphic post-level hook: transient per-level flips plus the
# persistent dead rows / stuck word columns of the physical span the chunk
# landed on), and its VerifyPolicy turns on detection: a per-word XOR check
# plane emitted *on the device* right behind the executor
# (``pim_exec.check_words`` -- the parity real hardware would generate in
# the array), refolded on the host only after injection -- any single
# corrupted bit per word position mismatches -- plus amortized numpy-oracle
# spot checks.  On mismatch the chunk retries with exponential backoff
# (transients re-roll per attempt); persistent failures re-home the chunk
# onto a spare physical span that the simulated BIST media scan certifies
# clean (abandoned spans go to runtime.faults' quarantine for the
# background scrubber; every dispatch attempt books endurance wear there
# too).  All of it wraps ``_dispatch_levelized`` from the outside -- both
# the row-value form and the packed-domain stage form behind
# ``dispatch_packed`` -- so every schedule kind x word layout x backend x
# output representation inherits the machinery and the compiled artifacts
# stay byte-identical (plan.compile_key excludes faults/verify).

#: Cumulative health counters (faults_injected/detected/corrected,
#: retries, remapped_rows, spot_checks, spot_mismatches) -- a
#: Counter-shaped view over the global telemetry registry's
#: ``pim.health.*`` names, so executor threads and the media scrubber
#: increment under one lock (the bare ``Counter`` this used to be lost
#: concurrent updates: ``c[k] += 1`` is a get-then-set pair).  Hot sites
#: use the atomic :meth:`~repro.runtime.telemetry.CounterGroup.add`;
#: :func:`drain_health` snapshots-and-resets (the serving runtime drains
#: per batch into its Stats).
HEALTH: "telemetry.CounterGroup" = telemetry.REGISTRY.group("pim.health")


def drain_health() -> dict:
    """Snapshot and reset :data:`HEALTH`; returns the non-zero counters.
    (Compatibility shim over ``HEALTH.drain()`` -- the historical API.)"""
    return HEALTH.drain()


class _Corrupt(Exception):
    """Internal: a chunk's verification failed (check-word mismatch or
    oracle spot-check miss); drives the retry loop, never escapes it."""


def _check_deadline(deadline: Optional[float]) -> None:
    """Raise :class:`DeadlineExceeded` when the absolute ``time.monotonic``
    deadline has passed (checked at dispatch and between chunks)."""
    if deadline is not None and time.monotonic() > deadline:
        raise DeadlineExceeded("deadline exceeded between chunks")


def _state_span(plan: ExecPlan, rows: int) -> int:
    """Physical rows covered by one chunk's packed state (incl. word/tile
    padding) -- the span the media scan certifies and the injectors
    corrupt; mirrors ``_dispatch_levelized``'s word-count computation."""
    shards = 1 if plan.mesh is None else plan.mesh.devices.size
    n_words = plan.layout.n_words(rows, plan.backend.pad_to * shards)
    return n_words * 32 * plan.layout.planes


def _chunk_salt(pkey: bytes, start: int) -> int:
    """Deterministic per-(program, chunk) transient-sampling salt."""
    return (int.from_bytes(pkey[:8], "little")
            ^ (start * 0x9E3779B97F4A7C15)) & ((1 << 64) - 1)


@dataclasses.dataclass
class _FaultCtx:
    """One dispatch attempt's injection + verification context, threaded
    into ``_dispatch_levelized``; the finalize closures call the
    ``process_*`` hook matching their output representation."""
    faults: Optional[FaultModel]
    verify: Optional[VerifyPolicy]
    row_base: int
    salt: int
    attempt: int

    def _checked(self, clean_chk, data, axis: int, injected: int):
        if injected:
            HEALTH.add("faults_injected", injected)
        # with no FaultModel nothing can have mutated the readback, so the
        # refold-and-compare is a guaranteed no-op: the clean fold above
        # models the hardware's parity generation cost, the compare only
        # runs when there is simulated media to distrust
        if clean_chk is not None and self.faults is not None:
            if not np.array_equal(np.bitwise_xor.reduce(data, axis=axis),
                                  clean_chk):
                HEALTH.add("faults_detected")
                raise _Corrupt("check-word mismatch")
        return data

    def process_values(self, o: np.ndarray, out_widths, n_levels: int,
                       clean_chk: Optional[np.ndarray]) -> np.ndarray:
        """Fused fast path: ``o`` is uint32[n_ports, padded_rows]."""
        if self.faults is not None and self.verify is not None \
                and clean_chk is None:
            clean_chk = np.bitwise_xor.reduce(o, axis=0)  # clean-copy fold
        injected = 0
        if self.faults is not None:
            o, injected = self.faults.inject_values(
                o, out_widths, row_base=self.row_base, salt=self.salt,
                attempt=self.attempt, n_levels=n_levels)
        return self._checked(clean_chk, o, 0, injected)

    def process_packed(self, sub: np.ndarray, n_levels: int,
                       clean_chk: Optional[np.ndarray]) -> np.ndarray:
        """Padded-io path: ``sub`` is the packed output block (cell axis
        -2, rows32 2-D or planes-leading 3-D)."""
        if self.faults is not None and self.verify is not None \
                and clean_chk is None:
            clean_chk = np.bitwise_xor.reduce(sub, axis=sub.ndim - 2)
        injected = 0
        if self.faults is not None:
            sub, injected = self.faults.inject_packed(
                sub, row_base=self.row_base, salt=self.salt,
                attempt=self.attempt, n_levels=n_levels)
        return self._checked(clean_chk, sub, sub.ndim - 2, injected)


# Rows verified since the last oracle spot check, shared across calls so
# the oracle cost amortizes per *row served*, not per call (a hot 8k-row
# array must not pay an exec_packed per invocation).  Starts saturated so
# the first verified execution in a process is always spot-checked.
_spot_debt = 1 << 62


class _VerifyRun:
    """Per-execution (one streaming run / one group) retry + remap state:
    the logical-start -> spare-span remap table and the spare allocator.
    The HEALTH counters aggregate across runs; this object holds only what
    must be consistent *within* one run (a remapped chunk stays remapped
    for its retries)."""

    def __init__(self, plan: ExecPlan):
        self.plan = plan
        self.faults = plan.faults
        self.policy = plan.verify
        self.spare_next = None if self.faults is None \
            else int(self.faults.spare_base)
        self.remap: Dict[int, int] = {}

    def _alloc(self, span: int) -> int:
        base = self.spare_next
        self.spare_next += (span + 63) // 64 * 64
        return base

    def _clean_spare(self, span: int, limit: int) -> int:
        base = self._alloc(span)
        tries = 0
        while self.faults.span_bad(base, span):
            tries += 1
            if tries > limit:
                raise FaultError(
                    f"media scan found no clean {span}-row spare span "
                    f"after {limit} candidates",
                    span_rows=span, scan_limit=limit)
            base = self._alloc(span)
        return base

    def place(self, start: int, span: int) -> int:
        """Physical base for the chunk at logical row ``start``: the
        existing remap target, or -- when the media scan flags the span's
        persistent faults -- a freshly scanned clean spare."""
        base = self.remap.get(start, start)
        if self.faults is None or self.policy is None:
            return base
        if self.faults.span_bad(base, span):
            note_quarantine(base, span)       # scrubber's work queue
            base = self._clean_spare(span, self.policy.scan_limit)
            self.remap[start] = base
            HEALTH.add("remapped_rows", span)
        return base

    def rehome(self, start: int, span: int) -> int:
        """Force a fresh spare placement (retry policy escalation: the
        current span keeps failing verification even though the scan
        called it clean -- treat it as marginal and move off it)."""
        if self.faults is None:
            return self.remap.get(start, start)
        note_quarantine(self.remap.get(start, start), span)
        base = self._clean_spare(span, self.policy.scan_limit)
        self.remap[start] = base
        HEALTH.add("remapped_rows", span)
        return base

    def maybe_spot(self, program, inputs, n_rows: int, out: dict) -> None:
        """Amortized numpy-oracle spot check: every ``spot_interval_rows``
        verified rows, recompute ``spot_rows`` sampled rows on the
        cycle-accurate oracle and compare bit-exactly (catches what the
        per-word parity cannot -- e.g. paired flips of one bit position).
        Raises :class:`_Corrupt` on mismatch so the chunk retries."""
        global _spot_debt
        pol = self.policy
        if pol is None or pol.spot_rows <= 0 or n_rows <= 0:
            return
        _spot_debt += n_rows
        if _spot_debt < pol.spot_interval_rows:
            return
        _spot_debt = 0
        HEALTH.add("spot_checks")
        k = min(pol.spot_rows, n_rows)
        idx = np.unique(np.linspace(0, n_rows - 1, num=k, dtype=np.int64))
        sub_in = {n: np.asarray(v)[idx] for n, v in inputs.items()}
        oplan = dataclasses.replace(
            self.plan, backend=BACKENDS["numpy"], mesh=None, layout=ROWS32,
            chunk_rows=None, faults=None, verify=None)
        want = run_program(program, sub_in, int(idx.size), oplan)
        for name, w in want.items():
            if not np.array_equal(np.asarray(out[name])[idx], w):
                HEALTH.add("spot_mismatches")
                HEALTH.add("faults_detected")
                raise _Corrupt(f"oracle spot check mismatch on {name!r}")


def _verified_dispatch(program, inputs: Dict[str, np.ndarray], n_rows: int,
                       plan: ExecPlan, pad_rows: Optional[int],
                       vrun: _VerifyRun, start: int) -> Callable:
    """Dispatch one chunk under the plan's fault model / verify policy;
    returns a ``finalize`` that runs the detect -> retry -> remap loop.

    The initial attempt dispatches asynchronously exactly like the plain
    path (pipelining is preserved when nothing is corrupted -- the common
    case); retries are synchronous re-dispatches inside finalize."""
    span = _state_span(plan, n_rows if pad_rows is None else pad_rows)
    base = vrun.place(start, span)
    pkey = content_key(program)
    salt = _chunk_salt(pkey, start)

    def dispatch(attempt: int, row_base: int) -> Callable:
        fctx = _FaultCtx(plan.faults, plan.verify, row_base, salt, attempt)
        record_wear(row_base, span)           # every attempt writes media
        return _dispatch_levelized(program, inputs, n_rows, plan,
                                   pad_rows=pad_rows, fctx=fctx)

    first = dispatch(0, base)

    def finalize() -> Dict[str, np.ndarray]:
        pol = plan.verify
        attempt, row_base, fin = 0, base, first
        while True:
            try:
                out = fin()
                vrun.maybe_spot(program, inputs, n_rows, out)
                break
            except _Corrupt:
                attempt += 1
                if pol is None or attempt > pol.max_retries:
                    raise FaultError(
                        f"rows [{start}, {start + n_rows}): verification "
                        f"still failing after {attempt - 1} retries",
                        program_key=pkey[:8].hex(), chunk_start=start,
                        rows=n_rows, attempts=attempt,
                        remapped_base=vrun.remap.get(start))
                HEALTH.add("retries")
                time.sleep(min(pol.backoff_s * (1 << (attempt - 1)), 0.05))
                if attempt >= pol.remap_after and plan.faults is not None:
                    row_base = vrun.rehome(start, span)
                fin = dispatch(attempt, row_base)
        if attempt:
            HEALTH.add("faults_corrected")
        return out

    return finalize


def _verified_dispatch_packed(program, n_rows: int, plan: ExecPlan,
                              vrun: _VerifyRun, stage: int, *,
                              inputs=None, packed_in=None, in_names=None,
                              deadline: Optional[float] = None) -> Callable:
    """Packed-domain stage under the plan's fault model / verify policy
    (the reduction-tree analog of :func:`_verified_dispatch`).

    Every packed stage is its own verify cut-point: the per-stage XOR
    check plane folds over the whole packed block (zero pad rows included
    -- they are the additive identity, so a corrupted pad still flips the
    parity and is caught), and because the stage's *input* block lives on
    the host between stages, a detected corruption re-runs only this
    stage, not the reduction levels already verified below it.  The whole
    tree shares one :class:`_VerifyRun` keyed at logical row 0 (each level
    physically reuses the same span, shrinking as the tree narrows), so a
    remap sticks for every later level; ``stage`` salts the transient
    stream so levels of one program don't roll identical flips."""
    span = _state_span(plan, n_rows)
    base = vrun.place(0, span)
    pkey = content_key(program)
    salt = _chunk_salt(pkey, stage)
    names = inputs if packed_in is None else {n: None for n in in_names}

    def dispatch(attempt: int, row_base: int) -> Callable:
        fctx = _FaultCtx(plan.faults, plan.verify, row_base, salt, attempt)
        record_wear(row_base, span)
        return _dispatch_levelized(program, names, n_rows, plan, fctx=fctx,
                                   packed_in=packed_in, packed_out=True)

    first = dispatch(0, base)

    def finalize() -> np.ndarray:
        pol = plan.verify
        attempt, row_base, fin = 0, base, first
        while True:
            try:
                out = fin()
                break
            except _Corrupt:
                attempt += 1
                if pol is None or attempt > pol.max_retries:
                    raise FaultError(
                        f"packed stage {stage} ({n_rows} rows): "
                        f"verification still failing after "
                        f"{attempt - 1} retries",
                        program_key=pkey[:8].hex(), stage=stage,
                        rows=n_rows, attempts=attempt,
                        remapped_base=vrun.remap.get(0))
                HEALTH.add("retries")
                _check_deadline(deadline)
                time.sleep(min(pol.backoff_s * (1 << (attempt - 1)), 0.05))
                if attempt >= pol.remap_after and plan.faults is not None:
                    row_base = vrun.rehome(0, span)
                fin = dispatch(attempt, row_base)
        if attempt:
            HEALTH.add("faults_corrected")
        return out

    return finalize


def _needs_ft(plan: ExecPlan) -> bool:
    return plan.faults is not None or plan.verify is not None


# --------------------------------------------------------------------------
# execution
# --------------------------------------------------------------------------

def _fit_packed(block: np.ndarray, n_words: int) -> np.ndarray:
    """Fit a pre-packed word block to the dispatch's padded word count:
    zero-pad the trailing word axis (pad rows are all-zero by the packing
    contract) or reject a block wider than the padded shape."""
    have = block.shape[-1]
    if have == n_words:
        return block
    if have > n_words:
        raise ValueError(
            f"packed input has {have} words, dispatch shape allows "
            f"{n_words}")
    pad = np.zeros(block.shape[:-1] + (n_words - have,), np.uint32)
    return np.concatenate([block, pad], axis=-1)


def _aot_call(comp, program, plan: ExecPlan, fn, args: tuple, static: dict):
    """Invoke a jitted executor, routing through the AOT-executable tier
    when a disk artifact cache is installed.

    Per exact call signature (executor name + operand shapes/dtypes +
    static kwargs), the first process pays ``lower().compile()`` once and
    serializes the XLA executable to disk; later processes (or a
    ``warm()``-ed replica) deserialize it in milliseconds and skip tracing
    entirely.  Any failure -- XLA refusing to serialize, version skew, a
    deserialized executable rejecting the operands -- permanently marks
    the signature failed for this entry, counts ``pim.cache.aot_failed``
    and falls back to the plain jit path, so AOT is strictly an
    optimization, never a correctness risk, and never a silent one.
    Mesh-sharded and trace-time-static paths never come through here."""
    if _artifacts is None or not getattr(_artifacts, "aot", False):
        return fn(*args, **static)
    memo = "|".join((
        fn.__name__,
        ";".join(f"{tuple(a.shape)}:{a.dtype}" for a in args),
        ";".join(f"{k}={static[k]!r}" for k in sorted(static))))
    loaded = comp.aot.get(memo)
    if loaded is not None:
        try:
            return loaded(*args)
        except Exception:
            del comp.aot[memo]
            comp.aot_failed.add(memo)
            _CACHE.add("aot_failed")
            return fn(*args, **static)
    if memo in comp.aot_failed:
        return fn(*args, **static)
    content = content_key(program)
    try:
        loaded = _artifacts.load_executable(content, plan, memo)
        if loaded is None:
            loaded = fn.lower(*args, **static).compile()
            _artifacts.store_executable(content, plan, memo, loaded,
                                        provenance=provenance_of(program))
        out = loaded(*args)
    except Exception:
        comp.aot_failed.add(memo)
        _CACHE.add("aot_failed")
        return fn(*args, **static)
    comp.aot[memo] = loaded
    return out


def _to_device(block: np.ndarray, mesh: Optional[Mesh]):
    """A host data block on the device, or split over the row mesh."""
    return jnp.asarray(block) if mesh is None else _place_rows(block, mesh)


def _dispatch_levelized(program, inputs: Dict[str, np.ndarray], n_rows: int,
                        plan: ExecPlan,
                        pad_rows: Optional[int] = None, *,
                        fctx: Optional[_FaultCtx] = None,
                        packed_in: Optional[np.ndarray] = None,
                        packed_out: bool = False,
                        chunk: int = 0) -> Callable:
    """Pack ``inputs`` and dispatch one levelized execution under ``plan``;
    returns a zero-arg ``finalize`` that blocks on the device result and
    unpacks it.

    Dispatch is asynchronous (JAX futures), so callers can overlap host
    packing of the next chunk with device execution of this one -- the
    streaming executor's pipeline.  ``pad_rows`` fixes the padded row count
    (>= n_rows) so every streaming chunk shares one compiled shape.

    ``packed_in``/``packed_out`` keep the data in the packed word domain
    (the in-memory composition contract behind :func:`dispatch_packed`):
    ``packed_in`` replaces host packing with a caller-supplied word block
    whose cell axis stacks the in-ports' cells in sorted-name order
    (``inputs`` then only names the ports), and ``packed_out`` makes
    ``finalize`` return the raw packed output block (out-ports stacked in
    ``output_names`` order) instead of unpacked row values.

    Each step runs in a ``pim.dispatch.*`` tracer span (DESIGN.md §15)
    whose args are the rows and ``chunk``, the chunk's index in its call:
    ``pack``, ``h2d`` and ``launch`` here; ``wait``, ``d2h`` and
    ``unpack`` in ``finalize``.
    """
    comp = compiled(program, plan)
    in_names = sorted(inputs)
    r = comp.resolve(program, plan, tuple(in_names))
    # one O(1) registry fold per dispatch: exec counters + the modeled
    # cycle/energy gauges precomputed at resolve time (DESIGN.md §15) --
    # the telemetry cost is a handful of dict ops, independent of rows
    # and schedule size, so the tracked-kernel overhead stays <2%
    telemetry.record_dispatch(n_rows, r.model)
    span = telemetry.TRACER.span

    layout, backend, mesh = plan.layout, plan.backend, plan.mesh
    planes = layout.planes
    shards = 1 if mesh is None else mesh.devices.size
    pad_to = backend.pad_to * shards
    n_words = layout.n_words(n_rows if pad_rows is None else pad_rows,
                             pad_to)
    is_pallas = backend.name == "pallas"
    use_fused = r.fused_ok and packed_in is None and not packed_out
    if use_fused:
        vals = [np.asarray(inputs[n]) for n in in_names]
        use_fused = all(v.dtype != object for v in vals)
    if use_fused:
        # fused fast path: the bit transposes run inside the executor's
        # XLA program; only (n_ports, n_rows) uint32 cross the boundary
        with span("pim.dispatch.pack", rows=n_rows, chunk=chunk):
            pad_rows_total = n_words * 32 * planes
            in_vals = np.empty((len(vals), pad_rows_total), np.uint32)
            for p, v in enumerate(vals):
                in_vals[p, :len(v)] = v       # same-kind cast in place
                in_vals[p, len(v):] = 0       # only the ragged tail zeroed
        with span("pim.dispatch.h2d", rows=n_rows, chunk=chunk):
            block = _to_device(in_vals, mesh)
        with span("pim.dispatch.launch", rows=n_rows, chunk=chunk):
            if r.use_static and not is_pallas:
                run = comp.get_static_chain(program, plan, in_names, True,
                                            r.in_widths, r.out_widths)
                outs = run(block)
            elif r.use_static and r.in_base == 0:
                run = comp.get_static_pallas(program, plan, in_names,
                                             r.in_widths, r.out_widths)
                outs = run(block)
            else:
                if is_pallas:
                    _refuse_interpret_only("slot-scan" if r.kind != "dense"
                                           else "dense gather")
                if r.kind != "dense":
                    fn = (pim_exec_slots_fused if is_pallas
                          else pim_exec_ref_slots_fused)
                    static = dict(n_cells=r.sched.n_cells,
                                  one_cell=r.one_cell,
                                  in_widths=r.in_widths,
                                  out_widths=r.out_widths,
                                  in_base=r.in_base, out_base=r.out_base,
                                  planes=planes)
                else:
                    fn = (pim_exec_level_fused if is_pallas
                          else pim_exec_ref_level_fused)
                    static = dict(n_cells=r.sched.n_cells,
                                  one_cell=r.one_cell,
                                  in_widths=r.in_widths,
                                  out_widths=r.out_widths, planes=planes)
                args = (block, r.in_idx, r.la, r.lb, r.lo, r.out_idx)
                if mesh is None:
                    outs = _aot_call(comp, program, plan, fn, args, static)
                else:
                    outs = _sharded_exec(fn, mesh, not is_pallas, 2,
                                         **static)(*args)

        # verified-under-fault plans emit the XOR check plane *on the
        # device* (pim_exec.check_words), dispatched asynchronously right
        # behind the executor: the parity generation rides the same device
        # pass, and the host only refolds after injection, when there is
        # simulated media to distrust AND a VerifyPolicy to act on a
        # mismatch -- verify-only and faults-only plans skip the fold
        # entirely (DESIGN.md §14)
        chk = check_words(outs, axis=0) if fctx is not None \
            and fctx.faults is not None and fctx.verify is not None \
            else None

        def finalize() -> Dict[str, np.ndarray]:
            with span("pim.dispatch.wait", rows=n_rows, chunk=chunk):
                jax.block_until_ready(outs)
            with span("pim.dispatch.d2h", rows=n_rows, chunk=chunk):
                o = np.asarray(outs)
            if fctx is not None:
                o = fctx.process_values(o, r.out_widths, r.sched.n_levels,
                                        None if chk is None
                                        else np.asarray(chk))
            with span("pim.dispatch.unpack", rows=n_rows, chunk=chunk):
                return {n: o[p, :n_rows].astype(np.uint64)
                        for p, n in enumerate(r.names)}
        return finalize
    with span("pim.dispatch.pack", rows=n_rows, chunk=chunk):
        if packed_in is not None:
            k_in = sum(len(r.sched.pack_cells(n)) for n in in_names)
            if packed_in.shape[-2] != k_in:
                raise ValueError(
                    f"packed input stacks {packed_in.shape[-2]} cells, "
                    f"in-ports {in_names} need {k_in}")
            in_rows = _fit_packed(packed_in, n_words)
        elif in_names:
            in_rows = np.concatenate(
                [_pack_port_words(inputs[n], len(r.sched.pack_cells(n)),
                                  n_words, layout) for n in in_names],
                axis=-2)
        else:
            in_rows = np.zeros(layout.state_shape(0, n_words), np.uint32)
    with span("pim.dispatch.h2d", rows=n_rows, chunk=chunk):
        block = _to_device(in_rows, mesh)
    with span("pim.dispatch.launch", rows=n_rows, chunk=chunk):
        if r.use_static and not is_pallas:
            run = comp.get_static_chain(program, plan, in_names, False,
                                        r.in_widths, r.out_widths)
            sub = run(block)
        else:
            if is_pallas:   # no wide-port or packed-domain static kernel
                _refuse_interpret_only("slot-scan" if r.kind != "dense"
                                       else "dense gather")
            if r.kind != "dense":
                exec_fn = (pim_exec_slots_io if is_pallas
                           else pim_exec_ref_slots_io)
                static = dict(n_cells=r.sched.n_cells, one_cell=r.one_cell,
                              k_out=r.k_out, in_base=r.in_base,
                              out_base=r.out_base)
            else:
                exec_fn = (pim_exec_level_padded_io if is_pallas
                           else pim_exec_ref_level_io)
                static = dict(n_cells=r.sched.n_cells, one_cell=r.one_cell)
            args = (block, r.in_idx, r.la, r.lb, r.lo, r.out_idx)
            if mesh is None:
                sub = _aot_call(comp, program, plan, exec_fn, args, static)
            else:
                sub = _sharded_exec(exec_fn, mesh, not is_pallas,
                                    in_rows.ndim, **static)(*args)

    # on-device check plane for the packed/padded-io path too: the fold
    # runs over the cell axis (-2) of the packed output block
    chk = check_words(sub, axis=sub.ndim - 2) if fctx is not None \
        and fctx.faults is not None and fctx.verify is not None else None

    def finalize():
        with span("pim.dispatch.wait", rows=n_rows, chunk=chunk):
            jax.block_until_ready(sub)
        with span("pim.dispatch.d2h", rows=n_rows, chunk=chunk):
            s = np.asarray(sub)
        if fctx is not None:
            s = fctx.process_packed(s, r.sched.n_levels,
                                    None if chk is None else np.asarray(chk))
        if packed_out:
            return s
        with span("pim.dispatch.unpack", rows=n_rows, chunk=chunk):
            return _unpack_sub(s,
                               [(n, len(r.sched.ports[n])) for n in r.names],
                               n_rows)
    return finalize


def run_program(program, inputs: Dict[str, np.ndarray], n_rows: int,
                plan=None, levelized: bool = True, *,
                backend=None, mesh=None, schedule=None, layout=None
                ) -> Dict[str, np.ndarray]:
    """Element-parallel execution of a gate program over ``n_rows`` rows.

    ``plan`` is an :class:`ExecPlan` -- or, for convenience, a backend
    name ('pallas' kernels, 'ref' jnp oracle, 'numpy' the
    cycle-accurate simulator's packed executor); the keyword strings
    (``backend=``/``schedule=``/``layout=``/``mesh=``) build a plan at
    this boundary.  'pallas' and 'ref' consume the levelized schedule by
    default; ``levelized=False`` selects the original gate-serial
    executors (rows32 only).  The plan's mesh (see :func:`row_mesh`)
    shards the packed word axis over devices; its layout picks the packed
    word form ('rows32' uint32 words, 'rows64' the paired 64-row layout).

    Returns the program's output ports -- all ports when the program does
    not declare port directions (the :func:`output_names` contract, which
    every backend path shares).
    """
    plan = as_plan(plan, backend=backend, mesh=mesh, schedule=schedule,
                   layout=layout, default_backend="pallas")
    if not levelized and (plan.mesh is not None or plan.layout.planes > 1):
        raise ValueError(
            "mesh sharding requires a levelized jax backend "
            f"(got backend={plan.backend.name!r}, levelized={levelized})"
            if plan.mesh is not None else
            f"layout {plan.layout.name!r} requires the levelized executors")
    if not levelized and _needs_ft(plan):
        raise ValueError("fault injection / verified execution require "
                         "the levelized executors")
    if plan.backend.name == "numpy":
        if plan.mesh is not None:       # unreachable (plan validates) --
            raise ValueError("mesh sharding requires a jax backend")
        telemetry.record_dispatch(n_rows, _serial_model(program))
        state = pack_rows(inputs, program.ports, n_rows, program.n_cells,
                          pad_to=1)
        st = np.ascontiguousarray(state.T)
        program.exec_packed(st)
        return unpack_rows(st.T, program.ports, n_rows,
                           names=output_names(program))
    if levelized:
        if _needs_ft(plan):
            return _verified_dispatch(program, inputs, n_rows, plan, None,
                                      _VerifyRun(plan), 0)()
        return _dispatch_levelized(program, inputs, n_rows, plan)()
    comp = compiled(program, plan)
    telemetry.record_dispatch(n_rows, comp.get_serial_model(program))
    ops, a, b, o, n_cells = comp.get_arrays(program)
    state = pack_rows(inputs, program.ports, n_rows, n_cells,
                      pad_to=plan.backend.pad_to)
    if plan.backend.name == "ref":
        final = np.asarray(pim_exec_ref(
            jnp.asarray(state), jnp.asarray(ops), jnp.asarray(a),
            jnp.asarray(b), jnp.asarray(o)))
    else:
        final = np.asarray(pim_exec_padded(
            jnp.asarray(state), jnp.asarray(ops), jnp.asarray(a),
            jnp.asarray(b), jnp.asarray(o), n_cells=n_cells))
    return unpack_rows(final, program.ports, n_rows,
                       names=output_names(program))


def run_program_streaming(program, inputs: Dict[str, np.ndarray],
                          n_rows: int, plan=None, *,
                          backend=None, chunk_rows=None, mesh=None,
                          schedule=None, layout=None,
                          deadline: Optional[float] = None
                          ) -> Dict[str, np.ndarray]:
    """Chunked, pipelined, optionally sharded execution over ``n_rows``.

    Rows are tiled into word-aligned chunks of the plan's chunk size; the
    loop dispatches chunk ``k`` to the device, packs chunk ``k+1`` on the
    host while ``k`` executes (JAX async dispatch), then blocks on ``k``'s
    result -- so host bridging and device execution overlap instead of one
    monolithic pack -> exec -> unpack.  Every chunk (including the ragged
    last one) is padded to the same shape, so the executor compiles once.

    Levelized jax backends only ('ref'/'pallas'); the plan's mesh
    additionally shards each chunk's word axis over devices
    (:func:`row_mesh`).

    ``deadline`` is an absolute ``time.monotonic()`` bound checked before
    dispatch and between chunks (:class:`DeadlineExceeded` on expiry) --
    the serving layer's per-request deadline hook.  A plan carrying a
    fault model / verify policy routes every chunk through the
    detect -> retry -> remap loop (DESIGN.md §12).
    """
    plan = as_plan(plan, backend=backend, chunk_rows=chunk_rows, mesh=mesh,
                   schedule=schedule, layout=layout)
    if not plan.backend.is_jax:
        raise ValueError("streaming requires a levelized jax backend, "
                         f"got {plan.backend.name!r}")
    chunk = plan.effective_chunk_rows
    _check_deadline(deadline)
    vrun = _VerifyRun(plan) if _needs_ft(plan) else None
    if n_rows <= chunk:
        if vrun is None:
            return run_program(program, inputs, n_rows, plan)
        return _verified_dispatch(program, inputs, n_rows, plan, None,
                                  vrun, 0)()
    inputs = {n: np.asarray(v) for n, v in inputs.items()}
    for n, v in inputs.items():
        if len(v) != n_rows:
            raise ValueError(
                f"input {n!r} has {len(v)} rows, expected {n_rows}")
    parts = []
    pending = None
    for start in range(0, n_rows, chunk):
        _check_deadline(deadline)
        rows_k = min(chunk, n_rows - start)
        chunk_in = {n: v[start:start + rows_k] for n, v in inputs.items()}
        if vrun is None:
            fin = _dispatch_levelized(program, chunk_in, rows_k, plan,
                                      pad_rows=chunk, chunk=start // chunk)
        else:
            fin = _verified_dispatch(program, chunk_in, rows_k, plan,
                                     chunk, vrun, start)
        if pending is not None:
            parts.append(pending())     # blocks on k-1 while k executes
        pending = fin
    parts.append(pending())
    with telemetry.TRACER.span("pim.dispatch.concat", rows=n_rows,
                               chunks=len(parts)):
        return {name: np.concatenate([p[name] for p in parts])
                for name in parts[0]}


def dispatch_program(program, inputs: Dict[str, np.ndarray], n_rows: int,
                     plan=None, *, backend=None, mesh=None, schedule=None,
                     layout=None, pad_rows: Optional[int] = None) -> Callable:
    """Asynchronously dispatch one levelized execution; returns a zero-arg
    ``finalize`` that blocks on the device result and unpacks the output
    ports.  The pipelining primitive behind :func:`run_program_streaming`
    and :func:`run_program_groups`: callers overlap host packing of the
    next unit of work with device execution of this one."""
    plan = as_plan(plan, backend=backend, mesh=mesh, schedule=schedule,
                   layout=layout)
    if not plan.backend.is_jax:
        raise ValueError("dispatch requires a levelized jax backend, "
                         f"got {plan.backend.name!r}")
    if _needs_ft(plan):
        return _verified_dispatch(program, inputs, n_rows, plan, pad_rows,
                                  _VerifyRun(plan), 0)
    return _dispatch_levelized(program, inputs, n_rows, plan,
                               pad_rows=pad_rows)


def dispatch_packed(program, n_rows: int, plan=None, *,
                    inputs: Optional[Dict[str, np.ndarray]] = None,
                    in_block: Optional[np.ndarray] = None,
                    in_names: Optional[Tuple[str, ...]] = None,
                    vrun: Optional[_VerifyRun] = None, stage: int = 0,
                    deadline: Optional[float] = None) -> Callable:
    """Dispatch one levelized execution that stays in the packed word
    domain; returns a zero-arg ``finalize`` yielding the packed output
    block (uint32, out-ports' cells stacked in ``output_names`` order,
    rows packed 32 per word along the trailing axis -- rows64 plans keep
    the planes-leading 3-D state shape).

    Feed it either ``inputs`` (row-value dict, packed once on the way in)
    or ``in_block`` + ``in_names`` (a block from a previous packed
    dispatch, cell axis stacking the named in-ports in sorted order) --
    the primitive behind the in-memory reduction trees of ``pim.dot``/
    ``pim.gemv``, where intermediate values never unpack between stages.

    Levelized jax backends only.  A plan carrying a fault model / verify
    policy routes the stage through the packed detect -> retry -> remap
    loop: pass one shared ``vrun`` across a tree's stages (so a remap
    sticks for later levels and a failed stage retries from the last
    verified level, not the leaves) and a distinct ``stage`` ordinal to
    salt each level's transient stream.  ``deadline`` (absolute
    ``time.monotonic()``) is checked before dispatch and between retry
    attempts -- what lets a deep GEMV reduction cancel mid-tree.
    """
    plan = as_plan(plan)
    if not plan.backend.is_jax:
        raise ValueError("packed dispatch requires a levelized jax "
                         f"backend, got {plan.backend.name!r}")
    if (in_block is None) == (inputs is None):
        raise ValueError("pass exactly one of inputs= or in_block=")
    _check_deadline(deadline)
    if in_block is not None:
        if not in_names:
            raise ValueError("in_block requires in_names")
        block = np.ascontiguousarray(np.asarray(in_block, np.uint32))
        if _needs_ft(plan):
            return _verified_dispatch_packed(
                program, n_rows, plan, vrun or _VerifyRun(plan), stage,
                packed_in=block, in_names=in_names, deadline=deadline)
        names = {n: None for n in in_names}
        return _dispatch_levelized(program, names, n_rows, plan,
                                   packed_in=block, packed_out=True)
    if _needs_ft(plan):
        return _verified_dispatch_packed(
            program, n_rows, plan, vrun or _VerifyRun(plan), stage,
            inputs=inputs, deadline=deadline)
    return _dispatch_levelized(program, inputs, n_rows, plan,
                               packed_out=True)


def run_program_groups(groups: Iterable[dict]) -> list:
    """Execute several coalesced program groups back to back with
    cross-group pipelining; returns their output dicts in input order.

    Each group is a dict: ``program``, ``inputs`` (port name -> row
    values), ``n_rows``, plus a ``plan`` (:class:`ExecPlan`; the legacy
    ``backend``/``schedule``/``chunk_rows``/``mesh`` keys still normalize
    into one here, at the boundary).  The loop dispatches group ``k`` (JAX
    async) and packs group ``k+1`` on the host while ``k`` executes -- the
    streaming pipeline generalized across *heterogeneous* programs, which
    is what lets the batched serving runtime keep the device busy across a
    mixed-traffic plan.  Groups larger than the plan's chunk size tile
    into word-aligned fixed-shape chunks inside the same pipeline (so one
    giant group cannot stall its successors' packing).  A numpy-backend
    group is a synchronization point (the oracle is host-synchronous).

    A group may carry a ``deadline`` (absolute ``time.monotonic()``),
    checked before each of its chunks dispatches; a plan with a fault
    model / verify policy runs its group's chunks through the verified
    detect -> retry -> remap loop (one :class:`_VerifyRun` per group, so a
    remapped chunk stays remapped for its retries).
    """
    groups = list(groups)
    parts: list = [[] for _ in groups]
    pending: "collections.deque" = collections.deque()

    def drain(limit: int) -> None:
        while len(pending) > limit:
            gi, fin = pending.popleft()
            parts[gi].append(fin())

    for gi, g in enumerate(groups):
        program, n_rows = g["program"], int(g["n_rows"])
        plan = as_plan(g.get("plan"), backend=g.get("backend"),
                       schedule=g.get("schedule"), layout=g.get("layout"),
                       mesh=g.get("mesh"), chunk_rows=g.get("chunk_rows"))
        deadline = g.get("deadline")
        inputs = {n: np.asarray(v) for n, v in g["inputs"].items()}
        for n, v in inputs.items():
            if len(v) != n_rows:
                raise ValueError(
                    f"group {gi}: input {n!r} has {len(v)} rows, "
                    f"expected {n_rows}")
        if plan.backend.name == "numpy":
            drain(0)
            _check_deadline(deadline)
            parts[gi].append(run_program(program, inputs, n_rows, plan))
            continue
        vrun = _VerifyRun(plan) if _needs_ft(plan) else None
        chunk = plan.effective_chunk_rows
        if n_rows <= chunk:
            _check_deadline(deadline)
            pending.append((gi, _dispatch_levelized(
                program, inputs, n_rows, plan) if vrun is None
                else _verified_dispatch(program, inputs, n_rows, plan,
                                        None, vrun, 0)))
            drain(1)
            continue
        for start in range(0, n_rows, chunk):
            _check_deadline(deadline)
            rows_k = min(chunk, n_rows - start)
            chunk_in = {n: v[start:start + rows_k]
                        for n, v in inputs.items()}
            pending.append((gi, _dispatch_levelized(
                program, chunk_in, rows_k, plan, pad_rows=chunk)
                if vrun is None
                else _verified_dispatch(program, chunk_in, rows_k, plan,
                                        chunk, vrun, start)))
            drain(1)
    drain(0)
    return [ps[0] if len(ps) == 1 else
            {k: np.concatenate([p[k] for p in ps]) for k in ps[0]}
            for ps in parts]
