"""ExecPlan pipeline (DESIGN.md §11): plan normalization/validation,
WordLayout bridge round-trips (rows32 + rows64 across the row/width edge
grid), bit-exact rows64 vs rows32 executor parity across every memoized
build_* family and all three schedules, plan-keyed group separation, and
the pin-vs-LRU-cap regression audit."""

import numpy as np
import pytest

from repro import pim_ufunc as pim
from repro.core import bitparallel, bitparallel_fp, bitserial, bitserial_fp
from repro.core.floatfmt import FP16, FORMATS
from repro.core.pim_numerics import program_for
from repro.kernels import ops as kops
from repro.kernels import plan as kplan
from repro.kernels import slots as kslots
from repro.runtime import pim_batch as pb


# ----------------------------------------------------------- plan normalize

def test_as_plan_normalization_and_validation():
    p = kops.as_plan(backend="ref", schedule="dense", layout="rows64")
    assert p.backend.name == "ref" and p.schedule == "dense"
    assert p.layout is kplan.ROWS64 and p.layout.rows_per_word == 64
    # a ready plan passes through untouched; overrides rebuild
    assert kops.as_plan(p) is p
    q = kops.as_plan(p, schedule="slots")
    assert q.schedule == "slots" and q.layout is kplan.ROWS64
    # positional backend-string convention still works
    assert kops.as_plan("pallas").backend.name == "pallas"
    assert kops.as_plan("pallas").backend.pad_to == kplan.TILE_W
    with pytest.raises(ValueError, match="unknown backend"):
        kops.as_plan(backend="cuda")
    with pytest.raises(ValueError, match="unknown schedule"):
        kops.as_plan(schedule="bogus")
    with pytest.raises(ValueError, match="unknown layout"):
        kops.as_plan(layout="rows128")
    with pytest.raises(ValueError, match="conflicting backends"):
        kops.as_plan("ref", backend="pallas")
    # layout/mesh constraints are enforced at construction, not dispatch
    with pytest.raises(ValueError, match="rows64"):
        kops.as_plan(backend="numpy", layout="rows64")
    with pytest.raises(TypeError):
        kops.as_plan(42)


def test_plan_keys_separate_every_dimension():
    base = kops.make_plan(backend="ref")
    assert base.key != kops.make_plan(backend="pallas").key
    assert base.key != kops.make_plan(backend="ref", schedule="dense").key
    assert base.key != kops.make_plan(backend="ref", layout="rows64").key
    assert base.key != kops.make_plan(backend="ref", chunk_rows=4096).key
    # a custom retuned Backend separates the group key too (its tunables
    # flatten into plan.key)
    retuned = kplan.Backend("ref", level_max_width=4)
    assert base.key != kops.make_plan(backend=retuned).key
    # compile_key tracks the artifact universe only: backend name, layout
    # and schedule kind are all excluded (ref/pallas share schedule
    # arrays; rows32/rows64 share every schedule artifact; one entry
    # lazily holds all schedule kinds) -- allocator tunables are included
    assert base.compile_key == kops.make_plan(backend="pallas").compile_key
    assert base.compile_key == \
        kops.make_plan(backend="ref", layout="rows64").compile_key
    assert base.compile_key == \
        kops.make_plan(backend="ref", schedule="dense").compile_key
    assert base.compile_key != kops.make_plan(backend=retuned).compile_key
    # chunk alignment follows the layout's word granularity
    assert kops.make_plan(chunk_rows=100).effective_chunk_rows == 128
    assert kops.make_plan(chunk_rows=100,
                          layout="rows64").effective_chunk_rows == 128
    assert kops.make_plan(chunk_rows=65,
                          layout="rows64").effective_chunk_rows == 128
    assert kops.make_plan(chunk_rows=1).effective_chunk_rows == 32


# -------------------------------------------------- WordLayout bridge tests

BRIDGE_ROWS = (0, 1, 31, 32, 33, 63, 64, 65)
BRIDGE_WIDTHS = (31, 32, 33, 64)


def _rand_width_vals(rng, rows, width):
    """Random row values of exactly `width` bits (object beyond 63)."""
    if width > 63:
        return np.array([int.from_bytes(rng.bytes(width // 8 + 1), "little")
                         & ((1 << width) - 1) for _ in range(rows)], object)
    return rng.integers(0, 1 << width, rows).astype(np.uint64) \
        if width < 64 else rng.integers(0, 1 << 63, rows).astype(np.uint64)


@pytest.mark.parametrize("layout_name", ["rows32", "rows64"])
def test_pack_unpack_round_trip_grid(layout_name):
    """pack_rows -> unpack_rows is the identity for every (rows, width)
    edge combination of both layouts, including the one_cell constant."""
    layout = kplan.LAYOUTS[layout_name]
    rng = np.random.default_rng(7)
    for rows in BRIDGE_ROWS:
        for width in BRIDGE_WIDTHS:
            vals = _rand_width_vals(rng, rows, width)
            ports = {"a": list(range(width)),
                     "b": list(range(width + 1, 2 * width + 1))}
            other = _rand_width_vals(rng, rows, width)
            n_cells = 2 * width + 2
            state = kops.pack_rows({"a": vals, "b": other}, ports, rows,
                                   n_cells, one_cell=width, pad_to=1,
                                   layout=layout)
            assert state.shape == layout.state_shape(
                n_cells, layout.n_words(rows, 1))
            # the folded INIT1 cell is all-ones in every plane
            assert (state[..., width, :] == np.uint32(0xFFFFFFFF)).all()
            got = kops.unpack_rows(state, ports, rows)
            for name, want in (("a", vals), ("b", other)):
                assert len(got[name]) == rows
                assert all(int(g) == int(w)
                           for g, w in zip(got[name], want)), \
                    (layout_name, rows, width, name)


def test_rows64_state_is_plane_split_of_rows32():
    """The paired layout is exactly the little-endian uint64 split of the
    rows32 words: plane h of word i == rows32 word 2i+h."""
    rng = np.random.default_rng(8)
    vals = rng.integers(0, 1 << 16, 130).astype(np.uint64)
    ports = {"v": list(range(16))}
    s32 = kops.pack_rows({"v": vals}, ports, 130, 16, pad_to=1,
                         layout=kplan.ROWS32)
    s64 = kops.pack_rows({"v": vals}, ports, 130, 16, pad_to=1,
                         layout=kplan.ROWS64)
    n64 = s64.shape[-1]
    # rows64 word-pairs cover ceil(130/64)*64 rows; pad the rows32 words
    # out to the same span before comparing strides
    w32 = np.zeros((16, 2 * n64), np.uint32)
    w32[:, :s32.shape[1]] = s32
    assert np.array_equal(s64[0], w32[:, 0::2])
    assert np.array_equal(s64[1], w32[:, 1::2])


@pytest.mark.parametrize("planes", [1, 2])
def test_pack_values_in_jit_round_trip(planes):
    """The fused in-jit butterfly bridges round-trip for both layouts and
    agree with the host packer."""
    import jax.numpy as jnp
    rng = np.random.default_rng(9)
    widths = (31, 32, 7)
    n_rows = 32 * planes * 3
    in_vals = np.stack([
        rng.integers(0, 1 << min(w, 32), n_rows).astype(np.uint32)
        for w in widths])
    packed = np.asarray(kslots.pack_values(jnp.asarray(in_vals), widths,
                                           planes))
    layout = kplan.ROWS32 if planes == 1 else kplan.ROWS64
    host = np.concatenate(
        [kops._pack_port_words(in_vals[p], w, layout.n_words(n_rows, 1),
                               layout)
         for p, w in enumerate(widths)], axis=-2)
    assert np.array_equal(packed, host)
    back = np.asarray(kslots.unpack_values(jnp.asarray(packed), widths,
                                           planes))
    assert np.array_equal(back, in_vals)


# ------------------------------------------- rows64 executor parity sweeps

def _family_cases():
    """One representative per memoized build_* family (pim_numerics
    program_for kinds), with oracle-checkable inputs."""
    rng = np.random.default_rng(11)
    n = 70                       # crosses the 64-row pair boundary
    x16 = rng.integers(0, 1 << 16, n).astype(np.uint64)
    y16 = rng.integers(0, 1 << 16, n).astype(np.uint64)
    d16 = rng.integers(1, 1 << 16, n).astype(np.uint64)
    fx = FP16.random_bits(rng, n, emin=10, emax=20).astype(np.uint64)
    fy = FP16.random_bits(rng, n, emin=10, emax=20).astype(np.uint64)
    return [
        ("int-serial", program_for("int-serial", "add", 16),
         {"x": x16, "y": y16}),
        ("int-serial-div", program_for("int-serial", "div", 16),
         {"z": x16, "d": d16}),
        ("int-parallel", program_for("int-parallel", "add", 16),
         {"x": x16, "y": y16}),
        ("fp-serial", program_for("fp-serial", "add", "fp16"),
         {"x": fx, "y": fy}),
        ("fp-parallel", program_for("fp-parallel", "mul", "fp16"),
         {"x": fx, "y": fy}),
    ]


@pytest.mark.parametrize("schedule", ["slots", "slots-static", "dense"])
def test_rows64_parity_all_families(schedule):
    """Acceptance: rows64 output is bit-exact with rows32 (and the numpy
    oracle) for every build_* family under every schedule.  The ref
    backend runs the full grid; the pallas executors share the exact same
    layout-polymorphic bodies and get their own cross-schedule check in
    :func:`test_rows64_parity_pallas` (running every family through the
    unrolled interpret-mode pallas kernels would double suite time for no
    added code coverage)."""
    for label, prog, inputs in _family_cases():
        n = len(next(iter(inputs.values())))
        want = kops.run_program(prog, inputs, n, "numpy")
        p32 = kops.make_plan(backend="ref", schedule=schedule,
                             layout="rows32")
        p64 = kops.make_plan(backend="ref", schedule=schedule,
                             layout="rows64")
        got32 = kops.run_program(prog, inputs, n, p32)
        got64 = kops.run_program(prog, inputs, n, p64)
        assert sorted(got32) == sorted(want) == sorted(got64)
        for port in want:
            assert np.array_equal(got32[port], want[port]), \
                (label, schedule, port)
            assert np.array_equal(got64[port], got32[port]), \
                (label, schedule, port)


def test_rows64_parity_pallas():
    """The pallas executor family (scan slot kernel, dense gather kernel,
    static-slice kernel) under both layouts, on the int-serial builders
    (divider included: two output ports)."""
    rng = np.random.default_rng(13)
    n = 70
    prog = program_for("int-serial", "div", 8)
    ins = {"z": rng.integers(0, 1 << 8, n).astype(np.uint64),
           "d": rng.integers(1, 1 << 8, n).astype(np.uint64)}
    want = kops.run_program(prog, ins, n, "numpy")
    for schedule in ("slots", "slots-static", "dense"):
        for layout in ("rows32", "rows64"):
            got = kops.run_program(prog, ins, n, kops.make_plan(
                backend="pallas", schedule=schedule, layout=layout))
            for port in want:
                assert np.array_equal(got[port], want[port]), \
                    (schedule, layout, port)


def test_pallas_interpret_mode_follows_platform():
    """Interpret mode is decided from the platform (this CPU process has
    no Mosaic compiler); an explicit bool wins."""
    from repro.kernels import pim_exec
    assert pim_exec.interpret_mode() is True
    assert pim_exec.interpret_mode(False) is False
    assert pim_exec.interpret_mode(True) is True


@pytest.mark.parametrize("kind,op,param,schedule,levelized,lowers", [
    ("fp-serial", "add", "fp16", "slots-static", True, True),
    ("int-serial", "add", 16, "slots", False, True),     # gate-serial
    ("fp-serial", "add", "fp16", "slots", True, False),  # slot scan
    ("fp-serial", "add", "fp16", "dense", True, False),  # dense gather
    ("int-serial", "mul", 32, "slots-static", True, False),  # wide ports
])
def test_pallas_plans_on_tpu_lower_or_raise(monkeypatch, kind, op, param,
                                            schedule, levelized, lowers):
    """With interpret mode off (as on a TPU), a Pallas plan reaches a
    Mosaic-lowerable kernel or raises naming the lowerable schedule; it
    never runs an interpret-only kernel.  Only the dispatcher's view of
    the platform is switched: the kernels that may run still run
    interpreted here, and must stay bit-exact."""
    monkeypatch.setattr(kops, "interpret_mode", lambda interpret=None: False)
    rng = np.random.default_rng(5)
    prog = program_for(kind, op, param)
    n = 96
    ins = {name: rng.integers(0, 1 << 8, n).astype(np.uint64)
           for name in prog.in_ports}
    plan = kops.make_plan(backend="pallas", schedule=schedule)
    if not lowers:
        with pytest.raises(ValueError, match="slots-static"):
            kops.run_program(prog, ins, n, plan)
        return
    got = kops.run_program(prog, ins, n, plan, levelized=levelized)
    want = kops.run_program(prog, ins, n, "numpy")
    for port in want:
        assert np.array_equal(got[port], want[port]), port


def test_rows64_ufunc_and_streaming_parity():
    rng = np.random.default_rng(12)
    n = 3000
    x = rng.integers(0, 1 << 16, n).astype(np.uint16)
    y = rng.integers(0, 1 << 16, n).astype(np.uint16)
    want = x.astype(np.uint64) + y.astype(np.uint64)
    got = pim.add(x, y, layout="rows64", chunk_rows=512)   # 6 chunks
    assert np.array_equal(got, want)
    with pim.options(layout="rows64"):
        assert pim.prepare("add", x, y).plan.layout is kplan.ROWS64
    assert pim.config.layout == "rows32"                   # scoped


def test_rows64_rejects_non_levelized_paths():
    prog = bitserial.build_add(8)
    x = np.arange(4, dtype=np.uint64)
    with pytest.raises(ValueError, match="rows64"):
        kops.run_program(prog, {"x": x, "y": x}, 4, "numpy",
                         layout="rows64")
    with pytest.raises(ValueError, match="rows64"):
        kops.run_program(prog, {"x": x, "y": x}, 4, "ref",
                         levelized=False, layout="rows64")


# ------------------------------------------------ plan-keyed group planning

def test_group_key_separates_word_layout():
    """Requests differing only in word layout must never coalesce (the
    packed states are shaped differently; merging would corrupt rows)."""
    x, y = np.uint8([1, 2]), np.uint8([3, 4])
    r32 = pim.prepare("add", x, y)
    r64 = pim.prepare("add", x, y, layout="rows64")
    assert r32.key == r64.key                     # same program structure
    assert pb.group_key(r32) != pb.group_key(r64)
    plan = pb.plan_groups([r32, r64, pim.prepare("add", x, y)])
    assert [g.members for g in plan] == [[0, 2], [1]]
    # ...and the merged group + the rows64 singleton both execute right
    rt = pb.BatchRuntime(pin_cap=4)
    try:
        res = rt.execute([r32, r64])
        assert np.array_equal(res[0].value, [4, 6])
        assert np.array_equal(res[1].value, [4, 6])
    finally:
        rt.close()


def test_group_key_covers_full_plan():
    """Every ExecPlan dimension lands in the group key -- including the
    per-backend tunables that used to be module globals."""
    x, y = np.uint8([5]), np.uint8([6])
    keys = {pb.group_key(pim.prepare("add", x, y, **kw))
            for kw in ({}, {"schedule": "dense"}, {"backend": "numpy"},
                       {"layout": "rows64"}, {"chunk_rows": 4096})}
    assert len(keys) == 5


# -------------------------------------------------- pin vs LRU-cap audit

def _mini_program(seed, n_gates=10):
    from repro.core.gates import Builder

    rng = np.random.default_rng(seed)
    b = Builder()
    avail = b.input("x", 8) + b.input("y", 8)
    for _ in range(n_gates):
        i, j = rng.integers(0, len(avail), 2)
        avail.append(b.nor(avail[i], avail[j]))
    b.output("z", avail[-8:])
    return b.finish()


def test_cap_shrink_below_pinned_count():
    """Regression (ISSUE 5 satellite): shrinking the LRU cap below the
    pinned count must never evict a pinned entry, must still evict the
    unpinned ones, and must leave no pin leak after release."""
    progs = [_mini_program(100 + i) for i in range(3)]
    cold = _mini_program(999)
    ins = {"x": np.arange(5, dtype=np.uint64) % 256,
           "y": np.arange(5, dtype=np.uint64) % 256}
    old_cap = kops.set_compiled_cache_cap(8)
    keys = []
    try:
        for p in progs:
            kops.run_program(p, ins, 5, "ref")
            keys.append(kops.pin_program(p))
        kops.run_program(cold, ins, 5, "ref")        # unpinned entry
        cold_key = kops.cache_key(cold)
        assert cold_key in kops._compiled
        kops.set_compiled_cache_cap(1)               # below pinned count
        for k in keys:
            assert k in kops._compiled               # pinned survive
            assert k in kops._pinned
        assert cold_key not in kops._compiled        # unpinned evicted
        assert len(kops._compiled) == 3              # over cap, all pinned
        # executions still resolve against the pinned (compiled) entries
        for p in progs:
            assert kops.is_compiled(p)
        # releasing pins lets the cache shrink back to cap
        for k in keys:
            assert kops.unpin_program(k) is False
        assert not kops._pinned
        assert len(kops._compiled) <= 1
    finally:
        for k in keys:                               # idempotent cleanup
            kops.unpin_program(k)
        kops.set_compiled_cache_cap(old_cap)


def test_saturated_cap_never_orphans_new_entries():
    """Regression (audit fix): with the cap fully saturated by pinned
    entries, compiling a *new* program must not evict the entry just
    created -- otherwise its artifacts are built on an orphaned object and
    a later pin lands on an empty twin (recompiling forever)."""
    pinned_progs = [_mini_program(200 + i) for i in range(2)]
    newcomer = _mini_program(300)
    ins = {"x": np.arange(3, dtype=np.uint64),
           "y": np.arange(3, dtype=np.uint64)}
    old_cap = kops.set_compiled_cache_cap(2)
    keys = []
    try:
        for p in pinned_progs:
            kops.run_program(p, ins, 3, "ref")
            keys.append(kops.pin_program(p))
        kops.set_compiled_cache_cap(1)               # saturated by pins
        kops.run_program(newcomer, ins, 3, "ref")
        # the just-compiled entry survived its own creation...
        assert kops.is_compiled(newcomer)
        # ...and pinning it pins the entry that holds the artifacts
        nk = kops.pin_program(newcomer)
        assert kops.is_compiled(newcomer)
        assert kops.unpin_program(nk) is False
    finally:
        for k in keys:
            kops.unpin_program(k)
        kops.set_compiled_cache_cap(old_cap)
    assert not kops._pinned


def test_pin_is_plan_scoped():
    """The LRU and the pin refcounts key on (structure, plan artifact
    identity): plans that share every compiled artifact -- rows32 vs
    rows64, ref vs pallas, slots vs dense (one entry lazily holds all
    schedule kinds) -- share one entry and one pin, while a retuned
    Backend (different allocator widths => different artifacts) gets its
    own entry that a default-plan pin does not cover."""
    prog = _mini_program(400)
    ins = {"x": np.arange(3, dtype=np.uint64),
           "y": np.arange(3, dtype=np.uint64)}
    retuned = kops.make_plan(backend=kplan.Backend("ref", slot_width=4))
    kops.run_program(prog, ins, 3, "ref")
    kops.run_program(prog, ins, 3, retuned)
    kdef = kops.cache_key(prog)
    kret = kops.cache_key(prog, retuned)
    assert kdef != kret
    # artifact-invariant plans dedup into the default entry
    for p in (kops.make_plan(backend="ref", layout="rows64"),
              kops.make_plan(backend="pallas"),
              kops.make_plan(backend="ref", schedule="dense")):
        assert kops.cache_key(prog, p) == kdef
    assert kops.is_compiled(prog) and kops.is_compiled(prog, retuned)
    # one entry, both schedule kinds: a dense run fills the same slot
    kops.run_program(prog, ins, 3, kops.make_plan(backend="ref",
                                                  schedule="dense"))
    assert kops.is_compiled(prog, kops.make_plan(backend="ref",
                                                 schedule="dense"))
    key = kops.pin_program(prog)                     # default plan only
    try:
        assert key == kdef
        assert kdef in kops._pinned and kret not in kops._pinned
    finally:
        assert kops.unpin_program(key) is False


# ---------------------------------------------------------- serve requests

def test_serve_request_layout_key():
    from repro.launch import serve
    r = serve.pim_request({"op": "add", "dtype": "uint8",
                           "x": [10, 20], "y": [1, 2],
                           "layout": "rows64"})
    assert r["result"] == [11, 22]
    bad = serve.pim_request({"op": "add", "dtype": "uint8",
                             "x": [1], "y": [2], "layout": "rows128"})
    assert bad["error"]["code"] == "bad_request"
    assert not bad["error"]["retriable"]
    assert "unknown layout" in bad["error"]["message"]
