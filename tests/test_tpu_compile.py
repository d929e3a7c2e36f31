"""Compile rehearsals for a TPU v5e that is described, not attached: the
kernels of the main path, at the sizes the chip runs them, go through the
TPU compiler (Mosaic for the Pallas kernels) from this CPU process.  A
kernel Mosaic refuses, or a program that does not fit the chip's 16 GiB,
fails here without any chip time.  Nothing runs, so these say nothing
about results or speed.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and pytest-xdist workers all import
this file (see the on-chip measurement notes in README.md)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.pim_numerics import program_for
from repro.kernels import ops as kops
from repro.kernels import pim_exec
from repro.kernels.slots import pim_exec_ref_slots_fused

V5E_HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                yield topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:    # noqa: BLE001 -- no TPU compiler
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _resolved(kind, op, param):
    """(program, resolved default-plan binding) with inputs x, y."""
    prog = program_for(kind, op, param)
    plan = kops.make_plan(backend="ref")
    return prog, kops.compiled(prog, plan).resolve(prog, plan, ("x", "y"))


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, f"{total / 2**30:.2f} GiB on a 16 GiB chip"
    return total


def test_static_slots_kernel_lowers_through_mosaic(one_chip):
    """The static-slice levelized kernel (``schedule="slots-static"``,
    ``backend="pallas"``): fp16 add over 1 Mi rows."""
    _, r = _resolved("fp-serial", "add", "fp16")
    run = pim_exec.make_slots_static(r.sched, r.in_widths, r.out_widths,
                                     r.names, interpret=False)
    c = run.lower(_sds((2, 1 << 20), jnp.uint32, one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


@pytest.mark.parametrize("kind,op,param", [
    ("int-serial", "add", 16),
    ("fp-serial", "div", "fp32"),     # the longest serial program
])
def test_gate_serial_kernel_lowers_through_mosaic(one_chip, kind, op,
                                                  param):
    """The gate-serial kernel (``levelized=False``): the whole cell axis
    of a tile sits in VMEM and the four lowered gate arrays arrive by
    scalar prefetch in SMEM -- fp32 division (about 12k gates, 1.8k
    cells) is the largest of both."""
    ops_, a, b, o, n_cells = program_for(kind, op, param).to_arrays()
    gates = [_sds(np.shape(v), jnp.int32, one_chip) for v in (ops_, a, b, o)]
    n_words = (1 << 20) // 32
    c = pim_exec.pim_exec_padded.lower(
        _sds((n_cells, n_words), jnp.uint32, one_chip), *gates,
        n_cells=n_cells, interpret=False).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


def _ref_slots_args(r, rows, data_sharding, index_sharding):
    return ((_sds((2, rows), jnp.uint32, data_sharding),)
            + tuple(_sds(v.shape, v.dtype, index_sharding)
                    for v in (r.in_idx, r.la, r.lb, r.lo, r.out_idx)))


def _ref_slots_static(r):
    return dict(n_cells=r.sched.n_cells, one_cell=r.one_cell,
                in_widths=r.in_widths, out_widths=r.out_widths,
                in_base=r.in_base, out_base=r.out_base, planes=1)


def test_ref_slots_full_memory_fits_one_chip(one_chip):
    """The default executor (``ref`` slot scan) for fp16 add over the
    paper's full memory, 64 Mi rows as one dispatch, fits one v5e."""
    _, r = _resolved("fp-serial", "add", "fp16")
    c = pim_exec_ref_slots_fused.lower(
        *_ref_slots_args(r, 1 << 26, one_chip, one_chip),
        **_ref_slots_static(r)).compile()
    assert _fits(c) > (2 << 30)       # the 2 GiB packed state is in there


def test_row_sharded_full_memory_on_four_chips(topo):
    """The same 64 Mi rows sharded over a 2x2 host's four chips: a
    quarter of the state per chip and no collective."""
    mesh = Mesh(np.array(topo.devices[:4]), ("rows",))
    _, r = _resolved("fp-serial", "add", "fp16")
    fn = kops._sharded_exec(pim_exec_ref_slots_fused, mesh, True, 2,
                            **_ref_slots_static(r))
    c = fn.lower(*_ref_slots_args(r, 1 << 26,
                                  NamedSharding(mesh, P(None, "rows")),
                                  NamedSharding(mesh, P()))).compile()
    assert _fits(c) < (2 << 30)
    text = c.as_text()
    for coll in ("all-gather", "all-reduce", "collective-permute",
                 "all-to-all"):
        assert coll not in text, coll
