"""The fleet's chunk steps compile for a TPU v5e, without the chip.

jaxlib's TPU compiler compiles for a described topology that is not
attached, so these tests catch what the chip's compiler would refuse
(lowering gaps, memory that does not fit, unwanted collectives) at no chip
time.  Nothing runs: they say nothing about results or times.

The topology is described inside a module-scoped fixture, never while
this module is imported: only one process at a time may load the TPU
library, and the test workers all import every test module.  Keep every
such compile in this one file, so that one worker loads the library.
"""
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import (Mesh, NamedSharding, PartitionSpec,  # noqa: E402
                          SingleDeviceSharding)

from repro.fleet import jaxexec  # noqa: E402
from repro.fleet.lowering import OPC_LIMBO, encode_program  # noqa: E402
from repro.fleet.state import build_template, replicate  # noqa: E402

QUEUES = ["DurableMSQ", "OptLinkedQ"]
INSTANCES = 1024
OPS, PREFILL, CHUNK = 96, 10, 48      # the chip smoke's per-instance widths
HBM_BYTES = 16 * 2**30                # one v5e chip
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|collective-permute|"
                        r"all-to-all|reduce-scatter)\b")
# per-instance indexing under vmap: the TPU compiler emits code per
# instance for these (889 MB and minutes of compile at 100k instances)
INDEXED = re.compile(r"\b(gather|scatter|sort)\(")
# an op program's whole-array select outside its row loop
OP_SELECT = re.compile(r'op_name="[^"]*/op-(enq|deq)/vmap\(jit\(_where\)\)'
                       r'/select_n"')
# an enqueue program's row loop
ENQ_ROW_LOOP = re.compile(r'op_name="[^"]*/op-enq/vmap\(\)/while"')


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to a persistent cache
        # but cannot be read back without the chip: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _lowered_args(queue, n, sharding, replicated):
    """-> (template, (state, kcols, oi)): the chunk step's arguments as
    shapes at the template's real per-instance widths, ``n`` instances."""
    t = build_template(queue, "optane-clwb", OPS, PREFILL)
    host = jaxexec.state_arrays(replicate(t.row, t.dims, 1))
    state = {k: jax.ShapeDtypeStruct((n,) + v.shape[1:], v.dtype,
                                     sharding=sharding)
             for k, v in host.items()}
    kcols = jax.ShapeDtypeStruct((n, CHUNK), np.uint8, sharding=sharding)
    oi = jax.ShapeDtypeStruct((CHUNK,), np.int32, sharding=replicated)
    return t, (state, kcols, oi)


@pytest.mark.parametrize("make", ["make_opcode_chunk_fn", "make_chunk_fn"])
@pytest.mark.parametrize("queue", QUEUES)
def test_chunk_step_compiles_for_one_v5e_chip(topo, queue, make):
    """jax-opcode and the unrolled jax step compile for one chip; the
    donated state is aliased to the output, the program fits HBM, and no
    gather, scatter or sort is left in it."""
    one = SingleDeviceSharding(topo.devices[0])
    t, args = _lowered_args(queue, INSTANCES, one, one)
    fn = jax.jit(getattr(jaxexec, make)(jax, t.programs, t.dims),
                 donate_argnums=(0,))
    compiled = fn.lower(*args).compile()
    assert not INDEXED.findall(compiled.as_text())
    mem = compiled.memory_analysis()
    state_bytes = sum(s.size * s.dtype.itemsize for s in args[0].values())
    assert mem.alias_size_in_bytes >= state_bytes * 0.99
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("queue", QUEUES)
def test_op_programs_leave_limbo_to_their_row_loops(topo, queue):
    """Each opcode row writes under the op's mask, so no op program
    selects a whole ``[N, lcap]`` limbo array after its row loop; and the
    enqueue program, whose table has no ``OPC_LIMBO`` row, carries no
    limbo array through its row loop."""
    one = SingleDeviceSharding(topo.devices[0])
    t, args = _lowered_args(queue, INSTANCES, one, one)
    enq = encode_program(t.programs.enq, t.dims.slot_attrs)
    assert OPC_LIMBO not in enq.table[:, 0]
    fn = jax.jit(jaxexec.make_opcode_chunk_fn(jax, t.programs, t.dims),
                 donate_argnums=(0,))
    lines = fn.lower(*args).compile().as_text().splitlines()
    limbo = f"[{INSTANCES},{t.dims.lcap}]"
    selects = [ln.split(" fusion(")[0] for ln in lines
               if " fusion(" in ln and OP_SELECT.search(ln)]
    assert selects                  # the per-instance scalars' commits
    assert not [ln for ln in selects if limbo in ln]
    loops = [ln.split(" while(")[0] for ln in lines
             if " while(" in ln and ENQ_ROW_LOOP.search(ln)]
    assert loops
    assert not [ln for ln in loops if limbo in ln]


def test_opcode_step_shards_over_four_chips_without_collectives(topo):
    """The instance axis splits over a 4-chip mesh with no communication:
    instances are independent, so the per-device program (the epoch
    advance's ``lax.cond`` included) holds no collective."""
    mesh = Mesh(np.array(topo.devices[:4]), ("i",))
    t, args = _lowered_args("OptLinkedQ", 4 * INSTANCES,
                            NamedSharding(mesh, PartitionSpec("i")),
                            NamedSharding(mesh, PartitionSpec()))
    fn = jax.jit(jaxexec.make_opcode_chunk_fn(jax, t.programs, t.dims,
                                              mesh),
                 donate_argnums=(0,))
    text = fn.lower(*args).compile().as_text()
    assert not COLLECTIVE.findall(text), sorted(set(COLLECTIVE.findall(text)))


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="Mosaic refuses the fleet kernel "
                          "(dynamic_slice in Pallas TPU lowering); the "
                          "lane-major redesign must make this pass")
@pytest.mark.parametrize("queue", QUEUES)
def test_pallas_kernel_compiles_for_v5e(topo, queue):
    from repro.kernels.fleet_step import make_pallas_chunk_fn
    one = SingleDeviceSharding(topo.devices[0])
    t, args = _lowered_args(queue, INSTANCES, one, one)
    fn = make_pallas_chunk_fn(jax, t.programs, t.dims, block=128,
                              interpret=False)
    fn.lower(*args).compile()


def test_named_scopes_leave_the_v5e_step_as_it_was(topo, monkeypatch):
    """The chunk step's named scopes (``epoch-advance``, ``op-enq``,
    ``op-deq``, ...) are metadata only: with them the v5e step is the
    module ``jit_chunk`` naming them, and its code is the size it is with
    every scope left out."""
    import contextlib
    one = SingleDeviceSharding(topo.devices[0])
    t, args = _lowered_args("OptLinkedQ", INSTANCES, one, one)

    def compiled():
        fn = jax.jit(jaxexec.make_opcode_chunk_fn(jax, t.programs, t.dims),
                     donate_argnums=(0,))
        return fn.lower(*args).compile()
    scoped = compiled()
    text = scoped.as_text()
    assert text.startswith("HloModule jit_chunk,")
    for scope in ("epoch-advance", "op-enq", "op-deq"):
        assert f"/{scope}/" in text, scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled()
    assert "/epoch-advance/" not in bare.as_text()
    assert (scoped.memory_analysis().generated_code_size_in_bytes
            == bare.memory_analysis().generated_code_size_in_bytes)
