"""Opcode-table gates: encoding round-trips and the compile-time bound.

Two contracts from the opcode stepper PR:

* ``encode_program`` emits a fixed-width int32 table that decodes back to
  the source :class:`repro.fleet.lowering.FleetProgram`'s effect entries
  exactly (validated at every encode; tampered or malformed tables are
  rejected) -- for all 8 queues x 3 memory models;
* the opcode-interpreting chunk fn's jaxpr does **not** grow with
  schedule depth (the unrolled stepper's does -- that asymmetry is the
  whole reason the opcode backend exists).
"""
import dataclasses

import numpy as np
import pytest

from repro.core.harness import ALL_QUEUES
from repro.fleet.lowering import (OPC_NOP, OPC_SLOT, OPCODE_COLUMNS,
                                  FleetLoweringError, FleetPrograms,
                                  OpcodeProgram, decode_opcodes,
                                  encode_program, validate_opcodes)
from repro.fleet.state import build_template, replicate

MODELS = ["optane-clwb", "eadr", "cxl"]


def _all_templates(ops=32):
    for q in ALL_QUEUES:
        for m in MODELS:
            yield build_template(q, m, ops=ops)


def test_encode_round_trips_all_queues_and_models():
    """Every lowered program encodes, and the decode reproduces its
    micro/aux entries (normal form: line -> recache, padd expanded)."""
    n = 0
    for t in _all_templates():
        for prog in t.programs:
            opc = encode_program(prog, t.dims.slot_attrs)
            assert opc.table.dtype == np.int32
            assert opc.table.shape[1] == OPCODE_COLUMNS
            assert 0 <= opc.n_micro <= opc.n_rows
            # encode_program already validates; decode once more here so
            # the test fails loudly if validation is ever weakened
            micro, aux = decode_opcodes(opc, t.dims.slot_attrs)
            assert len(micro) >= len([i for i in prog.micro])
            n += 1
    assert n == len(ALL_QUEUES) * len(MODELS) * 2


def test_nop_padding_is_inert_and_monotonic():
    t = build_template("DurableMSQ", "optane-clwb", ops=16)
    opc = encode_program(t.programs.enq, t.dims.slot_attrs)
    padded = opc.padded(opc.n_rows + 5)
    assert padded.n_rows == opc.n_rows + 5
    assert (padded.table[opc.n_rows:, 0] == OPC_NOP).all()
    assert decode_opcodes(padded, t.dims.slot_attrs) == \
        decode_opcodes(opc, t.dims.slot_attrs)
    with pytest.raises(ValueError):
        opc.padded(opc.n_rows - 1)


def test_validate_rejects_tampered_table():
    """Flipping any row's opcode must fail the round-trip validation."""
    t = build_template("OptLinkedQ", "optane-clwb", ops=16)
    prog = t.programs.enq
    opc = encode_program(prog, t.dims.slot_attrs)
    bad = opc.table.copy()
    bad[0, 0] = OPC_NOP if bad[0, 0] != OPC_NOP else OPC_SLOT
    with pytest.raises(FleetLoweringError):
        validate_opcodes(prog, OpcodeProgram(table=bad, n_micro=opc.n_micro),
                         t.dims.slot_attrs)


def test_validate_rejects_wrong_shape_and_region():
    t = build_template("DurableMSQ", "optane-clwb", ops=16)
    prog = t.programs.enq
    opc = encode_program(prog, t.dims.slot_attrs)
    with pytest.raises(FleetLoweringError):
        validate_opcodes(prog, OpcodeProgram(
            table=opc.table.astype(np.int64), n_micro=opc.n_micro),
            t.dims.slot_attrs)
    # a micro row pushed into the aux region is a structural error
    with pytest.raises(FleetLoweringError):
        decode_opcodes(OpcodeProgram(table=opc.table, n_micro=0),
                       t.dims.slot_attrs)


def test_encode_rejects_slot_outside_layout():
    """An aux slot store whose attribute is missing from the fleet-wide
    guard-slot layout cannot be encoded."""
    hit = False
    for t in _all_templates(ops=16):
        for prog in t.programs:
            if any(ax[0] == "slot" for ax in prog.aux):
                with pytest.raises(FleetLoweringError):
                    encode_program(prog, ())
                hit = True
    assert hit, "no queue with a guarded slot store? layout changed"


# ---- compile-time bound ---------------------------------------------------

jax = pytest.importorskip("jax", reason="trace-size tests need jax")


def _count_eqns(obj):
    """Total equations in a (closed) jaxpr, recursing into sub-jaxprs
    carried by scan/while/cond/pjit params."""
    if hasattr(obj, "jaxpr"):
        return _count_eqns(obj.jaxpr)
    total = len(obj.eqns)
    for eqn in obj.eqns:
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(sub, "jaxpr") or hasattr(sub, "eqns"):
                    total += _count_eqns(sub)
    return total


def _deepen(programs, k):
    """A synthetic deep schedule: the same programs with k copies of the
    micro sequence (still encodable and traceable -- semantics don't
    matter here, trace size does)."""
    return FleetPrograms(
        enq=dataclasses.replace(programs.enq, micro=programs.enq.micro * k),
        deq=dataclasses.replace(programs.deq, micro=programs.deq.micro * k))


def test_opcode_trace_size_independent_of_schedule_depth():
    """The acceptance bound: 8x deeper schedules leave the opcode chunk
    fn's jaxpr equation count unchanged, while the unrolled chunk fn's
    grows -- and on the deep variant the opcode trace is the smaller."""
    from repro.fleet.jaxexec import (make_chunk_fn, make_opcode_chunk_fn,
                                     state_arrays)

    t = build_template("DurableMSQ", "optane-clwb", ops=16)
    st = state_arrays(replicate(t.row, t.dims, 4))
    kcols = np.zeros((4, 8), dtype=np.uint8)
    oi = np.arange(8, dtype=np.int32)
    deep = _deepen(t.programs, 8)

    def eqns(make, programs):
        fn = make(jax, programs, t.dims)
        return _count_eqns(jax.make_jaxpr(fn)(st, kcols, oi))

    opcode_shallow = eqns(make_opcode_chunk_fn, t.programs)
    opcode_deep = eqns(make_opcode_chunk_fn, deep)
    unrolled_shallow = eqns(make_chunk_fn, t.programs)
    unrolled_deep = eqns(make_chunk_fn, deep)

    assert opcode_shallow == opcode_deep, (
        f"opcode trace scaled with depth: {opcode_shallow} -> {opcode_deep}")
    assert unrolled_deep > unrolled_shallow
    assert opcode_deep < unrolled_deep


# ---- masked row writes ----------------------------------------------------

BITS = ("cached", "finval", "everfl", "vtouched", "persisted")


def _busy_state(t, n, seed):
    """``n`` instances of the template's chunk-step state with random line
    and word bits and limbo contents, so that rows read and write values
    that differ between instances."""
    from repro.fleet.jaxexec import state_arrays
    st = state_arrays(replicate(t.row, t.dims, n))
    rng = np.random.default_rng(seed)
    for k in BITS:
        st[k] = rng.integers(0, 2, st[k].shape).astype(st[k].dtype)
    st["nlimbo"] = rng.integers(0, t.dims.lcap - 8, n).astype(np.int32)
    for k in ("limbo_a", "limbo_e"):
        st[k] = rng.integers(0, 5000, st[k].shape).astype(np.int32)
    return st


@pytest.mark.parametrize("queue", list(ALL_QUEUES))
def test_opcode_body_writes_only_under_the_mask(queue):
    """For both op programs of each queue, ``_apply_opcode_one`` leaves a
    masked-out instance's state bit-identical, every array and ``nlimbo``
    included, and gives a selected instance what the parent's formula
    gives: the unrolled body's unmasked result, committed under the mask
    (``where(m, new, old)``)."""
    from jax import lax
    jnp = jax.numpy
    from repro.fleet.jaxexec import _apply_one, _apply_opcode_one, _op_env

    t = build_template(queue, "optane-clwb", ops=32)
    dims, n = t.dims, 16
    st = {k: jnp.asarray(v) for k, v in _busy_state(t, n, 15).items()}
    m = jnp.asarray(np.arange(n) % 3 != 0)
    slot_keys = ["slot_" + a for a in dims.slot_attrs]
    for prog in t.programs:
        opc = encode_program(prog, dims.slot_attrs)

        def opcode(c, m):
            c = dict(c)
            slots = [c.pop(k) for k in slot_keys]
            c["slots"] = jnp.stack(slots) if slots else jnp.zeros(
                (1,), jnp.int32)
            c, env = _op_env(jnp, dims, prog, c, m)
            c = _apply_opcode_one(jnp, lax, dims, prog, opc, c, m, env)
            slots = c.pop("slots")
            c.update({k: slots[i] for i, k in enumerate(slot_keys)})
            return c

        def unrolled(c):
            c, env = _op_env(jnp, dims, prog, c, jnp.asarray(True))
            return _apply_one(jnp, dims, prog, c, jnp.asarray(True), env)

        got = jax.jit(jax.vmap(opcode))(st, m)
        new = jax.jit(jax.vmap(unrolled))(st)
        assert sorted(got) == sorted(st)
        mask = np.asarray(m)
        for k in st:
            old = np.asarray(st[k])
            g = np.asarray(got[k])
            np.testing.assert_array_equal(g[~mask], old[~mask],
                                          err_msg=f"{prog.code} {k}")
            sel = mask.reshape((n,) + (1,) * (old.ndim - 1))
            np.testing.assert_array_equal(
                g, np.where(sel, np.asarray(new[k]), old),
                err_msg=f"{prog.code} {k}")
        assert (np.asarray(got["counts"])[mask]
                != np.asarray(st["counts"])[mask]).any()
