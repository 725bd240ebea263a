"""Pallas chunk-stepper kernel for the fleet executor.

One ``pl.pallas_call`` advances every instance's Stats-only state through
a whole chunk of the op stream.  The grid tiles the (padded) instance
axis into blocks; each program id owns one block of rows from every state
array, loops over the chunk's ops with ``lax.fori_loop`` and applies the
*same* opcode interpreter the jax-opcode backend scans with
(:func:`repro.fleet.jaxexec._apply_opcode_one`, vmapped over the block
with one epoch advance per op step by
:func:`repro.fleet.jaxexec._batch_step`).
Sharing the interpreter is the point: the kernel adds a memory layout
(explicit per-block refs, one launch per chunk instead of one dispatch
per op), not a second semantics to keep bit-identical.

Bail flags come back through the ``active`` / ``bail_at`` state outputs
-- the runner's poll/rejoin protocol is unchanged.  All state inputs are
aliased to the outputs, so the chunk steps in place.

The kernel runs with ``interpret=True`` on the CPU platform only, which
is what CI's ``fleet-pallas-smoke`` job exercises; the
``tests/test_fleet_equivalence.py`` backend matrix gates bit-identity
with ``run_batched``.  Mosaic refuses it for TPU (``dynamic_slice`` of
the per-op index has no Pallas TPU lowering; the ``switch`` per opcode
row and the ``bool`` refs are behind it); ``tests/test_tpu_compile.py``
pins that refusal.
"""
from __future__ import annotations

from functools import partial

from ..fleet.jaxexec import (_ARRAY_FIELDS, _SCALAR_FIELDS,
                             _apply_opcode_one, _batch_step)
from ..fleet.lowering import encode_program

# state-dict keys in ref order; "slots" is the stacked guard-slot matrix
STATE_KEYS = tuple(_ARRAY_FIELDS) + ("counts", "slots") + \
    tuple(_SCALAR_FIELDS)


def make_pallas_chunk_fn(jax, programs, dims, block: int = 128,
                         interpret: bool = True):
    """-> jit'd ``chunk(st, kcols, oi)`` with the same signature and
    state-dict layout as :func:`repro.fleet.jaxexec.make_chunk_fn`.
    ``kcols`` is (npad, C) uint8 with npad a multiple of ``block``."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    import numpy as np

    progs = [(p, encode_program(p, dims.slot_attrs)) for p in programs]
    # a kernel cannot capture array constants -- the opcode tables and
    # static count vectors ride in as (broadcast) inputs instead
    const_arrays = []
    for p, opc in progs:
        const_arrays.append(opc.table)
        const_arrays.append(p.base_counts.astype(np.int32))
    n_const = len(const_arrays)

    def kernel(kc_ref, oi_ref, *refs):
        consts = [r[...] for r in refs[:n_const]]
        ops = [(prog, partial(_apply_opcode_one, jnp, lax, dims, prog, opc,
                              table=consts[2 * j],
                              base_counts=consts[2 * j + 1]))
               for j, (prog, opc) in enumerate(progs)]
        state_in = refs[n_const:n_const + len(STATE_KEYS)]
        state_out = refs[n_const + len(STATE_KEYS):]
        st = {key: r[...] for key, r in zip(STATE_KEYS, state_in)}
        kc = kc_ref[...]                    # (block, C)
        oi = oi_ref[...]                    # (C,)

        def step_op(ci, st):
            k = lax.dynamic_index_in_dim(kc, ci, axis=1, keepdims=False)
            o = lax.dynamic_index_in_dim(oi, ci, keepdims=False)
            return _batch_step(jax, dims, ops, st, k, o)

        st = lax.fori_loop(0, kc.shape[1], step_op, st)
        for key, r in zip(STATE_KEYS, state_out):
            r[...] = st[key]

    def full_spec(v):
        if v.ndim == 2:
            return pl.BlockSpec(v.shape, lambda i: (0, 0))
        return pl.BlockSpec(v.shape, lambda i: (0,))

    def chunk(st, kcols, oi):
        st = dict(st)
        if dims.slot_attrs:
            st["slots"] = jnp.stack(
                [st.pop("slot_" + a) for a in dims.slot_attrs], axis=-1)
        else:
            st["slots"] = jnp.zeros((kcols.shape[0], 1), jnp.int32)
        vals = [st[key] for key in STATE_KEYS]
        npad, C = kcols.shape

        def row_spec(v):
            if v.ndim == 2:
                return pl.BlockSpec((block, v.shape[1]), lambda i: (i, 0))
            return pl.BlockSpec((block,), lambda i: (i,))

        base = 2 + n_const
        out = pl.pallas_call(
            kernel,
            grid=(npad // block,),
            in_specs=[pl.BlockSpec((block, C), lambda i: (i, 0)),
                      pl.BlockSpec((C,), lambda i: (0,))] +
                     [full_spec(a) for a in const_arrays] +
                     [row_spec(v) for v in vals],
            out_specs=[row_spec(v) for v in vals],
            out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype)
                       for v in vals],
            input_output_aliases={base + j: j for j in range(len(vals))},
            interpret=interpret,
        )(kcols, oi, *const_arrays, *vals)
        res = dict(zip(STATE_KEYS, out))
        slots = res.pop("slots")
        for i, a in enumerate(dims.slot_attrs):
            res["slot_" + a] = slots[:, i]
        return res

    return jax.jit(chunk)
