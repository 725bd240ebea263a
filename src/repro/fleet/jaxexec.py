"""JAX backend: per-instance op functions, ``jax.vmap`` over the fleet,
``lax.scan`` over the op stream, sharded over a mesh of devices.

The op functions are a straight functional transcription of
:mod:`repro.fleet.stepper` for a *single* instance (scalars + small 1D
arrays); :func:`_batch_step` batches one op step of both lowered programs
over the instance axis and ``lax.scan`` drives the batch down a chunk of
the op stream.  Both programs run on every instance at every op, under
the op's mask (no per-instance ``lax.cond``: running the non-selected
program under a False mask is cheaper than divergence).

Reads and writes at a per-instance index are one-hot selects, and the
epoch advance compacts with shift networks: under ``vmap`` a gather,
scatter or sort with one index per instance lowers to TPU code that
grows with the instance count, and compiles for minutes at 100k
instances.  The opcode interpreter masks each write at its index: a
masked-out instance writes at ``_DROP``, an index the one-hot write
drops, so no whole state array is selected again after an op's row
loop.  The epoch advance runs once per scan step, between the programs'
bail detection and their bodies, over every instance that advances in
either program: one batch-level ``lax.cond``, taken only in steps where
some instance advances.

The compiled step is the XLA module ``jit_chunk``; its ops carry named
scopes in their HLO metadata (``op-enq``, ``op-deq``, ``slots-stack``,
``slots-unstack``; ``epoch-advance`` at step level, beside the op
scopes, not under them; inside an op program its ``guards``
and one ``row-<opcode>`` scope per opcode row kind of the interpreter:
``repro.obs.profiler.PH_FLEET_*``), and the backend records native
spans around its host work (plan packing and upload, dispatch, polls,
the counts' readback, state upload, compile).

The instance axis is sharded over a 1D mesh of the first ``devices`` JAX
devices (TPU chips; on the CPU platform, host devices, of which there
are more only where the caller's environment sets ``XLA_FLAGS=
--xla_force_host_platform_device_count=N``).  It is padded to a device
multiple; padding rows are born inactive.  Each chunk length's step is
compiled ahead of time (:meth:`JaxBackend.prepare`), so compilation is
set-up and the chunk loop runs only compiled code.

All arrays are int32/uint8 -- volatile addresses are offsets, counts are
int32 deltas (converted back to int64 on the host) -- so the backend never
needs jax x64 mode.  Bit-identity with the numpy stepper (and hence with
``run_batched``) is asserted by ``tests/test_fleet_equivalence.py``.

Two steppers share the sections that don't depend on schedule depth
(:func:`_batch_step`: tail record, bail detection, epoch machinery, env
binding + allocations):

* the original **unrolled** stepper (:func:`_apply_one` /
  :func:`make_chunk_fn`) traces every micro/aux entry inline -- fastest
  compiled steps, but the jit trace grows with schedule depth;
* the **opcode interpreter** (:func:`_apply_opcode_one` /
  :func:`make_opcode_chunk_fn`) drives a ``lax.fori_loop`` +
  ``lax.switch`` over the program's :class:`~repro.fleet.lowering.
  OpcodeProgram` table, so the trace size is independent of depth
  (asserted by ``tests/test_fleet_opcode.py``).  The same function is the
  body of the Pallas kernel in :mod:`repro.kernels.fleet_step`.
"""
from __future__ import annotations

import os
from collections import deque
from functools import partial
from pathlib import Path

import numpy as np

from ..core.nvram import (EV_COLD_DRAM, EV_COLD_NVM, EV_DRAM, EV_HIT,
                          EV_POSTFLUSH, LINE_WORDS)
from ..core.opsched import NULL, ST_EVERFL, ST_INVAL
from ..obs.profiler import (
    PH_FLEET_ADVANCE, PH_FLEET_COMPILE, PH_FLEET_COUNTS_READBACK,
    PH_FLEET_COUNTS_WAIT, PH_FLEET_GUARDS, PH_FLEET_KERNEL, PH_FLEET_OP_DEQ,
    PH_FLEET_OP_ENQ, PH_FLEET_PLAN_PACK, PH_FLEET_PLAN_UPLOAD,
    PH_FLEET_POLL_READBACK, PH_FLEET_POLL_WAIT, PH_FLEET_ROW_CLASS_P,
    PH_FLEET_ROW_CLASS_V, PH_FLEET_ROW_LIMBO, PH_FLEET_ROW_PADD,
    PH_FLEET_ROW_PDISCARD, PH_FLEET_ROW_RECACHE, PH_FLEET_ROW_SLOT,
    PH_FLEET_ROW_ST_EVERFL, PH_FLEET_ROW_ST_INVAL, PH_FLEET_SLOTS_STACK,
    PH_FLEET_SLOTS_UNSTACK, PH_FLEET_STATE_UPLOAD, PH_FLEET_STEP_DISPATCH,
    push, span)
from .lowering import (KIND_DEQ, KIND_ENQ, SYM, OPC_CLASS_P,
                       OPC_CLASS_V, OPC_LIMBO, OPC_PADD, OPC_PDISCARD,
                       OPC_RECACHE, OPC_SLOT, OPC_ST_EVERFL, OPC_ST_INVAL,
                       encode_program)
from .state import FleetState, Template
from .stepper import EPOCH_ADV_OPS

N_SYM = max(SYM.values()) + 1

E_NEW_P, E_NEW_V = SYM["new_p"], SYM["new_v"]
E_TAIL_P, E_TAIL_V = SYM["tail_p"], SYM["tail_v"]
E_HEAD_P, E_HEAD_V = SYM["head_p"], SYM["head_v"]
E_NEXT_P, E_NEXT_V = SYM["next_p"], SYM["next_v"]
E_PREV = SYM["prev"]

# FleetState fields carried on device (leading instance axis)
_ARRAY_FIELDS = ("cached", "finval", "everfl", "persisted", "vtouched",
                 "ring_p", "ring_v", "free_p", "vfree",
                 "limbo_a", "limbo_e", "limbo_k")
_SCALAR_FIELDS = ("head", "length", "dummy_p", "dummy_v", "nfree", "cursor",
                  "nvfree", "vcursor", "nlimbo", "epoch", "opsctr",
                  "active", "bail_at")

# the values of the opcode interpreter's row loop that each row opcode
# writes; "cdelta" is the op's count vector
_OPC_WRITES = {
    OPC_CLASS_P: ("cached", "finval", "cdelta"),
    OPC_CLASS_V: ("vtouched", "cdelta"),
    OPC_ST_INVAL: ("cached", "finval", "everfl"),
    OPC_ST_EVERFL: ("everfl",),
    OPC_RECACHE: ("cached", "finval"),
    OPC_LIMBO: ("limbo_a", "limbo_e", "limbo_k", "nlimbo"),
    OPC_SLOT: ("slots",),
    OPC_PDISCARD: ("persisted",),
    OPC_PADD: ("persisted",),
}

# each op program's named scope in the compiled step, by FleetProgram.code
_OP_SCOPE = {KIND_ENQ: PH_FLEET_OP_ENQ, KIND_DEQ: PH_FLEET_OP_DEQ}

# each opcode row kind's named scope: its ``switch`` branch in the row loop
_ROW_SCOPE = {
    OPC_CLASS_P: PH_FLEET_ROW_CLASS_P,
    OPC_CLASS_V: PH_FLEET_ROW_CLASS_V,
    OPC_ST_INVAL: PH_FLEET_ROW_ST_INVAL,
    OPC_ST_EVERFL: PH_FLEET_ROW_ST_EVERFL,
    OPC_RECACHE: PH_FLEET_ROW_RECACHE,
    OPC_LIMBO: PH_FLEET_ROW_LIMBO,
    OPC_SLOT: PH_FLEET_ROW_SLOT,
    OPC_PDISCARD: PH_FLEET_ROW_PDISCARD,
    OPC_PADD: PH_FLEET_ROW_PADD,
}

# the newest compiled chunk steps of this process, for readers of a device
# trace that map an op back to its named scope (:func:`compiled_steps`);
# bounded, since a test process builds many backends
_COMPILED_STEPS = deque(maxlen=4)


def compiled_steps() -> list:
    """The process's newest compiled chunk steps (``jax.stages.Compiled``),
    oldest first; ``as_text()`` of one is its optimized HLO, whose
    ``op_name`` metadata carries the step's named scopes."""
    return list(_COMPILED_STEPS)


# the checkout's root: src/repro/fleet/jaxexec.py -> parents[3]
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile.  A ``JAX_COMPILATION_CACHE_DIR`` set in the environment is
    honoured as JAX read it, and nothing is set in code.  Otherwise the
    cache goes to the checkout's ``.jax_cache``: a fixed path, because the
    path is part of the cache's key.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def state_arrays(state: FleetState) -> dict:
    """The chunk step's state dict for one FleetState batch, as host
    arrays: the on-device layout (``slot_<attr>`` keys, int32 counts)
    before padding."""
    st = {name: getattr(state, name)
          for name in _ARRAY_FIELDS + _SCALAR_FIELDS}
    st["counts"] = state.counts.astype(np.int32)
    for attr, arr in state.slots.items():
        st["slot_" + attr] = arr
    return st


def _take(jnp, x, i):
    """``x[i]`` for one instance's vector ``x`` and traced scalar ``i``,
    with JAX's indexing rules (a negative index counts from the end, an
    out-of-range one clamps), as a one-hot masked sum: under ``vmap`` a
    gather with one index per instance, which the TPU compiler emits as
    code that grows with the instance count."""
    n = x.shape[0]
    i = jnp.clip(jnp.where(i < 0, i + n, i), 0, n - 1)
    return jnp.where(jnp.arange(n) == i, x, 0).sum(dtype=x.dtype)


def _put(jnp, x, i, v):
    """``x.at[i].set(v)`` for traced scalars, as a one-hot select (a
    negative index counts from the end, an out-of-range one is dropped)."""
    n = x.shape[0]
    i = jnp.where(i < 0, i + n, i)
    return jnp.where(jnp.arange(n) == i, jnp.asarray(v).astype(x.dtype), x)


# an index every _put drops (it counts from the end only when negative):
# where a masked-out instance writes
_DROP = np.iinfo(np.int32).max


def _bump(jnp, x, i):
    """``x.at[i].add(1)`` for a traced scalar, as a one-hot add."""
    return x + (jnp.arange(x.shape[0]) == i).astype(x.dtype)


def _shift(jnp, x, s):
    """``y[p] = x[p + s]`` for a static ``s`` (negative: from the left),
    zero where ``p + s`` is out of range."""
    if s == 0:
        return x
    z = jnp.zeros((abs(s),), x.dtype)
    if s > 0:
        return jnp.concatenate([x[s:], z])
    return jnp.concatenate([z, x[:s]])


def _prefix_sum(jnp, x):
    """Inclusive prefix sum of one instance's int32 vector, in log-step
    shifted adds."""
    s = 1
    while s < x.shape[0]:
        x = x + _shift(jnp, x, -s)
        s *= 2
    return x


def _pack(jnp, xs, keep):
    """Stable compaction of one instance's vectors: the ``keep`` entries
    of every array in ``xs`` move to the front, in order.  Returns
    ``(packed, occupied)``; the values of unoccupied slots are
    unspecified.

    A shift network, not a scatter: each kept entry moves left by the
    number of dropped entries before it, one bit of that distance per
    step, low bit first.  No two entries ever land on one slot: two kept
    entries ``D`` apart differ by less than ``D`` in distance."""
    d = jnp.where(keep, _prefix_sum(jnp, (~keep).astype(jnp.int32)), 0)
    occ = keep
    for b in range(keep.shape[0].bit_length()):
        s = 1 << b
        go = occ & (((d >> b) & 1) == 1)
        came = _shift(jnp, go, s)
        xs = [jnp.where(came, _shift(jnp, x, s), x) for x in xs]
        d = jnp.where(came, _shift(jnp, d, s), d)
        occ = (occ & ~go) | came
    return xs, occ


def _advance_one(jnp, dims, c, adv):
    """Epoch advance for one instance (the identity when ``adv`` is False:
    the freed mask is empty and the epoch increment is masked).  Freed
    limbo entries are pushed, in order, onto the free stack of their kind
    (past the stack's capacity they are dropped); the rest keep their
    order at the front of the limbo list, and the freed and unused slots
    follow in their old order -- a stable partition."""
    min_e = c["epoch"]
    c = dict(c)
    c["epoch"] = jnp.where(adv, min_e + 1, min_e)
    lcap = dims.lcap
    j = jnp.arange(lcap, dtype=jnp.int32)
    inl = j < c["nlimbo"]
    fr = inl & (c["limbo_e"] + 2 <= min_e) & adv
    is_p = c["limbo_k"] == 0
    for sel, stack, nkey, slen in ((fr & is_p, "free_p", "nfree", dims.fcap),
                                   (fr & ~is_p, "vfree", "nvfree",
                                    dims.vfcap)):
        (vals,), occ = _pack(jnp, [c["limbo_a"]], sel)
        width = max(lcap, slen)
        if width > lcap:
            pad = jnp.zeros((width - lcap,), jnp.int32)
            vals = jnp.concatenate([vals, pad])
            occ = jnp.concatenate([occ, pad.astype(bool)])
        base = c[nkey]                      # the k-th freed entry -> base + k
        for b in range(width.bit_length()):     # base <= slen <= width
            on = ((base >> b) & 1) == 1
            vals = jnp.where(on, _shift(jnp, vals, -(1 << b)), vals)
            occ = jnp.where(on, _shift(jnp, occ, -(1 << b)), occ)
        c[stack] = jnp.where(occ[:slen], vals[:slen], c[stack])
        c[nkey] = base + sel.sum(dtype=jnp.int32)
    keep = inl & ~fr
    keys = ("limbo_a", "limbo_e", "limbo_k")
    front, occ = _pack(jnp, [c[k] for k in keys], keep)
    back, _ = _pack(jnp, [c[k][::-1] for k in keys], ~keep[::-1])
    for key, f, b in zip(keys, front, back):
        c[key] = jnp.where(occ, f, b[::-1])
    c["nlimbo"] = c["nlimbo"] - fr.sum(dtype=jnp.int32)
    return c


def _tail(jnp, dims, c):
    """(tail_p, tail_v): the newest node, or the dummy when empty."""
    has = c["length"] > 0
    tpos = (c["head"] + jnp.maximum(c["length"] - 1, 0)) % dims.cap
    return (jnp.where(has, _take(jnp, c["ring_p"], tpos), c["dummy_p"]),
            jnp.where(has, _take(jnp, c["ring_v"], tpos), c["dummy_v"]))


def _slot(dims, c, attr):
    """A guard slot's value, from either state layout: per-attr
    ``slot_<attr>`` keys (unrolled stepper) or the stacked ``slots``
    vector (opcode stepper)."""
    if "slots" in c:
        return c["slots"][dims.slot_attrs.index(attr)]
    return c["slot_" + attr]


def _op_begin(jax, dims, prog, c, sel, oi):
    """The front of one lowered op on one instance's state dict: bail
    detection (the program's guards under the ``guards`` scope) and
    op_begin's op counter.  Returns ``(c, m, adv)``: the op's mask and
    whether this op advances the epoch."""
    jnp = jax.numpy
    c = dict(c)
    m = c["active"] & sel
    # ---- bail detection --------------------------------------------------
    bail = jnp.asarray(False)
    if prog.code == KIND_DEQ:
        bail = bail | (c["length"] == 0)
    if prog.guards:
        with jax.named_scope(PH_FLEET_GUARDS):
            tail_p, _ = _tail(jnp, dims, c)
            for g in prog.guards:
                if g[0] == "slot_nonnull":
                    bail = bail | (_slot(dims, c, g[1]) == NULL)
                else:                       # tail_persisted
                    bail = bail | (_take(jnp, c["persisted"],
                                         tail_p // LINE_WORDS) == 0)
    if prog.allocs_p:
        bail = bail | ((c["nfree"] == 0) & (c["cursor"] >= dims.area_cap))
    if prog.allocs_v:
        bail = bail | ((c["nvfree"] == 0) & (c["vcursor"] >= dims.chunk_cap))
    newly = m & bail
    c["bail_at"] = jnp.where(newly, oi, c["bail_at"])
    c["active"] = c["active"] & ~newly
    m = m & ~newly
    # ---- op_begin --------------------------------------------------------
    adv = jnp.asarray(False)
    if prog.uses_ssmem:
        ctr = c["opsctr"] + 1
        adv = m & (ctr >= EPOCH_ADV_OPS)
        c["opsctr"] = jnp.where(m, jnp.where(adv, 0, ctr), c["opsctr"])
    return c, m, adv


def _op_env(jnp, dims, prog, c, m):
    """Env binding and allocations for one instance, after the epoch
    advance; returns ``(c, env)``."""
    c = dict(c)
    env = {}
    if prog.code == KIND_ENQ:
        env[E_TAIL_P], env[E_TAIL_V] = _tail(jnp, dims, c)
    else:
        hpos = c["head"] % dims.cap
        env[E_HEAD_P], env[E_HEAD_V] = c["dummy_p"], c["dummy_v"]
        env[E_NEXT_P] = _take(jnp, c["ring_p"], hpos)
        env[E_NEXT_V] = _take(jnp, c["ring_v"], hpos)
    for attr in prog.slot_attrs:
        env[E_PREV] = _slot(dims, c, attr)
    if prog.allocs_p:
        use = c["nfree"] > 0
        top = _take(jnp, c["free_p"], jnp.maximum(c["nfree"] - 1, 0))
        env[E_NEW_P] = jnp.where(
            use, top, dims.area_base + c["cursor"] * LINE_WORDS)
        c["nfree"] = jnp.where(m & use, c["nfree"] - 1, c["nfree"])
        c["cursor"] = jnp.where(m & ~use, c["cursor"] + 1, c["cursor"])
    if prog.allocs_v:
        use = c["nvfree"] > 0
        top = _take(jnp, c["vfree"], jnp.maximum(c["nvfree"] - 1, 0))
        env[E_NEW_V] = jnp.where(
            use, top, dims.chunk_base + c["vcursor"] * dims.node_words)
        c["nvfree"] = jnp.where(m & use, c["nvfree"] - 1, c["nvfree"])
        c["vcursor"] = jnp.where(m & ~use, c["vcursor"] + 1, c["vcursor"])
    return c, env


def _op_finish(jnp, dims, prog, apply, c, m):
    """The back of one lowered op on one instance's state dict, after the
    step's epoch advance: env binding and allocations, then ``apply(c, m,
    env) -> c``, the op's depth-dependent body."""
    c, env = _op_env(jnp, dims, prog, c, m)
    return apply(c, m, env)


def _batch_step(jax, dims, ops, st, k, oi):
    """One scan step of every op program across a batch of instances
    (leading axis).  ``ops`` is ``[(prog, apply)]``; instance ``i`` runs
    the program whose code is ``k[i]``.  Each program's vmapped bail
    detection and op counter runs under its own mask, then one epoch
    advance for the whole step, then each program's vmapped env binding
    and ``apply``.  Shared by the unrolled and opcode steppers and the
    Pallas kernel.

    The advance runs once per scan step, over every instance that
    advances in any program that uses SSMem, and only in steps where some
    instance advances (``lax.cond`` on the whole batch): under ``vmap``
    its compaction would otherwise run, masked, on every op.  This is
    exact.  The advance is the identity for an instance that does not
    advance, and depends only on the instance's own state.  An instance
    is selected by at most one program per step, and every other
    program's begin, env binding and rows are masked no-ops for it; so
    the numpy stepper's order (begin, advance, finish, one program after
    the other) leaves every instance in the state this order does."""
    jnp, lax = jax.numpy, jax.lax
    masks, adv = [], None
    for prog, _ in ops:
        with jax.named_scope(_OP_SCOPE[prog.code]):
            st, m, a = jax.vmap(partial(_op_begin, jax, dims, prog),
                                in_axes=(0, 0, None))(st, k == prog.code, oi)
        masks.append(m)
        if prog.uses_ssmem:
            adv = a if adv is None else adv | a
    if adv is not None:
        with jax.named_scope(PH_FLEET_ADVANCE):
            st = lax.cond(jnp.any(adv),
                          jax.vmap(partial(_advance_one, jnp, dims)),
                          lambda s, a: s, st, adv)
    for (prog, apply), m in zip(ops, masks):
        with jax.named_scope(_OP_SCOPE[prog.code]):
            st = jax.vmap(partial(_op_finish, jnp, dims, prog, apply))(st, m)
    return st


def _fifo_update(jnp, dims, prog, c, m, env):
    """The logical FIFO update of one instance's state dict ``c``, in
    place, masked by ``m``: an enqueue writes its ring slot at a masked
    index, so a masked-out instance writes nothing."""
    cap = dims.cap
    length, head = c["length"], c["head"]
    if prog.code == KIND_ENQ:
        pos = jnp.where(m, (head + length) % cap, _DROP)
        new_p = env[E_NEW_P] if prog.allocs_p else 0
        new_v = env[E_NEW_V] if prog.allocs_v else 0
        c["ring_p"] = _put(jnp, c["ring_p"], pos, new_p)
        c["ring_v"] = _put(jnp, c["ring_v"], pos, new_v)
        c["length"] = jnp.where(m, length + 1, length)
    else:
        c["dummy_p"] = jnp.where(m, env[E_NEXT_P], c["dummy_p"])
        c["dummy_v"] = jnp.where(m, env[E_NEXT_V], c["dummy_v"])
        c["head"] = jnp.where(m, (head + 1) % cap, head)
        c["length"] = jnp.where(m, length - 1, length)


def _apply_one(jnp, dims, prog, c, m, env):
    """The body of one lowered op on one instance's state dict, masked by
    ``m``, after :func:`_op_env` (unrolled form: every micro/aux entry
    traces inline)."""
    # ---- micro-ops on local copies --------------------------------------
    cached, finval, everfl = c["cached"], c["finval"], c["everfl"]
    vtouched, persisted = c["vtouched"], c["persisted"]
    cdelta = jnp.asarray(prog.base_counts.astype(np.int32))
    one, zero = jnp.uint8(1), jnp.uint8(0)
    for ins in prog.micro:
        tag, ref = ins[0], ins[1]
        a = ref.const if ref.mode == "const" else env[ref.sym] + ref.off
        if tag == "class_p":
            ln = a // LINE_WORDS
            ev = jnp.where(_take(jnp, cached, ln) == 1, EV_HIT,
                           jnp.where(_take(jnp, finval, ln) == 1,
                                     EV_POSTFLUSH,
                                     jnp.where(_take(jnp, everfl, ln) == 1,
                                               EV_COLD_NVM, EV_COLD_DRAM)))
            cdelta = _bump(jnp, cdelta, ev)
            cached = _put(jnp, cached, ln, one)
            finval = _put(jnp, finval, ln, zero)
        elif tag == "class_v":
            ev = jnp.where(_take(jnp, vtouched, a) == 1, EV_HIT, EV_DRAM)
            cdelta = _bump(jnp, cdelta, ev)
            vtouched = _put(jnp, vtouched, a, one)
        elif tag == "state":
            ln = a // LINE_WORDS
            mode = ins[2]
            if mode == ST_INVAL:
                cached = _put(jnp, cached, ln, zero)
                finval = _put(jnp, finval, ln, one)
                everfl = _put(jnp, everfl, ln, one)
            elif mode == ST_EVERFL:
                everfl = _put(jnp, everfl, ln, one)
            else:                           # ST_RECACHE
                cached = _put(jnp, cached, ln, one)
                finval = _put(jnp, finval, ln, zero)
        else:                               # "line"
            ln = a // LINE_WORDS
            cached = _put(jnp, cached, ln, one)
            finval = _put(jnp, finval, ln, zero)
    c["counts"] = jnp.where(m, c["counts"] + cdelta, c["counts"])
    # ---- logical FIFO ----------------------------------------------------
    _fifo_update(jnp, dims, prog, c, m, env)
    # ---- aux effects on local copies ------------------------------------
    limbo_a, limbo_e, limbo_k = c["limbo_a"], c["limbo_e"], c["limbo_k"]
    nlimbo = c["nlimbo"]
    touched_limbo = False
    for ax in prog.aux:
        t0 = ax[0]
        if t0 == "limbo":
            limbo_a = _put(jnp, limbo_a, nlimbo, env[ax[1]])
            limbo_e = _put(jnp, limbo_e, nlimbo, c["epoch"])
            limbo_k = _put(jnp, limbo_k, nlimbo,
                           jnp.uint8(0 if ax[2] == "p" else 1))
            nlimbo = nlimbo + 1
            touched_limbo = True
        elif t0 == "slot":
            key = "slot_" + ax[1]
            c[key] = jnp.where(m, env[ax[2]], c[key])
        elif t0 == "pdiscard":
            persisted = _put(jnp, persisted, env[ax[1]] // LINE_WORDS,
                             zero)
        else:                               # padd
            for sym in ax[1]:
                persisted = _put(jnp, persisted, env[sym] // LINE_WORDS,
                                 one)
    if touched_limbo:
        c["limbo_a"] = jnp.where(m, limbo_a, c["limbo_a"])
        c["limbo_e"] = jnp.where(m, limbo_e, c["limbo_e"])
        c["limbo_k"] = jnp.where(m, limbo_k, c["limbo_k"])
        c["nlimbo"] = jnp.where(m, nlimbo, c["nlimbo"])
    # commit the line/word-state locals
    c["cached"] = jnp.where(m, cached, c["cached"])
    c["finval"] = jnp.where(m, finval, c["finval"])
    c["everfl"] = jnp.where(m, everfl, c["everfl"])
    c["vtouched"] = jnp.where(m, vtouched, c["vtouched"])
    c["persisted"] = jnp.where(m, persisted, c["persisted"])
    return c


def _scan_chunk(jax, st, kcols, oi, step_op):
    """``lax.scan`` over the chunk's op stream of ``step_op(st, k, o)``,
    a whole-batch step.  ``kcols`` is (N, C) uint8, ``oi`` (C,) int32
    global op indices (shared across instances)."""
    def step(st, xs):
        return step_op(st, *xs), None
    out, _ = jax.lax.scan(step, st, (kcols.T, oi))
    return out


def _per_device(jax, chunk, mesh):
    """``chunk`` on each device's own rows of the instance axis
    (``shard_map`` over the mesh axis ``"i"``; ``mesh=None`` leaves it to
    the compiler).  Instances are independent, so each device steps its
    rows, and takes the epoch advance's batch-level ``lax.cond`` on them,
    with no collective."""
    if mesh is None:
        return chunk
    from jax.sharding import PartitionSpec
    rows = PartitionSpec("i")
    # check_vma off: lax.switch would reject branches that differ only
    # in which values vary across devices (a constant count vector
    # against one updated from the rows)
    return jax.shard_map(chunk, mesh=mesh,
                         in_specs=(rows, rows, PartitionSpec()),
                         out_specs=rows, check_vma=False)


def make_chunk_fn(jax, programs, dims, mesh=None):
    """-> chunk(st, kcols, oi): a lax.scan over the chunk's op stream,
    each step every op program vmapped over the instances with one epoch
    advance (:func:`_batch_step`), per device of ``mesh``
    (:func:`_per_device`)."""
    import jax.numpy as jnp

    step_op = partial(_batch_step, jax, dims,
                      [(p, partial(_apply_one, jnp, dims, p))
                       for p in programs])

    def chunk(st, kcols, oi):
        return _scan_chunk(jax, st, kcols, oi, step_op)

    return _per_device(jax, chunk, mesh)


def _apply_opcode_one(jnp, lax, dims, prog, opc, c, m, env,
                      table=None, base_counts=None):
    """The body of one lowered op on one instance's state dict, masked by
    ``m``, after :func:`_op_env` -- data-driven form: a ``fori_loop`` +
    ``switch`` interprets the int32 opcode table instead of tracing each
    micro/aux entry, so the jaxpr does not grow with schedule depth.
    Requires the stacked ``slots`` state layout (see
    :func:`make_opcode_chunk_fn`).  Bit-identical to :func:`_apply_one`,
    in the same effect order.

    Each row writes under the mask: a masked-out instance writes at
    ``_DROP``, which :func:`_put` drops, so no state array is selected
    again after the loop.  The loop carries only what some opcode of the
    table writes (:data:`_OPC_WRITES`), and its ``switch`` holds a branch
    for those opcodes only; the table is a host constant, so this is
    settled at trace time from the program's content.

    Each opcode's branch runs under its ``row-<opcode>`` named scope
    (:data:`_ROW_SCOPE`).

    ``table`` / ``base_counts`` default to trace constants from
    ``opc`` / ``prog``; the Pallas kernel passes them explicitly (a
    kernel cannot capture array constants)."""
    import jax
    # dense env vector for data-driven sym gathers (static keys)
    envv = jnp.zeros((N_SYM,), jnp.int32)
    for k, v in env.items():
        envv = envv.at[k].set(v)
    if table is None:
        table = jnp.asarray(opc.table)          # (R, 5) int32
    if base_counts is None:
        base_counts = jnp.asarray(prog.base_counts.astype(np.int32))
    present = sorted(set(opc.table[:, 0].tolist()) & _OPC_WRITES.keys())
    keys = sorted({k for op in present for k in _OPC_WRITES[op]})
    epoch = c["epoch"]
    one, zero = jnp.uint8(1), jnp.uint8(0)

    def row_step(r, t):
        row = table[r]
        kind, amode, aval, off, imm = (row[0], row[1], row[2], row[3],
                                       row[4])
        bound = envv[jnp.clip(aval, 0, N_SYM - 1)] + off
        a = jnp.where(amode == 1, bound, aval)
        ln = a // LINE_WORDS
        at_a, at_ln = jnp.where(m, a, _DROP), jnp.where(m, ln, _DROP)

        def b_nop(t):
            return t

        def b_class_p(t):
            everfl = t.get("everfl", c["everfl"])
            ev = jnp.where(_take(jnp, t["cached"], ln) == 1, EV_HIT,
                           jnp.where(_take(jnp, t["finval"], ln) == 1,
                                     EV_POSTFLUSH,
                                     jnp.where(_take(jnp, everfl, ln) == 1,
                                               EV_COLD_NVM, EV_COLD_DRAM)))
            return dict(t, cached=_put(jnp, t["cached"], at_ln, one),
                        finval=_put(jnp, t["finval"], at_ln, zero),
                        cdelta=_bump(jnp, t["cdelta"], ev))

        def b_class_v(t):
            ev = jnp.where(_take(jnp, t["vtouched"], a) == 1, EV_HIT, EV_DRAM)
            return dict(t, vtouched=_put(jnp, t["vtouched"], at_a, one),
                        cdelta=_bump(jnp, t["cdelta"], ev))

        def b_st_inval(t):
            return dict(t, cached=_put(jnp, t["cached"], at_ln, zero),
                        finval=_put(jnp, t["finval"], at_ln, one),
                        everfl=_put(jnp, t["everfl"], at_ln, one))

        def b_st_everfl(t):
            return dict(t, everfl=_put(jnp, t["everfl"], at_ln, one))

        def b_recache(t):
            return dict(t, cached=_put(jnp, t["cached"], at_ln, one),
                        finval=_put(jnp, t["finval"], at_ln, zero))

        def b_limbo(t):
            nl = t["nlimbo"]
            at = jnp.where(m, nl, _DROP)
            return dict(t, limbo_a=_put(jnp, t["limbo_a"], at, a),
                        limbo_e=_put(jnp, t["limbo_e"], at, epoch),
                        limbo_k=_put(jnp, t["limbo_k"], at, imm),
                        nlimbo=nl + m.astype(nl.dtype))

        def b_slot(t):
            return dict(t, slots=_put(jnp, t["slots"],
                                      jnp.where(m, imm, _DROP), a))

        def b_pdiscard(t):
            return dict(t, persisted=_put(jnp, t["persisted"], at_ln, zero))

        def b_padd(t):
            return dict(t, persisted=_put(jnp, t["persisted"], at_ln, one))

        branch = {OPC_CLASS_P: b_class_p, OPC_CLASS_V: b_class_v,
                  OPC_ST_INVAL: b_st_inval, OPC_ST_EVERFL: b_st_everfl,
                  OPC_RECACHE: b_recache, OPC_LIMBO: b_limbo,
                  OPC_SLOT: b_slot, OPC_PDISCARD: b_pdiscard,
                  OPC_PADD: b_padd}

        def scoped(op):                 # the branch under its row scope
            def run(t):
                with jax.named_scope(_ROW_SCOPE[op]):
                    return branch[op](t)
            return run

        # branch 0 is the NOP; opcode present[i] is branch i + 1
        idx = jnp.int32(0)
        for i, op in enumerate(present, 1):
            idx = jnp.where(kind == op, i, idx)
        return lax.switch(idx, [b_nop] + [scoped(op) for op in present], t)

    t = {k: base_counts if k == "cdelta" else c[k] for k in keys}
    # micro rows, then the logical FIFO update, then aux rows -- the same
    # effect order as _apply_one / the numpy stepper
    t = lax.fori_loop(0, opc.n_micro, row_step, t)
    _fifo_update(jnp, dims, prog, c, m, env)
    t = lax.fori_loop(opc.n_micro, opc.n_rows, row_step, t)
    cdelta = t.pop("cdelta", base_counts)
    c.update(t)
    c["counts"] = jnp.where(m, c["counts"] + cdelta, c["counts"])
    return c


def make_opcode_chunk_fn(jax, programs, dims, mesh=None):
    """Opcode-interpreting variant of :func:`make_chunk_fn` -- same
    ``chunk(st, kcols, oi)`` signature and the same state-dict layout
    outside the call (``slot_<attr>`` keys are stacked into a ``slots``
    matrix around the scan).  The jit trace holds one ``row_step`` body
    per op kind regardless of schedule depth."""
    import jax.numpy as jnp
    from jax import lax

    step_op = partial(_batch_step, jax, dims,
                      [(p, partial(_apply_opcode_one, jnp, lax, dims, p,
                                   encode_program(p, dims.slot_attrs)))
                       for p in programs])

    def chunk(st, kcols, oi):
        st = dict(st)
        with jax.named_scope(PH_FLEET_SLOTS_STACK):
            if dims.slot_attrs:
                st["slots"] = jnp.stack(
                    [st.pop("slot_" + a) for a in dims.slot_attrs], axis=-1)
            else:
                st["slots"] = jnp.zeros((kcols.shape[0], 1), jnp.int32)
        out = _scan_chunk(jax, st, kcols, oi, step_op)
        with jax.named_scope(PH_FLEET_SLOTS_UNSTACK):
            slots = out.pop("slots")
            for i, a in enumerate(dims.slot_attrs):
                out["slot_" + a] = slots[:, i]
        return out

    return _per_device(jax, chunk, mesh)


class JaxBackend:
    """Device-resident fleet state; same protocol as NumpyBackend.  The
    instance axis is sharded over the first ``devices`` JAX devices."""
    name = "jax"

    def __init__(self, template: Template, state: FleetState,
                 devices: int = 1):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        self.jax = jax
        self.t = template
        self.n = state.n
        self.devices = devices
        self.mesh = mesh = Mesh(np.array(jax.devices()[:devices]), ("i",))
        self.sharding = NamedSharding(mesh, PartitionSpec("i"))
        self.replicated = NamedSharding(mesh, PartitionSpec())
        self.npad = self._padded(state.n)

        def put(name, a):
            pad = self.npad - self.n
            if pad:
                tile = (np.zeros((pad,), dtype=a.dtype) if name == "active"
                        else np.repeat(a[:1], pad, axis=0))
                a = np.concatenate([a, tile], axis=0)
            return self._put(a)

        arrays = state_arrays(state)
        nbytes = self.npad * sum(a.nbytes // self.n for a in arrays.values())
        with span(PH_FLEET_STATE_UPLOAD, bytes=nbytes):
            self.st = {name: put(name, a) for name, a in arrays.items()}
        self._fn = self._make_fn()
        self._exe = {}

    def _padded(self, n: int) -> int:
        return -(-n // self.devices) * self.devices

    def _put(self, a):
        return self.jax.device_put(a, self.sharding)

    def _make_fn(self):
        return self.jax.jit(make_chunk_fn(self.jax, self.t.programs,
                                          self.t.dims, self.mesh),
                            donate_argnums=(0,))

    def _compiled(self, C: int):
        """The chunk step for chunk length ``C``, compiled ahead of time
        for the state's shapes and shardings."""
        if C not in self._exe:
            jax = self.jax
            st = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                          sharding=v.sharding)
                  for k, v in self.st.items()}
            kc = jax.ShapeDtypeStruct((self.npad, C), np.uint8,
                                      sharding=self.sharding)
            oi = jax.ShapeDtypeStruct((C,), np.int32,
                                      sharding=self.replicated)
            with span(PH_FLEET_COMPILE, C=C):
                self._exe[C] = self._fn.lower(st, kc, oi).compile()
            _COMPILED_STEPS.append(self._exe[C])
        return self._exe[C]

    def prepare(self, chunk_lengths) -> None:
        for C in chunk_lengths:
            self._compiled(C)

    def run_chunk(self, kinds: np.ndarray, start: int) -> None:
        C = kinds.shape[0]
        with span(PH_FLEET_PLAN_PACK, start=start):
            kc = np.zeros((self.npad, C), dtype=np.uint8)
            kc[:self.n] = kinds.T
            oi = np.arange(start, start + C, dtype=np.int32)
        with span(PH_FLEET_PLAN_UPLOAD, start=start,
                  bytes=kc.nbytes + oi.nbytes):
            kc = self._put(kc)
            oi = self.jax.device_put(oi, self.replicated)
        step = self._compiled(C)
        with span(PH_FLEET_STEP_DISPATCH, start=start):
            self.st = step(self.st, kc, oi)

    def poll(self):
        bail_at, active = self.st["bail_at"], self.st["active"]
        with span(PH_FLEET_POLL_WAIT):
            self.jax.block_until_ready((bail_at, active))
        with span(PH_FLEET_POLL_READBACK,
                  bytes=bail_at.nbytes + active.nbytes):
            bail_at = np.asarray(bail_at)[:self.n]
            active = np.asarray(active)[:self.n]
            fresh = (~active) & (bail_at >= 0)
            ids = np.nonzero(fresh)[0]
        return ids, bail_at

    def _set_rows(self, i: int, values: dict) -> None:
        st = dict(self.st)
        for name, val in values.items():
            # re-place: the compiled step accepts only its own shardings
            st[name] = self._put(st[name].at[i].set(val))
        self.st = st

    def rejoin(self, i: int, row: dict) -> None:
        values = {}
        for name, val in row.items():
            if name == "slots":
                for attr, v in val.items():
                    values["slot_" + attr] = v
            elif name == "counts":
                values["counts"] = val.astype(np.int32)
            else:
                values[name] = val
        values["active"] = True
        values["bail_at"] = -1
        self._set_rows(i, values)

    def retire_resident(self, i: int) -> None:
        from .runner import RESIDENT
        self._set_rows(i, {"active": False, "bail_at": RESIDENT})

    def counts(self) -> np.ndarray:
        """The counts on the host.  Opens the ``counts-readback`` span,
        which the caller closes (``pop``) once it has merged them."""
        with span(PH_FLEET_COUNTS_WAIT):
            self.jax.block_until_ready(self.st)
        counts = self.st["counts"]
        push(PH_FLEET_COUNTS_READBACK, bytes=counts.nbytes)
        return np.asarray(counts)[:self.n].astype(np.int64)


class OpcodeJaxBackend(JaxBackend):
    """JaxBackend with the opcode-interpreting chunk fn: identical state
    layout and protocol, but the jit trace no longer scales with schedule
    depth -- the win is compile time on deep schedules, at some per-step
    cost (a ``switch`` per table row instead of straight-line code)."""
    name = "jax-opcode"

    def _make_fn(self):
        return self.jax.jit(make_opcode_chunk_fn(self.jax, self.t.programs,
                                                 self.t.dims, self.mesh),
                            donate_argnums=(0,))


class PallasBackend(JaxBackend):
    """Opcode interpreter as a Pallas kernel: instances map to the grid in
    blocks, each program id steps its block's state rows through the whole
    chunk.  It runs in Pallas interpret mode on the CPU platform (still
    the kernel's dataflow, evaluated by XLA:CPU) -- what CI's
    ``fleet-pallas-smoke`` exercises.  Mosaic refuses the kernel on TPU,
    so the runner rejects ``pallas`` off the CPU before building anything.
    Single-device: the grid replaces the mesh sharding of the base
    backend."""
    name = "pallas"
    chunk_phase = PH_FLEET_KERNEL
    block = 128

    def __init__(self, template: Template, state: FleetState,
                 devices: int = 1):
        # shrink the block for tiny fleets (tests): padding 5 instances to
        # a 128-row block would cost 25x the interpret-mode work
        self.block = min(self.block, -(-state.n // 8) * 8)
        super().__init__(template, state, devices)

    def _padded(self, n: int) -> int:
        return -(-n // self.block) * self.block

    def _make_fn(self):
        from ..kernels.fleet_step import make_pallas_chunk_fn
        return make_pallas_chunk_fn(self.jax, self.t.programs, self.t.dims,
                                    block=self.block, interpret=True)
