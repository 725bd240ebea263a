"""Own device time of the chunk step's ops under given named scopes.

The readers of the scopes inside an op program (its ``guards`` and the
``row-<opcode>`` branches of its row loop) name the scopes they read.
Such a scope can be the first one opened inside a ``vmap``, and the op's
``op_name`` then shows it wrapped in the transform (``op-enq/vmap(guards)/
...``), which :func:`program_spans.scopes_of_hlo` does not read; the walk
here unwraps it, and otherwise reads an op's scopes as that one does: its
own ``op_name``, and the scopes of the ops that call its computation.
"""
from __future__ import annotations

import re
from typing import Dict, FrozenSet, Iterable, List, Optional, Set

from program_spans import (_CALLS, _COMPUTATION, _INSTRUCTION, _OP_NAME,
                           chunk_ops)
from trace_reduce import self_times

_TRANSFORM = re.compile(r"^[\w\-]+\((.*)\)$")     # vmap(guards) -> guards


def _scope(component: str) -> str:
    while True:
        m = _TRANSFORM.match(component)
        if not m:
            return component
        component = m.group(1)


def scopes_of_hlo(text: str, names: FrozenSet[str]) -> Dict[str, Set[str]]:
    """-> {op name: the scopes of ``names`` it runs under} for every
    instruction of an optimized HLO module's text."""
    own: Dict[str, Set[str]] = {}
    caller_of: Dict[str, List[str]] = {}
    home: Dict[str, str] = {}
    computation = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = m.group(1)
        home[op] = computation
        name = _OP_NAME.search(line)
        own[op] = ({_scope(c) for c in name.group(1).split("/")} & names
                   if name else set())
        for one, many in _CALLS.findall(line):
            for callee in ([one] if one else many.split(",")):
                caller_of.setdefault(callee.strip(), []).append(op)
    done: Dict[str, Set[str]] = {}

    def under(op: str) -> Set[str]:
        if op not in done:
            done[op] = own[op].union(
                *(under(c) for c in caller_of.get(home[op], ())))
        return done[op]

    return {op: under(op) for op in own}


def op_scopes(names: Iterable[str]) -> Optional[Dict[str, Set[str]]]:
    """The scopes of ``names`` of the newest compiled chunk step of this
    process, or None where the program keeps no compiled step."""
    try:
        from repro.fleet import jaxexec
    except ImportError:
        return None
    steps = getattr(jaxexec, "compiled_steps", None)
    found = steps() if steps is not None else []
    if not found:
        return None
    return scopes_of_hlo(found[-1].as_text(), frozenset(names))


def ns_per_op(ctx, names: Iterable[str]) -> Optional[float]:
    """Device nanoseconds of the ops under any of ``names`` per simulated
    queue-op on that device, averaged over the devices: their own time
    (less that of the ops nested in them) inside runs of the chunk
    program in the window.  None where no op of the step is under them or
    none of them ran."""
    if ctx.trace is None or ctx.window is None or not ctx.trace.devices:
        return None
    scopes = op_scopes(names)
    if scopes is None:
        return None
    under = {op for op, found in scopes.items() if found}
    if not under:
        return None
    times = []
    for dev in ctx.trace.devices:
        ops = chunk_ops(ctx.trace, dev, *ctx.window)
        times.append(sum(own for name, _, own in self_times(ops)
                         if name in under))
    if not any(times):
        return None
    return sum(times) / len(times) / (ctx.passes * ctx.tenants_per_device
                                      * ctx.ops)
