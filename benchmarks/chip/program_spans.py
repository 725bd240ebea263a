"""What the program records of itself, for the per-layer readers.

Two records, both kept in the process that ran the cell:

* the native spans of the fleet executor (``repro.obs.profiler.RECORD``):
  for each span name its newest host intervals ``(start_ns, end_ns,
  bytes)`` on ``time.perf_counter_ns``, the clock the harness's own spans
  (``Context.spans``, seconds) are taken on;
* the compiled chunk steps (``repro.fleet.jaxexec.compiled_steps``), whose
  optimized HLO names every device op of the trace and carries, in each
  op's ``op_name`` metadata, the ``jax.named_scope`` it was traced under.

A program that keeps neither (one from before they were added) gives
``None`` here, and the readers then read nothing.  The span and scope
names are written out below, not imported: they are part of what the
metrics measure.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Set, Tuple

from trace_reduce import CHUNK_PROGRAM, Event

# the fleet's native spans and the runner's phases (program spans)
NATIVE_SPANS = ("template", "replicate", "state-upload", "compile",
                "plan-pack", "plan-upload", "step-dispatch", "poll-wait",
                "poll-readback", "counts-wait", "counts-readback")
RUNNER_PHASES = ("lowering", "chunk-step", "kernel-interpret", "poll",
                 "bail-replay", "resident-replay")
# the named scopes of the compiled chunk step
SCOPES = ("epoch-advance", "op-enq", "op-deq", "slots-stack",
          "slots-unstack")


def record():
    """The program's native span record, or None where it keeps none."""
    try:
        from repro.obs import profiler
    except ImportError:
        return None
    return getattr(profiler, "RECORD", None)


def host_window_ns(ctx) -> Optional[Tuple[float, float]]:
    """The traced window on the host clock, in ns: the harness's
    ``window`` span."""
    for name, start, end in ctx.spans:
        if name == "window":
            return start * 1e9, end * 1e9
    return None


def window_intervals(ctx, name: str) -> Optional[List[tuple]]:
    """The intervals of native span ``name`` that lie in the traced
    window, or None where there are none to read."""
    rec, win = record(), host_window_ns(ctx)
    if rec is None or win is None:
        return None
    lo, hi = win
    found = [iv for iv in rec.intervals.get(name, ())
             if lo <= iv[0] and iv[1] <= hi]
    return found or None


def window_mean_ms(ctx, name: str) -> Optional[float]:
    """Mean host milliseconds of native span ``name`` in the traced
    window (one per chunk or per pass, as the span opens)."""
    found = window_intervals(ctx, name)
    if found is None:
        return None
    return sum(e - s for s, e, _ in found) / len(found) / 1e6


def setup_s(ctx, names) -> Optional[float]:
    """Host seconds of the set-up spans ``names`` of this run: those that
    started no earlier than the newest ``template`` span to end before the
    window, and ended before the window opened.  A run builds one
    template, so the spans of an earlier run in the same process are left
    out."""
    rec, win = record(), host_window_ns(ctx)
    if rec is None or win is None:
        return None
    opened = win[0]
    built = [s for s, e, _ in rec.intervals.get("template", ())
             if e <= opened]
    if not built:
        return None
    since = max(built)
    found = [e - s for name in names
             for s, e, _ in rec.intervals.get(name, ())
             if since <= s and e <= opened]
    if not found:
        return None
    return sum(found) / 1e9


def program_intervals(ctx) -> Optional[List[Tuple[float, float]]]:
    """Every program span of the traced window on the trace's clock: the
    runner's phases as the trace holds them, and the native spans mapped
    from the host clock by the two ends of the ``window`` span, which
    both clocks hold."""
    rec, win = record(), host_window_ns(ctx)
    if rec is None or win is None or ctx.window is None:
        return None
    (hlo, hhi), (lo, hi) = win, ctx.window
    scale = (hi - lo) / (hhi - hlo)
    out = [(lo + (s - hlo) * scale, lo + (e - hlo) * scale)
           for name in NATIVE_SPANS
           for s, e, _ in rec.intervals.get(name, ())
           if e >= hlo and s <= hhi]
    out += [(s, s + d) for name, s, d in ctx.trace.host
            if name in RUNNER_PHASES]
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=(%[\w.\-]+)|branch_computations=\{([^}]*)\}")


def scopes_of_hlo(text: str) -> Dict[str, Set[str]]:
    """-> {op name: the named scopes (``SCOPES``) it runs under} for every
    instruction of an optimized HLO module's text.  An op is under a scope
    that its own ``op_name`` names, or that the op calling its computation
    is under (a conditional's branches, a loop's body)."""
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
        own[op] = (set(name.group(1).split("/")) & set(SCOPES)
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


def op_scopes() -> Optional[Dict[str, Set[str]]]:
    """The scopes of the newest compiled chunk step of this process, or
    None where the program keeps no compiled step."""
    try:
        from repro.fleet import jaxexec
    except ImportError:
        return None
    steps = getattr(jaxexec, "compiled_steps", None)
    found = steps() if steps is not None else []
    if not found:
        return None
    return scopes_of_hlo(found[-1].as_text())


def chunk_ops(trace, device: int, lo: float, hi: float) -> List[Event]:
    """The op events of a device that ran inside a run of the chunk
    program (``CHUNK_PROGRAM``) within [lo, hi]: op names repeat across
    programs, so ops of another module are left out."""
    runs = sorted((s, s + d) for name, s, d in trace.devices[device]["modules"]
                  if CHUNK_PROGRAM.match(name) and lo <= s and s + d <= hi)
    out, i = [], 0
    for ev in sorted(trace.devices[device]["ops"], key=lambda e: e[1]):
        while i < len(runs) and runs[i][1] < ev[1]:
            i += 1
        if i < len(runs) and runs[i][0] <= ev[1] \
                and ev[1] + ev[2] <= runs[i][1]:
            out.append(ev)
    return out
