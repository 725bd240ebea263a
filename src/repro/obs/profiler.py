"""Scoped per-phase timers for the execution layers.

A :class:`PhaseProfiler` is a stack of named phases over a single
monotonic clock.  ``push(name)`` charges the elapsed time since the last
transition to the phase currently on top, then makes ``name`` the
current phase; ``pop()`` charges the top phase and resumes its parent at
the same timestamp.  Because every transition hands the clock from one
phase to the next with no gap, the sum over ``totals`` equals the wall
time between the outermost push and pop *exactly* -- the "phase sum
within 10% of wall" acceptance check holds by construction, with the
profiler's own overhead attributed to whichever phase was running when
the timer fired.

Phase names are plain strings so `repro.core` never imports this module:
the scheduler, record store, fleet runner and crash sweep take an
optional profiler object and call ``push``/``pop`` on it (duck-typed).
The canonical names used by the batched-execution layers are the
``PH_*`` constants below; `benchmarks/run.py profile` maps them to CSV
columns by replacing ``-`` with ``_``.

The fleet executor also records **native spans** of its own (:func:`span`,
:func:`push`, :func:`pop`): set-up, plan packing and upload, the step's
dispatch, the polls and the end-of-pass gather.  Each span opens a
``jax.profiler.TraceAnnotation`` -- only where jax is already imported, so
a numpy-backend run never imports it -- and so lands in a device trace on
the trace's clock; and each adds to the process-wide :data:`RECORD`
(count, host nanoseconds and ``bytes`` per name, plus the newest host
intervals).  Native spans never go through a caller's ``prof``.  This
module imports nothing outside the standard library, which is why
`repro.fleet` may import it (and no other part of `repro.obs`).
"""
import sys
from collections import deque
from contextlib import contextmanager
from time import perf_counter_ns
from typing import Deque, Dict, Iterator, List, Optional, Tuple

# Batched-execution phases (ClockScheduler / RecordStore / harness).
PH_HEAP = "heap-loop"               # heap pop/push + cursor advance
PH_INTERP_BODY = "interpreted-body" # compiled per-op fn (columnar/timed body)
PH_CHARGE = "record-charging"       # RecordStore.sync vector pass + flush_counts
PH_BOOKKEEPING = "bookkeeping"      # plan/thunk setup, store attach, teardown
PH_BAIL_REAL = "bail-real-op"       # fast-path bail: real per-primitive op

# Burst-execution phases (repro.core.burst; nested under heap-loop).
PH_BURST_PREDICT = "burst-predict"  # pool + duration/interleave prediction
PH_BURST_VERIFY = "burst-verify"    # plan + vector automaton + key compare
PH_BURST_APPLY = "burst-vector-apply"  # commit: staging, stores, splice
PH_BURST_REPLAY = "mispredict-replay"  # rejected bursts on the merged runner

# Fleet phases (repro.fleet.runner), pushed into a caller's ``prof``.
PH_FLEET_LOWER = "lowering"         # build_fleet, state upload, AOT compile
PH_FLEET_CHUNK = "chunk-step"       # backend.run_chunk
PH_FLEET_KERNEL = "kernel-interpret"   # the pallas backend's chunk phase
PH_FLEET_POLL = "poll"              # backend.poll: bail detection
PH_FLEET_BAIL = "bail-replay"       # per-instance replay + export + rejoin
PH_FLEET_RESIDENT = "resident-replay"  # instances finishing outside the fleet

# Fleet native spans (``span``/``push``/``pop``), nested in those phases.
PH_FLEET_TEMPLATE = "template"      # build_template: warm harness, lowering
PH_FLEET_REPLICATE = "replicate"    # the template row tiled on the host
PH_FLEET_STATE_UPLOAD = "state-upload"  # state padded and placed on devices
PH_FLEET_COMPILE = "compile"        # one chunk length's step, lower+compile
PH_FLEET_PLAN_PACK = "plan-pack"    # a chunk's padded, transposed plans
PH_FLEET_PLAN_UPLOAD = "plan-upload"    # plans and op indices to devices
PH_FLEET_STEP_DISPATCH = "step-dispatch"  # the call into the compiled step
PH_FLEET_POLL_WAIT = "poll-wait"    # waiting on the step's bail_at/active
PH_FLEET_POLL_READBACK = "poll-readback"  # bail_at/active to the host
PH_FLEET_COUNTS_WAIT = "counts-wait"    # waiting on the pass's last step
PH_FLEET_COUNTS_READBACK = "counts-readback"  # counts to host, int64, merge
FLEET_SPANS = (PH_FLEET_TEMPLATE, PH_FLEET_REPLICATE, PH_FLEET_STATE_UPLOAD,
               PH_FLEET_COMPILE, PH_FLEET_PLAN_PACK, PH_FLEET_PLAN_UPLOAD,
               PH_FLEET_STEP_DISPATCH, PH_FLEET_POLL_WAIT,
               PH_FLEET_POLL_READBACK, PH_FLEET_COUNTS_WAIT,
               PH_FLEET_COUNTS_READBACK)

# Named scopes in the fleet's compiled chunk step (HLO op_name metadata).
PH_FLEET_ADVANCE = "epoch-advance"  # the step's one lax.cond of the advance
PH_FLEET_OP_ENQ = "op-enq"          # one step of the enqueue program
PH_FLEET_OP_DEQ = "op-deq"          # one step of the dequeue program
PH_FLEET_SLOTS_STACK = "slots-stack"      # slot_<attr> keys -> slots matrix
PH_FLEET_SLOTS_UNSTACK = "slots-unstack"  # and back, after the scan
PH_FLEET_GUARDS = "guards"          # an op program's bail guards
# one opcode row kind of an op program's row loop (its ``switch`` branch)
PH_FLEET_ROW_CLASS_P = "row-class-p"      # persistent line classified
PH_FLEET_ROW_CLASS_V = "row-class-v"      # volatile word classified
PH_FLEET_ROW_ST_INVAL = "row-st-inval"    # line flushed (invalidated)
PH_FLEET_ROW_ST_EVERFL = "row-st-everfl"  # line flushed (kept cached)
PH_FLEET_ROW_RECACHE = "row-recache"      # line cached again
PH_FLEET_ROW_LIMBO = "row-limbo"          # limbo append
PH_FLEET_ROW_SLOT = "row-slot"            # guard slot stored
PH_FLEET_ROW_PDISCARD = "row-pdiscard"    # persisted-node hint cleared
PH_FLEET_ROW_PADD = "row-padd"            # persisted-node hint set

# Crash-sweep phases (repro.crash.sweep).
PH_CRASH_CAPTURE = "capture"        # boundary capture run
PH_CRASH_RESTORE = "restore"        # snapshot restore + log truncation
PH_CRASH_RECOVER = "recover"        # crash_and_recover
PH_CRASH_CHECK = "check"            # drain + durable-linearizability check


class PhaseProfiler:
    """Accumulates wall nanoseconds and entry counts per named phase."""

    __slots__ = ("totals", "counts", "_stack")

    def __init__(self) -> None:
        self.totals: Dict[str, int] = {}   # phase -> ns
        self.counts: Dict[str, int] = {}   # phase -> entries
        self._stack: List[list] = []       # [name, resumed_at_ns]

    def push(self, name: str) -> None:
        now = perf_counter_ns()
        stack = self._stack
        if stack:
            top = stack[-1]
            totals = self.totals
            totals[top[0]] = totals.get(top[0], 0) + now - top[1]
        stack.append([name, now])
        counts = self.counts
        counts[name] = counts.get(name, 0) + 1

    def pop(self) -> None:
        now = perf_counter_ns()
        name, since = self._stack.pop()
        totals = self.totals
        totals[name] = totals.get(name, 0) + now - since
        if self._stack:
            self._stack[-1][1] = now

    @contextmanager
    def phase(self, name: str) -> Iterator["PhaseProfiler"]:
        self.push(name)
        try:
            yield self
        finally:
            self.pop()

    def total_ns(self) -> int:
        """Sum over all phases (open phases counted up to their last
        transition only; call with an empty stack for exact totals)."""
        return sum(self.totals.values())

    def us_per_op(self, ops: int) -> Dict[str, float]:
        """totals as microseconds per op (ops <= 0 yields raw µs)."""
        div = ops if ops > 0 else 1
        return {k: v / 1000.0 / div for k, v in self.totals.items()}

    def coverage(self, wall_s: float) -> float:
        """Fraction of a measured wall time the phase sum accounts for."""
        if wall_s <= 0:
            return 0.0
        return self.total_ns() / (wall_s * 1e9)

    def as_dict(self) -> Dict[str, Dict[str, int]]:
        """JSON-ready ``{phase: {"ns": ..., "count": ...}}`` (manifests)."""
        return {name: {"ns": ns, "count": self.counts.get(name, 0)}
                for name, ns in sorted(self.totals.items())}

    def merge(self, other: Optional["PhaseProfiler"]) -> "PhaseProfiler":
        """Fold another profiler's totals/counts into this one."""
        if other is not None:
            for name, ns in other.totals.items():
                self.totals[name] = self.totals.get(name, 0) + ns
            for name, n in other.counts.items():
                self.counts[name] = self.counts.get(name, 0) + n
        return self

    def report(self, ops: int = 0, indent: str = "  ") -> str:
        """Human-readable per-phase table (µs/op when ops given)."""
        lines = []
        total = self.total_ns() or 1
        for name, ns in sorted(self.totals.items(), key=lambda kv: -kv[1]):
            frac = 100.0 * ns / total
            if ops > 0:
                lines.append(f"{indent}{name:<18} {ns / 1000.0 / ops:8.3f} "
                             f"us/op  {frac:5.1f}%  x{self.counts.get(name, 0)}")
            else:
                lines.append(f"{indent}{name:<18} {ns / 1e6:10.3f} ms  "
                             f"{frac:5.1f}%  x{self.counts.get(name, 0)}")
        return "\n".join(lines)


Interval = Tuple[int, int, int]            # start ns, end ns, bytes


class SpanRecord:
    """The native spans of one process: per name, how often it opened
    (``count``), its host nanoseconds (``ns``), its ``bytes`` attribute
    summed (``bytes``), and its newest ``keep`` host intervals
    (``intervals``: ``(start_ns, end_ns, bytes)`` on ``perf_counter_ns``).
    Spans nest: ``pop`` closes the newest open one.  Not thread-safe; the
    fleet opens spans from one thread."""

    __slots__ = ("count", "ns", "bytes", "intervals", "keep", "_stack")

    def __init__(self, keep: int = 4096) -> None:
        self.count: Dict[str, int] = {}
        self.ns: Dict[str, int] = {}
        self.bytes: Dict[str, int] = {}
        self.intervals: Dict[str, Deque[Interval]] = {}
        self.keep = keep
        self._stack: List[tuple] = []     # (name, bytes, annotation, start)

    def push(self, name: str, **attrs: int) -> None:
        """Open span ``name``; ``attrs`` (ints such as ``start``, ``bytes``,
        ``C``) go to its trace annotation, ``bytes`` also to the record."""
        ann = None
        jax = sys.modules.get("jax")
        if jax is not None:
            ann = jax.profiler.TraceAnnotation(name, **attrs)
            ann.__enter__()
        self._stack.append((name, attrs.get("bytes", 0), ann,
                            perf_counter_ns()))

    def pop(self) -> None:
        """Close the newest open span."""
        end = perf_counter_ns()
        name, nbytes, ann, start = self._stack.pop()
        if ann is not None:
            ann.__exit__(None, None, None)
        self.count[name] = self.count.get(name, 0) + 1
        self.ns[name] = self.ns.get(name, 0) + end - start
        self.bytes[name] = self.bytes.get(name, 0) + nbytes
        kept = self.intervals.get(name)
        if kept is None:
            kept = self.intervals[name] = deque(maxlen=self.keep)
        kept.append((start, end, nbytes))


#: The process's native span record (replace it to isolate a test).
RECORD = SpanRecord()


def push(name: str, **attrs: int) -> None:
    """Open a native span in :data:`RECORD` (see :meth:`SpanRecord.push`)."""
    RECORD.push(name, **attrs)


def pop() -> None:
    """Close the newest native span opened with :func:`push`."""
    RECORD.pop()


class span:
    """``with span(name, **attrs):`` -- a native span around a block, in
    :data:`RECORD` and, where jax is imported, in the profiler trace."""

    __slots__ = ("name", "attrs")

    def __init__(self, name: str, **attrs: int) -> None:
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        RECORD.push(self.name, **self.attrs)
        return self

    def __exit__(self, *exc) -> None:
        RECORD.pop()
