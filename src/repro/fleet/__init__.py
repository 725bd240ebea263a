"""Fleet executor: the schedule compiler's output, run as array data.

PR 5's :func:`repro.core.opsched.compile_schedule` reduces each queue's
steady-state enqueue/dequeue to one pre-reduced event-count vector plus a
short effect program.  This package lowers that program once more -- into a
**Stats-only vector micro-program** over integer state arrays -- and then
runs 10k-1M *independent queue instances* (one per simulated user/tenant,
one thread each) as a single batched array program:

* :mod:`repro.fleet.lowering` -- ``CompiledOp`` -> :class:`FleetProgram`
  (classification points, line-state updates, guards, allocator and
  epoch-reclamation effects; value stores drop out because per-instance
  ``Stats`` never depend on stored values);
* :mod:`repro.fleet.state` -- build one warmed template harness, export its
  integer state, replicate it across N instances (construction is
  deterministic, so every instance shares the template's address layout);
* :mod:`repro.fleet.stepper` -- the numpy reference stepper (mask-vectorized
  over instances; ``auto`` picks it off-TPU);
* :mod:`repro.fleet.jaxexec` -- the jax backends: a per-instance step
  function, ``jax.vmap`` over the fleet, ``lax.scan`` over the op stream,
  sharded over the first ``FleetConfig.devices`` JAX devices.  Three
  flavors: ``jax`` (unrolled trace), ``jax-opcode`` (interprets the
  fixed-width opcode tables emitted by the lowering, so compile time is
  independent of schedule depth) and ``pallas`` (the same opcode
  interpreter as a Pallas chunk kernel,
  :mod:`repro.kernels.fleet_step`);
* :mod:`repro.fleet.runner` -- chunked execution with the bail/rejoin
  protocol: instances that hit a fast-path bail condition fall out of the
  vector program into a real per-instance harness (the existing
  :class:`repro.core.opsched.FastPathExecutor` path) and rejoin at the next
  chunk boundary.

The correctness gate is the same one every layer of this repo carries:
per-instance fleet Stats (every counter *and* ``time_ns``) are
**bit-identical** to N independent :meth:`repro.core.harness.QueueHarness.
run_batched` runs (``tests/test_fleet_equivalence.py``).  See docs/fleet.md.
"""
from .jaxexec import enable_compile_cache
from .runner import (FleetConfig, FleetResult, build_fleet, check_instances,
                     fleet_kinds, run_fleet)
from .state import build_template

__all__ = [
    "FleetConfig", "FleetResult", "build_fleet", "build_template",
    "check_instances", "enable_compile_cache", "fleet_kinds", "run_fleet",
]
