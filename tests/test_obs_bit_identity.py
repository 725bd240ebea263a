"""The observability layer's non-interference gate.

``repro.obs`` is observation-only: attaching a :class:`PhaseProfiler` to
``run_batched`` (which threads it through ClockScheduler's heap loop, the
columnar record store's staged sync and the bail path) or a profiler +
:class:`Heartbeat` to ``run_fleet`` must leave every per-thread Stats
counter, linearization event, op record and simulated clock *bit
identical* to the untelemetered run -- the same contract the PR-3 trace
tap and the columnar engine are held to (`tests/test_fastpath_equivalence.py`).
"""
import io
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import ALL_QUEUES, MEMORY_MODELS, QueueHarness
from repro.fleet import FleetConfig, run_fleet
from repro.obs import Heartbeat, PhaseProfiler, profiler
from benchmarks.workloads import make_plans

QUEUES8 = sorted(ALL_QUEUES)


def _run(qname, model, profile=None, nthreads=3, ops=30, seed=0):
    h = QueueHarness(ALL_QUEUES[qname], nthreads=nthreads,
                     area_nodes=256, model=model)
    plans, prefill = make_plans("mixed5050", nthreads, ops, seed=seed)
    for i in range(prefill):
        h.queue.enqueue(0, ("pre", i))
    res = h.run_batched(plans, profile=profile)
    return h, res


@pytest.mark.parametrize("model", sorted(MEMORY_MODELS))
@pytest.mark.parametrize("qname", QUEUES8)
def test_profiled_run_bit_identical(qname, model):
    """8 queues x 3 models: profiler on vs off, everything identical."""
    h_ref, r_ref = _run(qname, model)
    prof = PhaseProfiler()
    h_obs, r_obs = _run(qname, model, profile=prof)
    s_ref, s_obs = h_ref.nvram.stats, h_obs.nvram.stats
    for t in s_ref:
        assert s_ref[t] == s_obs[t], (
            f"{qname}/{model}: thread {t} Stats diverge under profiling\n"
            f"  off: {s_ref[t]}\n  on:  {s_obs[t]}")
    assert r_ref.events == r_obs.events
    assert r_ref.ops == r_obs.ops
    assert r_ref.sim_time_ns == r_obs.sim_time_ns
    assert h_ref.queue.drain(0) == h_obs.queue.drain(0)
    # and the profiler actually observed the run
    assert len(r_obs.ops) > 0 and prof.total_ns() > 0
    assert "bookkeeping" in prof.totals


def test_profiled_run_covers_wall_and_names_exec_phases():
    """The profiled columnar run attributes time to the documented phases
    and the phase sum accounts for (essentially all of) the wall clock
    of ``run_batched`` -- the region the profiler instruments (harness
    construction and the per-primitive prefill are outside it)."""
    import time
    h = QueueHarness(ALL_QUEUES["DurableMSQ"], nthreads=4,
                     area_nodes=256, model="optane-clwb")
    plans, prefill = make_plans("mixed5050", 4, 200, seed=0)
    for i in range(prefill):
        h.queue.enqueue(0, ("pre", i))
    prof = PhaseProfiler()
    t0 = time.perf_counter()
    h.run_batched(plans, profile=prof)
    wall = time.perf_counter() - t0
    assert {"heap-loop", "interpreted-body", "bookkeeping"} <= set(prof.totals)
    per = prof.us_per_op(800)
    assert all(v >= 0 for v in per.values())
    # push/pop hand off at a shared timestamp, so covered time can only be
    # lost outside run_batched -- coverage must sit tight under 1.0
    assert 0.9 <= prof.coverage(wall) <= 1.01, (prof.coverage(wall), wall)


def test_profiler_detached_after_run():
    """run_batched must not leave the profiler hooked into the record
    store once it returns (a later unprofiled run would be polluted)."""
    prof = PhaseProfiler()
    h, _ = _run("DurableMSQ", "optane-clwb", profile=prof)
    assert h._rstore is None or h._rstore.profiler is None
    assert prof._stack == []  # every push matched by a pop


def _fleet_cfg():
    return FleetConfig(queue="DurableMSQ", instances=400, ops=24,
                       chunk=12, backend="numpy", seed=7)


def test_fleet_telemetry_bit_identical_and_heartbeat_emits():
    """Fleet cell: profiler + heartbeat on vs off -- identical counts,
    bails and residents; heartbeat lines land on the given stream."""
    ref = run_fleet(_fleet_cfg())
    prof = PhaseProfiler()
    stream = io.StringIO()
    hb = Heartbeat(interval_s=0.0, stream=stream, label="fleet-test")
    obs = run_fleet(_fleet_cfg(), profile=prof, heartbeat=hb)
    assert (ref.counts == obs.counts).all()
    assert ref.bails == obs.bails and ref.residents == obs.residents
    assert {"lowering", "chunk-step"} <= set(prof.totals)
    lines = stream.getvalue().splitlines()
    assert lines and lines[-1].startswith("# fleet-test-done:")
    assert any("-heartbeat:" in ln for ln in lines[:-1])
    assert "100.0%" in lines[-1]


def test_fleet_quiet_without_heartbeat():
    """No heartbeat object -> nothing written anywhere (the --quiet /
    test-suite default)."""
    res = run_fleet(_fleet_cfg())
    assert res.counts.shape[0] == 400


def _traced_fleet(devices, trace_dir):
    """A jax-opcode fleet run untraced, then under ``jax.profiler`` with a
    fresh native span record -> (counts identical, the record's span
    counts, the trace's host span counts, the expected span counts)."""
    import jax
    cfg = FleetConfig(queue="OptLinkedQ", instances=48, ops=16, chunk=8,
                      backend="jax-opcode", devices=devices, seed=5)
    ref = run_fleet(cfg)
    rec, kept = profiler.SpanRecord(), profiler.RECORD
    profiler.RECORD = rec
    try:
        jax.profiler.start_trace(trace_dir)
        obs = run_fleet(cfg, profile=PhaseProfiler())
        jax.profiler.stop_trace()
    finally:
        profiler.RECORD = kept
    pd = jax.profiler.ProfileData.from_file(
        str(next(Path(trace_dir).rglob("*.xplane.pb"))))
    traced = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in profiler.FLEET_SPANS:
                        traced[e.name] = traced.get(e.name, 0) + 1
    chunks = -(-cfg.ops // cfg.chunk)
    per = {"template": 1, "replicate": 1, "state-upload": 1, "compile": 1,
           "counts-wait": 1, "counts-readback": 1}
    per.update({name: chunks for name in (
        "plan-pack", "plan-upload", "step-dispatch", "poll-wait",
        "poll-readback")})
    same = bool((ref.counts == obs.counts).all()) and ref.bails == obs.bails
    return same, rec.count, traced, per


@pytest.mark.parametrize("devices", [1, 4])
def test_traced_jax_fleet_spans_and_bit_identity(devices, tmp_path):
    """Under the profiler, every native span of the fleet opens once per
    chunk or once per pass, in the record and in the trace, and the counts
    equal an untraced run's bit for bit.  Four devices are virtual CPU
    devices, in a process of their own."""
    pytest.importorskip("jax")
    if devices == 1:
        same, recorded, traced, per = _traced_fleet(devices, str(tmp_path))
    else:
        root = Path(__file__).resolve().parents[1]
        code = ("import json, test_obs_bit_identity as t\n"
                f"print(json.dumps(t._traced_fleet({devices}, "
                f"{str(tmp_path)!r})))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count="
                             f"{devices}",
                   PYTHONPATH=os.pathsep.join(
                       [str(root / "src"), str(root), str(root / "tests")]))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        same, recorded, traced, per = json.loads(out.stdout.splitlines()[-1])
    assert same
    assert recorded == per
    assert traced == per
