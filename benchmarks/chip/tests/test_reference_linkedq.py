"""LinkedQ's plain reference agrees with the program, the control does not,
and the readers of the op programs' guard and row scopes read what they
should.

The reference is held to the program's numpy and jax-opcode fleets at a
small size, tenant by tenant, under both traffic files the cells use;
OptLinkedQ's reference is held to them under ``producers`` too, the
traffic its new cell runs.  The readers read a hand-made trace and scope
map, and a small optimized HLO text."""
import json

import numpy as np
import pytest

import generator
import run_cell
import step_scopes
import trace_reduce as tr
from conftest import HERE
from control import control_readings
from reference import tenant_counts
from repro.fleet import FleetConfig, run_fleet
from run_cell import sample_tenants

CONFIGS = {"LinkedQ": "linkedq-optane-400k",
           "OptLinkedQ": "optlinkedq-optane-100k"}


def _config(queue, tenants, ops):
    config = json.loads((HERE / "configs" / f"{CONFIGS[queue]}.json")
                        .read_text())
    config.update(tenants=tenants, ops_per_tenant=ops, chunk=8)
    return config


def _traffic(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("queue,traffic,backend", [
    ("LinkedQ", "mixed5050", "numpy"),
    ("LinkedQ", "mixed5050", "jax-opcode"),
    ("LinkedQ", "producers", "numpy"),
    ("LinkedQ", "producers", "jax-opcode"),
    ("OptLinkedQ", "producers", "numpy"),
    ("OptLinkedQ", "producers", "jax-opcode"),
])
def test_reference_matches_the_fleet(queue, traffic, backend):
    traffic, n, ops = _traffic(traffic), 60, 96
    kinds = generator.generate(n, ops, 2**33 + 11, traffic)
    res = run_fleet(FleetConfig(queue=queue, instances=n, ops=ops,
                                prefill=traffic["prefill"], chunk=48,
                                backend=backend), kinds=kinds)
    assert res.bails == 0
    config = _config(queue, n, ops)
    for i in range(n):
        assert np.array_equal(
            tenant_counts(config, traffic["prefill"], kinds[:, i], i),
            res.counts[i]), f"tenant {i}"


def test_the_reference_takes_one_fence_per_op():
    """Section 5.2's claim, on the reference alone: one fence per
    operation, and flushed lines read back (LinkedQ's post-flush cost)."""
    from reference.memory import FENCE, POST_FLUSH
    traffic, ops = _traffic("mixed5050"), 96
    kinds = generator.generate(1, ops, 3, traffic)[:, 0]
    counts = tenant_counts(_config("LinkedQ", 1, ops), traffic["prefill"],
                           kinds, 0)
    # construction fences the allocator area and the dummy; the prefill,
    # the warm-up pair and the plan one fence each
    assert counts[FENCE] == 2 + traffic["prefill"] + 2 + ops
    assert counts[POST_FLUSH] > 0


@pytest.mark.parametrize("traffic", ["mixed5050", "producers"])
def test_control_is_not_correct(traffic):
    config = _config("LinkedQ", 300, 24)
    mismatched, widest = control_readings(config, _traffic(traffic), 1, 5)
    assert mismatched == len(sample_tenants(300, 1, 5)) and widest > 0


# one device, one run of jit_chunk in the window; own times: %while.1
# holds the rest, %fusion.3 (guards) 200, %fusion.4 (row-slot) 300,
# %fusion.5 (row-padd) 100, %fusion.6 (row-class-p) 400, %cond.2 (a
# row loop's switch, under no row scope) 100
def _trace():
    return tr.Trace(devices={0: {
        "modules": [("jit__lambda", 1000, 100), ("jit_chunk", 1200, 1500)],
        "ops": [("%fusion.3", 1000, 100),         # another module's op
                ("%while.1", 1200, 1500),
                ("%fusion.3", 1300, 200),
                ("%cond.2", 1500, 900),
                ("%fusion.4", 1500, 300),
                ("%fusion.5", 1800, 100),
                ("%fusion.6", 1950, 400)]}},
        host=[("window", 900, 2000)])


SCOPES = {"%while.1": set(), "%cond.2": set(), "%fusion.3": {"guards"},
          "%fusion.4": {"row-slot"}, "%fusion.5": {"row-padd"},
          "%fusion.6": {"row-class-p"}}


def _context(trace, tenants=10, passes=1):
    return run_cell.Context(spans=[], trace=trace, window=trace.window(),
                            tenants_per_device=tenants, ops=96,
                            chunks=[48, 48], passes=passes, dims=None,
                            peaks={})


@pytest.mark.parametrize("metric,own_ns", [("rows_ns_per_op", 800),
                                           ("guards_ns_per_op", 200),
                                           ("hint_rows_ns_per_op", 400)])
def test_readers_take_the_own_time_of_their_scopes(monkeypatch, metric,
                                                   own_ns):
    monkeypatch.setattr(step_scopes, "op_scopes", lambda names: {
        op: found & set(names) for op, found in SCOPES.items()})
    read = run_cell.load_reader(metric)
    assert read(_context(_trace())) == pytest.approx(own_ns / (10 * 96))
    assert read(_context(_trace(), tenants=20, passes=2)) \
        == pytest.approx(own_ns / (2 * 20 * 96))


@pytest.mark.parametrize("metric", ["rows_ns_per_op", "guards_ns_per_op",
                                    "hint_rows_ns_per_op"])
@pytest.mark.parametrize("scopes", [None, {op: set() for op in SCOPES}])
def test_readers_read_nothing_without_their_scopes(monkeypatch, metric,
                                                   scopes):
    """A program that keeps no compiled step, or one whose step names none
    of the scopes (as before they were added), gives nothing."""
    monkeypatch.setattr(step_scopes, "op_scopes", lambda names: scopes)
    assert run_cell.load_reader(metric)(_context(_trace())) is None


HLO = """HloModule jit_chunk, is_scheduled=true

%fused_computation (param_0: s32[4]) -> s32[4] {
  %param_0 = s32[4]{0} parameter(0)
  ROOT %max.1 = s32[4]{0} maximum(s32[4]{0} %param_0, s32[4]{0} %param_0), metadata={op_name="jit(chunk)/while/body/op-enq/vmap(guards)/jit(clip)/max"}
}

%branch (arg.1: s32[4]) -> s32[4] {
  %arg.1 = s32[4]{0} parameter(0)
  %copy.7 = s32[4]{0} copy(s32[4]{0} %arg.1)
  ROOT %select.9 = s32[4]{0} select(s32[4]{0} %copy.7), metadata={op_name="jit(chunk)/while/body/op-deq/vmap()/while/body/cond/branch_4_fun/row-slot/jit(_where)/select_n"}
}

%nop (arg.0: s32[4]) -> s32[4] {
  ROOT %arg.0 = s32[4]{0} parameter(0)
}

ENTRY %main.3 (p: s32[4], i: s32[]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  %i = s32[] parameter(1)
  %fusion.2 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(chunk)/while/body/op-enq/vmap(guards)/jit(clip)/max"}
  ROOT %cond.5 = s32[4]{0} conditional(s32[] %i, s32[4]{0} %fusion.2, s32[4]{0} %fusion.2), branch_computations={%nop, %branch}, metadata={op_name="jit(chunk)/while/body/op-deq/vmap()/while/body/cond"}
}
"""


def test_scopes_read_through_a_transform_and_from_the_calling_op():
    names = frozenset(("guards", "row-slot", "row-padd"))
    scopes = step_scopes.scopes_of_hlo(HLO, names)
    assert scopes["%fusion.2"] == {"guards"}       # vmap(guards)
    assert scopes["%max.1"] == {"guards"}
    assert scopes["%select.9"] == {"row-slot"}
    # the switch itself and its NOP branch are under no row scope
    assert scopes["%cond.5"] == set() and scopes["%arg.0"] == set()
    assert scopes["%copy.7"] == set()
