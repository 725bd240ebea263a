"""The trace reduction: busy time as a union, idle gaps named by the host
span open in them, and the chunk program's time, on a hand-made trace and
on a small trace recorded on a TPU v5e."""
import gzip
import json

import pytest

import run_cell
import trace_reduce as tr
from conftest import HERE
from repro.fleet import build_template


def _hand_made():
    # one device, two chunk programs and a copy, inside a 100 ns window
    t = tr.Trace(devices={0: {
        "modules": [("jit__lambda", 10.0, 5.0), ("jit_chunk", 15.0, 30.0),
                    ("jit_chunk", 60.0, 30.0)],
        "ops": [("copy", 10.0, 5.0), ("while.1", 15.0, 30.0),
                ("fusion.1", 15.0, 20.0), ("fusion.2", 35.0, 10.0),
                ("fusion.1", 60.0, 30.0)]}},
        host=[("window", 0.0, 100.0), ("pass", 5.0, 90.0),
              ("state-reset", 5.0, 4.0), ("chunk-step", 12.0, 3.0),
              ("poll", 46.0, 12.0), ("chunk-step", 58.0, 2.0)])
    return t


def test_busy_is_a_union_and_gaps_fill_the_rest():
    t = _hand_made()
    ev = tr.device_events(t, 0)
    assert tr.busy_ns(ev, 0.0, 100.0) == 5 + 30 + 30
    assert tr.gaps(ev, 0.0, 100.0) == [(0.0, 10.0), (45.0, 60.0),
                                       (90.0, 100.0)]
    assert tr.busy_ns(ev, 20.0, 70.0) == 25 + 10


def test_gaps_are_named_by_the_innermost_open_span():
    t = _hand_made()
    names = [name for name, _ in tr.longest_gaps(t, 0.0, 100.0)]
    assert names == ["poll", "state-reset", "pass"]
    # the while op's own time is what its body ops leave: none
    assert tr.top_ops(t, 0.0, 100.0) == [["fusion.1", 50e-9],
                                          ["fusion.2", 10e-9],
                                          ["copy", 5e-9], ["while.1", 0.0]]


def _context(trace, passes, chunks, tenants, queue="DurableMSQ", ops=96):
    t = build_template(queue, "optane-clwb", ops, 10)
    return run_cell.Context(
        spans=[], trace=trace, window=trace.window(),
        tenants_per_device=tenants / len(trace.devices), ops=ops,
        chunks=chunks, passes=passes, dims=t.dims,
        peaks={"hbm_bytes_per_s": 819e9})


def test_chunk_program_counted_once_per_chunk():
    t = _hand_made()
    assert tr.chunk_program_ns(t, 0, 0.0, 100.0, 2) == 60.0
    assert tr.chunk_program_ns(t, 0, 0.0, 100.0, 3) is None
    step = run_cell.load_reader("step_ns_per_op")
    assert step(_context(t, 1, [48, 48], 10)) == 60.0 / (10 * 96)


def test_readers_read_nothing_without_the_chunk_program():
    t = _hand_made()
    t.devices[0]["modules"] = [(n.replace("chunk", "kernel"), s, d)
                               for n, s, d in t.devices[0]["modules"]]
    ctx = _context(t, 1, [48, 48], 10)
    for name in ("step_ns_per_op", "chunk_step_roofline"):
        assert run_cell.load_reader(name)(ctx) is None


RECORDED = HERE / "tests" / "data" / "v5e_trace.json.gz"


def test_recorded_v5e_trace():
    doc = json.loads(gzip.decompress(RECORDED.read_bytes()))
    t = tr.Trace.from_json(json.dumps(doc["trace"]))
    ctx = _context(t, doc["passes"], doc["chunks"], doc["tenants"],
                   doc["queue"], doc["ops"])
    lo, hi = ctx.window
    values = {name: run_cell.load_reader(name)(ctx)
              for name in ("step_ns_per_op", "chunk_step_roofline",
                           "device_idle_pct")}
    assert all(v is not None and v > 0 for v in values.values()), values
    # as the chip run that recorded the trace read them
    assert values == pytest.approx({"step_ns_per_op": 106.2345708211263,
                                    "chunk_step_roofline": 0.1904582101044741,
                                    "device_idle_pct": 29.111730759890154},
                                   rel=1e-12)
    busy = tr.busy_ns(tr.device_events(t, 0), lo, hi)
    gap = sum(b - a for a, b in tr.gaps(tr.device_events(t, 0), lo, hi))
    assert busy + gap == pytest.approx(hi - lo)
    for name, _ in tr.longest_gaps(t, lo, hi):
        assert name in tr.HOST_SPANS or name == "outside any span"
