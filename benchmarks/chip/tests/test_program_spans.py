"""The readers of what the program records of itself: its native spans
(set-up, plan packing and upload, polls, the end-of-pass gather) and the
named scopes of its compiled chunk step, on hand-made records and traces,
and end to end through a traced run on the CPU."""
import gzip
import json
from collections import deque

import pytest

import program_spans
import run_cell
import trace_reduce as tr
from conftest import HERE
from repro.obs import profiler

PROGRAM_SPAN_METRICS = ("setup_template_s", "setup_state_s",
                        "setup_compile_s", "plan_pack_ms_per_chunk",
                        "plan_upload_ms_per_chunk",
                        "poll_readback_ms_per_chunk",
                        "counts_readback_ms_per_pass")
DEVICE_METRICS = ("advance_ns_per_op", "idle_unattributed_pct")


def _record(spans):
    rec = profiler.SpanRecord()
    for name, start, end, nbytes in spans:
        rec.intervals.setdefault(name, deque()).append((start, end, nbytes))
    return rec


def _context(trace=None, spans=(), passes=1, chunks=(48, 48), tenants=10):
    window = trace.window() if trace is not None else None
    return run_cell.Context(
        spans=list(spans), trace=trace, window=window,
        tenants_per_device=tenants, ops=96, chunks=list(chunks),
        passes=passes, dims=None, peaks={})


# host clock (ns): one run's set-up from 1000, an earlier run's before it;
# the harness's window is 10_000-20_000 ns, i.e. 10e-6-20e-6 s
HOST = [("template", 100, 300, 0), ("compile", 400, 900, 0),      # earlier
        ("template", 1000, 1400, 0), ("replicate", 1500, 1700, 64),
        ("state-upload", 1700, 2000, 64), ("compile", 2000, 5000, 0),
        ("plan-pack", 6000, 6100, 0),                               # warm pass
        ("plan-pack", 11_000, 11_200, 0), ("plan-upload", 11_200, 11_500, 8),
        ("step-dispatch", 11_500, 11_600, 0),
        ("poll-wait", 11_700, 14_000, 0), ("poll-readback", 14_000, 14_400, 5),
        ("plan-pack", 14_500, 14_900, 0), ("plan-upload", 14_900, 15_000, 8),
        ("step-dispatch", 15_000, 15_100, 0),
        ("poll-wait", 15_200, 18_000, 0), ("poll-readback", 18_000, 18_600, 5),
        ("counts-wait", 18_600, 18_700, 0),
        ("counts-readback", 18_700, 19_500, 48)]
HARNESS = [("window", 10e-6, 20e-6), ("pass", 10.5e-6, 19.8e-6)]


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(program_spans, "record", lambda: _record(HOST))


def test_setup_spans_of_this_run_only(recorded):
    ctx = _context(spans=HARNESS)
    read = {m: run_cell.load_reader(m)(ctx) for m in PROGRAM_SPAN_METRICS}
    assert read["setup_template_s"] == pytest.approx(400e-9)
    assert read["setup_state_s"] == pytest.approx(500e-9)
    assert read["setup_compile_s"] == pytest.approx(3000e-9)


def test_window_spans_per_chunk_and_per_pass(recorded):
    ctx = _context(spans=HARNESS)
    read = {m: run_cell.load_reader(m)(ctx) for m in PROGRAM_SPAN_METRICS}
    # the warm pass's plan-pack (6000-6100) lies outside the window
    assert read["plan_pack_ms_per_chunk"] == pytest.approx(300e-6)
    assert read["plan_upload_ms_per_chunk"] == pytest.approx(200e-6)
    assert read["poll_readback_ms_per_chunk"] == pytest.approx(500e-6)
    assert read["counts_readback_ms_per_pass"] == pytest.approx(800e-6)


def _device_trace():
    # the trace clock runs 1,000,000 ns ahead of the host clock
    off = 1_000_000.0
    return tr.Trace(devices={0: {
        "modules": [("jit__lambda", off + 10_600, 300),
                    ("jit_chunk", off + 11_600, 2400),
                    ("jit_chunk", off + 15_100, 2900)],
        "ops": [("%copy.1", off + 10_600, 300),
                ("%while.1", off + 11_600, 2400),
                ("%cond.2", off + 11_700, 1000),
                ("%fusion.3", off + 11_700, 600),
                ("%fusion.4", off + 12_800, 1000),
                ("%fusion.3", off + 15_100, 2900)]}},
        host=[("window", off + 10_000, 10_000),
              ("pass", off + 10_500, 9300),
              ("chunk-step", off + 11_000, 600),
              ("poll", off + 11_700, 2700),
              ("chunk-step", off + 14_500, 600),
              ("poll", off + 15_200, 3400)])


def test_idle_share_no_program_span_names(recorded):
    ctx = _context(_device_trace(), spans=HARNESS)
    # idle: 10000-10600, 10900-11600, 14000-15100, 18000-20000 (4400);
    # program spans open: 11000-11600, 11700-14400, 14500-15100,
    # 15200-19500; unnamed: 600 + 100 + 100 + 500
    assert run_cell.load_reader("idle_unattributed_pct")(ctx) \
        == pytest.approx(100.0 * 1300 / 4400)


def test_advance_time_from_the_scoped_ops(recorded, monkeypatch):
    monkeypatch.setattr(program_spans, "op_scopes", lambda: {
        "%cond.2": {"epoch-advance", "op-enq"},
        "%fusion.3": {"epoch-advance", "op-enq"},
        "%copy.1": {"epoch-advance"},     # a name of another module
        "%fusion.4": {"op-enq"}})
    ctx = _context(_device_trace(), spans=HARNESS, tenants=10)
    # own time under the scope, in runs of jit_chunk: %cond.2 400 (its
    # nested %fusion.3 takes 600), %fusion.3 600 + 2900
    assert run_cell.load_reader("advance_ns_per_op")(ctx) \
        == pytest.approx((400 + 600 + 2900) / (10 * 96))


def test_nothing_read_from_a_program_that_records_nothing(monkeypatch):
    monkeypatch.setattr(program_spans, "record", lambda: None)
    monkeypatch.setattr(program_spans, "op_scopes", lambda: None)
    ctx = _context(_device_trace(), spans=HARNESS)
    for m in PROGRAM_SPAN_METRICS + DEVICE_METRICS:
        assert run_cell.load_reader(m)(ctx) is None, m


def test_nothing_read_without_spans_before_or_in_the_window(monkeypatch):
    # a window of 1-2 ms; a template and a compile after it, a plan-pack
    # that straddles its end
    monkeypatch.setattr(program_spans, "record", lambda: _record(
        [("plan-pack", 1_900_000, 2_100_000, 0),
         ("template", 2_500_000, 2_600_000, 0),
         ("compile", 2_600_000, 2_700_000, 0)]))
    ctx = _context(spans=[("window", 1e-3, 2e-3)])
    for m in PROGRAM_SPAN_METRICS:
        assert run_cell.load_reader(m)(ctx) is None, m


HLO = """HloModule jit_chunk, is_scheduled=true

%fused_computation (param_0: s32[4]) -> s32[4] {
  %param_0 = s32[4]{0} parameter(0)
  ROOT %add.1 = s32[4]{0} add(s32[4]{0} %param_0, s32[4]{0} %param_0), metadata={op_name="jit(chunk)/while/body/op-enq/epoch-advance/cond/branch_1_fun/add"}
}

%region_1.2 (arg.1: s32[4]) -> s32[4] {
  %arg.1 = s32[4]{0} parameter(0)
  %copy.7 = s32[4]{0} copy(s32[4]{0} %arg.1)
  ROOT %fusion.9 = s32[4]{0} fusion(s32[4]{0} %copy.7), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(chunk)/while/body/op-enq/epoch-advance/cond/branch_1_fun/add"}
}

%region_0.1 (arg.0: s32[4]) -> s32[4] {
  ROOT %arg.0 = s32[4]{0} parameter(0)
}

ENTRY %main.3 (p: s32[4], i: s32[]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  %i = s32[] parameter(1)
  %fusion.2 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(chunk)/while/body/op-deq/vmap()/add"}
  ROOT %cond.5 = s32[4]{0} conditional(s32[] %i, s32[4]{0} %fusion.2, s32[4]{0} %fusion.2), branch_computations={%region_0.1, %region_1.2}, metadata={op_name="jit(chunk)/while/body/op-enq/epoch-advance/cond"}
}
"""


def test_scopes_read_from_op_name_and_from_the_calling_op():
    scopes = program_spans.scopes_of_hlo(HLO)
    assert scopes["%cond.5"] == {"op-enq", "epoch-advance"}
    # no metadata of its own: under the conditional whose branch holds it
    assert scopes["%copy.7"] == {"op-enq", "epoch-advance"}
    assert scopes["%fusion.9"] == {"op-enq", "epoch-advance"}
    assert scopes["%fusion.2"] == {"op-deq"}
    assert scopes["%p"] == set()


def _small(queue="optlinkedq-optane-100k", tenants=48):
    config = json.loads((HERE / "configs" / f"{queue}.json").read_text())
    config.update(tenants=tenants, ops_per_tenant=16, chunk=8)
    traffic = json.loads((HERE / "traffic" / "mixed5050.json").read_text())
    return config, traffic


@pytest.fixture
def no_compile_cache(monkeypatch, tmp_path):
    # the program then sets no cache of its own and writes nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_traced_cpu_runs_read_every_program_span(no_compile_cache):
    """Two traced runs in one process, as a test process may make: each
    reads every program-span metric, and the set-up readers read the
    second run's own spans."""
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    per_layer = [m for m in bench["per_layer"]
                 if m["name"] in PROGRAM_SPAN_METRICS + DEVICE_METRICS]
    assert len(per_layer) == 9
    for queue, tenants in (("durablemsq-optane-100k", 40),
                           ("optlinkedq-optane-100k", 48)):
        config, traffic = _small(queue, tenants)
        r = run_cell.run(config, traffic, 1, 2**33 + 7, 0.2, True,
                         per_layer=per_layer, backend="jax-opcode")
        assert r["correct"]
        got = {k: v["value"] for k, v in r["metrics"].items()}
        assert set(PROGRAM_SPAN_METRICS) <= set(got), got
        assert all(got[m] > 0 for m in PROGRAM_SPAN_METRICS), got
    rec = profiler.RECORD
    start, end, _ = rec.intervals["template"][-1]
    assert got["setup_template_s"] == pytest.approx((end - start) / 1e9)
    compiled = [e - s for s, e, _ in rec.intervals["compile"] if s >= start]
    assert len(compiled) == 1
    assert got["setup_compile_s"] == pytest.approx(compiled[0] / 1e9)


RECORDED = HERE / "tests" / "data" / "v5e_spans_trace.json.gz"


def test_recorded_v5e_trace_with_program_spans(monkeypatch):
    """A traced run recorded on one v5e with its program spans and scopes
    (2,048 tenants, two passes): every new reader reads what it read on
    the chip, and the advance is a part of the step."""
    doc = json.loads(gzip.decompress(RECORDED.read_bytes()))
    trace = tr.Trace.from_json(json.dumps(doc["trace"]))
    monkeypatch.setattr(program_spans, "record", lambda: _record(
        (name, *iv) for name, ivs in doc["record"].items() for iv in ivs))
    monkeypatch.setattr(program_spans, "op_scopes", lambda: {
        op: set(scopes) for op, scopes in doc["scopes"].items()})
    ctx = _context(trace, spans=[tuple(s) for s in doc["spans"]],
                   passes=doc["passes"], chunks=doc["chunks"],
                   tenants=doc["tenants"])
    names = PROGRAM_SPAN_METRICS + DEVICE_METRICS + ("step_ns_per_op",)
    values = {m: run_cell.load_reader(m)(ctx) for m in names}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values == pytest.approx({m: doc["readings"][m] for m in names},
                                   rel=1e-12)
    assert values["advance_ns_per_op"] < values["step_ns_per_op"]
