"""Native spans (``repro.obs.profiler.span``/``push``/``pop``) and the
fleet's named scopes.

The span facility nests, keeps attributes and a process-wide record, and
never forwards into a caller's ``prof``; the fleet's compiled chunk step is
the module ``jit_chunk``, whose HLO metadata names its scopes; and the
import rule holds: `repro.core` and `repro.crash` never import `repro.obs`,
`repro.fleet` imports `repro.obs.profiler` only."""
import ast
import subprocess
import sys
from pathlib import Path

import pytest

from repro.fleet import FleetConfig, run_fleet
from repro.obs import PhaseProfiler, profiler

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"
RUNNER_PHASES = {profiler.PH_FLEET_LOWER, profiler.PH_FLEET_CHUNK,
                 profiler.PH_FLEET_POLL, profiler.PH_FLEET_BAIL,
                 profiler.PH_FLEET_RESIDENT}


@pytest.fixture
def record(monkeypatch):
    rec = profiler.SpanRecord(keep=3)
    monkeypatch.setattr(profiler, "RECORD", rec)
    return rec


def test_spans_nest_and_keep_count_time_bytes(record):
    with profiler.span("outer", start=4):
        profiler.push("inner", bytes=10)
        profiler.pop()
        with profiler.span("inner", bytes=5, C=8):
            pass
    assert record.count == {"inner": 2, "outer": 1}
    assert record.bytes == {"inner": 15, "outer": 0}
    (o0, o1, _), = record.intervals["outer"]
    for s, e, _ in record.intervals["inner"]:
        assert o0 <= s <= e <= o1          # the parent encloses its child
    assert record.ns["outer"] >= sum(e - s for s, e, _ in
                                     record.intervals["inner"])
    assert record._stack == []


def test_intervals_are_bounded_totals_are_not(record):
    for _ in range(5):
        with profiler.span("chunk"):
            pass
    assert record.count["chunk"] == 5 and len(record.intervals["chunk"]) == 3


def test_span_closes_when_its_block_raises(record):
    with pytest.raises(ValueError):
        with profiler.span("failing"):
            raise ValueError
    assert record.count == {"failing": 1} and record._stack == []


def test_span_lands_in_the_jax_trace(record, tmp_path):
    jax = pytest.importorskip("jax")
    jax.profiler.start_trace(str(tmp_path))
    with profiler.span("traced-span", start=96, bytes=7):
        pass
    jax.profiler.stop_trace()
    pd = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    found = [{k: v for k, v in e.stats} for plane in pd.planes
             if plane.name.startswith("/host:") for line in plane.lines
             for e in line.events if e.name == "traced-span"]
    assert found == [{"start": 96, "bytes": 7}]


@pytest.mark.parametrize("backend", ["numpy", "jax-opcode"])
def test_native_spans_never_reach_prof(record, backend):
    prof = PhaseProfiler()
    run_fleet(FleetConfig(instances=16, ops=8, chunk=4, backend=backend),
              profile=prof)
    assert set(prof.totals) <= RUNNER_PHASES
    assert not set(prof.totals) & set(record.count)
    assert {profiler.PH_FLEET_TEMPLATE, profiler.PH_FLEET_REPLICATE,
            profiler.PH_FLEET_COUNTS_READBACK} <= set(record.count)
    assert record._stack == []


def test_numpy_fleet_run_does_not_import_jax():
    code = ("import sys\n"
            "from repro.fleet import FleetConfig, run_fleet\n"
            "run_fleet(FleetConfig(instances=8, ops=8, chunk=4, "
            "backend='numpy'))\n"
            "print('jax' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env={"PYTHONPATH": str(SRC.parent),
                              "PATH": "/usr/bin:/bin"})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def _imports(package):
    """-> {module file: the repro modules it imports}, from the source."""
    found = {}
    for path in (SRC / package).rglob("*.py"):
        names = set()
        dotted = ["repro"] + list(path.relative_to(SRC.parent).parent.parts[1:])
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom):
                base = dotted[:len(dotted) - node.level + 1] \
                    if node.level else []
                mod = ".".join(base + ([node.module] if node.module else []))
                names.update([mod] + [f"{mod}.{a.name}" for a in node.names])
            elif isinstance(node, ast.Import):
                names.update(a.name for a in node.names)
        found[path.name] = {n for n in names if n.startswith("repro.obs")}
    return found


@pytest.mark.parametrize("package", ["core", "crash"])
def test_core_and_crash_never_import_obs(package):
    found = _imports(package)
    # the ``python -m repro.crash`` command writes run manifests; it is a
    # front end, like benchmarks/run.py, not the crash library
    found.pop("__main__.py", None)
    assert not any(found.values()), found


def test_fleet_imports_the_profiler_module_only():
    used = set().union(*_imports("fleet").values())
    assert used and all(n.startswith("repro.obs.profiler") for n in used), \
        used


def test_fleet_names_come_from_the_profiler():
    """No string literal in the runner or the jax backend duplicates a
    fleet span, phase or scope name."""
    names = {v for k, v in vars(profiler).items()
             if k.startswith("PH_FLEET_")}
    for module in ("runner.py", "jaxexec.py"):
        tree = ast.parse((SRC / "fleet" / module).read_text())
        literals = {n.value for n in ast.walk(tree)
                    if isinstance(n, ast.Constant) and isinstance(n.value, str)}
        assert not literals & names, (module, literals & names)


def _compiled_step(queue):
    from repro.fleet.jaxexec import compiled_steps
    run_fleet(FleetConfig(queue=queue, instances=16, ops=8, chunk=4,
                          backend="jax-opcode"))
    return compiled_steps()[-1]


@pytest.mark.parametrize("queue", ["DurableMSQ", "OptLinkedQ"])
def test_chunk_module_is_named_and_carries_its_scopes(queue):
    """The device trace finds the chunk program by its module name, and an
    op's named scope by its metadata."""
    text = _compiled_step(queue).as_text()
    assert text.startswith("HloModule jit_chunk,")
    for scope in (profiler.PH_FLEET_ADVANCE, profiler.PH_FLEET_OP_ENQ,
                  profiler.PH_FLEET_OP_DEQ):
        assert f"/{scope}/" in text, scope
