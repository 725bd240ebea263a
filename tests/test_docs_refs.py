"""Docs cross-reference checks: links resolve, referenced symbols exist.

Keeps `docs/*.md` and the README honest as the code moves:

* every relative markdown link (``[text](path)`` and ``[text](path#anchor)``)
  must point at a file that exists in the repo;
* every backticked dotted reference to this package (``repro.x.y`` or
  ``repro.x.y.Symbol`` / ``:meth:`repro...```) must import, and a trailing
  attribute must exist on the imported module/class;
* every backticked repo path (``src/.../*.py``, ``tests/*.py``,
  ``benchmarks/*.py``, ``docs/*.md``) must exist;
* every `benchmarks/run.py` command line quoted in a doc names a real
  subcommand and real flags, every backticked ``--flag`` span is a flag
  some repo CLI actually defines, and the fleet CSV schema block in
  docs/fleet.md matches `benchmarks.run.FLEET_CSV_COLUMNS` exactly.

CI runs this as its docs step; it is also part of the tier-1 suite.
"""
import importlib
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOC_FILES = sorted(REPO.glob("docs/*.md")) + [REPO / "README.md"]

LINK_RE = re.compile(r"\[[^\]]+\]\(([^)\s]+)\)")
CODE_RE = re.compile(r"`+([^`]+)`+")
PKG_RE = re.compile(r"^(repro(?:\.\w+)+)$")
PATH_RE = re.compile(r"^(?:src|tests|benchmarks|docs|examples)/[\w./\-]+$")


def test_docs_exist():
    """The documentation set the architecture satellite promises."""
    for rel in ("docs/architecture.md", "docs/queues.md",
                "docs/benchmarking.md", "docs/fleet.md",
                "docs/observability.md", "README.md"):
        assert (REPO / rel).is_file(), f"missing {rel}"


@pytest.mark.parametrize("doc", DOC_FILES, ids=lambda p: p.name)
def test_relative_links_resolve(doc):
    text = doc.read_text()
    for target in LINK_RE.findall(text):
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        path = target.split("#", 1)[0]
        if not path:
            continue
        resolved = (doc.parent / path).resolve()
        if REPO not in resolved.parents and resolved != REPO:
            continue   # escapes the repo: a GitHub-site URL (CI badge), not a file
        assert resolved.exists(), (
            f"{doc.relative_to(REPO)}: broken link {target!r} "
            f"(resolved to {resolved})")


def _module_and_attrs(dotted):
    """Split 'repro.a.b.C.d' into the longest importable module + attrs."""
    parts = dotted.split(".")
    for cut in range(len(parts), 0, -1):
        modname = ".".join(parts[:cut])
        try:
            mod = importlib.import_module(modname)
        except ImportError:
            continue
        return mod, parts[cut:]
    return None, parts


@pytest.mark.parametrize("doc", DOC_FILES, ids=lambda p: p.name)
def test_code_spans_refer_to_real_things(doc):
    text = doc.read_text()
    for span in CODE_RE.findall(text):
        span = span.strip().rstrip("(),")
        # :meth:`repro...` / :class:`repro...` roles reduce to the dotted path
        span = re.sub(r"^:\w+:", "", span).strip("`")
        if PKG_RE.match(span):
            mod, attrs = _module_and_attrs(span)
            assert mod is not None, (
                f"{doc.relative_to(REPO)}: unimportable reference `{span}`")
            obj = mod
            for a in attrs:
                assert hasattr(obj, a), (
                    f"{doc.relative_to(REPO)}: `{span}`: "
                    f"{obj!r} has no attribute {a!r}")
                obj = getattr(obj, a)
        elif PATH_RE.match(span):
            assert (REPO / span).exists(), (
                f"{doc.relative_to(REPO)}: `{span}` names a missing path")


def test_readme_links_to_docs():
    """Satellite: the README must point readers at docs/."""
    text = (REPO / "README.md").read_text()
    for rel in ("docs/architecture.md", "docs/queues.md",
                "docs/benchmarking.md", "docs/fleet.md",
                "docs/observability.md"):
        assert rel in text, f"README does not link {rel}"


def test_docs_name_the_load_bearing_tests():
    """architecture.md must state the differential coupling rule and the
    calibration gate by naming their test files (which must exist)."""
    arch = (REPO / "docs" / "architecture.md").read_text()
    for rel in ("tests/test_engine_differential.py",
                "tests/test_contention_calibration.py"):
        assert rel in arch, f"architecture.md does not mention {rel}"
        assert (REPO / rel).is_file(), f"{rel} named in docs but missing"


def test_docs_name_the_columnar_record_engine():
    """Satellite: architecture.md documents the columnar op-record store
    by naming its load-bearing symbols (each verified importable by
    test_code_spans_refer_to_real_things) and its equivalence gates, and
    benchmarking.md states the flags behind the CI smoke thresholds."""
    arch = (REPO / "docs" / "architecture.md").read_text()
    for span in ("repro.core.records.RecordStore",
                 "repro.core.records.OpsView",
                 "repro.core.records.EventsView",
                 "repro.core.opsched.generate_columnar_runner",
                 "repro.crash.capture.Boundary.rec_snap",
                 'records="legacy"'):
        assert span in arch, f"architecture.md does not mention {span}"
    for rel in ("tests/test_columnar_equivalence.py",
                "tests/test_records_property.py"):
        assert rel in arch, f"architecture.md does not mention {rel}"
        assert (REPO / rel).is_file(), f"{rel} named in docs but missing"
    bench = (REPO / "docs" / "benchmarking.md").read_text()
    for flag in ("--max-us-per-op", "--differential", "--area-nodes"):
        assert flag in bench, f"benchmarking.md does not mention {flag}"


def test_docs_name_the_observability_layer():
    """Satellite: docs/observability.md pins the telemetry layer's
    load-bearing symbols (verified importable by
    test_code_spans_refer_to_real_things), the trajectory tool, and the
    non-interference gate; architecture.md links to it."""
    obs = (REPO / "docs" / "observability.md").read_text()
    for span in ("repro.obs.profiler.PhaseProfiler",
                 "repro.obs.Heartbeat",
                 "repro.obs.manifest.build_manifest",
                 "benchmarks/bench_history.py",
                 "benchmarks/history/BENCH_9.json"):
        assert span in obs, f"observability.md does not mention {span}"
    for rel in ("tests/test_obs_bit_identity.py",
                "tests/test_obs_manifest.py"):
        assert rel in obs, f"observability.md does not mention {rel}"
        assert (REPO / rel).is_file(), f"{rel} named in docs but missing"
    arch = (REPO / "docs" / "architecture.md").read_text()
    assert "observability.md" in arch, \
        "architecture.md does not link docs/observability.md"


def test_docs_name_the_burst_executor():
    """Satellite: architecture.md documents the vectorized burst
    executor by naming its load-bearing symbols (each verified
    importable by test_code_spans_refer_to_real_things) and its
    equivalence/property gates; benchmarking.md states the smoke's
    burst axis flags; observability.md names the burst phase group."""
    arch = (REPO / "docs" / "architecture.md").read_text()
    for span in ("repro.core.burst.predict_grants",
                 "repro.core.records.RecordStore.extend_staged",
                 "repro.fleet.lowering.encode_program",
                 "last_burst_stats"):
        assert span in arch, f"architecture.md does not mention {span}"
    for rel in ("tests/test_burst_equivalence.py",
                "tests/test_burst_property.py"):
        assert rel in arch, f"architecture.md does not mention {rel}"
        assert (REPO / rel).is_file(), f"{rel} named in docs but missing"
    bench = (REPO / "docs" / "benchmarking.md").read_text()
    for flag in ("--burst", "--burst-workload", "--burst-window",
                 "--min-speedup-burst"):
        assert flag in bench, f"benchmarking.md does not mention {flag}"
    obs = (REPO / "docs" / "observability.md").read_text()
    from repro.obs import (PH_BURST_APPLY, PH_BURST_PREDICT,
                           PH_BURST_REPLAY, PH_BURST_VERIFY)
    for phase in (PH_BURST_PREDICT, PH_BURST_VERIFY, PH_BURST_APPLY,
                  PH_BURST_REPLAY):
        assert phase in obs, (
            f"observability.md does not name the {phase!r} phase")


def test_docs_name_the_fleet_backends():
    """Satellite: docs/fleet.md carries the backend matrix (all four
    `--backend` values, with the kernel source file), and
    docs/observability.md names the Pallas chunk phase exactly as
    `repro.fleet.jaxexec.PallasBackend` reports it."""
    fleet = (REPO / "docs" / "fleet.md").read_text()
    for span in ("numpy", "jax-opcode", "pallas",
                 "src/repro/kernels/fleet_step.py",
                 "repro.fleet.lowering.encode_program"):
        assert span in fleet, f"fleet.md does not mention {span}"
    obs = (REPO / "docs" / "observability.md").read_text()
    from repro.fleet.jaxexec import PallasBackend
    assert PallasBackend.chunk_phase in obs, (
        f"observability.md does not name the {PallasBackend.chunk_phase!r} "
        f"phase")
    assert "_wall_us_per_op" in obs, (
        "observability.md must document backend-qualified headline cells")


ARGV0_RE = re.compile(r'argv\[0\] == "([\w-]+)"')
ADDARG_RE = re.compile(r'add_argument\(\s*"(--[\w-]+)"')
FLAG_TOKEN_RE = re.compile(r"(?<![=\w-])--[\w-]+")

# Every CLI whose flags the docs may quote: the benchmark driver, the
# crash-sweep/repro entry point it forwards to, the perf-trajectory
# gate (docs/observability.md quotes its fold/compare flags), and the
# dry-run artifact tools (merge + roofline table).
CLI_SOURCES = ("benchmarks/run.py", "src/repro/crash/__main__.py",
               "benchmarks/bench_history.py", "benchmarks/merge_results.py",
               "benchmarks/roofline.py")


def _known_cli():
    """(subcommands, flags) actually defined by the repo's CLIs."""
    subcommands, flags = set(), {"--help"}
    for rel in CLI_SOURCES:
        src = (REPO / rel).read_text()
        subcommands.update(ARGV0_RE.findall(src))
        flags.update(ADDARG_RE.findall(src))
    return subcommands, flags


def _doc_command_lines(text):
    """Command lines invoking benchmarks/run.py, continuations joined."""
    lines, buf = [], None
    for raw in text.splitlines():
        line = raw.strip()
        if buf is not None:
            buf += " " + line.rstrip("\\").strip()
            if not line.endswith("\\"):
                lines.append(buf)
                buf = None
            continue
        if "benchmarks/run.py" in line and (
                "python" in line or line.startswith("benchmarks/")):
            if line.endswith("\\"):
                buf = line.rstrip("\\").strip()
            else:
                lines.append(line)
    return lines


@pytest.mark.parametrize("doc", DOC_FILES, ids=lambda p: p.name)
def test_doc_cli_commands_are_real(doc):
    """Satellite: every `benchmarks/run.py` invocation a doc quotes names
    a subcommand the driver dispatches and flags some parser defines."""
    subcommands, flags = _known_cli()
    text = doc.read_text()
    for cmd in _doc_command_lines(text):
        tail = cmd.split("benchmarks/run.py", 1)[1].split("`", 1)[0].strip()
        tokens = tail.split()
        if tokens and not tokens[0].startswith("-"):
            assert tokens[0] in subcommands, (
                f"{doc.relative_to(REPO)}: quoted command {cmd!r} uses "
                f"unknown subcommand {tokens[0]!r} (known: "
                f"{sorted(subcommands)})")
        for flag in FLAG_TOKEN_RE.findall(tail):
            assert flag in flags, (
                f"{doc.relative_to(REPO)}: quoted command {cmd!r} uses "
                f"unknown flag {flag!r}")


@pytest.mark.parametrize("doc", DOC_FILES, ids=lambda p: p.name)
def test_doc_flag_spans_are_real(doc):
    """Every backticked span that *starts* with `--` must be a flag one of
    the repo CLIs defines (catches renamed/retired flags in prose)."""
    _, flags = _known_cli()
    text = doc.read_text()
    for span in CODE_RE.findall(text):
        span = span.strip()
        if not span.startswith("--"):
            continue
        flag = span.split()[0].split("=", 1)[0]
        assert flag in flags, (
            f"{doc.relative_to(REPO)}: `{span}` quotes unknown flag "
            f"{flag!r}")


def test_fleet_csv_schema_block_matches_code():
    """Satellite: the fleet CSV schema block in docs/fleet.md must equal
    `benchmarks.run.FLEET_CSV_COLUMNS` -- same names, same order."""
    from benchmarks.run import FLEET_CSV_COLUMNS
    text = (REPO / "docs" / "fleet.md").read_text()
    section = text.split("## Fleet CSV schema", 1)[1].split("\n## ", 1)[0]
    m = re.search(r"```\n(.*?)```", section, re.S)
    assert m, "docs/fleet.md: no fenced schema block under 'Fleet CSV schema'"
    documented = [t for t in re.split(r"[\s,]+", m.group(1)) if t]
    assert documented == list(FLEET_CSV_COLUMNS), (
        f"docs/fleet.md schema block {documented} != "
        f"benchmarks.run.FLEET_CSV_COLUMNS {list(FLEET_CSV_COLUMNS)}")


def test_queue_enumeration_single_source_of_truth():
    """Satellite: docs/queues.md defers to the code's queue registries.

    `repro.core.DURABLE_QUEUES` is the documented source of truth for the
    queue enumeration: queues.md must say so, its table must list exactly
    the `ALL_QUEUES` names (7 durable + the MSQ baseline), and no doc may
    claim a queue that the registries do not know.
    """
    from repro.core import ALL_QUEUES, DURABLE_QUEUES
    text = (REPO / "docs" / "queues.md").read_text()
    assert "DURABLE_QUEUES" in text, \
        "queues.md must name repro.core.DURABLE_QUEUES as source of truth"
    assert len(DURABLE_QUEUES) == 7 and len(ALL_QUEUES) == 8
    table_names = {m.group(1) for m in
                   re.finditer(r"^\|\s*(\w+)\s*\|\s*`", text, re.M)}
    assert table_names == set(ALL_QUEUES), (
        f"queues.md table lists {sorted(table_names)} but the registries "
        f"enumerate {sorted(ALL_QUEUES)}")
