"""Run-manifest schema and the perf-trajectory gate.

Covers the three pieces of observability plumbing that CI leans on:
``repro.obs.manifest`` (build/validate/write/load round trip, the
``x.csv -> x.manifest.json`` naming convention), ``PhaseProfiler``
accounting (nesting hands off at a shared timestamp, so phase totals sum
to covered wall exactly), and ``benchmarks/bench_history.py`` (fold run
manifests into a ``BENCH_<pr>.json`` snapshot; compare flags an injected
30% regression, tolerates an 8% wobble, warns in between, and is
direction-aware for higher-is-better cells).
"""
import json
import time

import pytest

import benchmarks.bench_history as bh
from repro.obs import (ManifestError, PhaseProfiler, build_manifest,
                       load_manifest, manifest_path_for, validate_manifest,
                       write_manifest)
from repro.obs.manifest import MANIFEST_SCHEMA


def _manifest(headline=None, **kw):
    return build_manifest(
        "fastpath-smoke", config={"threads": 4, "ops": 100},
        metrics=[{"queue": "DurableMSQ", "us_per_op": 4.7}],
        headline=headline or {"fastpath/DurableMSQ/compiled_us_per_op": 4.7},
        wall_s=1.25, **kw)


# ---------------------------------------------------------------- manifest

def test_manifest_round_trip(tmp_path):
    man = _manifest(phases={"heap-loop": {"ns": 1000, "count": 3}})
    assert man["schema"] == MANIFEST_SCHEMA
    assert man["git"] and "sha" in man["git"]
    assert man["env"]["python"]
    out = tmp_path / "smoke.manifest.json"
    write_manifest(man, out)
    back = load_manifest(out)
    assert back["headline"] == man["headline"]
    assert back["phases"]["heap-loop"]["count"] == 3
    assert back["wall_s"] == 1.25


def test_manifest_records_the_jax_device(tmp_path):
    """A run on a JAX backend names its device; a host run records none."""
    assert _manifest()["device"] is None
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    out = write_manifest(_manifest(device=dev), tmp_path / "m.json")
    assert load_manifest(out)["device"] == dev


def test_manifest_path_convention(tmp_path):
    assert str(manifest_path_for("out/fleet.csv")).endswith(
        "out/fleet.manifest.json")
    assert str(manifest_path_for(tmp_path / "x.csv")) == str(
        tmp_path / "x.manifest.json")


def test_manifest_extra_merges_top_level():
    man = _manifest(extra={"post_flush_attribution": {"OptUnlinkedQ": {}}})
    assert man["post_flush_attribution"] == {"OptUnlinkedQ": {}}


@pytest.mark.parametrize("mutate", [
    lambda m: m.pop("schema"),
    lambda m: m.__setitem__("schema", "bogus/v9"),
    lambda m: m.__setitem__("headline", {"k": "not-a-number"}),
    lambda m: m.__setitem__("metrics", "not-a-list"),
    lambda m: m.pop("subcommand"),
    lambda m: m.__setitem__("device", {"platform": "tpu", "count": 1}),
    lambda m: m.__setitem__("device", "tpu"),
])
def test_manifest_validation_rejects_corruption(mutate):
    man = _manifest()
    mutate(man)
    with pytest.raises(ManifestError):
        validate_manifest(man)


def test_load_manifest_rejects_corrupt_file(tmp_path):
    path = tmp_path / "bad.manifest.json"
    man = _manifest()
    man["headline"] = {"cell": [1, 2]}
    path.write_text(json.dumps(man))
    with pytest.raises(ManifestError):
        load_manifest(path)


# ---------------------------------------------------------------- profiler

def test_profiler_nesting_sums_to_covered_wall():
    prof = PhaseProfiler()
    t0 = time.perf_counter_ns()
    prof.push("outer")
    time.sleep(0.002)
    prof.push("inner")
    time.sleep(0.002)
    prof.pop()
    time.sleep(0.002)
    prof.pop()
    wall = time.perf_counter_ns() - t0
    # handoff at a shared timestamp: no gaps, no double counting
    assert prof.total_ns() <= wall
    assert prof.total_ns() >= 0.95 * wall
    assert prof.counts == {"outer": 1, "inner": 1}
    assert prof.totals["inner"] >= 1_500_000  # ~2ms
    assert prof._stack == []


def test_profiler_us_per_op_and_merge():
    a, b = PhaseProfiler(), PhaseProfiler()
    a.totals = {"heap-loop": 4_000}
    a.counts = {"heap-loop": 2}
    b.totals = {"heap-loop": 2_000, "bookkeeping": 1_000}
    b.counts = {"heap-loop": 1, "bookkeeping": 1}
    a.merge(b)
    assert a.totals == {"heap-loop": 6_000, "bookkeeping": 1_000}
    assert a.counts == {"heap-loop": 3, "bookkeeping": 1}
    per = a.us_per_op(7)
    assert per["heap-loop"] == pytest.approx(6.0 / 7)
    assert a.as_dict()["bookkeeping"] == {"ns": 1_000, "count": 1}


# ------------------------------------------------------------ bench_history

def _write_manifest(tmp_path, name, headline):
    man = _manifest(headline=headline)
    path = tmp_path / name
    write_manifest(man, path)
    return str(path)


BASE = {
    "fastpath/DurableMSQ/compiled_us_per_op": 5.0,
    "fastpath/DurableMSQ/speedup_vs_cap": 60.0,
    "crash-sweep/recoveries_per_s": 2000.0,
}


def test_fold_snapshot_round_trip(tmp_path):
    m1 = _write_manifest(tmp_path, "a.manifest.json", dict(BASE))
    m2 = _write_manifest(tmp_path, "b.manifest.json",
                         {"fleet/m/off/Q/wall_us_per_op": 0.8})
    snap, warnings = bh.fold([m1, m2], pr=8)
    assert not warnings
    assert snap["schema"] == bh.SNAPSHOT_SCHEMA and snap["pr"] == 8
    assert len(snap["cells"]) == 4
    out = tmp_path / "BENCH_8.json"
    out.write_text(json.dumps(snap))
    assert bh.load_snapshot(str(out))["cells"] == snap["cells"]
    with pytest.raises(ManifestError):
        bh.validate_snapshot({**snap, "cells": {"k": "oops"}})


def _compare(tmp_path, scale_us, scale_rate=1.0, **kw):
    """Fold BASE, then compare a manifest whose us/op cells are scaled by
    ``scale_us`` and whose rate cells are scaled by ``scale_rate``."""
    base = _write_manifest(tmp_path, "base.manifest.json", dict(BASE))
    snap, _ = bh.fold([base], pr=8)
    cur = {k: v * (scale_us if k.endswith("_us_per_op") else scale_rate)
           for k, v in BASE.items()}
    man = _write_manifest(tmp_path, "cur.manifest.json", cur)
    return bh.compare(snap, [man], **kw)


def test_compare_flags_30pct_regression(tmp_path):
    res = _compare(tmp_path, scale_us=1.30)
    assert res["fails"] == 1
    status = {k: s for s, k, *_ in res["rows"]}
    assert status["fastpath/DurableMSQ/compiled_us_per_op"] == "FAIL"
    # unchanged cells stay green
    assert status["crash-sweep/recoveries_per_s"] == "ok"


def test_compare_tolerates_8pct_wobble(tmp_path):
    res = _compare(tmp_path, scale_us=1.08, scale_rate=0.93)
    assert res["fails"] == 0 and res["warns"] == 0


def test_compare_warns_between_thresholds(tmp_path):
    res = _compare(tmp_path, scale_us=1.12)
    assert res["fails"] == 0 and res["warns"] == 1


def test_compare_direction_aware(tmp_path):
    # recoveries_per_s and speedup_vs_cap are higher-is-better: a 40% DROP
    # is the regression; us/op improving must never trip the gate
    res = _compare(tmp_path, scale_us=0.5, scale_rate=0.6)
    failing = {k for s, k, *_ in res["rows"] if s == "FAIL"}
    assert failing == {"crash-sweep/recoveries_per_s",
                       "fastpath/DurableMSQ/speedup_vs_cap"}
    assert bh.is_higher_better("fleet/m/off/Q/wall_us_per_op") is False
    assert bh.is_higher_better("x/speedup_same_scale") is True


def test_compare_ignores_unshared_cells(tmp_path):
    base = _write_manifest(tmp_path, "base.manifest.json", dict(BASE))
    snap, _ = bh.fold([base], pr=8)
    man = _write_manifest(tmp_path, "new.manifest.json",
                          {"fleet/new/cell_us_per_op": 99.0})
    res = bh.compare(snap, [man])
    assert res["rows"] == [] and res["fails"] == 0
    assert res["only_current"] == ["fleet/new/cell_us_per_op"]
    assert set(res["only_base"]) == set(BASE)


def test_bench_history_cli_smoke(tmp_path, capsys):
    m = _write_manifest(tmp_path, "s.manifest.json", dict(BASE))
    snap_path = tmp_path / "BENCH_8.json"
    assert bh.main(["fold", "--pr", "8", "--out", str(snap_path), m]) == 0
    assert bh.main(["compare", "--baseline", str(snap_path), m]) == 0
    slow = {k: v * 2 if k.endswith("_us_per_op") else v
            for k, v in BASE.items()}
    m_slow = _write_manifest(tmp_path, "slow.manifest.json", slow)
    assert bh.main(["compare", "--baseline", str(snap_path), m_slow]) == 1
    out = capsys.readouterr().out
    assert "FAIL fastpath/DurableMSQ/compiled_us_per_op" in out
    assert bh.main(["show", str(snap_path)]) == 0


def test_compare_summary_writes_delta_table(tmp_path):
    """``compare --summary`` appends the GFM delta table CI shows in the
    job summary: one row per shared cell with a status mark, plus
    gone/new rows for unshared cells."""
    base = _write_manifest(tmp_path, "base.manifest.json", dict(BASE))
    snap_path = tmp_path / "BENCH_8.json"
    assert bh.main(["fold", "--pr", "8", "--out", str(snap_path), base]) == 0
    cur = {k: (v * 1.5 if k.endswith("_us_per_op") and "compiled" in k else v)
           for k, v in BASE.items()}
    del cur["crash-sweep/recoveries_per_s"]
    cur["fleet/m/off/Q/pallas_wall_us_per_op"] = 1.0
    m = _write_manifest(tmp_path, "cur.manifest.json", cur)
    summary = tmp_path / "summary.md"
    rc = bh.main(["compare", "--baseline", str(snap_path),
                  "--summary", str(summary), m])
    assert rc == 1  # the 50% regression still fails the gate
    text = summary.read_text()
    assert text.startswith("### Perf trajectory vs `BENCH_8.json` (PR 8)")
    assert "| ❌ FAIL | `fastpath/DurableMSQ/compiled_us_per_op` |" in text
    assert "| ✅ ok | `fastpath/DurableMSQ/speedup_vs_cap` |" in text
    assert "| gone | `crash-sweep/recoveries_per_s` |" in text
    assert "| new | `fleet/m/off/Q/pallas_wall_us_per_op` |" in text
    assert "2 cells compared: 1 fail, 0 warn" in text
    # appends (CI reuses $GITHUB_STEP_SUMMARY across steps)
    assert bh.main(["compare", "--baseline", str(snap_path),
                    "--summary", str(summary), base]) == 0
    assert summary.read_text().count("### Perf trajectory") == 2


def test_committed_bench_8_snapshot_is_valid():
    """The committed trajectory bootstrap: BENCH_8.json exists, validates,
    and carries the three cell families the gate is built around."""
    path = bh.latest_snapshot_path()
    assert path is not None, "no committed BENCH_*.json under benchmarks/history/"
    snap = bh.load_snapshot(path)
    cells = snap["cells"]
    assert any(k.startswith("fastpath/") and k.endswith("_us_per_op")
               for k in cells)
    assert any(k.startswith("fleet/") and k.endswith("wall_us_per_op")
               for k in cells)
    assert "crash-sweep/recoveries_per_s" in cells
