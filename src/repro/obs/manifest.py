"""Versioned JSON run manifests written alongside benchmark CSVs.

Every ``benchmarks/run.py`` subcommand records *how* a number was
produced next to the number itself: git state, full CLI config, seed,
host environment, per-phase timings, and a flat ``headline`` dict of the
metrics worth tracking across PRs.  ``benchmarks/bench_history.py``
folds those headline cells into committed ``BENCH_<pr>.json`` snapshots
and gates CI on ratio-vs-baseline drift.

The schema is intentionally flat and versioned (``MANIFEST_SCHEMA``);
:func:`validate_manifest` collects *all* problems before raising so a
malformed manifest is diagnosable in one round trip.  Only stdlib is
used here -- the module must import in CI jobs that install nothing.
"""
import json
import os
import platform
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Union

MANIFEST_SCHEMA = "repro.obs.manifest/v1"
MANIFEST_VERSION = 1

_REPO_ROOT = Path(__file__).resolve().parents[3]


class ManifestError(ValueError):
    """A manifest (or snapshot) failed schema validation."""


def _git(args: List[str], cwd: Path) -> Optional[str]:
    try:
        out = subprocess.run(["git", *args], cwd=str(cwd), timeout=10,
                             capture_output=True, text=True)
    except (OSError, subprocess.SubprocessError):
        return None
    if out.returncode != 0:
        return None
    return out.stdout.strip()


def collect_git(cwd: Optional[Union[str, Path]] = None) -> Dict[str, Any]:
    """Best-effort git state: ``{sha, branch, dirty}`` (None/False when
    git or the repo is unavailable -- manifests must never fail a run)."""
    root = Path(cwd) if cwd is not None else _REPO_ROOT
    sha = _git(["rev-parse", "HEAD"], root)
    branch = _git(["rev-parse", "--abbrev-ref", "HEAD"], root)
    status = _git(["status", "--porcelain"], root)
    return {"sha": sha, "branch": branch,
            "dirty": bool(status) if status is not None else False}


def collect_env() -> Dict[str, Any]:
    """Host facts that make a perf number comparable (or explain why two
    numbers are not): interpreter, platform, CPU count, CI marker."""
    return {
        "python": sys.version.split()[0],
        "implementation": platform.python_implementation(),
        "platform": platform.platform(),
        "machine": platform.machine(),
        "cpu_count": os.cpu_count(),
        "hostname": platform.node(),
        "ci": bool(os.environ.get("CI")),
    }


def build_manifest(subcommand: str,
                   config: Dict[str, Any],
                   metrics: Optional[List[Dict[str, Any]]] = None,
                   headline: Optional[Dict[str, float]] = None,
                   phases: Optional[Dict[str, Dict[str, int]]] = None,
                   wall_s: Optional[float] = None,
                   extra: Optional[Dict[str, Any]] = None,
                   device: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Assemble a schema-valid manifest dict.

    ``config`` is the resolved CLI namespace (seed included), ``metrics``
    the per-row measurements mirroring the CSV, ``headline`` the flat
    ``key -> number`` cells bench_history tracks, ``phases`` a
    ``PhaseProfiler.as_dict()``, ``extra`` free-form sections (e.g. the
    paper-§8 post-flush attribution from `repro.trace.analyze`),
    ``device`` the JAX device a run's numbers came from --
    ``{platform, kind, count}`` as ``jax.devices()`` reports it -- or
    None when no JAX backend ran (host numbers).
    """
    man: Dict[str, Any] = {
        "schema": MANIFEST_SCHEMA,
        "version": MANIFEST_VERSION,
        "subcommand": subcommand,
        "created_unix": time.time(),
        "git": collect_git(),
        "env": collect_env(),
        "config": dict(config),
        "metrics": list(metrics) if metrics is not None else [],
        "headline": dict(headline) if headline is not None else {},
        "phases": dict(phases) if phases is not None else None,
        "wall_s": wall_s,
        "device": dict(device) if device is not None else None,
    }
    if extra:
        man.update(extra)
    return validate_manifest(man)


def validate_manifest(man: Any) -> Dict[str, Any]:
    """Check shape + types; raise :class:`ManifestError` listing every
    problem at once. Returns the manifest unchanged when valid."""
    problems: List[str] = []
    if not isinstance(man, dict):
        raise ManifestError(f"manifest must be a dict, got {type(man).__name__}")
    if man.get("schema") != MANIFEST_SCHEMA:
        problems.append(f"schema must be {MANIFEST_SCHEMA!r}, "
                        f"got {man.get('schema')!r}")
    if man.get("version") != MANIFEST_VERSION:
        problems.append(f"version must be {MANIFEST_VERSION}, "
                        f"got {man.get('version')!r}")
    if not isinstance(man.get("subcommand"), str) or not man.get("subcommand"):
        problems.append("subcommand must be a non-empty string")
    if not isinstance(man.get("created_unix"), (int, float)):
        problems.append("created_unix must be a number")
    for key in ("git", "env", "config", "headline"):
        if not isinstance(man.get(key), dict):
            problems.append(f"{key} must be a dict")
    if not isinstance(man.get("metrics"), list) or any(
            not isinstance(row, dict) for row in man.get("metrics") or []):
        problems.append("metrics must be a list of dicts")
    if isinstance(man.get("headline"), dict):
        for k, v in man["headline"].items():
            if not isinstance(k, str):
                problems.append(f"headline key {k!r} must be a string")
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                problems.append(f"headline[{k!r}] must be a number, got {v!r}")
    phases = man.get("phases")
    if phases is not None:
        if not isinstance(phases, dict):
            problems.append("phases must be a dict or None")
        else:
            for name, cell in phases.items():
                if (not isinstance(cell, dict) or "ns" not in cell
                        or "count" not in cell):
                    problems.append(
                        f"phases[{name!r}] must be a dict with ns+count")
    device = man.get("device")
    if device is not None and not (
            isinstance(device, dict)
            and isinstance(device.get("platform"), str)
            and isinstance(device.get("kind"), str)
            and isinstance(device.get("count"), int)):
        problems.append("device must be None or a dict with str platform, "
                        "str kind and int count")
    wall = man.get("wall_s")
    if wall is not None and not isinstance(wall, (int, float)):
        problems.append("wall_s must be a number or None")
    if problems:
        raise ManifestError("invalid manifest: " + "; ".join(problems))
    return man


def manifest_path_for(out: Union[str, Path]) -> Path:
    """Sibling manifest path for a CSV output path: ``x.csv`` ->
    ``x.manifest.json`` (non-``.csv`` paths get ``.manifest.json``
    appended), honouring whatever output directory ``--out`` chose."""
    out = Path(out)
    if out.suffix == ".csv":
        return out.with_suffix(".manifest.json")
    return out.with_name(out.name + ".manifest.json")


def write_manifest(man: Dict[str, Any], path: Union[str, Path]) -> Path:
    """Validate + write (creating parent dirs); returns the path."""
    validate_manifest(man)
    path = Path(path)
    if path.parent and not path.parent.exists():
        path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(man, indent=2, sort_keys=False,
                               default=_json_default) + "\n")
    return path


def load_manifest(path: Union[str, Path]) -> Dict[str, Any]:
    """Read + validate a manifest file."""
    with open(path) as fh:
        man = json.load(fh)
    try:
        return validate_manifest(man)
    except ManifestError as e:
        raise ManifestError(f"{path}: {e}") from None


def _json_default(obj: Any) -> Any:
    """Serialize numpy scalars and Paths without importing numpy."""
    if isinstance(obj, Path):
        return str(obj)
    for attr in ("item",):   # numpy scalar protocol
        fn = getattr(obj, attr, None)
        if callable(fn):
            return fn()
    raise TypeError(f"not JSON serializable: {type(obj).__name__}")
