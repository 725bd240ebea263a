"""The fleet picks its path from the JAX platform and never hides it.

``auto`` is jax-opcode on a TPU and the numpy reference elsewhere; an
unknown backend, a mesh larger than the devices JAX has, and the Pallas
kernel off the CPU all raise instead of degrading; results name the device
they ran on; the compile cache honours ``JAX_COMPILATION_CACHE_DIR`` and
otherwise sits at a fixed path in the checkout.  The TPU cases are
steered here by replacing ``jax.devices``, on the CPU platform.
"""
import types
from pathlib import Path

import pytest

from repro.fleet import FleetConfig, enable_compile_cache, run_fleet
from repro.fleet.runner import _resolve_backend

jax = pytest.importorskip("jax")

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def fake_tpus(monkeypatch):
    """Make ``jax.devices()`` report four v5e chips."""
    chips = [types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                                   id=i) for i in range(4)]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: chips)
    return chips


def test_auto_resolves_to_numpy_off_tpu():
    assert jax.devices()[0].platform == "cpu"
    assert _resolve_backend("auto", 1) == ("numpy", 1)


def test_auto_without_jax_is_numpy(monkeypatch):
    import importlib.util
    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec",
                        lambda name, *a: None if name == "jax"
                        else real(name, *a))
    assert _resolve_backend("auto", 1) == ("numpy", 1)


@pytest.mark.parametrize("devices", [1, 4])
def test_auto_resolves_to_jax_opcode_on_tpu(fake_tpus, devices):
    assert _resolve_backend("auto", devices) == ("jax-opcode", devices)


def test_pallas_refused_on_tpu(fake_tpus):
    with pytest.raises(NotImplementedError, match="Mosaic"):
        _resolve_backend("pallas", 1)


@pytest.mark.parametrize("name", ["jaxx", "", "tpu"])
def test_unknown_backend_raises(name):
    with pytest.raises(ValueError, match="unknown fleet backend"):
        run_fleet(FleetConfig(backend=name, instances=2, ops=4, chunk=4))


@pytest.mark.parametrize("backend", ["jax", "jax-opcode"])
def test_mesh_larger_than_device_count_fails(backend):
    too_many = len(jax.devices()) + 1
    with pytest.raises(ValueError, match=f"{too_many}-device mesh"):
        run_fleet(FleetConfig(backend=backend, devices=too_many,
                              instances=2, ops=4, chunk=4))


@pytest.mark.parametrize("backend,devices", [("numpy", 0), ("pallas", 2)])
def test_bad_device_counts_raise(backend, devices):
    with pytest.raises(ValueError):
        _resolve_backend(backend, devices)


@pytest.mark.parametrize("backend", ["numpy", "jax-opcode"])
def test_result_names_the_device(backend):
    res = run_fleet(FleetConfig(queue="OptLinkedQ", backend=backend,
                                instances=3, ops=8, chunk=4))
    if backend == "numpy":
        assert res.device is None
    else:
        d = jax.devices()[0]
        assert res.device == {"platform": "cpu", "kind": d.device_kind,
                              "count": 1}


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache" in (REPO / ".gitignore").read_text().split()


@pytest.mark.parametrize("backend,platform,cell", [
    ("numpy", "host", "wall_us_per_op"),
    ("jax-opcode", "cpu", "jax-opcode_wall_us_per_op"),
])
def test_fleet_cli_rows_name_the_device(monkeypatch, tmp_path, backend,
                                        platform, cell):
    """`run.py fleet` rows and manifest say where the numbers came from;
    a host or CPU row never carries a device-qualified cell."""
    import csv
    import json

    from benchmarks.run import fleet_main
    # an env-set cache dir keeps the helper from pointing JAX's cache at
    # the checkout for the rest of this test process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = tmp_path / "fleet.csv"
    fleet_main(["--backend", backend, "--instances", "4", "--ops", "8",
                "--chunk", "4", "--queues", "OptLinkedQ", "--check", "2",
                "--quiet", "--out", str(out)])
    (row,) = list(csv.DictReader(out.open()))
    assert (row["backend"], row["platform"], row["devices"]) == (
        backend, platform, "1")
    man = json.loads((tmp_path / "fleet.manifest.json").read_text())
    assert list(man["headline"]) == [
        f"fleet/optane-clwb/off/OptLinkedQ/{cell}"]
    if backend == "numpy":
        assert man["device"] is None and row["device_kind"] == ""
    else:
        assert man["device"] == {"platform": "cpu", "count": 1,
                                 "kind": row["device_kind"]}
