"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (set-up, window, comparison) on the
CPU at a small size, past the harness's look for a chip, with one fault
planted in the compiled chunk step that the window calls.  The program
has no exchange between chips (tenants are independent, the mesh holds
no collective), so that fault has no place to be planted."""
import json

import jax
import pytest

import run_cell
from conftest import HERE
from repro.fleet.jaxexec import JaxBackend

TENANTS = 48


def _run(chips=1):
    config = json.loads((HERE / "configs" / "optlinkedq-optane-100k.json")
                        .read_text())
    config.update(tenants=TENANTS, ops_per_tenant=16, chunk=8)
    traffic = json.loads((HERE / "traffic" / "mixed5050.json").read_text())
    return run_cell.run(config, traffic, chips, 2**33 + 5, 0.2, False,
                        backend="jax-opcode")


def _plant(monkeypatch, fault):
    """Wrap every compiled chunk step in ``fault(step, st, kc, oi)``."""
    compiled = JaxBackend._compiled

    def broken(self, C):
        step = compiled(self, C)
        return lambda st, kc, oi: fault(self, step, st, kc, oi)

    monkeypatch.setattr(JaxBackend, "_compiled", broken)


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch, tmp_path):
    # the program then sets no cache of its own and writes nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"] and r["failed"] == 0
    assert list(r)[-1] == "checks"


def test_state_returned_unchanged(monkeypatch):
    _plant(monkeypatch, lambda be, step, st, kc, oi: st)
    r = _run()
    assert not r["correct"] and r["checks"]["mismatched_tenants"]["value"]


def test_half_the_batch_left_out(monkeypatch):
    def half(be, step, st, kc, oi):
        keep = {k: v[TENANTS // 2:] for k, v in st.items()}
        out = step(st, kc, oi)
        return {k: be._put(v.at[TENANTS // 2:].set(keep[k]))
                for k, v in out.items()}
    _plant(monkeypatch, half)
    r = _run()
    assert not r["correct"]
    assert r["checks"]["mismatched_tenants"]["value"] >= TENANTS // 2 - 1


def test_answer_altered_where_produced(monkeypatch):
    def altered(be, step, st, kc, oi):
        out = dict(step(st, kc, oi))
        out["counts"] = be._put(out["counts"].at[:, 0].add(1))
        return out
    _plant(monkeypatch, altered)
    r = _run()
    assert not r["correct"] and r["checks"]["widest_count_gap"]["value"] > 0


def test_sound_run_over_four_devices():
    if len(jax.devices()) < 4:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=4")
    assert _run(chips=4)["correct"]
