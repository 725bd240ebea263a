"""Chip smoke test: the fleet executor's jax-opcode path on a TPU.

Runs ``repro.fleet.run_fleet`` with ``backend="jax-opcode"`` -- the path
``benchmarks/run.py fleet`` takes on a TPU -- at the fleet CLI's default
size (100,000 instances x 96 ops, chunk 48, prefill 10, seed 0,
``optane-clwb``) for DurableMSQ and OptLinkedQ.  For each queue the whole
``(instances, N_EV)`` counts matrix must equal the numpy reference
stepper's on the same plans, and 8 sampled instances must be
bit-identical to independent ``run_batched`` harnesses
(``check_instances``).

  python chip_smoke.py            # one chip
  python chip_smoke.py --chips 4  # only the sharded path: OptLinkedQ,
                                  # 400,000 instances over 4 chips

The timings printed are smoke timings, not benchmark numbers.  Any
mismatch, exception, or a JAX platform other than TPU exits nonzero and
prints no result.  On success the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Everything runs in this one process, which holds the chip.
"""
import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
OPS = 96
T0 = time.perf_counter()


def log(msg: str) -> None:
    """A progress line on standard error, with the seconds since start,
    so that a run stopped from outside shows how far it got."""
    print(f"chip_smoke [{time.perf_counter() - T0:7.1f} s] {msg}",
          file=sys.stderr, flush=True)


def smoke_cell(queue: str, instances: int, devices: int) -> bool:
    """Run one fleet cell on jax-opcode, check it against the numpy
    reference and sampled run_batched harnesses, print one line."""
    import numpy as np
    from repro.fleet import FleetConfig, check_instances, run_fleet

    cfg = FleetConfig(queue=queue, model="optane-clwb", instances=instances,
                      ops=OPS, chunk=48, prefill=10, seed=0,
                      backend="jax-opcode", devices=devices)
    log(f"{queue}: {instances} instances on jax-opcode")
    res = run_fleet(cfg)
    log(f"{queue}: jax-opcode done (set-up {res.build_s:.1f} s, run "
        f"{res.run_s:.1f} s); numpy reference")
    ref = run_fleet(replace(cfg, backend="numpy", devices=1),
                    kinds=res.kinds)
    counts_equal = bool(np.array_equal(res.counts, ref.counts))
    log(f"{queue}: numpy done; 8 run_batched checks")
    checks = check_instances(res, sample=8)
    ok = sum(r["ok"] for r in checks)
    print(f"smoke {queue}: {instances} instances x {OPS} ops on "
          f"{res.device['count']} {res.device['kind']} "
          f"({res.device['platform']}): setup_incl_compile_s={res.build_s} "
          f"run_s={res.run_s} bails={res.bails} residents={res.residents} "
          f"counts_equal_numpy={counts_equal} "
          f"run_batched_identical={ok}/{len(checks)}", flush=True)
    return (res.device["platform"] == "tpu" and counts_equal
            and ok == len(checks) == 8)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: DurableMSQ and OptLinkedQ at 100k instances "
                         "on one chip; 4: only OptLinkedQ at 400k "
                         "instances sharded over four chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))     # runs from a checkout, no PYTHONPATH

    import jax
    found = jax.devices()
    if found[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{found[0].platform!r})", file=sys.stderr)
        return 1
    if len(found) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX has "
              f"{len(found)} TPU device(s)", file=sys.stderr)
        return 1

    from repro.fleet import enable_compile_cache
    print(f"# smoke timings, not benchmark numbers; compile cache "
          f"{enable_compile_cache()}", flush=True)
    cells = ([("OptLinkedQ", 400_000, 4)] if args.chips == 4 else
             [("DurableMSQ", 100_000, 1), ("OptLinkedQ", 100_000, 1)])
    passed = [smoke_cell(q, n, d) for q, n, d in cells]
    if not all(passed):
        print("chip_smoke: a fleet cell disagreed with its references",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": found[0].platform, "kind": found[0].device_kind,
        "count": len(found)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
