"""Fleet executor demo: run thousands of independent queue instances as
one vectorized program, then prove a sample of them bit-identical to
independent per-instance ``run_batched`` runs (docs/fleet.md).

  PYTHONPATH=src python examples/fleet_demo.py
  PYTHONPATH=src python examples/fleet_demo.py --quick   # CI smoke
"""
import argparse

from repro.fleet import (FleetConfig, check_instances, enable_compile_cache,
                         run_fleet)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--instances", type=int, default=20_000,
                    help="fleet size (default 20000)")
    ap.add_argument("--ops", type=int, default=96,
                    help="plan steps per instance (default 96)")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "numpy", "jax", "jax-opcode"),
                    help="auto: jax-opcode on a TPU, numpy otherwise")
    ap.add_argument("--quick", action="store_true",
                    help="reduced fleet for CI smoke (2000 x 48, numpy)")
    args = ap.parse_args()
    instances, ops, backend = args.instances, args.ops, args.backend
    if args.quick:
        instances, ops, backend = 2_000, 48, "numpy"
    if backend != "numpy":
        enable_compile_cache()

    for queue in ("DurableMSQ", "OptUnlinkedQ", "OptLinkedQ"):
        cfg = FleetConfig(queue=queue, model="optane-clwb",
                          instances=instances, ops=ops, backend=backend)
        res = run_fleet(cfg)
        agg = res.aggregate()
        checks = check_instances(res, sample=4)
        ok = sum(1 for c in checks if c["ok"])
        assert ok == len(checks), f"{queue}: fleet diverged from run_batched"
        where = (f"{res.backend} ({res.device['platform']})" if res.device
                 else f"{res.backend} (host)")
        print(f"{queue:14s} {instances} instances x {ops} ops on "
              f"{where}: {res.ops_per_sec / 1e6:.2f} Mops/s wall, "
              f"{agg.time_ns / res.total_ops:.1f} sim-ns/op, "
              f"{agg.fences / res.total_ops:.2f} fences/op, "
              f"bails={res.bails}, checked {ok}/{len(checks)} bit-identical")


if __name__ == "__main__":
    main()
