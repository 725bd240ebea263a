"""The control of the comparison that decides ``correct``.

The control is the plain reference put in the program's place with one
guarantee of the configuration broken: the platform's flushes are left
out (``needs_flush`` off on a platform that needs them), the step that a
faster-looking change would take.  Its counts for the sampled tenants
take the place of what a pass produced, at the cell's own size and
sample, and must come out not correct.  The benchmark's own runs never
run it.

  python benchmarks/chip/control.py --workload <cell> --seeds 1,2,3
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def control_readings(config: dict, traffic: dict, chips: int, seed: int):
    """-> (mismatched tenants, widest count gap) of the control."""
    import generator
    from reference import tenant_counts
    from run_cell import compare, sample_tenants
    broken = dict(config, platform=dict(config["platform"],
                                        needs_flush=False))
    n, ops = config["tenants"], config["ops_per_tenant"]
    kinds = generator.generate(n, ops, seed, traffic)
    sample = sample_tenants(n, chips, seed)
    rows = np.stack([tenant_counts(broken, traffic["prefill"], kinds[:, i],
                                   int(i)) for i in sample])
    mismatched, widest, _ = compare(config, traffic["prefill"], kinds,
                                    sample, [rows])
    return mismatched, widest


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = json.loads((ROOT / files[cell["config"]]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    for seed in (int(s) for s in args.seeds.split(",")):
        mismatched, widest = control_readings(config, traffic,
                                              cell["chips"], seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "mismatched_tenants": mismatched,
                          "widest_count_gap": widest}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    sys.exit(main())
