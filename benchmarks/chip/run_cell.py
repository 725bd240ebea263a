"""Run one benchmark cell of the fleet executor on the chip, once.

  python benchmarks/chip/run_cell.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

A cell (``workloads`` in ``BENCHMARK.json``) is a configuration file
(``configs/``: the queue, its memory platform, the tenant count, the plan
length and the chunk) under a traffic file (``traffic/``).  Everything is
found by name, so a cell is added with files and entries, not code.

Set-up, timed from process start as ``setup_s``: the compile cache, the
warmed template (``build_template``), the plans from ``--seed``, the
fleet state placed on the cell's chips by the backend ``auto`` picks on
a TPU (jax-opcode), every chunk length compiled ahead of time, and one
warm pass.  The backend's initial state stays on the device untouched.

Window: whole passes, back to back, until ``--seconds`` have gone.  A
pass is one complete fleet answer (every tenant, every op of its plan):
a device-side copy of the initial state (the step donates its state),
then the runner's own chunk loop (``run_chunk``, ``poll``, bail and
resident replay, the counts back on the host).  ``queue_ops_per_s`` is
the simulated queue-ops of those passes over the window's wall time.

Then ``correct``: the event counts that every pass produced for a sample
of tenants drawn from the seed (with the first and last tenant of each
chip's shard) must equal, exactly, what the plain reference
(``reference/``) gives for the same plans.  ``--trace 1`` runs the window
under the profiler and prints the per-layer metrics (``metrics/``) in
place of the end-to-end ones.

The last line of standard output is one JSON object; the last lines of
standard error are the numbers compared, each beside its limit.  With no
TPU, or fewer chips than the cell asks for, it exits 1 and prints no
result.
"""
import time

T0 = time.perf_counter()  # the process's start, as near as the script sees

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"     # fixed: the path is part of the key
SAMPLE = 2048                       # tenants compared with the reference
LIMITS = {"mismatched_tenants": 0, "widest_count_gap": 0}


def log(msg: str) -> None:
    print(f"run_cell [{time.perf_counter() - T0:8.2f} s] {msg}",
          file=sys.stderr, flush=True)


class Spans:
    """The ``prof`` object the runner's chunk loop takes (``push`` and
    ``pop``): each phase is timed on the host clock and opened as a
    ``jax.profiler.TraceAnnotation``, so the device trace shows it on its
    own clock."""

    def __init__(self):
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self._open = []
        self.done = []              # (name, start s, end s), host clock

    def push(self, name: str) -> None:
        ann = self._annotation(name)
        ann.__enter__()
        self._open.append((name, time.perf_counter(), ann))

    def pop(self) -> None:
        name, start, ann = self._open.pop()
        end = time.perf_counter()
        ann.__exit__(None, None, None)
        self.done.append((name, start, end))


class _NoSpans:
    def push(self, name: str) -> None:
        pass

    def pop(self) -> None:
        pass


@dataclass
class Context:
    """What a per-layer metric's reader gets."""
    spans: list                 # Spans.done of the traced window
    trace: object               # trace_reduce.Trace, or None
    window: object              # (start ns, end ns) on the trace clock
    tenants_per_device: float
    ops: int                    # plan length per tenant
    chunks: list                # the chunk lengths of one pass, in order
    passes: int                 # whole passes in the traced window
    dims: object                # the template's FleetDims
    peaks: dict                 # the device's published peaks


def sample_tenants(tenants: int, chips: int, seed: int):
    """Sorted tenant ids to compare: SAMPLE drawn from the seed, and the
    first and last tenant of each chip's shard."""
    import numpy as np
    rng = np.random.default_rng([seed, 1])
    ids = set(rng.choice(tenants, size=min(SAMPLE, tenants),
                         replace=False).tolist())
    shard = -(-tenants // chips)
    for d in range(chips):
        lo = d * shard
        if lo < tenants:
            ids.update((lo, min(tenants, lo + shard) - 1))
    return np.array(sorted(ids), dtype=np.int64)


def compare(config: dict, prefill: int, kinds, sample, rows):
    """Each pass's counts of the sampled tenants against the plain
    reference: -> (mismatched tenants, widest count gap, failed ops)."""
    import numpy as np
    from reference import tenant_counts
    ref = np.stack([tenant_counts(config, prefill, kinds[:, i], int(i))
                    for i in sample])
    gap = np.abs(np.stack(rows) - ref[None])            # (passes, k, 12)
    wrong = gap.max(axis=2) > 0                         # (passes, k)
    ops = kinds.shape[0]
    return (int(wrong.any(axis=0).sum()), int(gap.max()),
            int(wrong.sum()) * ops)


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name.replace('.', '_')}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run(config: dict, traffic: dict, chips: int, seed: int, seconds: float,
        trace: bool, per_layer=(), backend: str = "auto") -> dict:
    """One run of a cell on the first ``chips`` JAX devices -> the
    result object.  ``backend`` is ``auto`` on the chip; the tests name
    ``jax-opcode`` to drive the same path on the CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.fleet import FleetConfig, build_template, enable_compile_cache
    from repro.fleet import runner
    from repro.fleet.state import replicate

    import generator
    import trace_reduce
    from peaks import peaks_of

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    n, ops, chunk = (config["tenants"], config["ops_per_tenant"],
                     config["chunk"])
    prefill = traffic["prefill"]
    template = build_template(config["queue"], config["platform"]["name"],
                              ops, prefill)
    kinds = generator.generate(n, ops, seed, traffic)
    cfg = FleetConfig(queue=config["queue"],
                      model=config["platform"]["name"], instances=n,
                      ops=ops, prefill=prefill, seed=seed,
                      p_deq=traffic["p_deq"], chunk=chunk, backend=backend,
                      devices=chips)
    name, devices = runner._resolve_backend(backend, chips)
    if name != "jax-opcode":
        raise RuntimeError(f"backend {backend!r} resolved to {name!r}, "
                           f"not jax-opcode")
    log(f"template and plans ready; placing {n} tenants on {devices} "
        f"device(s)")
    be = runner._make_backend(name, template,
                              replicate(template.row, template.dims, n),
                              devices)
    chunks = [min(chunk, ops - s) for s in range(0, ops, chunk)]
    be.prepare(sorted(set(chunks)))
    init = be.st                    # never passed to the step: it donates
    copy = jax.jit(lambda st: jax.tree.map(jnp.copy, st),
                   out_shardings=jax.tree.map(lambda a: a.sharding, init)
                   ).lower(init).compile()
    be.st = copy(init)
    runner._run_batch(template, cfg, kinds, be)           # the warm pass
    sample = sample_tenants(n, chips, seed)
    log("set-up done; window opens")

    spans = Spans() if trace else _NoSpans()
    tmp = tempfile.TemporaryDirectory() if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    rows, pass_s, bails = [], [], 0
    t_open = time.perf_counter()
    spans.push("window")
    while True:
        t_pass = time.perf_counter()
        spans.push("pass")
        spans.push("state-reset")
        be.st = copy(init)
        spans.pop()
        counts, b, _ = runner._run_batch(template, cfg, kinds, be,
                                         prof=spans)
        spans.pop()
        rows.append(counts[sample])
        bails += b
        pass_s.append(time.perf_counter() - t_pass)
        if time.perf_counter() - t_open >= seconds:
            break
    spans.pop()
    window_s = time.perf_counter() - t_open
    passes = len(pass_s)
    if trace:
        jax.profiler.stop_trace()
    log(f"window closed: {passes} passes in {window_s} s, {bails} bails; "
        f"pass seconds {pass_s}")

    used = jax.devices()[:devices]
    stats = [d.memory_stats() or {} for d in used]
    dev = used[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": devices,
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                       for s in stats)}
    del be, init, copy
    gc.collect()

    mismatched, widest, failed = compare(config, prefill, kinds, sample, rows)
    checks = {"mismatched_tenants": mismatched, "widest_count_gap": widest}
    correct = all(v <= LIMITS[k] for k, v in checks.items())
    log(f"compared {len(sample)} tenants x {passes} passes with the "
        f"plain reference")

    result = {"correct": correct, "attempted": passes * n * ops,
              "failed": failed}
    if not trace:
        result["metrics"] = {
            "queue_ops_per_s": {"value": passes * n * ops / window_s / 1e6,
                                "unit": "Mops/s"},
            "setup_s": {"value": t_open - T0, "unit": "s"}}
    else:
        tr = trace_reduce.load_xplane(next(Path(tmp.name).rglob(
            "*.xplane.pb")))
        tmp.cleanup()
        win = tr.window()
        log(f"trace read: {sum(len(v) for d in tr.devices.values() for v in d.values())} "
            f"device events, {len(tr.host)} host spans")
        ctx = Context(spans=spans.done, trace=tr, window=win,
                      tenants_per_device=n / devices, ops=ops,
                      chunks=chunks, passes=passes, dims=template.dims,
                      peaks=(peaks_of(dev.device_kind)
                             if dev.platform == "tpu" else {}))
        metrics = {}
        for m in per_layer:
            value = load_reader(m["name"])(ctx)
            if value is None:
                log(f"metric {m['name']}: nothing to read in this run")
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if win is not None and tr.devices:
            lo, hi = win
            busy = [trace_reduce.busy_ns(trace_reduce.device_events(tr, d),
                                         lo, hi) for d in tr.devices]
            device["busy_s"] = sum(busy) / len(busy) / 1e9
            device["window_s"] = (hi - lo) / 1e9
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(tr, lo, hi),
                "idle_gaps": trace_reduce.longest_gaps(tr, lo, hi)}
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                        for k, v in checks.items()}
    for k, v in checks.items():
        print(f"check {k}: {v} (limit {LIMITS[k]})", file=sys.stderr,
              flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"run_cell: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = json.loads((ROOT / files[cell["config"]]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    per_layer = [m for m in bench["per_layer"]
                 if args.workload in m.get("workloads", [args.workload])]

    # the cache lives in this checkout, whatever the environment says
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    found = jax.devices()
    if found[0].platform != "tpu":
        print(f"run_cell: no TPU found (JAX platform is "
              f"{found[0].platform!r})", file=sys.stderr)
        return 1
    if len(found) < cell["chips"]:
        print(f"run_cell: cell {args.workload} needs {cell['chips']} "
              f"chips, JAX has {len(found)}", file=sys.stderr)
        return 1
    result = run(config, traffic, cell["chips"], args.seed, args.seconds,
                 bool(args.trace), per_layer if args.trace else ())
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
