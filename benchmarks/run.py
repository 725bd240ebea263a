"""Benchmark harness -- one section per paper table/figure.

  B1 (Fig. 2, amended): workload x queue x thread count x **memory model**
      x **contention** -> simulated throughput (the B1' sweep; `eadr` /
      `cxl` columns show how the paper's ranking shifts on other
      persistence platforms, and the contended column restores the CAS
      retry + helping costs the op-granularity executor cannot observe)
  B2 (§5/§6 accounting): fences/op + post-flush accesses/op per queue,
      per memory model -- uncontended at 1 thread (the paper's per-op
      schedule) and contended at 4 threads (retry-inflated per-op costs)
  B3 (§2.1): ONLL upper-bound construction accounting
  B4 (assignment): roofline terms per (arch x shape x mesh) from the
      dry-run artifacts (benchmarks/dryrun_results.jsonl if present)

Prints ``name,us_per_call,derived`` CSV lines per the harness contract, and
(with ``--out``) writes the full row set to a CSV file (the CI artifact)
plus a versioned run manifest (git sha, config, env, phase timings,
headline metrics -- see docs/observability.md) alongside it.

Examples::

  PYTHONPATH=src python benchmarks/run.py --smoke     # CI smoke run
  PYTHONPATH=src python benchmarks/run.py --ops 1000 --threads 1,2,4,8,16,32,64
  PYTHONPATH=src python benchmarks/run.py --models eadr --workloads mixed5050
  PYTHONPATH=src python benchmarks/run.py --contention learned --threads 8,16
  PYTHONPATH=src python benchmarks/run.py --engine exact --trace-out traces/
  PYTHONPATH=src python benchmarks/run.py fit-profiles   # refit learned.json
  PYTHONPATH=src python benchmarks/run.py crash-sweep --out crash.csv
  PYTHONPATH=src python benchmarks/run.py fastpath-smoke --out fp.csv
  PYTHONPATH=src python benchmarks/run.py fleet --instances 100000 --check 8
  PYTHONPATH=src python benchmarks/run.py profile --out profile.csv

``repro`` comes from the pyproject / ``PYTHONPATH=src`` convention (under
pytest the pythonpath is configured for you); there is no ``sys.path``
mutation here.
"""
from __future__ import annotations

import argparse
import csv
import os
import sys
import time

from repro.core import ALL_QUEUES, DURABLE_QUEUES, NVRAM, ONLL, QueueHarness
from repro.obs import (Heartbeat, PhaseProfiler, build_manifest,
                       manifest_path_for, write_manifest)

try:        # package import (pytest / `python -m benchmarks.run`)
    from benchmarks.workloads import (contention_label, make_plans,
                                      run_workload)
except ModuleNotFoundError:   # script mode: sibling module on sys.path[0]
    from workloads import contention_label, make_plans, run_workload

# The queue axis is owned by repro.core.DURABLE_QUEUES (the crash sweep
# shards over the same registry); tests/test_benchmark_queues.py asserts
# this stays true so new queues cannot silently drop out of benchmarks.
DURABLE = list(DURABLE_QUEUES)
WORKLOADS = ["mixed5050", "pairs", "producers", "consumers", "prodcons"]
MODELS = ["optane-clwb", "eadr", "cxl"]


def _emit_manifest(subcommand: str, args, rows, headline,
                   phases=None, wall_s=None, extra=None, device=None):
    """Write the versioned run manifest for a subcommand.

    The path follows the ``--out`` CSV convention (``x.csv`` ->
    ``x.manifest.json`` in the same directory); ``--manifest`` overrides
    it (and works without a CSV).  No-op when neither is given."""
    path = getattr(args, "manifest", None)
    if not path and getattr(args, "out", None):
        path = manifest_path_for(args.out)
    if not path:
        return None
    man = build_manifest(subcommand=subcommand, config=vars(args),
                         metrics=rows, headline=headline, phases=phases,
                         wall_s=wall_s, extra=extra, device=device)
    path = write_manifest(man, path)
    print(f"# wrote manifest {path}")
    return path


def _trace_attribution(trace_out):
    """Fold every captured trace's paper-§8 post-flush attribution into a
    manifest section: which sites re-read flushed content, how often."""
    import glob

    from repro.trace import load_trace
    from repro.trace.analyze import post_flush_per_op, post_flush_sites
    out = {}
    for path in sorted(glob.glob(os.path.join(trace_out, "*.trace.npz"))):
        tr = load_trace(path)
        name = os.path.basename(path)[:-len(".trace.npz")]
        out[name] = {
            "post_flush_per_op": {k: round(v, 4) for k, v in
                                  post_flush_per_op(tr).items()},
            "sites": [{"op_kind": s.op_kind, "region": s.region,
                       "prim": s.prim, "count": s.count,
                       "per_op": round(s.per_op, 4)}
                      for s in post_flush_sites(tr)[:16]],
        }
    return out or None


def _trace_path(trace_out, *parts) -> str:
    if not trace_out:
        return None
    os.makedirs(trace_out, exist_ok=True)
    return os.path.join(trace_out,
                        "_".join(str(p) for p in parts) + ".trace.npz")


def bench_fig2(ops_per_thread: int, threads: list, models: list,
               workloads: list, queues: list, engine: str,
               contention: list, trace_out: str = None) -> list:
    rows = []
    print("# B1: Fig.2 workloads x memory models x contention "
          "(simulated latency model)")
    print("name,us_per_call,derived")
    for wl in workloads:
        # full thread sweep on the headline workload, endpoints elsewhere
        tlist = threads if wl == "mixed5050" else \
            sorted({threads[0], threads[-1]})
        for model in models:
            for cont in contention:
                for nt in tlist:
                    for q in queues:
                        r = run_workload(q, wl, nt, ops_per_thread,
                                         model=model, engine=engine,
                                         contention=cont,
                                         trace_path=_trace_path(
                                             trace_out, "b1", wl, model, q,
                                             f"t{nt}"))
                        rows.append(r)
                        print(f"fig2/{wl}/{model}/{r['contention']}/t{nt}/{q},"
                              f"{r['us_per_op']:.3f},"
                              f"mops={r['mops_per_s']:.3f};"
                              f"retries_per_op={r['retries_per_op']:.2f}")
    return rows


# B2's contended column runs at this thread count: enough co-scheduled ops
# to exercise retries while keeping per-op accounting comparable.
B2_CONTENDED_THREADS = 4


def bench_persist_counts(ops: int, models: list, queues: list,
                         engine: str, contention: list,
                         trace_out: str = None) -> list:
    # 'native' (exact engine) keeps the paper's 1-thread per-op schedule:
    # its contention axis is collapsed to that single column
    cells = []   # (setting, label, thread count) actually run
    for cont in contention:
        label = contention_label(cont) if engine == "batched" else "native"
        nt = 1 if label in ("off", "native") else B2_CONTENDED_THREADS
        cells.append((cont, label, nt))
    columns = ", ".join(f"{label} = {nt} thread{'s' if nt > 1 else ''}"
                        for _, label, nt in cells)
    print(f"\n# B2: persist-op accounting ({ops} ops, per memory model; "
          f"{columns})")
    print("name,us_per_call,derived")
    rows = []
    for model in models:
        for cont, label, nt in cells:
            for q in queues:
                r = run_workload(q, "pairs", nt, ops, model=model,
                                 engine=engine, contention=cont,
                                 trace_path=_trace_path(trace_out, "b2",
                                                        model, q, f"t{nt}"))
                rows.append(r)
                print(f"counts/{model}/{r['contention']}/{q},"
                      f"{r['us_per_op']:.3f},"
                      f"fences_per_op={r['fences_per_op']:.2f};"
                      f"post_flush_per_op={r['post_flush_per_op']:.2f};"
                      f"retries_per_op={r['retries_per_op']:.2f}")
    return rows


def bench_onll(n: int = 200) -> None:
    print("\n# B3: ONLL universal construction (upper bound, §2.1)")
    print("name,us_per_call,derived")
    nv = NVRAM(1)
    obj = ONLL(nv, 1, lambda s, o: (s + o, s + o), 0)
    base = nv.total_stats()
    for _ in range(n):
        obj.update(0, 1)
    d = nv.total_stats().minus(base)
    print(f"onll/update,{d.time_ns / n / 1e3:.3f},"
          f"fences_per_op={d.fences / n:.2f};"
          f"post_flush_per_op={d.post_flush_accesses / n:.2f}")


def bench_roofline(path: str = None) -> None:
    base = os.path.dirname(__file__)
    merged = os.path.join(base, "dryrun_merged.jsonl")
    path = path or (merged if os.path.exists(merged)
                    else os.path.join(base, "dryrun_results.jsonl"))
    print("\n# B4: roofline terms from the multi-pod dry-run")
    if not os.path.exists(path):
        print(f"(no dry-run artifacts at {path}; run "
              "`python -m repro.launch.dryrun` first)")
        return
    print("name,us_per_call,derived")
    try:
        from benchmarks.roofline import load_cells, roofline_terms
    except ModuleNotFoundError:
        from roofline import load_cells, roofline_terms
    for cell in load_cells(path):
        t = roofline_terms(cell)
        if t is None:
            print(f"roofline/{cell['arch']}/{cell['shape']}/{cell['mesh']},"
                  f"nan,error={cell.get('error', '?')[:60]}")
            continue
        dom = t["bottleneck"]
        print(f"roofline/{cell['arch']}/{cell['shape']}/{cell['mesh']},"
              f"{t['step_us']:.1f},"
              f"compute_ms={t['compute_ms']:.2f};mem_ms={t['memory_ms']:.2f};"
              f"coll_ms={t['collective_ms']:.2f};bound={dom};"
              f"useful={t['useful_ratio']:.2f};"
              f"roofline_frac={t['roofline_fraction']:.3f}")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ops", type=int, default=200,
                    help="ops per thread (default 200; seed engine capped "
                         "at ~60)")
    ap.add_argument("--threads", default="1,2,4,8,16",
                    help="comma-separated thread counts, 1..64")
    ap.add_argument("--models", default=",".join(MODELS),
                    help="comma-separated memory models "
                         f"(default {','.join(MODELS)})")
    ap.add_argument("--workloads", default=",".join(WORKLOADS))
    ap.add_argument("--queues", default=",".join(DURABLE))
    ap.add_argument("--engine", choices=["batched", "exact"],
                    default="batched")
    ap.add_argument("--contention", default="off,on",
                    help="comma-separated contention axis values: off, on "
                         "(calibrated default model), learned "
                         "(trace-fitted profiles from "
                         "benchmarks/profiles/learned.json), or a float "
                         "retry_scale (batched engine only; the exact "
                         "engine's contention is native)")
    ap.add_argument("--trace-out", default=None,
                    help="directory for captured traces (*.trace.npz); "
                         "exact-engine runs only -- the trace subsystem "
                         "records real interleavings")
    ap.add_argument("--out", default=None,
                    help="write all B1/B2 rows to this CSV file")
    ap.add_argument("--manifest", default=None,
                    help="run-manifest destination (default: alongside "
                         "--out as <stem>.manifest.json)")
    ap.add_argument("--sections", default="b1,b2,b3,b4")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run: 30 ops/thread, threads 1,4")
    args = ap.parse_args(argv)
    for tok in args.contention.split(","):
        try:
            contention_label(tok)
        except ValueError:
            ap.error(f"--contention: {tok!r} is not off, on, learned, or "
                     "a float retry_scale")
    if args.trace_out and args.engine != "exact":
        ap.error("--trace-out needs --engine exact: the trace subsystem "
                 "records real per-primitive interleavings")
    if args.smoke:
        args.ops = 30
        args.threads = "1,4"
    return args


def fastpath_smoke_main(argv) -> None:
    """`run.py fastpath-smoke`: the schedule-compiler acceptance smoke.

    Four runs of the same workload per queue:

    * ``per-op@cap``   -- the pre-compiler stack (per-primitive replay,
      per-primitive allocator-area zeroing, collector running) at that
      stack's practical scale cap (``--cap-ops``, default 6400 total ops
      at 64 threads -- "a few thousand ops" per the pre-compiler docs);
    * ``per-op``       -- the same stack pushed to the full ``--ops``
      scale (areas amortize; the steady per-op cost);
    * ``per-op+bulk-alloc`` -- per-op ops with this PR's vectorized
      allocator seam + GC pause, isolating those two contributions;
    * ``compiled``     -- the full fast path at full scale.

    Three gates, all enforced: the compiled path must be ``--min-speedup``
    (default 30x) cheaper per op than the per-op stack at its practical
    cap, ``--min-speedup-same-scale`` (default 4x) cheaper than the
    per-op stack at the identical full scale, and absolutely cheaper than
    ``--max-us-per-op`` (default 10 us -- the columnar engine measures
    ~4.5-8 us/op run to run on the reference container; the margin
    absorbs CI-runner noise), inside ``--budget-s`` wall clock.  All four
    us/op figures are printed and written to the CSV, so no ratio hides
    another.

    The cap baseline keeps the pre-compiler stack's stock allocator
    config (4096-node areas) -- it is a historical reference point, not a
    tunable.  The three full-scale modes share ``--area-nodes`` so the
    same-scale ratio compares like for like.

    ``--differential`` reruns the compiled workload on the legacy record
    path (``QueueHarness(records="legacy")``) and requires every
    per-thread Stats field to be bit-identical to the columnar run -- the
    CI columnar-vs-legacy differential smoke, at full smoke scale rather
    than the equivalence suite's test sizes.

    ``--burst`` adds the burst-executor rows: ``--burst-workload``
    (default ``producers``) at the full ``--ops`` scale, once on the
    merged columnar runner and once with the vectorized burst executor
    (``run_batched(burst=...)``, window ``--burst-window``).  Two gates:
    per-thread Stats must be bit-identical between the two runs, and the
    burst run must be ``--min-speedup-burst`` (default 3x) cheaper per
    op at the identical scale -- the PR-10 sub-microsecond cell the
    trajectory snapshot tracks as
    ``fastpath-burst/<queue>/burst_us_per_op``.
    """
    ap = argparse.ArgumentParser(
        prog="run.py fastpath-smoke",
        description=fastpath_smoke_main.__doc__.splitlines()[0])
    ap.add_argument("--threads", type=int, default=64)
    ap.add_argument("--ops", type=int, default=100_000,
                    help="total ops across all threads (default 100k)")
    ap.add_argument("--cap-ops", type=int, default=6400,
                    help="total ops for the per-op stack's practical-cap "
                         "baseline (default 6400: the pre-compiler reach)")
    ap.add_argument("--queues", default="DurableMSQ,OptUnlinkedQ")
    ap.add_argument("--workload", default="mixed5050")
    ap.add_argument("--model", default="optane-clwb")
    ap.add_argument("--area-nodes", type=int, default=1024,
                    help="designated-area size (nodes/area) for the three "
                         "full-scale modes (default 1024: right-sized for "
                         "this workload's ~800 allocs/thread -- the stock "
                         "4096 spends most of an area's zeroing cost on "
                         "nodes the smoke never allocates); the per-op@cap "
                         "baseline keeps the pre-compiler stock 4096")
    ap.add_argument("--min-speedup", type=float, default=30.0,
                    help="required compiled (at --ops) vs per-op (at "
                         "--cap-ops) per-op speedup (default 30x; measured "
                         "~43-75x against the stock-config cap baseline)")
    ap.add_argument("--min-speedup-same-scale", type=float, default=4.0,
                    help="required compiled vs per-op speedup at the "
                         "identical --ops scale (default 4x; measured "
                         "~5-9x, the margin absorbs CI-runner noise)")
    ap.add_argument("--max-us-per-op", type=float, default=10.0,
                    help="absolute ceiling on compiled us/op (default 10; "
                         "measured ~4.5-8 on the reference container)")
    ap.add_argument("--budget-s", type=float, default=60.0,
                    help="wall-clock budget per compiled run")
    ap.add_argument("--differential", action="store_true",
                    help="rerun the compiled workload with records='legacy' "
                         "and require bit-identical per-thread Stats")
    ap.add_argument("--burst", action="store_true",
                    help="add the burst-executor rows: run --burst-workload "
                         "at full scale on the columnar runner and again "
                         "with run_batched(burst=...), require bit-identical "
                         "per-thread Stats and >= --min-speedup-burst")
    ap.add_argument("--burst-queues", default="MSQ",
                    help="comma-separated queues for the burst rows "
                         "(default MSQ: the queue whose op programs the "
                         "whole-burst vector fast paths fully collapse)")
    ap.add_argument("--burst-workload", default="producers",
                    help="workload for the burst rows (default producers: "
                         "the uncontended enqueue-only shape burst "
                         "prediction targets)")
    ap.add_argument("--burst-window", type=int, default=32768,
                    help="burst window in ops (default 32768)")
    ap.add_argument("--min-speedup-burst", type=float, default=3.0,
                    help="required burst vs columnar speedup at identical "
                         "scale (default 3x; measured ~3.3-3.6x on the "
                         "reference container)")
    ap.add_argument("--out", default=None, help="CSV destination")
    ap.add_argument("--manifest", default=None,
                    help="run-manifest destination (default: alongside "
                         "--out as <stem>.manifest.json)")
    args = ap.parse_args(argv)
    ops_per_thread = max(1, -(-args.ops // args.threads))
    total = ops_per_thread * args.threads
    cap_per_thread = max(1, -(-args.cap_ops // args.threads))
    cap_total = cap_per_thread * args.threads
    t_run0 = time.perf_counter()
    headline = {}
    modes = [
        # (label, ops/thread, compiled?, vectorized allocator seam?,
        #  pause GC?, area nodes) -- the first two reproduce the stack as
        # it stood before the schedule compiler: every primitive and
        # every allocator-area zeroing replayed one Python call at a
        # time, with the collector running.  The cap baseline keeps the
        # pre-compiler stock area size; the full-scale modes share
        # --area-nodes.
        ("per-op@cap", cap_per_thread, False, False, False, 4096),
        ("per-op", ops_per_thread, False, False, False, args.area_nodes),
        ("per-op+bulk-alloc", ops_per_thread, False, True, True,
         args.area_nodes),
        ("compiled", ops_per_thread, True, True, True, args.area_nodes),
    ]
    rows, failures = [], []
    print(f"# fastpath-smoke: {args.workload} x {args.threads} threads x "
          f"{total} ops ({args.model}; per-op cap baseline {cap_total} ops)")
    print("name,us_per_call,derived")
    for qname in args.queues.split(","):
        cell = {}
        for label, opt, compiled, bulk, pause_gc, area_nodes in modes:
            h = QueueHarness(ALL_QUEUES[qname], nthreads=args.threads,
                             model=args.model, area_nodes=area_nodes)
            h.nvram.enable_bulk_init = bulk
            plans, prefill = make_plans(args.workload, args.threads,
                                        opt, seed=0)
            for i in range(prefill):
                h.queue.enqueue(0, ("pre", i))
            base_stats = h.nvram.total_stats()
            t0 = time.perf_counter()
            res = h.run_batched(plans, compiled=compiled, pause_gc=pause_gc)
            wall = time.perf_counter() - t0
            n = opt * args.threads
            assert res.ops_completed == n
            us = wall * 1e6 / n
            cell[label] = us
            d = h.nvram.total_stats().minus(base_stats)
            if compiled:
                columnar_stats = {t: h.nvram.stats[t].snapshot()
                                  for t in range(args.threads)}
            rows.append({
                "queue": qname, "workload": args.workload,
                "model": args.model, "threads": args.threads, "mode": label,
                "ops": n, "wall_s": round(wall, 3),
                "us_per_op": round(us, 3),
                "post_flush_per_op": round(d.post_flush_accesses / n, 3),
                "fast_ops": h.fast.fast_ops if h.fast else 0,
                "bailed_ops": h.fast.bailed_ops if h.fast else 0,
                "speedup_vs_cap": "", "speedup_same_scale": "",
                "speedup_burst": "",
            })
        speedup_cap = cell["per-op@cap"] / cell["compiled"]
        speedup_same = cell["per-op"] / cell["compiled"]
        rows[-1]["speedup_vs_cap"] = round(speedup_cap, 2)
        rows[-1]["speedup_same_scale"] = round(speedup_same, 2)
        headline[f"fastpath/{qname}/compiled_us_per_op"] = \
            round(cell["compiled"], 4)
        headline[f"fastpath/{qname}/speedup_vs_cap"] = round(speedup_cap, 2)
        headline[f"fastpath/{qname}/speedup_same_scale"] = \
            round(speedup_same, 2)
        print(f"fastpath/{qname}/compiled,{cell['compiled']:.3f},"
              f"perop_cap_us={cell['per-op@cap']:.1f};"
              f"perop_us={cell['per-op']:.1f};"
              f"perop_bulk_us={cell['per-op+bulk-alloc']:.1f};"
              f"speedup_vs_cap={speedup_cap:.1f}x;"
              f"speedup_same_scale={speedup_same:.1f}x")
        wall_compiled = rows[-1]["wall_s"]
        if speedup_cap < args.min_speedup:
            failures.append(
                f"{qname}: {speedup_cap:.1f}x vs per-op@cap < "
                f"{args.min_speedup:.0f}x required")
        if speedup_same < args.min_speedup_same_scale:
            failures.append(
                f"{qname}: {speedup_same:.1f}x at same scale < "
                f"{args.min_speedup_same_scale:.0f}x required")
        if cell["compiled"] > args.max_us_per_op:
            failures.append(
                f"{qname}: compiled {cell['compiled']:.2f} us/op > "
                f"{args.max_us_per_op:.1f} us ceiling")
        if args.differential:
            h = QueueHarness(ALL_QUEUES[qname], nthreads=args.threads,
                             model=args.model, area_nodes=args.area_nodes,
                             records="legacy")
            h.nvram.enable_bulk_init = True
            plans, prefill = make_plans(args.workload, args.threads,
                                        ops_per_thread, seed=0)
            for i in range(prefill):
                h.queue.enqueue(0, ("pre", i))
            base_stats = h.nvram.total_stats()
            t0 = time.perf_counter()
            res = h.run_batched(plans, compiled=True, pause_gc=True)
            wall = time.perf_counter() - t0
            assert res.ops_completed == total
            d = h.nvram.total_stats().minus(base_stats)
            mismatches = [
                (t, f)
                for t in range(args.threads)
                for f in columnar_stats[t].__dict__
                if getattr(h.nvram.stats[t], f) != getattr(
                    columnar_stats[t], f)
            ]
            rows.append({
                "queue": qname, "workload": args.workload,
                "model": args.model, "threads": args.threads,
                "mode": "compiled-legacy", "ops": total,
                "wall_s": round(wall, 3),
                "us_per_op": round(wall * 1e6 / total, 3),
                "post_flush_per_op": round(
                    d.post_flush_accesses / total, 3),
                "fast_ops": h.fast.fast_ops if h.fast else 0,
                "bailed_ops": h.fast.bailed_ops if h.fast else 0,
                "speedup_vs_cap": "", "speedup_same_scale": "",
                "speedup_burst": "",
            })
            print(f"fastpath/{qname}/differential,"
                  f"{wall * 1e6 / total:.3f},"
                  f"legacy_stats={'MISMATCH' if mismatches else 'identical'}")
            if mismatches:
                t, f = mismatches[0]
                failures.append(
                    f"{qname}: legacy records diverge from columnar on "
                    f"{len(mismatches)} Stats fields (first: thread {t} "
                    f"{f}: legacy={getattr(h.nvram.stats[t], f)} "
                    f"columnar={getattr(columnar_stats[t], f)})")
        if wall_compiled > args.budget_s:
            failures.append(f"{qname}: compiled run took {wall_compiled}s "
                            f"(> {args.budget_s}s budget)")
    if args.burst:
        bw = {"window": args.burst_window}
        for qname in args.burst_queues.split(","):
            burst_cell, burst_stats = {}, {}
            for label, burst in (("columnar@burst-wl", None), ("burst", bw)):
                # warm codegen caches outside timing, like `profile` cells
                hw = QueueHarness(ALL_QUEUES[qname], nthreads=args.threads,
                                  model=args.model,
                                  area_nodes=args.area_nodes)
                hw.nvram.enable_bulk_init = True
                wplans, wprefill = make_plans(args.burst_workload,
                                              args.threads, 8, seed=0)
                for i in range(wprefill):
                    hw.queue.enqueue(0, ("pre", i))
                hw.run_batched(wplans, compiled=True, pause_gc=True,
                               burst=burst)
                h = QueueHarness(ALL_QUEUES[qname], nthreads=args.threads,
                                 model=args.model,
                                 area_nodes=args.area_nodes)
                h.nvram.enable_bulk_init = True
                plans, prefill = make_plans(args.burst_workload,
                                            args.threads, ops_per_thread,
                                            seed=0)
                for i in range(prefill):
                    h.queue.enqueue(0, ("pre", i))
                base_stats = h.nvram.total_stats()
                t0 = time.perf_counter()
                res = h.run_batched(plans, compiled=True, pause_gc=True,
                                    burst=burst)
                wall = time.perf_counter() - t0
                assert res.ops_completed == total
                us = wall * 1e6 / total
                burst_cell[label] = us
                burst_stats[label] = {t: h.nvram.stats[t].snapshot()
                                      for t in range(args.threads)}
                d = h.nvram.total_stats().minus(base_stats)
                rows.append({
                    "queue": qname, "workload": args.burst_workload,
                    "model": args.model, "threads": args.threads,
                    "mode": label, "ops": total, "wall_s": round(wall, 3),
                    "us_per_op": round(us, 3),
                    "post_flush_per_op": round(
                        d.post_flush_accesses / total, 3),
                    "fast_ops": h.fast.fast_ops if h.fast else 0,
                    "bailed_ops": h.fast.bailed_ops if h.fast else 0,
                    "speedup_vs_cap": "", "speedup_same_scale": "",
                    "speedup_burst": "",
                })
                bstats = h.last_burst_stats or {}
            speedup_burst = burst_cell["columnar@burst-wl"] / \
                burst_cell["burst"]
            rows[-1]["speedup_burst"] = round(speedup_burst, 2)
            mismatches = [
                (t, f)
                for t in range(args.threads)
                for f in burst_stats["burst"][t].__dict__
                if getattr(burst_stats["burst"][t], f) != getattr(
                    burst_stats["columnar@burst-wl"][t], f)
            ]
            headline[f"fastpath-burst/{qname}/burst_us_per_op"] = \
                round(burst_cell["burst"], 4)
            headline[f"fastpath-burst/{qname}/columnar_us_per_op"] = \
                round(burst_cell["columnar@burst-wl"], 4)
            headline[f"fastpath-burst/{qname}/speedup_vs_columnar"] = \
                round(speedup_burst, 2)
            print(f"fastpath-burst/{qname}/burst,"
                  f"{burst_cell['burst']:.3f},"
                  f"columnar_us={burst_cell['columnar@burst-wl']:.3f};"
                  f"speedup_burst={speedup_burst:.2f}x;"
                  f"bursted={bstats.get('ops_bursted', 0)};"
                  f"mispredicts={bstats.get('mispredicts', 0)};"
                  f"stats={'MISMATCH' if mismatches else 'identical'}")
            if speedup_burst < args.min_speedup_burst:
                failures.append(
                    f"{qname}: burst {speedup_burst:.2f}x vs columnar < "
                    f"{args.min_speedup_burst:.1f}x required")
            if mismatches:
                t, f = mismatches[0]
                failures.append(
                    f"{qname}: burst run diverges from columnar on "
                    f"{len(mismatches)} Stats fields (first: thread {t} "
                    f"{f}: burst="
                    f"{getattr(burst_stats['burst'][t], f)} columnar="
                    f"{getattr(burst_stats['columnar@burst-wl'][t], f)})")
    if args.out:
        with open(args.out, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
            w.writeheader()
            w.writerows(rows)
        print(f"# wrote {len(rows)} rows to {args.out}")
    _emit_manifest("fastpath-smoke", args, rows, headline,
                   wall_s=time.perf_counter() - t_run0)
    if failures:
        for msg in failures:
            print(f"# FASTPATH SMOKE FAILURE: {msg}", file=sys.stderr)
        sys.exit(1)


# `run.py fleet` CSV schema -- tests/test_docs_refs.py checks that the
# column list quoted in docs/fleet.md matches this constant.
FLEET_CSV_COLUMNS = [
    "queue", "model", "contention", "backend", "platform", "device_kind",
    "devices", "instances",
    "ops_per_instance", "total_ops", "chunk", "bails", "residents",
    "build_s", "run_s", "fleet_mops_per_s", "sim_ns_per_op",
    "fences_per_op", "post_flush_per_op", "checked", "check_ok",
]


def fleet_main(argv) -> None:
    """`run.py fleet`: queue-ops/sec across a simulated user fleet.

    Runs 10k-1M independent queue instances (one per simulated
    user/tenant, one thread each) as a single vectorized array program
    (repro.fleet): each queue x model compiled schedule is lowered to
    stacked event-count/effect arrays and driven by a vmapped lax.scan
    stepper sharded over the first --devices JAX devices; instances hitting a
    fast-path bail condition fall out to the real per-instance executor
    and rejoin at the next chunk boundary.  ``--check N`` re-runs N
    sampled instances per cell on independent ``run_batched`` harnesses
    and requires bit-identical Stats (every counter and ``time_ns``) --
    the fleet's correctness gate; failures exit nonzero.  One thread per
    instance means contended counts are bit-identical to uncontended
    ones (see docs/fleet.md), so ``--contention`` is a reporting axis.
    """
    ap = argparse.ArgumentParser(
        prog="run.py fleet",
        description=fleet_main.__doc__.splitlines()[0])
    ap.add_argument("--instances", type=int, default=100_000,
                    help="fleet size (default 100k; 1M is practical with "
                         "--batch)")
    ap.add_argument("--ops", type=int, default=96,
                    help="plan steps per instance (default 96)")
    ap.add_argument("--queues", default="DurableMSQ,OptUnlinkedQ,OptLinkedQ",
                    help=f"comma-separated, from {','.join(ALL_QUEUES)}")
    ap.add_argument("--models", default="optane-clwb",
                    help=f"comma-separated memory models ({','.join(MODELS)})")
    ap.add_argument("--contention", default="off",
                    help="comma-separated: off, on (reporting axis; "
                         "per-instance counts are bit-identical either way "
                         "at one thread per instance)")
    ap.add_argument("--backend",
                    choices=["auto", "numpy", "jax", "jax-opcode", "pallas"],
                    default="numpy",
                    help="numpy (default; the host reference), jax (the "
                         "sharded unrolled XLA path), jax-opcode (the "
                         "opcode-interpreting scan: depth-independent "
                         "compile; the TPU path), pallas (the opcode "
                         "interpreter as a Pallas chunk kernel; CPU "
                         "interpret mode only, Mosaic refuses it on TPU), "
                         "or auto (jax-opcode on TPU, numpy otherwise)")
    ap.add_argument("--devices", type=int, default=1,
                    help="jax mesh size: shard instances over the first N "
                         "JAX devices (fails if fewer exist)")
    ap.add_argument("--chunk", type=int, default=48,
                    help="plan steps per vector chunk (bail/rejoin "
                         "granularity)")
    ap.add_argument("--batch", type=int, default=0,
                    help="instances per state batch (0 = whole fleet at "
                         "once; bound memory at 1M scale)")
    ap.add_argument("--prefill", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", type=int, default=0,
                    help="equivalence-check this many sampled instances per "
                         "cell against independent run_batched harnesses")
    ap.add_argument("--heartbeat", type=float, default=5.0,
                    help="seconds between fleet progress lines on stderr "
                         "(chunks done, bails, rejoins, residents, us/op "
                         "so far)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the stderr heartbeat (tests/CI logs)")
    ap.add_argument("--out", default=None, help="CSV destination")
    ap.add_argument("--manifest", default=None,
                    help="run-manifest destination (default: alongside "
                         "--out as <stem>.manifest.json)")
    args = ap.parse_args(argv)
    from repro.fleet import (FleetConfig, check_instances,
                             enable_compile_cache, run_fleet)
    if args.backend != "numpy":
        enable_compile_cache()
    rows, failures = [], []
    device = None
    headline = {}
    t_run0 = time.perf_counter()
    print(f"# fleet: {args.instances} instances x {args.ops} ops "
          f"(backend {args.backend}, chunk {args.chunk})")
    print("name,us_per_call,derived")
    for model in args.models.split(","):
        for cont in args.contention.split(","):
            for qname in args.queues.split(","):
                cfg = FleetConfig(
                    queue=qname, model=model, instances=args.instances,
                    ops=args.ops, prefill=args.prefill, seed=args.seed,
                    chunk=args.chunk, backend=args.backend,
                    devices=args.devices, batch=args.batch, contention=cont)
                hb = None if args.quiet else Heartbeat(
                    interval_s=args.heartbeat,
                    label=f"fleet {model}/{cont}/{qname}")
                res = run_fleet(cfg, heartbeat=hb)
                agg = res.aggregate()
                total = res.total_ops
                sim_ns = agg.time_ns / total
                checked = check_ok = 0
                if args.check:
                    checks = check_instances(
                        res, sample=args.check,
                        contention=(True if cont == "on" else None))
                    checked = len(checks)
                    check_ok = sum(r["ok"] for r in checks)
                    for r in checks:
                        if not r["ok"]:
                            failures.append(
                                f"{qname}/{model}/{cont}: instance "
                                f"{r['instance']} fleet Stats != run_batched "
                                f"Stats")
                device = res.device
                platform = device["platform"] if device else "host"
                rows.append({
                    "queue": qname, "model": model, "contention": cont,
                    "backend": res.backend, "platform": platform,
                    "device_kind": device["kind"] if device else "",
                    "devices": res.devices,
                    "instances": args.instances,
                    "ops_per_instance": args.ops, "total_ops": total,
                    "chunk": args.chunk, "bails": res.bails,
                    "residents": res.residents,
                    "build_s": round(res.build_s, 3),
                    "run_s": round(res.run_s, 3),
                    "fleet_mops_per_s": round(res.ops_per_sec / 1e6, 3),
                    "sim_ns_per_op": round(sim_ns, 2),
                    "fences_per_op": round(agg.fences / total, 3),
                    "post_flush_per_op": round(
                        agg.post_flush_accesses / total, 3),
                    "checked": checked, "check_ok": check_ok,
                })
                print(f"fleet/{model}/{cont}/{qname},"
                      f"{res.run_s * 1e6 / total:.4f},"
                      f"mops={res.ops_per_sec / 1e6:.2f};"
                      f"sim_ns_per_op={sim_ns:.1f};"
                      f"fences_per_op={agg.fences / total:.2f};"
                      f"backend={res.backend};platform={platform};"
                      f"bails={res.bails};"
                      f"checked={check_ok}/{checked}")
                # the numpy reference keeps the legacy trajectory cell
                # name; other backends get backend-qualified cells, and
                # off-CPU platforms platform-qualified ones, so the perf
                # gate never compares across backends or host and device
                cell = ("wall_us_per_op" if res.backend == "numpy"
                        else f"{res.backend}_wall_us_per_op")
                if platform not in ("host", "cpu"):
                    cell = f"{platform}_{cell}"
                headline[f"fleet/{model}/{cont}/{qname}/{cell}"] = \
                    round(res.run_s * 1e6 / total, 4)
    if args.out:
        with open(args.out, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=FLEET_CSV_COLUMNS)
            w.writeheader()
            w.writerows(rows)
        print(f"# wrote {len(rows)} rows to {args.out}")
    _emit_manifest("fleet", args, rows, headline,
                   wall_s=time.perf_counter() - t_run0, device=device)
    if failures:
        for msg in failures:
            print(f"# FLEET CHECK FAILURE: {msg}", file=sys.stderr)
        sys.exit(1)


def fit_profiles_main(argv) -> None:
    """`run.py fit-profiles`: capture exact-scheduler traces and refit the
    learned contention profiles (benchmarks/profiles/learned.json)."""
    ap = argparse.ArgumentParser(
        prog="run.py fit-profiles",
        description="Trace the exact scheduler and fit per-queue contention "
                    "profiles (repro.trace.fit); writes the JSON the "
                    "--contention learned axis reads.")
    # all 8 queues, MSQ included: the volatile baseline gets a learned
    # profile too so every contention axis value covers every queue
    ap.add_argument("--queues", default=",".join(ALL_QUEUES))
    ap.add_argument("--threads", default="2,4,8,12",
                    help="thread counts to trace (default 2,4,8,12: the "
                         "12-thread sample anchors the extrapolation "
                         "region; exact runs get slow past 12)")
    ap.add_argument("--ops", type=int, default=24,
                    help="ops per thread per trace (default 24)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--model", default="optane-clwb")
    ap.add_argument("--trace-out", default=None,
                    help="also save the captured traces to this directory")
    ap.add_argument("--out",
                    default=os.path.join(os.path.dirname(__file__),
                                         "profiles", "learned.json"),
                    help="profile JSON destination (default: the checked-in "
                         "benchmarks/profiles/learned.json)")
    args = ap.parse_args(argv)
    from repro.trace.fit import fit_all, save_profiles
    profiles = fit_all(
        args.queues.split(","),
        thread_counts=[int(t) for t in args.threads.split(",")],
        ops_per_thread=args.ops, seed=args.seed, model=args.model,
        trace_dir=args.trace_out, log=print)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    save_profiles(args.out, profiles)
    print(f"# wrote learned profiles for {len(profiles)} queues "
          f"to {args.out}")


def crash_sweep_main(argv) -> None:
    """`run.py crash-sweep`: durable linearizability at every scheduler
    step, via the snapshot/restore crash engine (repro.crash).  Emits the
    coverage/recovery-cost CSV (--out) and, on violations, one repro
    artifact per failure (--artifacts-dir) before exiting nonzero."""
    from repro.crash.__main__ import sweep_main
    rc = sweep_main(argv)
    if rc:
        sys.exit(rc)


# Execution phases the `profile` subcommand reports for run_batched cells
# (see repro.obs.profiler); CSV columns replace '-' with '_'.
EXEC_PHASES = ("heap-loop", "interpreted-body", "record-charging",
               "bookkeeping", "bail-real-op")
BURST_PHASES = ("burst-predict", "burst-verify", "burst-vector-apply",
                "mispredict-replay")
FLEET_PHASES = ("lowering", "chunk-step", "poll", "bail-replay",
                "resident-replay")
CRASH_PHASES = ("capture", "restore", "recover", "check")


def _phase_cols(per, names):
    """{phase -> value} -> ordered (column, value) pairs for CSV rows."""
    return [(ph.replace("-", "_") + "_us", round(per.get(ph, 0.0), 4))
            for ph in names]


def profile_main(argv) -> None:
    """`run.py profile`: per-phase µs/op attribution across the layers.

    For every queue x model cell, runs the standard workload under an
    attached :class:`repro.obs.PhaseProfiler` and prints where each
    microsecond goes: ``heap-loop`` (dispatch + cursor bookkeeping),
    ``interpreted-body`` (the compiled per-op fns -- the interpreted
    Python the vectorized-burst roadmap item targets), ``record-charging``
    (the columnar store's staged-burst sync passes), ``bookkeeping``
    (setup/teardown) and ``bail-real-op`` (real per-primitive fallbacks).
    The phase sum is within 10% of wall time by construction (gap-free
    scoped timers); a coverage outside [0.9, 1.1] prints a warning.

    ``--sections burst`` reruns the cells with the vectorized burst
    executor attached (``run_batched(burst=...)``) and adds its phase
    group: ``burst-predict`` (heap simulation as segmented cumsums),
    ``burst-verify`` (key comparison against the prediction),
    ``burst-vector-apply`` (bulk memory effects + staged records) and
    ``mispredict-replay`` (bounded columnar replay of rejected
    stretches).  ``--sections fleet`` and ``--sections crash`` add the
    fleet runner (lowering / chunk-step / poll / bail-replay /
    resident-replay) and crash-sweep recovery (capture / restore /
    recover / check) phase breakdowns.  Each cell does a small warmup
    run first so codegen and cache fills are not attributed to the
    measured phases.
    """
    ap = argparse.ArgumentParser(
        prog="run.py profile",
        description=profile_main.__doc__.splitlines()[0])
    ap.add_argument("--queues", default=",".join(ALL_QUEUES),
                    help="comma-separated (default: all 8 queues)")
    ap.add_argument("--models", default="optane-clwb",
                    help=f"comma-separated memory models ({','.join(MODELS)})")
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--ops", type=int, default=2000, help="ops per thread")
    ap.add_argument("--workload", default="mixed5050")
    ap.add_argument("--area-nodes", type=int, default=1024)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sections", default="exec",
                    help="comma-separated: exec (run_batched phases), "
                         "burst (run_batched with the burst executor: "
                         "predict/verify/vector-apply/mispredict-replay), "
                         "fleet (fleet-runner phases), crash (crash-sweep "
                         "recovery phases)")
    ap.add_argument("--burst-window", type=int, default=32768,
                    help="burst window for --sections burst cells")
    ap.add_argument("--fleet-instances", type=int, default=2000)
    ap.add_argument("--fleet-ops", type=int, default=48)
    ap.add_argument("--crash-ops", type=int, default=2,
                    help="enqueues per thread for the crash-profile cell")
    ap.add_argument("--out", default=None, help="CSV destination")
    ap.add_argument("--manifest", default=None,
                    help="run-manifest destination (default: alongside "
                         "--out as <stem>.manifest.json)")
    args = ap.parse_args(argv)
    sections = set(args.sections.split(","))
    unknown = sections - {"exec", "burst", "fleet", "crash"}
    if unknown:
        ap.error(f"unknown --sections {sorted(unknown)}")
    queues = args.queues.split(",")
    models = args.models.split(",")
    rows, headline = [], {}
    all_phases = PhaseProfiler()
    t_run0 = time.perf_counter()
    print(f"# profile: per-phase us/op ({args.workload} x {args.threads} "
          f"threads x {args.ops} ops/thread; sections "
          f"{','.join(sorted(sections))})")
    print("name,us_per_call,derived")
    if "exec" in sections:
        for model in models:
            for qname in queues:
                # warmup: executor codegen + numpy caches, outside timing
                hw = QueueHarness(ALL_QUEUES[qname], nthreads=args.threads,
                                  model=model, area_nodes=args.area_nodes)
                wplans, wprefill = make_plans(args.workload, args.threads,
                                              8, seed=args.seed)
                for i in range(wprefill):
                    hw.queue.enqueue(0, ("pre", i))
                hw.run_batched(wplans)
                h = QueueHarness(ALL_QUEUES[qname], nthreads=args.threads,
                                 model=model, area_nodes=args.area_nodes)
                plans, prefill = make_plans(args.workload, args.threads,
                                            args.ops, seed=args.seed)
                for i in range(prefill):
                    h.queue.enqueue(0, ("pre", i))
                prof = PhaseProfiler()
                t0 = time.perf_counter()
                res = h.run_batched(plans, profile=prof)
                wall = time.perf_counter() - t0
                n = res.ops_completed
                per = prof.us_per_op(n)
                cov = prof.coverage(wall)
                us = wall * 1e6 / max(n, 1)
                row = {"section": "exec", "queue": qname, "model": model,
                       "threads": args.threads, "ops": n,
                       "wall_s": round(wall, 4), "us_per_op": round(us, 4),
                       "coverage": round(cov, 4),
                       "fast_ops": h.fast.fast_ops if h.fast else 0,
                       "bailed_ops": h.fast.bailed_ops if h.fast else 0}
                row.update(_phase_cols(per, EXEC_PHASES))
                rows.append(row)
                derived = ";".join(
                    f"{c}={v}" for c, v in _phase_cols(per, EXEC_PHASES))
                print(f"profile/{model}/{qname},{us:.3f},"
                      f"{derived};coverage={cov:.3f}")
                if not 0.9 <= cov <= 1.1:
                    print(f"# profile WARNING: {model}/{qname} phase sum "
                          f"covers {cov:.2f}x of wall time "
                          f"(expected within 10%)", file=sys.stderr)
                headline[f"profile/{model}/{qname}/us_per_op"] = \
                    round(us, 4)
                all_phases.merge(prof)
    if "burst" in sections:
        bw = {"window": args.burst_window}
        for model in models:
            for qname in queues:
                hw = QueueHarness(ALL_QUEUES[qname], nthreads=args.threads,
                                  model=model, area_nodes=args.area_nodes)
                wplans, wprefill = make_plans(args.workload, args.threads,
                                              8, seed=args.seed)
                for i in range(wprefill):
                    hw.queue.enqueue(0, ("pre", i))
                hw.run_batched(wplans, burst=bw)
                h = QueueHarness(ALL_QUEUES[qname], nthreads=args.threads,
                                 model=model, area_nodes=args.area_nodes)
                plans, prefill = make_plans(args.workload, args.threads,
                                            args.ops, seed=args.seed)
                for i in range(prefill):
                    h.queue.enqueue(0, ("pre", i))
                prof = PhaseProfiler()
                t0 = time.perf_counter()
                res = h.run_batched(plans, profile=prof, burst=bw)
                wall = time.perf_counter() - t0
                n = res.ops_completed
                per = prof.us_per_op(n)
                cov = prof.coverage(wall)
                us = wall * 1e6 / max(n, 1)
                bs = h.last_burst_stats or {}
                row = {"section": "burst", "queue": qname, "model": model,
                       "threads": args.threads, "ops": n,
                       "wall_s": round(wall, 4), "us_per_op": round(us, 4),
                       "coverage": round(cov, 4),
                       "burst_commits": bs.get("commits", 0),
                       "burst_mispredicts": bs.get("mispredicts", 0),
                       "burst_rejects": bs.get("rejects", 0),
                       "ops_bursted": bs.get("ops_bursted", 0),
                       "replayed_ops": bs.get("replayed_ops", 0)}
                row.update(_phase_cols(per, EXEC_PHASES + BURST_PHASES))
                rows.append(row)
                derived = ";".join(
                    f"{c}={v}" for c, v in _phase_cols(per, BURST_PHASES))
                print(f"profile-burst/{model}/{qname},{us:.3f},"
                      f"{derived};bursted={bs.get('ops_bursted', 0)};"
                      f"coverage={cov:.3f}")
                if not 0.9 <= cov <= 1.1:
                    print(f"# profile WARNING: burst {model}/{qname} phase "
                          f"sum covers {cov:.2f}x of wall time "
                          f"(expected within 10%)", file=sys.stderr)
                headline[f"profile-burst/{model}/{qname}/us_per_op"] = \
                    round(us, 4)
                all_phases.merge(prof)
    if "fleet" in sections:
        from repro.fleet import FleetConfig, run_fleet
        for model in models:
            for qname in queues:
                cfg = FleetConfig(queue=qname, model=model,
                                  instances=args.fleet_instances,
                                  ops=args.fleet_ops, seed=args.seed,
                                  backend="numpy")
                prof = PhaseProfiler()
                t0 = time.perf_counter()
                res = run_fleet(cfg, profile=prof)
                wall = time.perf_counter() - t0
                n = res.total_ops
                per = prof.us_per_op(n)
                cov = prof.coverage(wall)
                us = res.run_s * 1e6 / n
                row = {"section": "fleet", "queue": qname, "model": model,
                       "threads": 1, "ops": n, "wall_s": round(wall, 4),
                       "us_per_op": round(us, 4), "coverage": round(cov, 4),
                       "fast_ops": 0, "bailed_ops": res.bails}
                row.update(_phase_cols(per, FLEET_PHASES))
                rows.append(row)
                derived = ";".join(
                    f"{c}={v}" for c, v in _phase_cols(per, FLEET_PHASES))
                print(f"profile-fleet/{model}/{qname},{us:.4f},"
                      f"{derived};coverage={cov:.3f}")
                headline[f"profile-fleet/{model}/{qname}/us_per_op"] = \
                    round(us, 4)
                all_phases.merge(prof)
    if "crash" in sections:
        from repro.crash.sweep import sweep_queue
        for model in models:
            for qname in queues:
                if qname not in DURABLE_QUEUES:
                    continue   # the volatile baseline has no recovery
                prof = PhaseProfiler()
                t0 = time.perf_counter()
                r = sweep_queue(qname, per_thread=args.crash_ops,
                                model=model, profile=prof)
                wall = time.perf_counter() - t0
                cov_info = r.coverage()
                checks = max(cov_info["crashes_checked"], 1)
                per = prof.us_per_op(checks)   # us per recovery check
                cov = prof.coverage(wall)
                us = cov_info["recovery_us_total"] / checks
                row = {"section": "crash", "queue": qname, "model": model,
                       "threads": 3, "ops": checks,
                       "wall_s": round(wall, 4), "us_per_op": round(us, 4),
                       "coverage": round(cov, 4),
                       "fast_ops": 0, "bailed_ops": 0}
                row.update(_phase_cols(per, CRASH_PHASES))
                rows.append(row)
                derived = ";".join(
                    f"{c}={v}" for c, v in _phase_cols(per, CRASH_PHASES))
                print(f"profile-crash/{model}/{qname},{us:.3f},"
                      f"{derived};coverage={cov:.3f}")
                headline[f"profile-crash/{model}/{qname}"
                         f"/recoveries_per_s"] = round(1e6 / max(us, 1e-9), 2)
                all_phases.merge(prof)
    if args.out and rows:
        fieldnames = []
        for r in rows:
            for k in r:
                if k not in fieldnames:
                    fieldnames.append(k)
        with open(args.out, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=fieldnames, restval="")
            w.writeheader()
            w.writerows(rows)
        print(f"# wrote {len(rows)} rows to {args.out}")
    _emit_manifest("profile", args, rows, headline,
                   phases=all_phases.as_dict(),
                   wall_s=time.perf_counter() - t_run0)


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "fit-profiles":
        return fit_profiles_main(argv[1:])
    if argv and argv[0] == "crash-sweep":
        return crash_sweep_main(argv[1:])
    if argv and argv[0] == "fastpath-smoke":
        return fastpath_smoke_main(argv[1:])
    if argv and argv[0] == "fleet":
        return fleet_main(argv[1:])
    if argv and argv[0] == "profile":
        return profile_main(argv[1:])
    args = parse_args(argv)
    threads = sorted({int(t) for t in args.threads.split(",")})
    models = args.models.split(",")
    workloads = args.workloads.split(",")
    queues = args.queues.split(",")
    contention = args.contention.split(",")
    if args.engine == "exact":
        contention = ["off"]   # exact runs contend natively; one column
    sections = set(args.sections.split(","))
    rows = []
    t_run0 = time.perf_counter()
    if "b1" in sections:
        rows += bench_fig2(args.ops, threads, models, workloads, queues,
                           args.engine, contention,
                           trace_out=args.trace_out)
    if "b2" in sections:
        rows += bench_persist_counts(args.ops, models, queues, args.engine,
                                     contention, trace_out=args.trace_out)
    if "b3" in sections:
        bench_onll(args.ops)
    if "b4" in sections:
        bench_roofline()
    if args.out:
        if rows:
            with open(args.out, "w", newline="") as f:
                w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
                w.writeheader()
                w.writerows(rows)
            print(f"\n# wrote {len(rows)} rows to {args.out}")
        else:
            print(f"\n# warning: no CSV rows produced (sections "
                  f"{sorted(sections)} emit none); {args.out} not written")
    # simulated per-op latencies are deterministic, so headline cells
    # only move when the cost model (or a queue's schedule) changes --
    # exactly the drift the manifest trajectory should record
    headline = {}
    for r in rows:
        headline[f"{r['workload']}/{r['model']}/{r['contention']}"
                 f"/t{r['threads']}/{r['queue']}/us_per_op_sim"] = \
            round(r["us_per_op"], 4)
    extra = None
    if args.trace_out:
        attribution = _trace_attribution(args.trace_out)
        if attribution:
            extra = {"post_flush_attribution": attribution}
    _emit_manifest("bench", args, rows, headline,
                   wall_s=time.perf_counter() - t_run0, extra=extra)


if __name__ == "__main__":
    main()
