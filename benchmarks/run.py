"""Benchmark harness.

Two families of suites:

* scenario suites (``--suite scenarios|smoke|paper``) — declarative
  Scenario specs executed by :class:`repro.experiments.ExperimentRunner`
  across each scenario's backend matrix (default: the paper's
  containerd/junctiond pair; ``--backends`` widens it to any registered
  set), emitting a machine-readable ``BENCH_<suite>.json`` artifact
  (``--json``) with per-scenario latency histograms, knee/SLO metrics,
  and paper-claim deltas computed from the claims pair.  Open-mode
  scenarios locate their SLO knee with the adaptive search by default
  (``--search-budget`` caps its per-backend probe count); scenarios that
  pin explicit rate grids sweep them unchanged.
* ``--suite legacy`` (default) — the original one-module-per-figure
  benches, printing ``name,value,derived`` CSV.
* ``--list`` — enumerate registered backends and scenarios (names, modes,
  rate grids; fleet scenarios additionally show their simulated worker
  count, placement policies and image-distribution strategies) without
  running anything.

Exit status is nonzero when any bench or scenario cell fails.

Examples::

    python -m benchmarks.run --suite smoke --json BENCH_ci.json
    python -m benchmarks.run --suite smoke \
        --backends containerd,junctiond,quark,wasm,firecracker,gvisor \
        --json BENCH_ci.json
    python -m benchmarks.run --suite scenarios --json BENCH_scenarios.json \
        --workers 4
    python -m benchmarks.run --list
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

from repro.core.backends import available_backends, get_backend_class
from repro.experiments import (SMOKE_DURATION_SCALE, SUITES,
                               ExperimentRunner, build_artifact, build_scenarios,
                               get_suite, metric_row, metrics_csv,
                               write_artifact)

def _legacy_benches():
    # imported lazily: aes_function pulls in jax, which --list and the
    # scenario suites never need
    from benchmarks import (aes_function, coldstart, fig5_latency, fig6_load,
                            multitenant, polling_efficiency)
    return [
        ("fig5_latency", fig5_latency),
        ("fig6_load", fig6_load),
        ("coldstart", coldstart),
        ("polling_efficiency", polling_efficiency),
        ("multitenant", multitenant),
        ("aes_function", aes_function),
    ]


def run_legacy(args) -> int:
    all_rows, failures = [], []
    for name, mod in _legacy_benches():
        print(f"\n===== {name} =====")
        # simlint: allow[wall-clock] prints host elapsed per legacy bench
        t0 = time.time()
        try:
            rows, _ = mod.run(verbose=True)
            all_rows.extend(rows)
        except Exception as e:
            print(f"  BENCH FAILED: {e!r}")
            all_rows.append((f"{name}_FAILED", float("nan"), repr(e)))
            failures.append({"scenario": name, "backend": "-",
                             "error": repr(e)})
        # simlint: allow[wall-clock] prints host elapsed per legacy bench
        print(f"  [{time.time() - t0:.1f}s]")
    print("\nname,value,derived")
    for name, value, derived in all_rows:
        v = float(value) if isinstance(value, (int, float)) else float("nan")
        print(f"{name},{v:.3f},{derived}")
    metrics = [metric_row(n, v, d) for n, v, d in all_rows]
    if args.sim_throughput:
        print()
        run_sim_throughput({"metrics": metrics})
    if args.json:
        write_artifact(args.json, build_artifact("legacy", [], metrics,
                                                 failures))
        print(f"\nwrote {args.json}")
    if failures:
        print(f"\n{len(failures)} bench(es) FAILED", file=sys.stderr)
    return 1 if failures else 0


def measure_sim_throughput(duration_s: float = 8.0, rate_rps: float = 1200.0,
                           backend: str = "containerd", seed: int = 0,
                           repeats: int = 3):
    """Simulated-requests-per-wall-second of both ``drive`` engines on a
    reference workload (containerd just under its SLO knee — deep
    queueing, the regime the pre-PR generator driver spent its wall time
    in).

    Each engine runs several times on a fresh same-seed runtime and
    keeps the *minimum* wall: the simulation itself is deterministic, so
    run-to-run spread is pure machine noise, and that noise is one-sided
    (contention only ever adds time).  min-wall is the stable estimator
    a hard CI gate can sit on.  The events engine is ~25x cheaper per
    run, so it gets ``2 * repeats + 1`` attempts to land in a quiet
    scheduling window for the price of a fraction of one process run.

    Returns ``{"events": {...}, "process": {...}, "speedup": float}``
    where each engine entry carries ``n`` (admitted requests), ``wall_s``
    and ``sim_rps``.  The events/process ratio is the raw-speed gate CI
    asserts on (>= 20x)."""
    from repro.core import (FaasdRuntime, FunctionSpec, LoadSpec, Simulator,
                            drive)
    out = {}
    for engine in ("events", "process"):
        wall, n = float("inf"), 0
        tries = 2 * repeats + 1 if engine == "events" else repeats
        for _ in range(max(1, tries)):
            sim = Simulator(seed=seed)
            rt = FaasdRuntime(sim, backend=backend)
            rt.deploy_blocking(FunctionSpec(name="aes"))
            load = LoadSpec.single("aes", rate_rps, duration_s=duration_s)
            # simlint: allow[wall-clock] benchmarks the simulator itself
            t0 = time.perf_counter()
            res = drive(rt, load, engine=engine)
            # simlint: allow[wall-clock] benchmarks the simulator itself
            wall = min(wall, max(time.perf_counter() - t0, 1e-9))
            n = res["n"]
        out[engine] = {"n": n, "wall_s": wall, "sim_rps": n / wall}
    out["speedup"] = out["events"]["sim_rps"] / out["process"]["sim_rps"]
    return out


def measure_fleet_sim_throughput(duration_s: float = 4.0,
                                 rate_rps: float = 12000.0,
                                 n_workers: int = 32,
                                 backend: str = "containerd", seed: int = 0,
                                 repeats: int = 3):
    """Simulated-requests-per-wall-second of ``drive`` over the fleet
    reference: a 32-worker containerd cluster behind one gateway, offered
    an aggregate open-loop rate sized to the single-runtime reference
    (1200 rps x ~10 workers' worth of headroom), least-loaded placement.

    Same min-wall estimator as :func:`measure_sim_throughput`.  Returns
    ``{"n", "wall_s", "sim_rps", "per_worker_rps"}`` where
    ``per_worker_rps`` normalises by the fleet size — the
    machine-portable sanity figure (routing + per-worker pools cost a
    bounded factor over the single-runtime driver, not a per-worker
    slowdown)."""
    from repro.core import FunctionSpec, LoadSpec, Simulator, drive
    from repro.fleet import Cluster
    wall, n = float("inf"), 0
    for _ in range(max(1, 2 * repeats + 1)):
        sim = Simulator(seed=seed)
        cl = Cluster(sim, n_workers, backend=backend)
        cl.deploy_blocking(FunctionSpec(name="aes"))
        load = LoadSpec.single("aes", rate_rps, duration_s=duration_s)
        # simlint: allow[wall-clock] benchmarks the simulator itself
        t0 = time.perf_counter()
        res = drive(cl, load)
        # simlint: allow[wall-clock] benchmarks the simulator itself
        wall = min(wall, max(time.perf_counter() - t0, 1e-9))
        n = res["n"]
    return {"n": n, "wall_s": wall, "sim_rps": n / wall,
            "per_worker_rps": n / wall / n_workers}


def run_sim_throughput(doc=None) -> dict:
    """Measure, print the stable one-line summaries CI greps, and (when
    an artifact dict is given) append the metric rows."""
    m = measure_sim_throughput()
    ev, pr = m["events"], m["process"]
    print(f"sim_throughput: events={ev['sim_rps']:.0f} req/s "
          f"process={pr['sim_rps']:.0f} req/s speedup={m['speedup']:.1f}x "
          f"(n={ev['n']}, containerd@1200rps)")
    fl = measure_fleet_sim_throughput()
    m["fleet"] = fl
    print(f"fleet_sim_throughput: events={fl['sim_rps']:.0f} req/s "
          f"({fl['n']} requests, 32 workers, containerd@12000rps "
          f"aggregate)")
    if doc is not None:
        doc["metrics"].append(metric_row(
            "sim_throughput", ev["sim_rps"],
            f"{ev['n']} simulated requests / {ev['wall_s']:.3f}s wall "
            f"(events engine, containerd@1200rps)"))
        doc["metrics"].append(metric_row(
            "sim_throughput_speedup", m["speedup"],
            f"events {ev['sim_rps']:.0f} req/s vs process "
            f"{pr['sim_rps']:.0f} req/s on the reference workload"))
        doc["metrics"].append(metric_row(
            "fleet_sim_throughput", fl["sim_rps"],
            f"{fl['n']} simulated requests / {fl['wall_s']:.3f}s wall "
            f"(32-worker containerd cluster @ 12000rps aggregate)"))
    return m


def run_profile(args) -> int:
    """Run one (scenario, backend) cell under cProfile and print the
    top-25 cumulative entries — the starting point for perf work."""
    import cProfile
    import pstats
    spec = args.profile
    scenario_name, _, backend = spec.partition(":")
    scenarios = {sc.name: sc for sc in build_scenarios().values()}
    if scenario_name not in scenarios:
        raise SystemExit(f"unknown scenario {scenario_name!r}; "
                         f"see --list for names")
    sc = scenarios[scenario_name]
    backend = backend or sc.backends[0]
    if backend not in sc.backends:
        sc = dataclasses.replace(sc, backends=(backend,))
    smoke = args.suite == "smoke"
    scale = args.duration * (SMOKE_DURATION_SCALE if smoke else 1.0)
    runner = ExperimentRunner(duration_scale=scale, smoke=smoke,
                              verbose=False)
    print(f"profiling {scenario_name}/{backend} "
          f"(duration_scale={scale:.2f})")
    prof = cProfile.Profile()
    prof.enable()
    runner.run_suite([dataclasses.replace(sc, backends=(backend,))],
                     suite="profile")
    prof.disable()
    pstats.Stats(prof).sort_stats("cumulative").print_stats(25)
    return 0


def _parse_backends(spec: str):
    names = list(dict.fromkeys(      # dedupe, keeping the given order
        b.strip() for b in spec.split(",") if b.strip()))
    registered = available_backends()
    unknown = [b for b in names if b not in registered]
    if unknown:
        raise SystemExit(f"unknown backend(s) {', '.join(unknown)}; "
                         f"registered: {', '.join(registered)}")
    return tuple(names)


def run_list(args) -> int:
    """Enumerate registered backends and scenarios without running."""
    print("registered backends:")
    for name in available_backends():
        cls = get_backend_class(name)
        cs = cls.coldstart
        print(f"  {name:11s} runtime={cls.runtime.name:8s} "
              f"stack={cls.stack_costs.name:9s} "
              f"coldstart={cs.deploy_ms:g}ms query={cs.query_ms:g}ms")
    print("\nscenarios:")
    for name, sc in sorted(build_scenarios().items()):
        asc = sc.autoscaler.policy if sc.autoscaler else "-"
        search = sc.search_spec()
        load = "search" if search is not None else \
            "grid" if sc.mode in ("open", "mixed", "fleet") and sc.rates \
            else "-"
        print(f"  {name:17s} mode={sc.mode:6s} arrival={sc.arrival.kind:8s} "
              f"load={load:6s} backends={','.join(sc.backends)} "
              f"claims={sc.claims_kind or '-'} autoscaler={asc}")
        if search is not None:
            print(f"    search: rel_tol={search.rel_tol:g} "
                  f"max_probes={search.max_probes} "
                  f"(smoke {search.smoke_rel_tol:g}/"
                  f"{search.smoke_max_probes}) "
                  f"growth={search.growth:g} "
                  f"rate0={'auto' if search.rate0 is None else search.rate0}")
        elif sc.mode in ("open", "mixed", "fleet") and sc.rates:
            unit = " rps/worker" if sc.mode == "fleet" else ""
            for b, grid in sorted(sc.rates.items()):
                print(f"    rates[{b}] = "
                      f"{', '.join(f'{r:g}' for r in grid)}{unit}")
        if sc.fleet is not None:
            fl = sc.fleet
            storm = (f" storm={fl.storm_replicas}r@"
                     f"{fl.storm_t_frac:g}T" if fl.storm_replicas else "")
            print(f"    fleet: workers={fl.n_workers} "
                  f"placement={'/'.join(fl.placements())} "
                  f"distribution={'/'.join(fl.distributions())} "
                  f"spread={fl.spread} image={fl.image_mb:g}MB{storm}")
    print("\nsuites:")
    for suite, names in sorted(SUITES.items()):
        print(f"  {suite:10s} = {', '.join(names)}")
    return 0


def run_scenarios(args) -> int:
    smoke = args.suite == "smoke"
    scale = args.duration * (SMOKE_DURATION_SCALE if smoke else 1.0)
    runner = ExperimentRunner(duration_scale=scale, smoke=smoke,
                              workers=args.workers, verbose=True)
    scenarios = get_suite(args.suite)
    if args.backends:
        matrix = _parse_backends(args.backends)
        scenarios = [dataclasses.replace(sc, backends=matrix)
                     for sc in scenarios]
    if args.search_budget is not None:
        if args.search_budget < 1:
            raise SystemExit("--search-budget must be >= 1")
        # cap the per-(backend, seed) open-loop sample budget of every
        # searched scenario; grid/mixed/closed scenarios are unaffected
        def _capped(sc):
            spec = sc.search_spec()
            if spec is None:
                return sc
            return dataclasses.replace(sc, search=dataclasses.replace(
                spec, max_probes=args.search_budget,
                smoke_max_probes=args.search_budget))
        scenarios = [_capped(sc) for sc in scenarios]
    backend_union = sorted({b for sc in scenarios for b in sc.backends})
    print(f"suite={args.suite}: {len(scenarios)} scenarios x "
          f"{{{', '.join(backend_union)}}}, duration_scale={scale:.2f}, "
          f"workers={args.workers}")
    doc = runner.run_suite(scenarios, suite=args.suite)
    for entry in doc["scenarios"]:
        print(f"\n===== {entry['name']} ({entry['mode']}, "
              f"{entry['arrival_kind']} arrivals) =====")
        for backend, res in entry["backends"].items():
            bits = [f"n={res.get('n', 0)}"]
            if res.get("knee_rps") is not None and entry["mode"] == "open":
                bits.append(f"knee={res['knee_rps']:.0f}rps")
            if "search" in res:
                s = res["search"]
                # non-convergence has two distinct causes: the probe
                # budget ran out, or no failing bound was found within it
                # (knee is only a lower bound / nothing was sustainable)
                tag = "" if s["converged"] else (
                    " (budget)" if any(t["n_probes"] >=
                                       s["spec"]["max_probes"]
                                       for t in s["trace"])
                    else " (unbounded)")
                bits.append(f"probes={s['n_probes']}{tag}")
            if isinstance(res.get("median_ms"), float):
                bits.append(f"median={res['median_ms']:.3f}ms")
                bits.append(f"p99={res['p99_ms']:.3f}ms")
            if "autoscaler" in res:
                a = res["autoscaler"]
                bits.append(f"scale_events={a['n_scale_events']} "
                            f"reaction_p50={a['reaction_p50_ms']:.1f}ms")
            if "fleet" in res:
                fl = res["fleet"]
                bits.append(f"workers={fl['n_workers']}x{fl['placement']}")
                spd = fl.get("tree_provisioning_speedup")
                if spd is not None:
                    bits.append(f"tree_speedup={spd:g}x")
            bits.append(f"[{res.get('elapsed_s', 0):.1f}s]")
            print(f"  {backend:11s} " + " ".join(bits))
        for key, cl in entry.get("claims", {}).items():
            paper = f" (paper {cl['paper']})" if "paper" in cl else ""
            print(f"    claim {key:28s} = {cl['measured']}{paper}")
    if args.sim_throughput:
        print()
        run_sim_throughput(doc)
    print()
    print(metrics_csv(doc))
    if args.json:
        write_artifact(args.json, doc)
        print(f"\nwrote {args.json} "
              f"({doc['meta']['wall_s']:.1f}s wall)")
    if doc["failures"]:
        for f in doc["failures"]:
            print(f"\nFAILED {f['scenario']}/{f['backend']}:\n{f['error']}",
                  file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--suite", default="legacy",
                    choices=["legacy"] + sorted(SUITES),
                    help="which suite to run (default: legacy)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the machine-readable bench artifact here")
    ap.add_argument("--duration", type=float, default=1.0, metavar="SCALE",
                    help="duration scale factor on top of the suite default "
                         "(smoke already applies %.2fx)" % SMOKE_DURATION_SCALE)
    ap.add_argument("--workers", type=int, default=0, metavar="N",
                    help="parallel OS processes the harness farms scenario "
                         "cells out to (0 = in-process, deterministic "
                         "ordering); unrelated to a fleet scenario's "
                         "simulated worker count, which is fixed by its "
                         "FleetSpec.n_workers (see --list)")
    ap.add_argument("--backends", metavar="A,B,...", default=None,
                    help="comma-separated registered backend names to run "
                         "every scenario against (default: each scenario's "
                         "own matrix, normally containerd,junctiond)")
    ap.add_argument("--search-budget", type=int, default=None, metavar="N",
                    help="cap the adaptive knee search at N open-loop "
                         "probes per (backend, seed); applies to every "
                         "search-mode scenario (grid scenarios unaffected)")
    ap.add_argument("--profile", metavar="SCENARIO[:BACKEND]", default=None,
                    help="run one (scenario, backend) cell under cProfile "
                         "and print the top-25 cumulative entries, then "
                         "exit (default backend: the scenario's first)")
    ap.add_argument("--sim-throughput", action="store_true",
                    help="also measure simulated-requests-per-wall-second "
                         "of both drive() engines on the reference workload "
                         "and record sim_throughput / "
                         "sim_throughput_speedup in the artifact")
    ap.add_argument("--list", action="store_true",
                    help="list registered backends, scenarios and suites, "
                         "then exit")
    args = ap.parse_args(argv)
    if args.list:
        return run_list(args)
    if args.profile:
        return run_profile(args)
    if args.suite == "legacy":
        # simlint: allow[float-eq] argparse default sentinel, no arithmetic
        if args.duration != 1.0 or args.workers or args.backends \
                or args.search_budget is not None:
            print("note: --duration/--workers/--backends/--search-budget "
                  "only apply to scenario suites; the legacy suite ignores "
                  "them", file=sys.stderr)
        return run_legacy(args)
    return run_scenarios(args)


if __name__ == "__main__":
    sys.exit(main())
