"""Benchmark harness entry point: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines. Runs on 8 real CPU devices
(its own process; never inherits the dry-run's fake 512).

    PYTHONPATH=src python -m benchmarks.run [--only primitives|apps|roofline]
    PYTHONPATH=src python -m benchmarks.run --profile [--cache-dir DIR]

Every run of the primitives section seeds the bench trajectory at
``--bench-json`` (default ``BENCH_primitives.json`` at the repo root): one
row per measured primitive cell (primitive, flow, stage, nbytes,
measured_us, est_us, est_source) plus a ``programs`` section of measured
multi-op schedules (name, ops, measured_us, plan_est_us, serial_est_us,
est_source).

``--profile`` exercises the tuning subsystem end to end: run the primitive
sweep with analytic estimates, ``tune()`` on the live substrate (per-op
alpha-beta models AND program-level overlap factors), save the
``CommProfile`` into the cache dir, *reload it under the same topology
fingerprint*, install it, and re-run the sweep -- the emitted
``profile/meas_over_est`` lines compare the median measurement/estimate
ratio before and after calibration (the calibrated median must sit strictly
closer to 1.0), and the program section re-runs under the installed profile
so its joint plans are measured-sourced.

``--check-against SEED[=FRESH]`` is the CI regression gate: after the run,
every (primitive, flow, nbytes) row *and* every named ``programs`` entry
(the multi-op schedules plus the end-to-end ``train_step`` row) of the
fresh bench JSON is compared against SEED and the process exits non-zero
when any cell's best ``measured_us`` regresses beyond
``--tolerance`` (default 2x -- CPU-substrate wall times are noisy; the
gate catches order-of-magnitude breakage, not percent drift).  Seed cells
are lifted to the ``--floor-us`` absolute floor before the tolerance
applies, so a zero/denormal seed cell cannot fail the gate on noise.

The flag repeats to gate several bench files in one invocation -- each
occurrence names a committed seed and, after ``=``, the fresh JSON to hold
against it (defaulting to this run's ``--bench-json``), so the primitive
trajectory and the serving trajectory (``BENCH_serving.json``, produced by
``benchmarks/serving.py``) share one gate with per-file coverage warnings:

    python -m benchmarks.run --profile --bench-json BENCH_fresh.json \\
        --check-against BENCH_primitives.json \\
        --check-against BENCH_serving.json=BENCH_serving_fresh.json
"""
import argparse
import json
import statistics
import sys

from benchmarks._timing import ensure_devices

BENCH_JSON = "BENCH_primitives.json"

SEED_RECIPE = """\
bench-regression gate:
  compare a fresh run against the committed seed (CI does this per matrix
  leg; exits 1 on any >tolerance regression):
      python -m benchmarks.run --profile --bench-json BENCH_fresh.json \\
          --check-against BENCH_primitives.json

seed refresh (after an intentional perf or schema change):
      python -m benchmarks.run --profile --cache-dir .tuning-cache \\
          --bench-json BENCH_primitives.json
      git add BENCH_primitives.json   # commit the new trajectory seed

serving trajectory (seeded by benchmarks/serving.py, gated here):
      python -m benchmarks.serving --bench-json BENCH_serving_fresh.json
      python -m benchmarks.run --profile --bench-json BENCH_fresh.json \\
          --check-against BENCH_primitives.json \\
          --check-against BENCH_serving.json=BENCH_serving_fresh.json
"""


def _write_bench_json(path: str, rows, programs=(), extra: dict | None = None
                      ) -> None:
    doc = {"schema": ["primitive", "flow", "stage", "nbytes", "measured_us",
                      "est_us", "est_source"],
           "program_schema": ["name", "ops", "measured_us", "plan_est_us",
                              "serial_est_us", "est_source"],
           "rows": list(rows),
           "programs": list(programs)}
    if extra:
        doc.update(extra)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path} ({len(doc['rows'])} rows, "
          f"{len(doc['programs'])} programs)", file=sys.stderr)


def _median_ratio(rows) -> float:
    """Median measured/estimated ratio over rows with a usable estimate."""
    ratios = [r["measured_us"] / r["est_us"] for r in rows
              if r.get("est_us", 0) > 0]
    return statistics.median(ratios) if ratios else float("nan")


def _best_by_key(rows) -> dict:
    """Best (minimum) measured_us per (primitive, flow, nbytes) -- several
    algorithm requests can execute the same flow at the same size, and the
    min damps single-run noise on both sides of the comparison."""
    out: dict[tuple, float] = {}
    for r in rows:
        key = (r["primitive"], r["flow"], r["nbytes"])
        us = float(r["measured_us"])
        if key not in out or us < out[key]:
            out[key] = us
    return out


def _best_by_name(programs) -> dict:
    """Best (minimum) measured_us per program-row name."""
    out: dict[str, float] = {}
    for r in programs:
        us = float(r["measured_us"])
        if r["name"] not in out or us < out[r["name"]]:
            out[r["name"]] = us
    return out


def check_against(seed_path: str, fresh_path: str,
                  tolerance: float = 2.0, floor_us: float = 5.0
                  ) -> list[str]:
    """Compare a fresh bench JSON against the committed seed; returns the
    list of regression descriptions (empty = gate passes).  Gates both the
    primitive ``rows`` (keyed by primitive/flow/nbytes) and the ``programs``
    section (keyed by name).  Rows present in the seed but missing from the
    fresh run are reported as warnings (a coverage drop cannot "pass"
    silently) without failing the gate.

    ``floor_us`` is the absolute comparison floor: the seed value is lifted
    to at least this many microseconds before the tolerance multiplies it.
    Without it a zero (or denormally small) seed cell makes the gate
    hair-trigger -- any measurable fresh value exceeds ``tolerance * ~0``
    and fails on pure noise instead of a real regression."""
    with open(seed_path) as f:
        seed = json.load(f)
    with open(fresh_path) as f:
        fresh = json.load(f)
    failures = []
    label = seed_path

    def gate(section, seed_best, fresh_best):
        for key, seed_us in sorted(seed_best.items()):
            fresh_us = fresh_best.get(key)
            tag = key if isinstance(key, str) else "/".join(
                str(k) for k in key)
            if fresh_us is None:
                print(f"# check-against[{label}]: {section} {tag} missing "
                      "from fresh run (coverage dropped)", file=sys.stderr)
                continue
            if fresh_us > tolerance * max(seed_us, floor_us):
                failures.append(
                    f"{label}: {tag}: {fresh_us:.1f}us vs seed "
                    f"{seed_us:.1f}us (> {tolerance:g}x tolerance)")
        new = sorted(set(fresh_best) - set(seed_best))
        if new:
            print(f"# check-against[{label}]: {len(new)} new {section} "
                  "cells not in the seed (refresh the seed to start "
                  "tracking them)", file=sys.stderr)

    gate("row", _best_by_key(seed["rows"]), _best_by_key(fresh["rows"]))
    gate("program", _best_by_name(seed.get("programs", [])),
         _best_by_name(fresh.get("programs", [])))
    return failures


def profile_mode(cache_dir: str, out_json: str) -> None:
    """tune -> save -> reload (same fingerprint) -> re-run the sweep."""
    from benchmarks import primitives
    from repro.core import planner
    from repro.tuning import Tuner

    cube = primitives._setup((8,), ("d",))

    # 1. analytic baseline sweep
    primitives.ROWS.clear()
    primitives.fig14_fig16_primitives()
    analytic_rows = list(primitives.ROWS)
    med_analytic = _median_ratio(analytic_rows)

    # 2. tune on the live substrate (per-op models + overlap) and persist
    tuner = Tuner(cache_dir=cache_dir)
    profile = tuner.tune(cube, sizes=(64 * 1024, 256 * 1024, 512 * 1024,
                                      1024 * 1024))
    path = tuner.profile_path(cube)
    print(f"# tuned {profile.describe()} -> {path}", file=sys.stderr)

    # 3. reload under the same topology fingerprint (load() rejects drift)
    reloaded = tuner.load(cube)

    # 4. calibrated sweep + program-level section under the reloaded
    # profile: the joint plans (and their interleaving budgets) are priced
    # from the measured models and overlap factors
    primitives.ROWS.clear()
    primitives.PROGRAM_ROWS.clear()
    with planner.install_profile(reloaded):
        primitives.fig14_fig16_primitives()
        primitives.program_fusion()
        primitives.program_overlap()
        primitives.fused_kernels()
    # 5. end-to-end step accounting.  The train-step bench runs on the
    # multi-pod (2x2x2) cube, a different topology fingerprint than the
    # ring sweep above -- tune that cube too so the step's collective
    # estimates (incl. the DCN hop) price measured-sourced.
    from benchmarks import train_step
    pod_cube = train_step._setup_train()[1].cube
    pod_profile = tuner.tune(pod_cube, sizes=(64 * 1024, 256 * 1024,
                                              1024 * 1024))
    print(f"# tuned {pod_profile.describe()} (train-step cube)",
          file=sys.stderr)
    with planner.install_profile(pod_profile):
        train_step.run()
    measured_rows = list(primitives.ROWS)
    med_measured = _median_ratio(measured_rows)

    emit_rows = analytic_rows + measured_rows
    closer = abs(med_measured - 1.0) < abs(med_analytic - 1.0)
    _write_bench_json(out_json, emit_rows, primitives.PROGRAM_ROWS, extra={
        "median_meas_over_est": {"analytic": med_analytic,
                                 "measured": med_measured},
        "calibration_improved": closer,
        "profile_path": path})
    print(f"profile/meas_over_est/analytic,{med_analytic:.3f},")
    print(f"profile/meas_over_est/measured,{med_measured:.3f},"
          f"closer_to_1={closer}")
    if not closer:
        print("# WARNING: calibrated estimates did not improve on the "
              "analytic baseline", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog=SEED_RECIPE)
    ap.add_argument("--only", default=None,
                    choices=["primitives", "apps", "roofline"])
    ap.add_argument("--profile", action="store_true",
                    help="tune -> save -> reload -> calibrated re-run of "
                         "the primitive sweep (incl. program-level overlap)")
    ap.add_argument("--cache-dir", default=".tuning-cache",
                    help="CommProfile cache directory for --profile")
    ap.add_argument("--bench-json", default=BENCH_JSON,
                    help="bench-trajectory output path (never written "
                         "anywhere else)")
    ap.add_argument("--check-against", action="append", default=None,
                    metavar="SEED[=FRESH]",
                    help="after the run, gate a fresh bench JSON against "
                         "this committed seed; exit 1 on regression. "
                         "Repeatable; FRESH defaults to --bench-json, so "
                         "extra occurrences can gate other trajectories "
                         "(e.g. BENCH_serving.json=BENCH_serving_fresh.json)")
    ap.add_argument("--tolerance", type=float, default=2.0,
                    help="check-against noise tolerance as a ratio "
                         "(default 2.0 = fail when a row doubles)")
    ap.add_argument("--floor-us", type=float, default=5.0,
                    help="check-against absolute floor: seed cells are "
                         "lifted to at least this many microseconds before "
                         "the tolerance applies (a zero seed cell must not "
                         "fail the gate on noise)")
    args = ap.parse_args()

    ensure_devices(8)

    print("name,us_per_call,derived")
    wrote_bench = False
    if args.profile:
        profile_mode(args.cache_dir, args.bench_json)
        wrote_bench = True
    else:
        if args.only in (None, "primitives"):
            from benchmarks import primitives, train_step
            primitives.run()
            train_step.run()
            _write_bench_json(args.bench_json, primitives.ROWS,
                              primitives.PROGRAM_ROWS)
            wrote_bench = True
        if args.only in (None, "apps"):
            from benchmarks import apps
            apps.run()
        if args.only in (None, "roofline"):
            from benchmarks import roofline
            roofline.run()

    if args.check_against:
        failures = []
        for spec in args.check_against:
            seed, _, fresh = spec.partition("=")
            if not fresh:
                # gating this run's own output needs this run to have
                # produced it; an explicit SEED=FRESH pair gates a file
                # written by another harness (e.g. benchmarks/serving.py)
                if not wrote_bench:
                    print("# check-against requires a run that writes the "
                          "bench JSON (primitives or --profile)",
                          file=sys.stderr)
                    sys.exit(2)
                fresh = args.bench_json
            failures += check_against(seed, fresh, args.tolerance,
                                      args.floor_us)
        if failures:
            print("# BENCH REGRESSION:", file=sys.stderr)
            for f in failures:
                print(f"#   {f}", file=sys.stderr)
            print("# intentional change? refresh the seed (see --help)",
                  file=sys.stderr)
            sys.exit(1)
        print(f"# check-against {', '.join(args.check_against)}: "
              f"ok (tolerance {args.tolerance:g}x)", file=sys.stderr)


if __name__ == '__main__':
    main()
