"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes the same rows as a
machine-readable JSON artifact (``BENCH_serving.json`` by default) so the
perf trajectory is tracked across PRs.  ``us_per_call`` is host wall time
where a software path is actually timed; hardware-model rows (SPICE-
calibrated) carry 0 there and put the paper-comparable quantity in
``derived``.  Rows whose name ends in ``_skipped`` record a measurement
this host cannot take (e.g. sharded-pool rows on a single-device machine)
without failing the run.

``--smoke`` shrinks sizes in every module that supports it (a ``smoke``
keyword on its ``rows()``) — the CI bench-smoke step runs this to catch
bench bitrot: any module raising still fails the process.

``--check-regression BASELINE`` turns the run into a perf gate: after the
modules finish, key serving rows are compared against the committed
baseline JSON and the process exits non-zero on a regression.

  * structural rows (``*_burst_rounds_per_fetch`` higher-is-better,
    ``*_fetches_per_round`` lower-is-better, the ISSUE 5 migration
    witnesses ``*_migration_count`` / ``*_migration_padding_saved_ratio``,
    the ISSUE 6 overload witness ``*_overload_ladder_transitions``, both
    higher-is-better, the ISSUE 7 fused-step witness
    ``*_fused_roundtrips_per_chunk``, lower-is-better, and the ISSUE 10
    readout-diet witness ``*_d2h_bytes_ratio``, lower-is-better) count
    blocking transfers per executed round and
    the control plane's work — machine-independent and deterministic
    at fixed sizes, so they get the tight ``--tol`` (default 0.35 = 35%).
    These catch "the ring quietly started fetching every round" and "the
    scheduler quietly stopped migrating/degrading" class bugs.
  * wall-time rows (``*_slab_p99_ms`` and the overload SLO rows
    ``*_overload_p99_{none,ladder}_ms``, lower-is-better) get the loose
    ``--tol-time`` (default 3.0 = 4x baseline) so the gate survives CI
    machine variance, and are skipped entirely when the run's ``--smoke``
    flag differs from the baseline's (different sizes, incomparable).

CI runs ``--smoke --check-regression benchmarks/BENCH_smoke_baseline.json``
(a committed smoke-sized baseline, regenerated whenever serving perf
characteristics intentionally move).
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import time

# (suffix, direction): how a key row may move before the gate fails.
# "higher" = regression when current drops below baseline*(1-tol);
# "lower"  = regression when current rises above baseline*(1+tol).
_GATE_STRUCTURAL = (
    ("_burst_rounds_per_fetch", "higher"),
    ("_fetches_per_round", "lower"),
    # adaptive control plane (ISSUE 5): the rate-ramp scenario must keep
    # migrating lanes (count) and keep shrinking the H2D padding vs the
    # static policy (ratio) — both machine-independent at fixed sizes
    ("_migration_count", "higher"),
    ("_migration_padding_saved_ratio", "higher"),
    # overload ladder (ISSUE 6): the 2x flash-crowd scenario must keep
    # actuating tier transitions — zero means the ladder stopped observing,
    # deciding, or actuating
    ("_overload_ladder_transitions", "higher"),
    # fused chunk-step (ISSUE 7): the megakernel must keep the whole
    # STCF->TOS->BER->score step in ONE pallas_call — this row rising above
    # 1 means the step quietly split back into multiple launches.  Presence
    # is the gate (fail-closed on a missing row); the analytic events/s
    # rows ride along ungated since they are model outputs, not timings.
    ("_fused_roundtrips_per_chunk", "lower"),
    # pipelined pump + fleet packing (ISSUE 8): the backlog-burst pass must
    # keep staging blocks ahead of the dispatch point (structural overlap,
    # (B-2)/B at depth 2), and the pack policy must keep shrinking padded
    # H2D upload bytes on the heterogeneous fleet vs never-packed static
    # placement — both machine-independent at fixed sizes
    ("_pump_stage_overlap_ratio", "higher"),
    ("_pack_padding_saved_ratio", "higher"),
    # compact D2H readout (ISSUE 10): result bytes per fetch under
    # readout="compact" relative to dense on the sparse-corner fleet —
    # this ratio rising means the readout quietly fell back to dense
    # slabs (or the overflow fallback started firing on sparse traffic);
    # structural shape math, machine-independent at fixed sizes
    ("_d2h_bytes_ratio", "lower"),
    # fleet SLO scenarios (ISSUE 9): the diurnal ramp must keep migrating
    # lanes, the flash crowd must keep actuating ladder transitions, and
    # the heterogeneous mix must keep packing sparse buckets — all
    # structural control-plane witnesses, zero means the policy quietly
    # stopped observing/deciding/actuating under its scenario; their p99
    # and rate rows ride along ungated (smoke-sized wall time is noise)
    ("_slo_migrations", "higher"),
    ("_slo_transitions", "higher"),
    ("_slo_pack_moves", "higher"),
)
_GATE_TIME = (
    ("_slab_p99_ms", "lower"),
    # overload SLO: p99 of a serving round under 2x overload, with and
    # without graceful degradation — the ladder's latency win must not
    # quietly erode (and the no-ladder reference must not quietly explode)
    ("_overload_p99_none_ms", "lower"),
    ("_overload_p99_ladder_ms", "lower"),
)


def check_regression(records: dict, baseline_path: str, *, smoke: bool,
                     tol: float, tol_time: float) -> int:
    """Compare this run's rows against a committed baseline; returns the
    number of regressions (also printed to stderr).

    The gate fails closed: a baseline row with a gated suffix that is
    missing from (or skipped in) the current run counts as a regression —
    a rename or a crashed bench module must not silently shrink the gate
    to zero rows — and checking zero rows overall is itself a failure.
    """
    with open(baseline_path) as f:
        base = json.load(f)
    base_rows = base.get("rows", {})
    time_comparable = bool(base.get("smoke")) == bool(smoke)
    if not time_comparable:
        print("# gate: smoke flag differs from baseline — wall-time rows "
              "skipped, structural rows still checked", file=sys.stderr)
    gates = list(_GATE_STRUCTURAL)
    if time_comparable:
        gates += list(_GATE_TIME)

    failures = 0
    checked = 0
    for name, brec in sorted(base_rows.items()):
        if brec.get("skipped"):
            continue
        for suffix, direction in gates:
            if not name.endswith(suffix):
                continue
            ref = float(brec["derived"])
            if ref <= 0:
                continue
            rec = records.get(name)
            if rec is None or rec.get("skipped"):
                failures += 1
                print(f"# REGRESSION {name}: gated baseline row missing "
                      f"from this run (renamed row, or its bench module "
                      f"failed) — regenerate the baseline if intentional",
                      file=sys.stderr)
                continue
            t = tol if (suffix, direction) in _GATE_STRUCTURAL else tol_time
            cur = float(rec["derived"])
            checked += 1
            bad = (cur < ref * (1 - t)) if direction == "higher" \
                else (cur > ref * (1 + t))
            if bad:
                failures += 1
                print(f"# REGRESSION {name}: {cur:.6g} vs baseline "
                      f"{ref:.6g} (allowed {direction}-is-better drift "
                      f"{t:.0%})", file=sys.stderr)
    if checked == 0 and failures == 0:
        failures += 1
        print(f"# REGRESSION: no gated rows found in {baseline_path} — "
              f"the gate checked nothing (stale or empty baseline)",
              file=sys.stderr)
    print(f"# gate: {checked} row(s) checked against {baseline_path}, "
          f"{failures} regression(s)", file=sys.stderr)
    return failures


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (CI bitrot check, not a measurement)")
    ap.add_argument("--json-out", default="BENCH_serving.json",
                    help="machine-readable artifact path ('' disables)")
    ap.add_argument("--check-regression", metavar="BASELINE", default=None,
                    help="compare key serving rows against this committed "
                         "baseline JSON; exit non-zero on regression")
    ap.add_argument("--tol", type=float, default=0.35,
                    help="allowed drift for structural ratio rows "
                         "(fraction of baseline; default 0.35)")
    ap.add_argument("--tol-time", type=float, default=3.0,
                    help="allowed drift for wall-time rows (fraction of "
                         "baseline; default 3.0 = 4x, machine variance)")
    args = ap.parse_args(argv)

    from benchmarks import (
        bench_auc,
        bench_dvfs,
        bench_hwmodel,
        bench_streaming,
        bench_throughput,
        bench_tos_kernels,
        roofline_table,
        scenarios,
    )

    modules = [
        ("hwmodel(fig9,fig10)", bench_hwmodel),
        ("throughput(fig1b,fig10d)", bench_throughput),
        ("dvfs(tableI,fig8)", bench_dvfs),
        ("auc(fig11)", bench_auc),
        ("tos_kernels(perf)", bench_tos_kernels),
        ("streaming(serving)", bench_streaming),
        ("scenarios(slo)", scenarios),
        ("roofline(dryrun)", roofline_table),
    ]
    print("name,us_per_call,derived")
    records: dict = {}
    errors: list = []
    failures = 0
    for label, mod in modules:
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(mod.rows).parameters:
            kwargs["smoke"] = True
        t0 = time.perf_counter()
        try:
            for name, us, derived in mod.rows(**kwargs):
                print(f"{name},{us:.3f},{derived:.6g}")
                rec = {"us_per_call": float(us), "derived": float(derived),
                       "module": label}
                if name.endswith("_skipped"):
                    rec["skipped"] = True
                records[name] = rec
        except Exception as e:  # pragma: no cover
            failures += 1
            errors.append({"module": label, "error":
                           f"{type(e).__name__}: {e}"})
            print(f"{label}_ERROR,0,0  # {type(e).__name__}: {e}",
                  file=sys.stderr)
        dt = time.perf_counter() - t0
        print(f"# {label} done in {dt:.1f}s", file=sys.stderr)

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"smoke": args.smoke, "rows": records,
                       "errors": errors}, f, indent=2, sort_keys=True)
        print(f"# wrote {len(records)} rows -> {args.json_out}",
              file=sys.stderr)
    if args.check_regression:
        failures += check_regression(
            records, args.check_regression, smoke=args.smoke,
            tol=args.tol, tol_time=args.tol_time,
        )
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
