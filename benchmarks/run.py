"""Benchmark entrypoint: one benchmark per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
                                          [--json PATH]

Prints human tables plus a machine-readable ``name,us_per_call,derived``
CSV summary at the end.  ``--json PATH`` additionally appends the same
summary rows to PATH (a JSON list of run records), so every benchmark —
not just moe_hotpath — feeds the BENCH_* perf trajectory.
"""
from __future__ import annotations

import argparse
import sys
import time


def append_json(path: str, rows) -> None:
    """Append one run record to a BENCH-style JSON trajectory file."""
    from benchmarks.trajectory import append_record
    append_record(path, {
        "unix_time": time.time(),
        "rows": [{"name": n, "us_per_call": us, "derived": d}
                 for n, us, d in rows],
    })


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="shrink the lost-experts training run")
    ap.add_argument("--only", default=None,
                    choices=[None, "recovery", "lost_experts",
                             "compile_cache", "reinit", "roofline",
                             "slo", "moe_hotpath", "fleet_slo",
                             "fleet_campaign"])
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="append the CSV-summary rows to PATH as JSON")
    args = ap.parse_args(argv)
    csv_rows = [("name", "us_per_call", "derived")]

    def want(name):
        return args.only in (None, name)

    if want("moe_hotpath"):
        from benchmarks import moe_hotpath
        rows = moe_hotpath.run(quick=args.quick)
        moe_hotpath.print_table(rows)
        moe_hotpath.save_json(rows, quick=args.quick)
        for r in rows:
            if "accepted_per_step" in r:
                # speculation efficiency, not just latency: accepted
                # tokens per speculative step + window-width histogram
                hist = "|".join(f"{g}:{n}" for g, n in
                                sorted(r["window_hist"].items()))
                csv_rows.append((f"moe_hotpath_{r['name']}",
                                 f"{r['metric_us']:.0f}",
                                 f"accepted_per_step="
                                 f"{r['accepted_per_step']:.2f},"
                                 f"windows={r['spec_windows']},"
                                 f"hist={hist}"))
            elif r.get("kind") == "engine":
                # host/device overlap surface: wall-clock TPOT plus the
                # share of step time the host spent not waiting on a
                # readback
                ov = r.get("overlap", {})
                extra = (f",planned_ahead={ov['planned_ahead']}"
                         f",replans={ov['replans']}" if ov else "")
                csv_rows.append((f"moe_hotpath_{r['name']}",
                                 f"{r['metric_us']:.0f}",
                                 f"host_share="
                                 f"{r['host_share']:.4f}{extra}"))
            elif "mega_us" in r:
                csv_rows.append((f"moe_hotpath_{r['name']}_mega",
                                 f"{r['mega_us']:.0f}",
                                 f"composed_us={r['composed_us']:.0f},"
                                 f"speedup={r['speedup']:.2f}x"))
            else:
                csv_rows.append((f"moe_hotpath_{r['name']}_fused",
                                 f"{r['fused_us']:.0f}",
                                 f"dense_us={r['dense_us']:.0f},"
                                 f"speedup={r['speedup']:.2f}x"))

    if want("reinit"):
        from benchmarks import reinit_breakdown
        rows = reinit_breakdown.run()
        reinit_breakdown.print_table(rows)
        total = next(r["seconds"] for r in rows if r["category"] == "TOTAL")
        gen = next(r["share"] for r in rows if r["category"] == "generator")
        csv_rows.append(("reinit_breakdown", f"{total * 1e6:.0f}",
                         f"generator_share={gen:.2f}"))

    if want("recovery"):
        from benchmarks import recovery_time
        rows = recovery_time.run()
        recovery_time.print_table(rows)
        base = next(r for r in rows
                    if r["scenario"] == "baseline_cached_reinit")
        others = [r for r in rows if r is not base]
        best = min(others, key=lambda r: r["total_s"])
        worst = max(others, key=lambda r: r["total_s"])
        csv_rows.append(("recovery_best_case",
                         f"{best['total_s'] * 1e6:.0f}",
                         f"reduction_vs_baseline="
                         f"{100 * (1 - best['total_s'] / base['total_s']):.1f}%"))
        csv_rows.append(("recovery_worst_case",
                         f"{worst['total_s'] * 1e6:.0f}",
                         f"reduction_vs_baseline="
                         f"{100 * (1 - worst['total_s'] / base['total_s']):.1f}%"))

    if want("compile_cache"):
        from benchmarks import compile_cache
        rows = compile_cache.run()
        compile_cache.print_table(rows)
        cold = rows[0]["read_cache_s"] + rows[0]["compile_s"]
        pre = rows[2]["read_cache_s"] + rows[2]["compile_s"]
        csv_rows.append(("compile_cold", f"{cold * 1e6:.0f}", ""))
        csv_rows.append(("compile_precompiled", f"{pre * 1e6:.0f}",
                         f"speedup={cold / max(pre, 1e-9):.0f}x"))

    if want("lost_experts"):
        from benchmarks import lost_experts
        rows = lost_experts.run(train_steps=150 if args.quick else 400)
        lost_experts.print_table(rows)
        base = rows[0]
        r32 = next((r for r in rows if r["scheme"] == "every_nth"
                    and abs(r["fraction"] - 1 / 32) < 1e-9), None)
        if r32:
            csv_rows.append(("lost_experts_r32_dCE", "0",
                             f"delta_ce={r32['ce'] - base['ce']:+.4f}"))

    if want("fleet_slo"):
        from benchmarks import fleet_slo
        out = fleet_slo.run(quick=args.quick)
        fleet_slo.print_table(out)
        fleet_slo.save_json(out)
        for name, res in out["policies"].items():
            csv_rows.append((f"fleet_slo_{name}_p99_ttft",
                             f"{res['p99_ttft_s'] * 1e6:.0f}",
                             f"p99_degradation_ms="
                             f"{res['p99_degradation_s'] * 1e3:.0f}"))
        csv_rows.append(("fleet_slo_revive_beats_restart",
                         "1" if out["revive_beats_restart"] else "0", ""))
        if "frontend" in out:
            fr = out["frontend"]
            csv_rows.append((
                "fleet_slo_frontend_req_per_s",
                f"{1e6 / max(fr['req_per_s'], 1e-9):.0f}",
                f"req_per_s={fr['req_per_s']:.3f},"
                f"p99_s={fr['p99_latency_s']:.3f},"
                f"host_share={fr['host_share']:.4f}"))

    if want("fleet_campaign"):
        from benchmarks import fleet_campaign
        out = fleet_campaign.run(quick=args.quick)
        fleet_campaign.print_table(out)
        fleet_campaign.save_json(out)
        fleet_campaign.write_forensics(out)
        for name, res in out["policies"].items():
            csv_rows.append((f"fleet_campaign_{name}_slo_burn",
                             f"{res['slo_burn_s'] * 1e6:.0f}",
                             f"finished={res['finished']}/{res['n']}"))
        csv_rows.append(("fleet_campaign_arbiter_beats_forced",
                         "1" if out["arbiter_beats_best_forced"] else "0",
                         f"best_forced={out['best_forced_policy']}"))

    if want("slo"):
        from benchmarks import slo_timeline
        res = slo_timeline.run()
        slo_timeline.print_table(res)
        csv_rows.append(("slo_worst_stall", f"{res['stall_s'] * 1e6:.0f}",
                         f"recovery_total_ms="
                         f"{res['recovery_total_s'] * 1e3:.0f}"))

    if want("roofline"):
        from benchmarks import roofline
        rows = roofline.run()
        if rows:
            roofline.print_table(rows)
            csv_rows.append(("roofline_combos", "0", f"n={len(rows)}"))
        else:
            print("\n(no dry-run records yet: run "
                  "`python -m repro.launch.dryrun_all` first)")

    print("\n# CSV summary")
    for row in csv_rows:
        print(",".join(str(x) for x in row))
    if args.json:
        append_json(args.json, csv_rows[1:])
        print(f"\nappended {len(csv_rows) - 1} rows to {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
