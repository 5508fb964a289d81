"""Find a cell's knee once, when the cell is defined: one process, one
set-up, rising rates (open loop) in short windows. Not part of the driver's
command; its result is written into ``cells/<cell>.json`` as a number.

    python -m benchmark.sweep --workload <cell> --seed <n> --seconds 15 --rates 2,3,4,5

Per rate it prints the share of requests that met both of the mix's limits
(a failed request misses), the TTFT and TPOT percentiles, and how many
requests were still unfinished when the window closed against how many
arrive in one second (a backlog that grows shows here). The knee is the
highest rate at which the share is at least the mix's ``attainment`` and
the backlog does not grow.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from benchmark import metrics as M
from benchmark import run as R
from benchmark.traffic import build_schedule


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--env", action="append")
    args = ap.parse_args()
    args.rehearse, args.trace, args.control = None, 0, None
    args.limit_s = 3000.0  # many windows in one process
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    run, devices, compiled, deadline, model = R.prepare(args)
    limits = run.mix["limits"]
    app, base = R.boot(run, deadline)
    try:
        R.warm_requests(run, base, model, run.sizes["vocab"], app)
        for i, rate in enumerate(float(x) for x in args.rates.split(",")):
            run.seed = args.seed + i
            schedule = build_schedule(run.mix, {"rate_rps": rate}, run.sizes["vocab"],
                                      run.seed, run.seconds)
            R.drive_window(run, base, model, schedule, compiled)
            R.collect_program_records(run, app)
            R.log_dispatches(run)
            if run.window_compiles:
                R.log(f"sweep: COMPILED IN THE WINDOW {run.window_compiles}")
            ttft = [M.ttft_s(r, run.deadline) for r in run.measured]
            tpot = [M.tpot_s(r) or 0.0 for r in run.measured]
            met = sum(
                1 for r, a, b in zip(run.measured, ttft, tpot)
                if not M.is_failed(r) and a * 1e3 <= limits["ttft_ms"] and b * 1e3 <= limits["tpot_ms"]
            )
            unfinished = sum(1 for r in run.records
                             if r["due"] < run.w1 and (r["done"] is None or r["done"] > run.w1))
            line = {
                "rate_rps": rate, "requests": len(run.measured),
                "met_share": met / len(run.measured),
                "failed": sum(1 for r in run.measured if M.is_failed(r)),
                "ttft_p50_ms": M.percentile(ttft, 50) * 1e3, "ttft_p90_ms": M.percentile(ttft, 90) * 1e3,
                "ttft_mean_ms": sum(ttft) / len(ttft) * 1e3,
                "tpot_mean_ms": (M.tpot_mean_s(run.measured) or 0.0) * 1e3,
                "tpot_p50_ms": M.percentile(tpot, 50) * 1e3, "tpot_p90_ms": M.percentile(tpot, 90) * 1e3,
                "unfinished_at_close": unfinished,
                "out_tok_s": M.tokens_in_window(run.records, run.w0, run.w1) / run.seconds,
                "late_p99_ms": M.percentile([M.late_s(r) for r in run.measured], 99) * 1e3,
                "engine": R.http_get(base, "/admin/engine")[1]["data"]["engine"]["state"],
                "pool_rejects": sum(1 for r in run.flights if r.get("pool_reject_reason")),
                "bytes_in_use": max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices),
            }
            R.log("sweep: " + json.dumps(line))
            print(json.dumps(line), file=out, flush=True)
        stats = [d.memory_stats() or {} for d in devices]
        print(json.dumps({"memory_peak_bytes": max(s.get("peak_bytes_in_use") or 0 for s in stats),
                          "bytes_limit": stats[0].get("bytes_limit")}), file=out, flush=True)
    finally:
        app.shutdown()
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except R.RunFailure as exc:
        R.log(f"FAILED ({exc.code}): {exc}")
        code = exc.code
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
