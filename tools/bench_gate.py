"""CI perf-regression gate: compare a bench artifact against the
committed ``bench_baseline.json``.

CI has no TPU, so this gate runs the HOST-SIDE echo/CPU bench there
(see the perf-gate job; ``BENCH_PLATFORM=cpu`` pins it), always uploads
the artifact, and FAILS the build when the serving stack's host-side
overheads regress beyond tolerance vs the committed baseline. These are
host timings, not device numbers (ROADMAP D4):

- ``req_per_sec`` (TTFT-path throughput through the real HTTP
  transport/batcher/scheduler stack) must stay above
  ``baseline * BENCH_GATE_RPS_FACTOR`` (default 0.40 — CI runners are
  noisy; the gate catches structural regressions, not jitter);
- ``value`` (p50 TTFT ms) must stay below
  ``baseline * BENCH_GATE_TTFT_FACTOR`` (default 2.5);
- the paged-KV microbench must still show copied-bytes SAVINGS:
  paged copied-KV-bytes per prefix hit strictly below the slot/copy
  model's, and the admission path must not blow up
  (``paged admission_ms <= slot_copy admission_ms *
  BENCH_GATE_KV_FACTOR``, default 3.0 — aliasing bookkeeping may cost
  a little CPU; it must never cost an order of magnitude);
- the host-mesh round (sharded block tables over tp=2 fake devices)
  must stay bookkeeping-cheap: mesh per-token dispatch latency
  ``<= single * BENCH_GATE_MESH_FACTOR`` (default 5.0 — loose-first;
  tighten as the trajectory stabilizes) and mesh copied-KV-bytes per
  prefix hit ``<= single + 64`` (sharding must never introduce KV
  copies; aliasing is placement-agnostic);
- the durable generation journal (resumable streams) must stay
  per-token cheap: ``journal_microbench.per_token_us <= baseline *
  BENCH_GATE_JOURNAL_FACTOR`` (default 5.0 — the journal append is a
  GIL-atomic list append; a regression here taxes EVERY stream);
- journal PERSISTENCE (the crash-durable WAL, journal_wal.py) must
  stay a bounded tax on top of that:
  ``journal_wal_microbench.per_token_us_wal <= baseline *
  BENCH_GATE_WAL_FACTOR`` (default 10.0, loose-first — a WAL append
  is a buffered write + flush; a blow-up means the frame/rotation
  path grew a stall or an fsync leaked into the default policy);
- deadline-aware serving must stay fast at saying no:
  ``shed_microbench.shed_p50_us <= baseline *
  BENCH_GATE_SHED_FACTOR`` (default 10.0, loose-first — the shed path
  is what overload leans on) and an abandoned stream's KV blocks must
  reclaim within ``baseline reclaim_ms * BENCH_GATE_RECLAIM_FACTOR``
  (default 10.0 — "within one chunk" is the contract; an order of
  magnitude past baseline means the abort hook stopped reaching the
  decode loop);
- pooled speculative decoding must keep earning its dispatches:
  pooled-spec decode tok/s must stay ``>= plain pooled decode *
  BENCH_GATE_SPEC_FACTOR`` (default 1.5 — the ROADMAP's "cheaper
  tokens" floor), ``tokens_per_dispatch`` must stay above the
  ABSOLUTE 1.5 floor (a verify that stops carrying multiple tokens
  has silently become plain decode, whatever the baseline said), and
  the echo n-gram acceptance must stay above zero;
- the disaggregated KV handoff must stay protocol-cheap: the
  cross-replica transfer path (pull + verify + install + aliased
  admission over real HTTP) must finish within
  ``local_prefill_ms_p50 * BENCH_GATE_TRANSFER_FACTOR`` (default
  10.0, loose-first — echo "prefill" is nearly free so the ratio
  prices pure protocol overhead; a blow-up here means the wire
  format or the pin/verify path grew a stall), every pull must take
  the fast path (``fallbacks == 0`` — a silent fallback would make
  the latency number a lie), and one pull's wire size must stay
  within ``baseline * 2`` (framing bloat: checksums + headers are
  bounded, payload is the payload);
- fleet tracing must stay cheap on both sides: the per-request hop
  stamp (request-id sanitize + ``X-Gofr-Hop`` mint + parse-back, paid
  on the router hot path) within ``baseline stamp_us *
  BENCH_GATE_TRACE_FACTOR`` and one ``/admin/fleet/trace`` timeline
  assembly within ``baseline assemble_us`` times the same factor
  (default 10.0, loose-first — stamping is string work that must stay
  microseconds; a blow-up means the correlation layer started taxing
  every routed request);
- the dispatch cost model (tpu/costmodel.py) must stay a dict lookup
  plus a handful of float ops on the dispatch path:
  ``costmodel_microbench.per_dispatch_us <= baseline *
  BENCH_GATE_COSTMODEL_FACTOR`` (default 10.0, loose-first — predict
  at begin + residual EMA at finish ride EVERY dispatch record), and
  the microbench's healthy loop must report ``anomalies == 0`` (an
  anomaly raised by steady-state traffic means the watchtower's
  false-positive floor broke);
- SLO + tenant metering (slo.py, telemetry.TenantLedger) must stay a
  bounded tax on the flight-record path:
  ``slo_microbench.per_request_us <= baseline *
  BENCH_GATE_SLO_FACTOR`` (default 10.0, loose-first — the measured
  loop deliberately churns the sketch's eviction path, its worst
  case), and the microbench's all-ok loop must report
  ``burn_alerts == 0`` (a burn alert raised by healthy traffic means
  the multi-window judge or its thresholds broke — the one regression
  that pages a human at 3am for nothing).

Usage::

    python tools/bench_gate.py BENCH.json [BASELINE.json]

Exit 0 = within tolerance, 1 = regression (each failure printed).
Refreshing the baseline is an explicit act: run the bench locally with
the same env as the CI job and commit the new ``bench_baseline.json``
next to the change that moved it — the file is the perf contract.
"""

from __future__ import annotations

import json
import os
import sys


def _num(d: dict, key: str):
    v = d.get(key)
    return v if isinstance(v, (int, float)) else None


def gate(bench: dict, baseline: dict) -> list[str]:
    failures: list[str] = []
    rps_factor = float(os.environ.get("BENCH_GATE_RPS_FACTOR", "0.40"))
    ttft_factor = float(os.environ.get("BENCH_GATE_TTFT_FACTOR", "2.5"))
    kv_factor = float(os.environ.get("BENCH_GATE_KV_FACTOR", "3.0"))
    mesh_factor = float(os.environ.get("BENCH_GATE_MESH_FACTOR", "5.0"))
    journal_factor = float(os.environ.get("BENCH_GATE_JOURNAL_FACTOR", "5.0"))
    wal_factor = float(os.environ.get("BENCH_GATE_WAL_FACTOR", "10.0"))
    shed_factor = float(os.environ.get("BENCH_GATE_SHED_FACTOR", "10.0"))
    reclaim_factor = float(os.environ.get("BENCH_GATE_RECLAIM_FACTOR", "10.0"))
    transfer_factor = float(
        os.environ.get("BENCH_GATE_TRANSFER_FACTOR", "10.0")
    )
    spec_factor = float(os.environ.get("BENCH_GATE_SPEC_FACTOR", "1.5"))
    trace_factor = float(os.environ.get("BENCH_GATE_TRACE_FACTOR", "10.0"))
    costmodel_factor = float(
        os.environ.get("BENCH_GATE_COSTMODEL_FACTOR", "10.0")
    )
    slo_factor = float(os.environ.get("BENCH_GATE_SLO_FACTOR", "10.0"))

    if bench.get("backend") != baseline.get("backend"):
        failures.append(
            f"backend mismatch: bench ran on {bench.get('backend')!r}, "
            f"baseline is {baseline.get('backend')!r} — not comparable"
        )
        return failures

    rps, base_rps = _num(bench, "req_per_sec"), _num(baseline, "req_per_sec")
    if base_rps:
        if rps is None:
            failures.append("req_per_sec missing from the bench artifact")
        elif rps < base_rps * rps_factor:
            failures.append(
                f"req/s regression: {rps} < {base_rps} * {rps_factor} "
                f"(= {base_rps * rps_factor:.2f})"
            )
    ttft, base_ttft = _num(bench, "value"), _num(baseline, "value")
    if base_ttft:
        if ttft is None:
            failures.append("p50 TTFT missing from the bench artifact")
        elif ttft > base_ttft * ttft_factor:
            failures.append(
                f"p50 TTFT regression: {ttft}ms > {base_ttft}ms * "
                f"{ttft_factor} (= {base_ttft * ttft_factor:.2f}ms)"
            )

    kv = bench.get("kv_microbench") or {}
    if baseline.get("kv_microbench"):
        paged, slot = kv.get("paged"), kv.get("slot_copy")
        if not (paged and slot):
            failures.append("kv_microbench missing from the bench artifact")
        else:
            if paged["copied_kv_bytes_per_hit"] >= slot["copied_kv_bytes_per_hit"]:
                failures.append(
                    "paged KV no longer saves copies: "
                    f"{paged['copied_kv_bytes_per_hit']} bytes/hit paged vs "
                    f"{slot['copied_kv_bytes_per_hit']} slot-copy"
                )
            if paged["admission_ms"] > slot["admission_ms"] * kv_factor:
                failures.append(
                    f"paged admission latency blew up: "
                    f"{paged['admission_ms']}ms > "
                    f"{slot['admission_ms']}ms * {kv_factor}"
                )

    mesh = bench.get("mesh_microbench") or {}
    if baseline.get("mesh_microbench"):
        single, meshed = mesh.get("single"), mesh.get("mesh")
        if not (single and meshed):
            failures.append("mesh_microbench missing from the bench artifact")
        else:
            if (
                meshed["per_token_dispatch_ms"]
                > single["per_token_dispatch_ms"] * mesh_factor
            ):
                failures.append(
                    "host-mesh per-token dispatch blew up: "
                    f"{meshed['per_token_dispatch_ms']}ms > "
                    f"{single['per_token_dispatch_ms']}ms * {mesh_factor}"
                )
            if (
                meshed["copied_kv_bytes_per_hit"]
                > single["copied_kv_bytes_per_hit"] + 64
            ):
                failures.append(
                    "sharded block tables introduced KV copies: "
                    f"{meshed['copied_kv_bytes_per_hit']} bytes/hit mesh vs "
                    f"{single['copied_kv_bytes_per_hit']} single (+64 slack)"
                )
    journal = bench.get("journal_microbench") or {}
    base_journal = baseline.get("journal_microbench") or {}
    if base_journal:
        per_token = _num(journal, "per_token_us")
        base_token = _num(base_journal, "per_token_us")
        if per_token is None:
            failures.append("journal_microbench missing from the bench artifact")
        elif base_token and per_token > base_token * journal_factor:
            failures.append(
                f"journal per-token overhead regression: {per_token}us > "
                f"{base_token}us * {journal_factor} "
                f"(= {base_token * journal_factor:.3f}us)"
            )
    wal = bench.get("journal_wal_microbench") or {}
    base_wal = baseline.get("journal_wal_microbench") or {}
    if base_wal:
        per_token = _num(wal, "per_token_us_wal")
        base_token = _num(base_wal, "per_token_us_wal")
        if per_token is None:
            failures.append(
                "journal_wal_microbench missing from the bench artifact"
            )
        elif base_token and per_token > base_token * wal_factor:
            failures.append(
                f"journal WAL per-token overhead regression: {per_token}us "
                f"> {base_token}us * {wal_factor} "
                f"(= {base_token * wal_factor:.2f}us)"
            )
    shed = bench.get("shed_microbench") or {}
    base_shed = baseline.get("shed_microbench") or {}
    if base_shed:
        p50, base_p50 = _num(shed, "shed_p50_us"), _num(base_shed, "shed_p50_us")
        if p50 is None:
            failures.append("shed_microbench missing from the bench artifact")
        elif base_p50 and p50 > base_p50 * shed_factor:
            failures.append(
                f"deadline shed latency regression: {p50}us > "
                f"{base_p50}us * {shed_factor} "
                f"(= {base_p50 * shed_factor:.1f}us)"
            )
        reclaim = _num(shed, "reclaim_ms")
        base_reclaim = _num(base_shed, "reclaim_ms")
        if base_reclaim:
            if reclaim is None:
                failures.append(
                    "abandoned-stream KV blocks never reclaimed "
                    "(reclaim_ms null in the bench artifact)"
                )
            elif reclaim > base_reclaim * reclaim_factor:
                failures.append(
                    f"abandoned-stream reclaim regression: {reclaim}ms > "
                    f"{base_reclaim}ms * {reclaim_factor} "
                    f"(= {base_reclaim * reclaim_factor:.1f}ms)"
                )
    spec = bench.get("spec_microbench") or {}
    base_spec = baseline.get("spec_microbench") or {}
    if base_spec:
        speedup = _num(spec, "speedup")
        tpd = _num(spec.get("spec") or {}, "tokens_per_dispatch")
        if speedup is None or tpd is None:
            failures.append("spec_microbench missing from the bench artifact")
        else:
            if speedup < spec_factor:
                failures.append(
                    f"pooled-spec speedup regression: {speedup}x < "
                    f"{spec_factor}x over plain pooled decode (the whole "
                    "point of speculation is cheaper tokens)"
                )
            # absolute floor, not baseline-relative: a verify dispatch
            # that stops carrying multiple tokens has silently become
            # plain decode whatever the baseline said
            if tpd <= 1.5:
                failures.append(
                    f"pooled-spec tokens_per_dispatch collapsed: {tpd} "
                    "<= 1.5 (speculation is no longer batching verifies)"
                )
            accept = _num(spec.get("spec") or {}, "accept_rate")
            if accept is not None and accept <= 0.0:
                failures.append(
                    "pooled-spec acceptance hit zero — the draft source "
                    "is proposing garbage (or the verify rejects "
                    "everything)"
                )
    transfer = bench.get("transfer_microbench") or {}
    base_transfer = baseline.get("transfer_microbench") or {}
    if base_transfer:
        t_p50 = _num(transfer, "transfer_ms_p50")
        local_p50 = _num(transfer, "local_prefill_ms_p50")
        if t_p50 is None or local_p50 is None:
            failures.append(
                "transfer_microbench missing from the bench artifact"
            )
        else:
            if local_p50 and t_p50 > local_p50 * transfer_factor:
                failures.append(
                    f"kv-transfer latency regression: {t_p50}ms p50 > "
                    f"local-prefill {local_p50}ms * {transfer_factor} "
                    f"(= {local_p50 * transfer_factor:.2f}ms)"
                )
            if transfer.get("fallbacks"):
                failures.append(
                    "kv-transfer pulls silently fell back to local "
                    f"prefill ({transfer['fallbacks']}/"
                    f"{transfer.get('rounds')}) — the transfer latency "
                    "number is not measuring the transfer path"
                )
            wire = _num(transfer, "wire_bytes_per_pull")
            base_wire = _num(base_transfer, "wire_bytes_per_pull")
            # wire bytes scale with the prompt (BENCH_TRANSFER_PROMPT):
            # only comparable when this run used the baseline's size
            same_prompt = (
                _num(transfer, "prompt_tokens")
                == _num(base_transfer, "prompt_tokens")
            )
            if base_wire and same_prompt:
                if wire is None:
                    failures.append(
                        "wire_bytes_per_pull missing from the bench artifact"
                    )
                elif wire > base_wire * 2:
                    failures.append(
                        f"kv wire format bloated: {wire} bytes/pull > "
                        f"baseline {base_wire} * 2"
                    )
    trace = bench.get("trace_microbench") or {}
    base_trace = baseline.get("trace_microbench") or {}
    if base_trace:
        for key, what in (
            ("stamp_us", "per-request hop stamp"),
            ("assemble_us", "trace assembly"),
        ):
            got, base = _num(trace, key), _num(base_trace, key)
            if got is None:
                failures.append(
                    f"trace_microbench.{key} missing from the bench artifact"
                )
            elif base and got > base * trace_factor:
                failures.append(
                    f"fleet-tracing {what} regression: {got}us > "
                    f"{base}us * {trace_factor} "
                    f"(= {base * trace_factor:.2f}us)"
                )
    costmodel = bench.get("costmodel_microbench") or {}
    base_costmodel = baseline.get("costmodel_microbench") or {}
    if base_costmodel:
        got = _num(costmodel, "per_dispatch_us")
        base = _num(base_costmodel, "per_dispatch_us")
        if got is None:
            failures.append(
                "costmodel_microbench missing from the bench artifact"
            )
        else:
            if base and got > base * costmodel_factor:
                failures.append(
                    f"cost-model per-dispatch overhead regression: {got}us "
                    f"> {base}us * {costmodel_factor} "
                    f"(= {base * costmodel_factor:.2f}us)"
                )
            anomalies = _num(costmodel, "anomalies")
            if anomalies:
                failures.append(
                    f"cost-model microbench raised {anomalies} anomalies on "
                    "a healthy steady-state loop — the false-positive floor "
                    "(COSTMODEL_MIN_ANOMALY_MS) is broken"
                )
    slo = bench.get("slo_microbench") or {}
    base_slo = baseline.get("slo_microbench") or {}
    if base_slo:
        got = _num(slo, "per_request_us")
        base = _num(base_slo, "per_request_us")
        if got is None:
            failures.append("slo_microbench missing from the bench artifact")
        else:
            if base and got > base * slo_factor:
                failures.append(
                    f"tenant-metering per-request overhead regression: "
                    f"{got}us > {base}us * {slo_factor} "
                    f"(= {base * slo_factor:.2f}us)"
                )
            burn_alerts = _num(slo, "burn_alerts")
            if burn_alerts:
                failures.append(
                    f"SLO microbench raised {burn_alerts} burn alerts on an "
                    "all-ok loop — a healthy run must never page "
                    "(slo.py burn thresholds or judge logic are broken)"
                )
    return failures


def main(argv: list[str]) -> int:
    if len(argv) < 2:
        print(__doc__)
        return 2
    bench_path = argv[1]
    base_path = argv[2] if len(argv) > 2 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_baseline.json",
    )
    with open(bench_path) as f:
        bench = json.load(f)
    with open(base_path) as f:
        baseline = json.load(f)
    failures = gate(bench, baseline)
    print(
        f"bench gate: backend={bench.get('backend')} "
        f"req/s={bench.get('req_per_sec')} (baseline "
        f"{baseline.get('req_per_sec')}) p50={bench.get('value')}ms "
        f"(baseline {baseline.get('value')}ms) "
        f"kv={json.dumps(bench.get('kv_microbench'))}"
    )
    if failures:
        for f_ in failures:
            print(f"FAIL: {f_}")
        return 1
    print("bench gate: OK (within tolerance of bench_baseline.json)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
