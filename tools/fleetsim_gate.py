"""CI gate for the fleet-scale chaos simulation: compare a FLEETSIM
artifact (``tools/fleetsim.py``) against the committed
``fleetsim_baseline.json``.

Two classes of check (gate the artifact, always upload it, loose-first
tolerances):

ABSOLUTE invariants — correctness under chaos, no tolerance:

- every verified seeded stream token-exact: ``duplicated_tokens == 0``
  and ``missing_tokens == 0`` and ``token_exact == verified``;
- ``resume.failures == 0`` — every mid-stream failover on a seeded
  stream spliced a continuation (100% resume success);
- ``shed.p9 == 0`` — the protected priority-9 cohort is never shed;
- the tier-9 tenant's error budget never exhausts: the artifact's
  per-tenant SLO lines must carry ``t-platinum`` with
  ``budget_remaining > 0`` against its 0.9995 availability target
  (under default chaos the protected cohort stays INSIDE its SLO,
  not merely un-shed — errors count too);
- ``pools_idle`` — every replica's paged-KV pool balanced back to idle
  (zero leaked blocks after wedges, drains, aborts, corrupt pulls);
- the hardening A/B holds: jittered probe spread strictly below the
  synchronized sweep's full-round burst, and the quota lease cache
  strictly below 1.0 redis syncs/request (a sync = the read + write
  pipeline pair, i.e. two real round trips);
- the scheduled stream-mangling chaos actually FIRED (error burst,
  slow-loris, disconnect each injected > 0 times) and at least one
  stream resume was exercised — a run whose faults missed the traffic
  would otherwise pass the resume/token-exactness invariants
  VACUOUSLY.

RELATIVE tolerances vs baseline (CI runners are noisy; these catch
structural regressions, not jitter — tighten as the trajectory
stabilizes):

- ``slo.ttft_p99_ms <= max(baseline * FLEETSIM_GATE_TTFT_FACTOR,
  FLEETSIM_GATE_TTFT_FLOOR_MS)`` (factor 4.0, floor 15000 — chaos-
  window p99 swings several-x on shared runners, and a lucky-fast
  baseline must not turn jitter into failures; the floor stays under
  the 20s request deadline);
- ``slo.errors <= max(baseline + 2, baseline * 3, 4)`` — transient
  non-shed failures must stay rare;
- ``slo.shed.rate <= max(baseline * FLEETSIM_GATE_SHED_FACTOR, 0.10)``
  (factor 3.0; the floor keeps the check ALIVE against a zero-shed
  baseline) — a shed-rate explosion means admission broke, not the
  trace;
- ``slo.breaker_flaps <= max(baseline * 3, baseline + 8)`` — flapping
  breakers mean the probation/cooldown machinery stopped damping.

Usage::

    python tools/fleetsim_gate.py FLEETSIM.json [fleetsim_baseline.json]

Exit 0 = pass, 1 = gate failure (each printed). Refreshing the
baseline is an explicit act: run ``tools/fleetsim.py`` with the CI
seed/env and commit the new baseline next to the change that moved it.
"""

from __future__ import annotations

import json
import os
import sys


def _num(d: dict, *path: str) -> float:
    cur: object = d
    for key in path:
        if not isinstance(cur, dict):
            return 0.0
        cur = cur.get(key)
    return float(cur) if isinstance(cur, (int, float)) else 0.0


def _absolute_failures(slo: dict, hardening: dict) -> list[str]:
    failures: list[str] = []
    streams = slo.get("streams") or {}
    if streams.get("duplicated_tokens") or streams.get("missing_tokens"):
        failures.append(
            "seeded streams lost/duplicated tokens: "
            f"{streams.get('missing_tokens')} missing, "
            f"{streams.get('duplicated_tokens')} duplicated"
        )
    if streams.get("token_exact") != streams.get("verified"):
        failures.append(
            f"only {streams.get('token_exact')}/{streams.get('verified')} "
            "verified streams were token-exact"
        )
    resume = slo.get("resume") or {}
    if resume.get("failures"):
        failures.append(
            f"{resume['failures']} stream resume(s) failed "
            f"(exhausted={resume.get('exhausted')}, "
            f"refused={resume.get('refused')}) — resume success must be 100%"
        )
    if _num(slo, "shed", "p9") > 0:
        failures.append(
            f"priority-9 requests were shed ({slo['shed']['p9']}) — "
            "the protected cohort must never shed"
        )
    if not slo.get("pools_idle"):
        failures.append(
            "replica pools did not converge to idle (leaked KV blocks "
            "or a replica never returned to serving)"
        )
    if hardening:
        spread = hardening.get("probe_spread") or {}
        before = _num(spread, "before", "max_probes_in_window")
        after = _num(spread, "after", "max_probes_in_window")
        if before and after >= before:
            failures.append(
                f"probe jitter stopped spreading fan-out: {after} probes "
                f"per window jittered vs {before} synchronized"
            )
        quota = hardening.get("quota") or {}
        if _num(quota, "after", "syncs_per_request") >= 1.0:
            failures.append(
                "quota lease cache is not cutting redis syncs "
                f"({_num(quota, 'after', 'syncs_per_request')}/request)"
            )
    return failures


def _tenant_budget_failures(slo: dict) -> list[str]:
    """The protected cohort's SLO, gated: the tier-9 tenant line must
    exist (its traffic share guarantees requests in every trace) and
    its availability budget must not exhaust under default chaos."""
    lines = slo.get("tenants")
    if not isinstance(lines, list) or not lines:
        return ["artifact carries no per-tenant SLO lines (slo.tenants)"]
    platinum = next(
        (row for row in lines if row.get("tenant") == "t-platinum"), None
    )
    if platinum is None:
        return ["no SLO line for the protected tenant 't-platinum' — "
                "the tier-9 cohort never made it into the artifact"]
    remaining = platinum.get("budget_remaining")
    if not isinstance(remaining, (int, float)) or remaining <= 0:
        return [
            "the protected tenant 't-platinum' exhausted its "
            f"availability budget (budget_remaining={remaining}, "
            f"availability={platinum.get('availability')} vs target "
            f"{platinum.get('target')}) — tier 9 must stay inside its "
            "SLO under default chaos"
        ]
    return []


def _chaos_fired_failures(artifact: dict, slo: dict) -> list[str]:
    """Anti-vacuity: the invariants above only mean something if the
    chaos they guard against actually intersected traffic."""
    failures: list[str] = []
    injected = (artifact.get("scenario") or {}).get("injected") or {}
    for mode in ("error_burst", "slow_loris", "disconnect_after"):
        if not injected.get(mode):
            failures.append(
                f"scheduled chaos mode '{mode}' never fired — the run's "
                "correctness invariants are vacuous for that fault "
                "(progress-gated scheduling should make this impossible "
                "unless the trace shrank too far)"
            )
    if not _num(slo, "resume", "resumed"):
        failures.append(
            "no stream resume was exercised (resume.resumed == 0) — "
            "'100% resume success' is vacuously true; the aimed "
            "disconnect burst must cut at least one live stream"
        )
    return failures


def _relative_failures(slo: dict, base_slo: dict) -> list[str]:
    failures: list[str] = []
    ttft_factor = float(os.environ.get("FLEETSIM_GATE_TTFT_FACTOR", "4.0"))
    ttft_floor = float(os.environ.get("FLEETSIM_GATE_TTFT_FLOOR_MS",
                                      "15000"))
    shed_factor = float(os.environ.get("FLEETSIM_GATE_SHED_FACTOR", "3.0"))
    p99, base_p99 = _num(slo, "ttft_p99_ms"), _num(base_slo, "ttft_p99_ms")
    # the floor mirrors the error check: chaos-window p99 on a shared
    # runner swings several-x run to run, and a LUCKY-fast baseline
    # must not turn ordinary jitter into a gate failure — the floor
    # sits under FLEET_DEADLINE_S (20s), so a fleet that makes clients
    # wait out their whole budget still fails
    allowed_p99 = max(base_p99 * ttft_factor, ttft_floor)
    if base_p99 and p99 > allowed_p99:
        failures.append(
            f"fleet p99 TTFT regression: {p99}ms > {allowed_p99:.1f}ms "
            f"(baseline {base_p99}ms * {ttft_factor}, floor "
            f"{ttft_floor:.0f}ms)"
        )
    errors, base_errors = _num(slo, "errors"), _num(base_slo, "errors")
    # floor of 4: a zero-error baseline must not turn two noisy client
    # timeouts on a loaded CI box into a gate failure
    allowed_errors = max(base_errors + 2, base_errors * 3, 4.0)
    if errors > allowed_errors:
        failures.append(
            f"non-shed error count blew up: {errors:.0f} > "
            f"{allowed_errors:.0f} (baseline {base_errors:.0f})"
        )
    rate, base_rate = _num(slo, "shed", "rate"), _num(base_slo, "shed", "rate")
    # floor of 0.10: a zero-shed baseline must not DISABLE the check —
    # a 50%-shed admission regression has to fail even when the
    # baseline never shed at all
    allowed_rate = max(base_rate * shed_factor, 0.10)
    if rate > allowed_rate:
        failures.append(
            f"shed rate regression: {rate} > {allowed_rate:.2f} "
            f"(baseline {base_rate} * {shed_factor}, floor 0.10)"
        )
    flaps = _num(slo, "breaker_flaps")
    base_flaps = _num(base_slo, "breaker_flaps")
    allowed_flaps = max(base_flaps * 3, base_flaps + 8)
    if flaps > allowed_flaps:
        failures.append(
            f"breaker flap count blew up: {flaps:.0f} > "
            f"{allowed_flaps:.0f} (baseline {base_flaps:.0f})"
        )
    return failures


def _process_kill_failures(artifact: dict, slo: dict) -> list[str]:
    """process_kill scenario (routers >= 2, a supervised subprocess
    replica): the run must PROVE process death was survivable, not just
    scheduled — kills fired, the supervisor respawned the victim, the
    reborn process rehydrated its WAL, and at least one client rode the
    router failover when the router-tier instance died."""
    if artifact.get("scenario_mode") != "process_kill":
        return []
    failures: list[str] = []
    block = artifact.get("process_kill") or {}
    if not block:
        return ["process_kill scenario produced no process_kill evidence "
                "block"]
    if _num(block, "replica_kills") < 1:
        failures.append(
            "no replica SIGKILL landed (replica_kills == 0) — the "
            "process-death invariants are vacuous"
        )
    if _num(block, "supervisor_restarts") < 1:
        failures.append(
            "the supervisor never respawned the SIGKILLed replica "
            "(supervisor_restarts == 0)"
        )
    rehydrated = block.get("victim_rehydrated")
    if rehydrated is None:
        failures.append(
            "the reborn victim's journal block was unreadable — WAL "
            "rehydration cannot be verified"
        )
    if artifact.get("routers", 1) >= 2:
        if _num(block, "router_kills") < 1:
            failures.append("the scheduled router kill never applied")
        if _num(slo, "router_failovers") < 1:
            failures.append(
                "no client ever failed over between routers "
                "(router_failovers == 0) — the no-single-point-of-"
                "failure invariant is vacuous"
            )
    return failures


def gate(artifact: dict, baseline: dict) -> list[str]:
    failures: list[str] = []
    if artifact.get("kind") != "FLEETSIM":
        return [f"not a FLEETSIM artifact (kind={artifact.get('kind')!r})"]
    if artifact.get("replicas", 0) < baseline.get("replicas", 0):
        failures.append(
            f"fleet shrank: {artifact.get('replicas')} replicas < "
            f"baseline {baseline.get('replicas')} — scale trace length, "
            "not replica count"
        )
    slo = artifact.get("slo") or {}
    failures += _absolute_failures(slo, artifact.get("hardening") or {})
    failures += _tenant_budget_failures(slo)
    failures += _chaos_fired_failures(artifact, slo)
    failures += _process_kill_failures(artifact, slo)
    failures += _relative_failures(slo, baseline.get("slo") or {})
    return failures


def main(argv: list[str]) -> int:
    if len(argv) < 2:
        print(__doc__)
        return 2
    base_path = argv[2] if len(argv) > 2 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "fleetsim_baseline.json",
    )
    with open(argv[1]) as f:
        artifact = json.load(f)
    with open(base_path) as f:
        baseline = json.load(f)
    failures = gate(artifact, baseline)
    slo = artifact.get("slo") or {}
    print(
        f"fleetsim gate: seed={artifact.get('seed')} "
        f"replicas={artifact.get('replicas')} "
        f"requests={slo.get('requests')} ok={slo.get('ok')} "
        f"errors={slo.get('errors')} p99_ttft={slo.get('ttft_p99_ms')}ms "
        f"shed_rate={_num(slo, 'shed', 'rate')} "
        f"resume={slo.get('resume')} pools_idle={slo.get('pools_idle')}"
    )
    if failures:
        for failure in failures:
            print(f"FAIL: {failure}")
        return 1
    print("fleetsim gate: OK (within tolerance of fleetsim_baseline.json)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
