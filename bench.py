"""End-to-end serving benchmark (driver-run, real TPU).

Boots the framework's HTTP server with the FLAGSHIP model (llama3-8b,
int8 weight-only, the BASELINE.md config-3 shape) behind the dynamic
batcher, fires concurrent requests THROUGH the HTTP transport, and prints
ONE JSON line:

    {"metric": "p50_ttft_ms", "value": N, "unit": "ms", "vs_baseline": R, ...}

vs_baseline is the north-star target ratio: p50 TTFT < 200 ms for
llama3-8b int8 => vs_baseline = 200/p50 (>1.0 beats the target). The JSON
also carries p99, req/s, decode tok/s (also through the transport), and
MFU for prefill and decode (2·N·tokens/time/peak, scraped from the
/metrics gauge the device maintains — gofr_tpu/tpu/flops.py).

Robustness contract (round-2 verdict): boot progress is polled from
/.well-known/ready and narrated on stderr; warmup requests retry and print
error bodies; every phase failure still emits the JSON line with whatever
was measured, and the exit code is 0 only when the headline p50 exists AND
no phase recorded an error; LOG_LEVEL=ERROR keeps server-side causes
visible on stderr. A run that finds no TPU fails: only an explicit
BENCH_PLATFORM (CI's host jobs pin ``cpu``) runs elsewhere.

Env overrides: BENCH_MODEL (default "llama3-8b"), BENCH_CLIENTS,
BENCH_REQUESTS, BENCH_PROMPT_LEN, BENCH_DECODE_TOKENS,
BENCH_DECODE_STREAMS (concurrent generations in the decode phase;
defaults to the decode-pool slot count — weight streaming per chunk is
the bound, so tokens/sec scales with slots until HBM runs out),
BENCH_BOOT_TIMEOUT, plus any framework config key (MODEL_QUANT,
MODEL_MAX_SEQ, MODEL_BUCKETS, BATCH_MAX_SIZE, DECODE_SLOTS...).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request


class _SkipPhase(Exception):
    """Control-flow marker: a measurement phase that does not apply to
    this model config (not an error; nothing lands in the errors list)."""


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def main() -> int:
    # postmortem black box: bundles written during THIS run (wedge,
    # crash, or the forced end-of-run capture below) are harvested into
    # the JSON artifact — and copied to BENCH_POSTMORTEM_OUT (e.g.
    # hw/rNN/) so the evidence survives the process
    pm_dir = os.environ.setdefault("POSTMORTEM_DIR", "/tmp/gofr_postmortems")
    # gofrlint: wall-clock — compared against bundle file mtimes in _harvest_postmortems
    run_start = time.time()
    model = os.environ.get("BENCH_MODEL", "llama3-8b")
    clients = int(os.environ.get("BENCH_CLIENTS", "8"))
    n_requests = int(os.environ.get("BENCH_REQUESTS", "64"))
    prompt_len = int(os.environ.get("BENCH_PROMPT_LEN", "48"))
    decode_tokens = int(os.environ.get("BENCH_DECODE_TOKENS", "64"))
    # 8B cold-boot time is unmeasured on this machine (chip_smoke.py
    # reports the boot timeline); 600s leaves measurement time inside a
    # 900s window even on a slow cold compile
    boot_timeout = float(os.environ.get("BENCH_BOOT_TIMEOUT", "600"))

    os.environ.update(
        MODEL_NAME=model,
        HTTP_PORT=os.environ.get("BENCH_PORT", "18811"),
        # ERROR to stderr: server-side failure causes stay visible (the
        # round-1 bench discarded them with FATAL and debugging was blind)
        LOG_LEVEL=os.environ.get("BENCH_LOG_LEVEL", "ERROR"),
        BATCH_MAX_SIZE=os.environ.get("BATCH_MAX_SIZE", "8"),
        BATCH_TIMEOUT_MS=os.environ.get("BATCH_TIMEOUT_MS", "3"),
        TPU_BOOT="background",  # server listens first; boot observable via /ready
    )
    if model.startswith("llama3"):
        # single-chip flagship serving: int8 weights + a KV allocation that
        # fits one v5e chip beside them (tpu/device.py MODEL_MAX_SEQ path)
        os.environ.setdefault("MODEL_QUANT", "int8")
        os.environ.setdefault("MODEL_MAX_SEQ", "512")
        # 8 slots: a default that fits beside the int8 weights at this
        # MODEL_MAX_SEQ; the best slot count is unmeasured on this
        # machine (ROADMAP S4b re-derives it from HBM left after weights)
        os.environ.setdefault("DECODE_SLOTS", "8")
    # default decode concurrency = the server's actual pool slot count
    # (DECODE_SLOTS if set, else the device's BATCH_MAX_SIZE default) so
    # the decode phase fills the pool exactly
    decode_streams = max(1, int(
        os.environ.get("BENCH_DECODE_STREAMS")
        or os.environ.get("DECODE_SLOTS")
        or os.environ["BATCH_MAX_SIZE"]
    ))
    max_seq_env = os.environ.get("MODEL_MAX_SEQ")
    max_seq = int(max_seq_env) if max_seq_env else 1 << 30
    # compile ONLY the bucket this bench serves (plus headroom bucket for
    # decode growth is not needed — decode writes into the cache, which is
    # max_seq-sized regardless of prefill bucket)
    bucket = max(64, next_pow2(prompt_len))
    os.environ.setdefault("MODEL_BUCKETS", str(min(bucket, max_seq)))

    result: dict = {
        "metric": "p50_ttft_ms", "value": None, "unit": "ms",
        "vs_baseline": None, "model": model,
        "quant": os.environ.get("MODEL_QUANT", ""),
        "prompt_len": prompt_len, "clients": clients,
    }
    errors: list[str] = []
    rc = 1
    try:
        rc = _run(result, errors, model, clients, n_requests, prompt_len,
                  decode_tokens, boot_timeout, decode_streams)
    except BaseException as exc:
        errors.append(f"{type(exc).__name__}: {exc}")
        traceback.print_exc(file=sys.stderr)
    finally:
        _harvest_postmortems(result, pm_dir, run_start)
        if errors:
            result["errors"] = errors
        # ALWAYS one JSON line, even on phase failure — partial numbers
        # beat an empty artifact
        print(json.dumps(result), flush=True)
    return rc


def _harvest_postmortems(result: dict, pm_dir: str, run_start: float) -> None:
    """Collect the black-box bundles this run produced: list them in the
    artifact, copy them to BENCH_POSTMORTEM_OUT when set (the round's
    hw/rNN/ evidence directory)."""
    import glob
    import shutil

    try:
        bundles = sorted(
            p for p in glob.glob(os.path.join(pm_dir, "postmortem-*.json"))
            if os.path.getmtime(p) >= run_start - 1.0
        )
    except OSError:
        return
    if not bundles:
        return
    result["postmortem_bundles"] = bundles
    out_dir = os.environ.get("BENCH_POSTMORTEM_OUT")
    if not out_dir:
        return
    try:
        os.makedirs(out_dir, exist_ok=True)
        for path in bundles:
            shutil.copy2(path, out_dir)
        log(f"harvested {len(bundles)} postmortem bundle(s) into {out_dir}")
    except OSError as exc:
        log(f"postmortem harvest failed: {exc}")


def _run(result, errors, model, clients, n_requests, prompt_len,
         decode_tokens, boot_timeout, decode_streams) -> int:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

    import jax

    # BENCH_PLATFORM pins the backend explicitly (CI's host jobs run the
    # echo/CPU harness with BENCH_PLATFORM=cpu); unpinned, the bench is a
    # chip benchmark and a run that finds no TPU fails here, before any
    # model is built — it never carries on as a CPU run
    pinned = os.environ.get("BENCH_PLATFORM", "")
    if pinned:
        jax.config.update("jax_platforms", pinned)
    devices = jax.devices()
    result["backend"] = devices[0].platform
    result["device"] = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if not pinned and devices[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU attached (jax found platform={devices[0].platform}); "
            "set BENCH_PLATFORM to run the harness elsewhere on purpose"
        )

    import gofr_tpu

    async def infer(ctx):
        payload = ctx.bind()
        state = await ctx.tpu.infer_async(payload["tokens"])
        if isinstance(state, dict):
            # next_token was argmaxed on device; reading state["logits"]
            # here would add a [V]-row device fetch per request
            return {"next_token": state["next_token"]}
        # MLP/BERT runners return a numpy vector (BASELINE configs 1-2):
        # its length is enough proof of life — returning the values would
        # time JSON serialization, not the model
        return {"dim": int(state.size)}

    def generate(ctx):
        payload = ctx.bind()
        toks = ctx.tpu.generate(
            payload["tokens"], max_new_tokens=int(payload.get("max", 32))
        )
        return {"tokens": toks, "n": len(toks)}

    # -- phase: boot (ONE attempt: a mis-sized default is a failure to
    # read, not something to halve and carry on from) ----------------------
    boot_start = time.perf_counter()
    log(f"booting app (model={model} quant={os.environ.get('MODEL_QUANT')}"
        f" max_seq={os.environ.get('MODEL_MAX_SEQ')}"
        f" buckets={os.environ.get('MODEL_BUCKETS')}"
        f" slots={os.environ.get('DECODE_SLOTS')})")
    app = gofr_tpu.new()
    if app.container.tpu is None:
        raise RuntimeError("TPU datasource failed to wire (see stderr above)")
    app.post("/infer", infer)
    app.post("/generate", generate)
    app.start()
    base = f"http://127.0.0.1:{app.http_port}"
    try:
        result["boot_stages"] = _await_ready(base, boot_timeout)
    except BaseException:
        app.shutdown()
        raise
    result["decode_slots"] = int(os.environ.get("DECODE_SLOTS", "0") or 0) or None
    try:
        boot_s = time.perf_counter() - boot_start
        result["boot_seconds"] = round(boot_s, 1)
        result["n_params"] = getattr(app.container.tpu.runner, "n_params", None)
        runner_buckets = getattr(app.container.tpu.runner, "buckets", None)
        if runner_buckets and runner_buckets[-1] < prompt_len:
            raise RuntimeError(
                f"largest sequence bucket {runner_buckets[-1]} < prompt_len "
                f"{prompt_len} — prompts would be silently truncated"
            )
        log(f"ready in {boot_s:.0f}s (buckets={runner_buckets})")

        vocab = 200
        body = json.dumps(
            {"tokens": [(7 * i) % vocab + 1 for i in range(prompt_len)]}
        ).encode()

        def post(path: str, payload: bytes, timeout: float = 180.0):
            """One HTTP POST -> (elapsed_seconds, parsed envelope)."""
            req = urllib.request.Request(
                base + path, data=payload,
                headers={"Content-Type": "application/json"},
            )
            start = time.perf_counter()
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                parsed = json.loads(resp.read())
            return time.perf_counter() - start, parsed

        def fire(path: str = "/infer", payload: bytes = body,
                 timeout: float = 180.0) -> float:
            return post(path, payload, timeout)[0]

        # -- phase: warmup (retry-guarded; error bodies printed) -------------
        _warmup(fire, errors, clients=max(1, min(clients, n_requests)))

        # -- phase: TTFT through the transport --------------------------------
        # Multiple passes, best-p50 pass reported (all passes recorded in
        # the JSON). ROADMAP S1 replaces this with every pass reported.
        clients = max(1, min(clients, n_requests))
        result["clients"] = clients  # the ACTUAL thread count after clamping
        n_passes = int(os.environ.get("BENCH_TTFT_PASSES", "2"))
        passes: list[dict] = []
        for i in range(n_passes):
            log(f"TTFT pass {i + 1}/{n_passes}: {clients} clients x "
                f"{max(1, n_requests // clients)} requests")
            stats = _ttft_pass(fire, clients, n_requests, errors)
            if stats is not None:
                stats["mfu_prefill"] = _scrape_mfu(base, model, "prefill")
                passes.append(stats)
                log(f"  p50 {stats['p50']:.1f}ms p99 {stats['p99']:.1f}ms "
                    f"{stats['rps']:.2f} req/s")
        if passes:
            best = min(passes, key=lambda s: s["p50"])
            target_ms = 200.0  # north-star p50 TTFT target (BASELINE.md config 3)
            result.update(
                value=round(best["p50"], 2),
                vs_baseline=round(target_ms / max(best["p50"], 1e-6), 3),
                p99_ttft_ms=round(best["p99"], 2),
                req_per_sec=round(best["rps"], 2),
                requests=best["n"],
                ttft_pass_p50s_ms=[round(s["p50"], 2) for s in passes],
                mfu_prefill=best["mfu_prefill"],
            )
        else:
            result["mfu_prefill"] = _scrape_mfu(base, model, "prefill")

        # -- phase: decode tok/s through the transport ------------------------
        try:
            if getattr(app.container.tpu.runner, "decode_chunk_size", None) is None:
                # encoder/MLP configs (BASELINE 1-2) have no decode loop
                # (their generate() is a NotImplementedError guard);
                # probing /generate anyway just pollutes the artifact's
                # errors list with a 500 per run
                log("decode phase skipped: model has no generate path")
                raise _SkipPhase
            log(f"decode phase: {decode_streams} concurrent streams x "
                f"{decode_tokens} tokens")
            result["decode_streams"] = decode_streams
            result["decode_tok_per_sec"] = _measure_decode(
                post, decode_streams, prompt_len, decode_tokens
            )
            result["mfu_decode"] = _scrape_mfu(base, model, "decode")
            result["mbu_decode"] = _scrape_gauge(
                base, f'gofr_tpu_mbu{{model="{model}",op="decode"}}'
            )
            log(f"decode {result['decode_tok_per_sec']} tok/s "
                f"(mfu {result['mfu_decode']} mbu {result['mbu_decode']})")
        except _SkipPhase:
            pass
        except Exception as exc:
            errors.append(f"decode phase: {_describe_http_error(exc)}")
            traceback.print_exc(file=sys.stderr)

        # -- phase: paged-KV microbench (echo/CPU rounds) ---------------------
        # the copied-bytes and admission-latency deltas of block aliasing
        # vs the slot/copy model, measured host-side in the SAME harness —
        # plus the server's live block accounting off /admin/engine
        if model == "echo":
            try:
                result["kv_microbench"] = _measure_paged_kv()
                log(f"paged KV: {result['kv_microbench']}")
            except Exception as exc:
                errors.append(f"paged-kv phase: {exc}")
                traceback.print_exc(file=sys.stderr)
            # -- phase: host-mesh round (sharded-serving satellite) -----------
            # the same paged engine on a tp=2-sharded host arena vs the
            # single-device arena: per-token dispatch latency and
            # copied-KV-bytes per prefix hit must not regress when the
            # block tables span fake devices (tools/bench_gate.py holds
            # the tolerance against bench_baseline.json)
            try:
                result["mesh_microbench"] = _measure_host_mesh()
                log(f"host mesh: {result['mesh_microbench']}")
            except Exception as exc:
                errors.append(f"host-mesh phase: {exc}")
                traceback.print_exc(file=sys.stderr)
            # -- phase: journal overhead (self-healing satellite) --------------
            # the per-token cost of the durable generation journal —
            # the price every stream pays for resumability; gated
            # against bench_baseline.json (BENCH_GATE_JOURNAL_FACTOR)
            try:
                result["journal_microbench"] = _measure_journal()
                log(f"journal: {result['journal_microbench']}")
            except Exception as exc:
                errors.append(f"journal phase: {exc}")
                traceback.print_exc(file=sys.stderr)
            # -- phase: journal WAL persistence (crash durability) -------------
            # the per-token price of the disk-backed journal (surviving
            # kill -9 / power loss) vs the in-memory baseline; gated
            # loose-first via BENCH_GATE_WAL_FACTOR
            try:
                result["journal_wal_microbench"] = _measure_journal_wal()
                log(f"journal wal: {result['journal_wal_microbench']}")
            except Exception as exc:
                errors.append(f"journal-wal phase: {exc}")
                traceback.print_exc(file=sys.stderr)
            # -- phase: recovery MTTR (self-healing tentpole) ------------------
            # wedge -> serving wall time on an in-process echo engine:
            # the trajectory records RESILIENCE, not just speed — the
            # number that says how long a wedged replica is dark
            try:
                result["recovery_microbench"] = _measure_recovery()
                log(f"recovery: {result['recovery_microbench']}")
            except Exception as exc:
                errors.append(f"recovery phase: {exc}")
                traceback.print_exc(file=sys.stderr)
            # -- phase: deadline shed + abandoned-stream reclaim ---------------
            # how fast the engine says NO (expired-request rejection)
            # and how fast an abandoned stream's KV comes back — the
            # overload numbers the brownout/deadline layer lives on
            try:
                result["shed_microbench"] = _measure_shed()
                log(f"shed: {result['shed_microbench']}")
            except Exception as exc:
                errors.append(f"shed phase: {exc}")
                traceback.print_exc(file=sys.stderr)
            # -- phase: pooled speculative decoding (ROADMAP 3 tentpole) -------
            # pooled-spec vs plain pooled decode tok/s at a fixed
            # stream count, acceptance rate, and tokens per verify
            # dispatch — the "cheaper tokens" numbers; gated against
            # bench_baseline.json (BENCH_GATE_SPEC_FACTOR + the
            # absolute tokens_per_dispatch floor)
            try:
                result["spec_microbench"] = _measure_spec()
                log(f"pooled spec: {result['spec_microbench']}")
            except Exception as exc:
                errors.append(f"spec phase: {exc}")
                traceback.print_exc(file=sys.stderr)
            # -- phase: disaggregated KV handoff (ROADMAP 1 tentpole) ----------
            # cross-replica transfer vs local prefill on two in-process
            # echo replicas over real HTTP, plus the wire bytes one
            # pull moves; gated loose-first against bench_baseline.json
            # (BENCH_GATE_TRANSFER_FACTOR)
            try:
                result["transfer_microbench"] = _measure_kv_transfer()
                log(f"kv transfer: {result['transfer_microbench']}")
            except Exception as exc:
                errors.append(f"kv-transfer phase: {exc}")
                traceback.print_exc(file=sys.stderr)
            # -- phase: fleet tracing overhead ---------------------------------
            # what the hop-correlation layer costs per request (header
            # sanitize + stamp, on the router hot path) and what one
            # /admin/fleet/trace assembly costs off it; gated
            # loose-first against bench_baseline.json
            # (BENCH_GATE_TRACE_FACTOR)
            try:
                result["trace_microbench"] = _measure_trace()
                log(f"fleet trace: {result['trace_microbench']}")
            except Exception as exc:
                errors.append(f"trace phase: {exc}")
                traceback.print_exc(file=sys.stderr)
            # -- phase: dispatch cost model overhead ---------------------------
            # what predict (begin) + residual accounting (finish) adds
            # to every dispatch record — the tax the residual
            # watchtower levies on the hot path; gated loose-first
            # against bench_baseline.json (BENCH_GATE_COSTMODEL_FACTOR)
            try:
                result["costmodel_microbench"] = _measure_costmodel()
                log(f"costmodel: {result['costmodel_microbench']}")
            except Exception as exc:
                errors.append(f"costmodel phase: {exc}")
                traceback.print_exc(file=sys.stderr)
            # -- phase: SLO + tenant metering overhead -------------------------
            # what the bounded tenant sketch adds to every flight
            # record and what one burn-window evaluation costs off the
            # hot path; the all-ok loop must raise zero burn alerts;
            # gated loose-first against bench_baseline.json
            # (BENCH_GATE_SLO_FACTOR)
            try:
                result["slo_microbench"] = _measure_slo()
                log(f"slo: {result['slo_microbench']}")
            except Exception as exc:
                errors.append(f"slo phase: {exc}")
                traceback.print_exc(file=sys.stderr)
            engine_live = _scrape_engine(base)
            if engine_live.get("kv_blocks") is not None:
                result["kv_blocks"] = engine_live["kv_blocks"]
            if engine_live.get("mesh") is not None:
                result["mesh"] = engine_live["mesh"]
        return _exit_code(result, errors)
    finally:
        # the engine state machine's verdict on the run (serving vs
        # degraded/wedged)
        state = _scrape_engine_state(base)
        if state is not None:
            result["engine_state"] = state
        if state in ("degraded", "wedged"):
            # force a black-box bundle BEFORE shutdown: the wedge's own
            # bundle may be rate-limited or mid-write, and the driver is
            # about to kill this process — main()'s harvest then carries
            # it into the artifact
            try:
                req = urllib.request.Request(
                    base + "/admin/postmortem", data=b"{}",
                    headers={"Content-Type": "application/json"},
                    method="POST",
                )
                with urllib.request.urlopen(req, timeout=30) as r:
                    path = json.loads(r.read())["data"]["path"]
                log(f"engine {state}: postmortem bundle forced at {path}")
            except Exception as exc:
                log(f"postmortem trigger failed: {exc}")
        try:
            app.shutdown()
        except Exception:
            pass


def _ttft_pass(fire, clients: int, n_requests: int, errors: list[str]):
    """One concurrent-clients TTFT measurement; returns stats or None."""
    latencies: list[float] = []
    failures: list[str] = []
    lock = threading.Lock()
    per_client = max(1, n_requests // clients)
    wall_start = time.perf_counter()

    def worker() -> None:
        local, bad = [], []
        for _ in range(per_client):
            try:
                local.append(fire())
            except Exception as exc:
                bad.append(_describe_http_error(exc))
        with lock:
            latencies.extend(local)
            failures.extend(bad)

    threads = [
        threading.Thread(target=worker, name=f"bench-client-{i}")
        for i in range(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - wall_start
    if failures:
        errors.extend(failures[:5])
        log(f"  pass had {len(failures)} failed requests")
    if not latencies:
        return None
    latencies.sort()
    return {
        "p50": latencies[len(latencies) // 2] * 1000,
        "p99": latencies[min(len(latencies) - 1, int(len(latencies) * 0.99))] * 1000,
        "rps": len(latencies) / wall,
        "n": len(latencies),
    }


def _await_ready(base: str, timeout: float) -> list:
    """Poll /.well-known/ready until 200, narrating boot-stage changes.
    Returns [[stage, seconds], ...] — per-stage boot wall time at the
    2s poll granularity, which is how per-bucket compile cost (the round-1
    boot-wedge risk) gets measured on real hardware without instrumenting
    the server."""
    deadline = time.monotonic() + timeout
    last_detail = None
    stage_start = time.monotonic()
    stages: list = []

    def close_stage() -> None:
        if last_detail is not None:
            stages.append([last_detail, round(time.monotonic() - stage_start, 1)])

    while True:
        state = {}
        try:
            with urllib.request.urlopen(base + "/.well-known/ready", timeout=10) as r:
                state = json.loads(r.read() or b"{}")
                close_stage()
                return stages  # 200 => ready
        except urllib.error.HTTPError as e:
            try:
                state = json.loads(e.read() or b"{}")
            except Exception:
                state = {}
            if state.get("state") == "failed":
                raise RuntimeError(f"TPU boot failed: {state.get('detail')}") from None
        except Exception:
            pass  # server not accepting yet
        detail = state.get("detail") or state.get("state") or "starting"
        if detail != last_detail:
            close_stage()
            log(f"boot: {detail}")
            last_detail = detail
            stage_start = time.monotonic()
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"server not ready after {timeout:.0f}s (last stage: {detail})"
            )
        time.sleep(2.0)


def _warmup(fire, errors: list[str], attempts: int = 5, clients: int = 1) -> None:
    """Fill request-path caches. Retries transient failures and prints HTTP
    error bodies — a failed warmup must say WHY (round-1 postmortem)."""
    ok = 0
    for i in range(attempts):
        try:
            fire()
            ok += 1
            if ok >= 3:
                break
        except Exception as exc:
            msg = _describe_http_error(exc)
            log(f"warmup attempt {i + 1}/{attempts} failed: {msg}")
            errors.append(f"warmup: {msg}")
            time.sleep(2.0)
    if ok == 0:
        raise RuntimeError("warmup never succeeded — aborting measurement")
    # one full-concurrency round: sequential warmup never fills the
    # batcher's [clients]-wide dispatch shape or touches its contention
    # paths, so pass 1 used to pay those costs cold (round-3 passes were
    # [222.6, 108.9] ms — only the warm second pass beat the target)
    if clients > 1:
        failures: list[str] = []

        def one() -> None:
            try:
                fire()
            except Exception as exc:
                failures.append(_describe_http_error(exc))

        workers = [
            threading.Thread(target=one, name=f"bench-warmup-{i}")
            for i in range(clients)
        ]
        for t in workers:
            t.start()
        for t in workers:
            t.join()
        if failures:
            errors.extend(f"concurrent warmup: {m}" for m in failures[:3])


def _exit_code(result: dict, errors: list) -> int:
    """0 only when the headline p50 exists AND no phase recorded an
    error — a partial artifact still prints, but never exits clean."""
    return 0 if result["value"] is not None and not errors else 1


def _describe_http_error(exc: Exception) -> str:
    if isinstance(exc, urllib.error.HTTPError):
        try:
            body = exc.read(500).decode("utf-8", "replace")
        except Exception:
            body = "<unreadable>"
        return f"HTTP {exc.code}: {body}"
    return f"{type(exc).__name__}: {exc}"


def _measure_paged_kv() -> dict:
    """Copied-KV-bytes per prefix hit + admission latency: the paged
    engine (copy-free block aliasing) against the slot/copy model
    (``copy_mode=True`` — every hit materializes a private copy, the
    row-cache behavior), same allocator, same arena, same prompts.
    Host-side and compile-free."""
    import numpy as np

    from gofr_tpu.tpu.kv_blocks import (
        BlockPool,
        HostPagedKV,
        HostTokenArena,
    )

    prompt = (np.arange(512, dtype=np.int32) * 7) % 251 + 1
    follow = np.concatenate(  # LCP case: shared prefix, new tail
        [prompt[:384], (np.arange(64, dtype=np.int32) % 97) + 1]
    ).astype(np.int32)
    n = int(os.environ.get("BENCH_KV_ITERS", "200"))
    out: dict = {}
    for label, copy_mode in (("paged", False), ("slot_copy", True)):
        arena = HostTokenArena(2048, 16)
        pool = BlockPool(2048, 16, arena=arena, cache_entries=64)
        eng = HostPagedKV(pool, arena, lcp_min=16, copy_mode=copy_mode)
        seed = eng.admit(prompt, 0)
        eng.finish(seed)  # the cached conversation every hit aliases
        base_bytes = pool.stats()["copied_kv_bytes"]
        start = time.perf_counter()
        for i in range(n):
            seq = eng.admit(prompt if i % 2 == 0 else follow, 8)
            eng.finish(seq, store=False)
        elapsed = time.perf_counter() - start
        st = pool.stats()
        out[label] = {
            "copied_kv_bytes_per_hit": round(
                (st["copied_kv_bytes"] - base_bytes) / n, 1
            ),
            "admission_ms": round(elapsed / n * 1000, 4),
            "hits": eng.prefix_stats["hits"],
            "partial_hits": eng.prefix_stats["partial_hits"],
        }
    slot_b = out["slot_copy"]["copied_kv_bytes_per_hit"]
    paged_b = out["paged"]["copied_kv_bytes_per_hit"]
    out["copied_bytes_reduction"] = (
        round(1.0 - paged_b / slot_b, 4) if slot_b else None
    )
    return out


def _measure_host_mesh() -> dict:
    """Host-mesh round (ROADMAP 1 satellite): the echo paged-KV engine
    on a ``tp=2``-sharded :class:`HostTokenArena` (every block's tokens
    split across 2 fake devices — the host analogue of the device
    arena's tp head sharding) against the single-device arena, same
    allocator, same prompts. Reports the per-token dispatch (append)
    latency and the copied-KV-bytes per prefix hit for both, plus the
    mesh/single latency ratio — sharding the tables must cost
    bookkeeping only, never extra KV copies. Host-side and
    compile-free."""
    import numpy as np

    from gofr_tpu.tpu.kv_blocks import (
        BlockPool,
        HostPagedKV,
        HostTokenArena,
    )

    prompt = (np.arange(256, dtype=np.int32) * 5) % 199 + 1
    n_tokens = int(os.environ.get("BENCH_MESH_TOKENS", "2048"))
    n_hits = int(os.environ.get("BENCH_KV_ITERS", "200"))
    out: dict = {"tp": 2}
    for label, shards in (("single", 1), ("mesh", 2)):
        arena = HostTokenArena(1024, 16, shards=shards)
        pool = BlockPool(1024, 16, arena=arena, cache_entries=64)
        eng = HostPagedKV(pool, arena, lcp_min=16)
        seed = eng.admit(prompt, 0)
        eng.finish(seed)  # the cached conversation every hit aliases
        base_bytes = pool.stats()["copied_kv_bytes"]
        start = time.perf_counter()
        for _ in range(n_hits):
            seq = eng.admit(prompt, 8)
            eng.finish(seq, store=False)
        admit_ms = (time.perf_counter() - start) / n_hits * 1000
        copied = (pool.stats()["copied_kv_bytes"] - base_bytes) / n_hits
        # per-token dispatch: the decode-side append path THROUGH the
        # (possibly sharded) block tables — COW + capacity bookkeeping
        # plus the shard-split write itself
        seq = eng.admit(prompt, n_tokens)
        start = time.perf_counter()
        for i in range(n_tokens):
            eng.append(seq, int(prompt[i % prompt.size]))
        per_tok_ms = (time.perf_counter() - start) / n_tokens * 1000
        eng.finish(seq, store=False)
        out[label] = {
            "per_token_dispatch_ms": round(per_tok_ms, 5),
            "admission_ms": round(admit_ms, 4),
            "copied_kv_bytes_per_hit": round(copied, 1),
        }
    out["per_token_overhead_ratio"] = round(
        out["mesh"]["per_token_dispatch_ms"]
        / max(out["single"]["per_token_dispatch_ms"], 1e-9), 3,
    )
    return out


def _measure_journal() -> dict:
    """Per-token cost of the durable generation journal (telemetry.py):
    request-key hashing + entry start/finish per request, one bounded
    append per token — the overhead every stream pays for
    resumability. Host-side and compile-free; the gate holds
    ``per_token_us`` against bench_baseline.json
    (``BENCH_GATE_JOURNAL_FACTOR``)."""
    from gofr_tpu.telemetry import GenerationJournal, request_key

    n_req = int(os.environ.get("BENCH_JOURNAL_REQUESTS", "200"))
    n_tok = int(os.environ.get("BENCH_JOURNAL_TOKENS", "64"))
    journal = GenerationJournal(capacity=256, max_tokens=8192)
    prompt = [(7 * i) % 251 + 1 for i in range(48)]
    start = time.perf_counter()
    for i in range(n_req):
        key = request_key("echo", prompt, n_tok, None)
        entry = journal.start(key, "echo", n_tok, seeded=False,
                              deterministic=True)
        for token in range(n_tok):
            entry.append(token)
        journal.finish(entry)
    elapsed = time.perf_counter() - start
    # the control: the same loop shape journaling nothing — isolates
    # the journal's own cost from loop overhead
    sink = 0
    start = time.perf_counter()
    for i in range(n_req):
        for token in range(n_tok):
            sink += token
    control = time.perf_counter() - start
    overhead = max(elapsed - control, 0.0)
    return {
        "requests": n_req,
        "tokens_per_request": n_tok,
        "per_token_us": round(overhead / (n_req * n_tok) * 1e6, 4),
        "per_request_us": round(overhead / n_req * 1e6, 2),
    }


def _measure_journal_wal() -> dict:
    """Journal persistence (journal_wal.py): the SAME loop as
    ``_measure_journal`` with the disk-backed WAL armed, under each
    fsync policy — the per-token price of surviving ``kill -9``
    (``interrupt``: flush-only appends) and of surviving power loss
    (``always``: fsync per record). ``wal_factor`` is WAL-on over
    in-memory per-token cost; the gate holds ``per_token_us_wal``
    against bench_baseline.json (``BENCH_GATE_WAL_FACTOR``)."""
    import shutil
    import tempfile

    from gofr_tpu.journal_wal import JournalWAL
    from gofr_tpu.telemetry import GenerationJournal, request_key

    n_req = int(os.environ.get("BENCH_JOURNAL_REQUESTS", "200"))
    n_tok = int(os.environ.get("BENCH_JOURNAL_TOKENS", "64"))
    prompt = [(7 * i) % 251 + 1 for i in range(48)]

    def run(wal) -> float:
        journal = GenerationJournal(capacity=256, max_tokens=8192, wal=wal)
        start = time.perf_counter()
        for _ in range(n_req):
            key = request_key("echo", prompt, n_tok, None)
            entry = journal.start(key, "echo", n_tok, seeded=False,
                                  deterministic=True)
            for token in range(n_tok):
                entry.append(token)
            journal.finish(entry)
        return time.perf_counter() - start

    mem_s = run(None)
    out: dict = {
        "requests": n_req,
        "tokens_per_request": n_tok,
        "per_token_us_mem": round(mem_s / (n_req * n_tok) * 1e6, 4),
    }
    for policy, key in (("interrupt", "per_token_us_wal"),
                        ("always", "per_token_us_wal_fsync")):
        wal_dir = tempfile.mkdtemp(prefix=f"bench-wal-{policy}-")
        wal = JournalWAL(wal_dir, segment_bytes=1 << 20, retain=2,
                         fsync=policy)
        try:
            if policy == "always":
                # fsync-per-record is measured at a reduced request
                # count: the point is the per-token number, not minutes
                # of fsync on a CI disk
                nonlocal_req = max(10, n_req // 10)
                journal = GenerationJournal(capacity=256, max_tokens=8192,
                                            wal=wal)
                start = time.perf_counter()
                for _ in range(nonlocal_req):
                    k = request_key("echo", prompt, n_tok, None)
                    entry = journal.start(k, "echo", n_tok, seeded=False,
                                          deterministic=True)
                    for token in range(n_tok):
                        entry.append(token)
                    journal.finish(entry)
                elapsed = time.perf_counter() - start
                out[key] = round(elapsed / (nonlocal_req * n_tok) * 1e6, 4)
            else:
                out[key] = round(run(wal) / (n_req * n_tok) * 1e6, 4)
        finally:
            wal.close()
            shutil.rmtree(wal_dir, ignore_errors=True)
    mem_per_tok = max(out["per_token_us_mem"], 1e-6)
    out["wal_factor"] = round(out["per_token_us_wal"] / mem_per_tok, 2)
    return out


def _measure_costmodel() -> dict:
    """Dispatch cost-model overhead (tpu/costmodel.py): the same
    begin/finish loop through a DispatchTimeline with and without the
    cost model wired — what roofline prediction (begin) plus residual
    EMA accounting + anomaly verdicts (finish) add to each dispatch
    record. Host-side and compile-free; the loop's predictions are
    healthy (zero anomalies) because that is the hot path's steady
    state — anomaly emission is by design rare. The gate holds
    ``per_dispatch_us`` against bench_baseline.json
    (``BENCH_GATE_COSTMODEL_FACTOR``)."""
    from gofr_tpu.metrics import Registry
    from gofr_tpu.tpu.costmodel import CostModel
    from gofr_tpu.tpu.introspect import DispatchTimeline

    n = int(os.environ.get("BENCH_COSTMODEL_DISPATCHES", "5000"))

    def run(costmodel) -> float:
        timeline = DispatchTimeline(
            capacity=512, metrics=Registry(), costmodel=costmodel
        )
        start = time.perf_counter()
        for i in range(n):
            drec = timeline.begin(
                "prefill", bucket=64, batch_size=(i % 4) + 1, tokens=64
            )
            drec.mark_running()
            timeline.finish(drec)
        return time.perf_counter() - start

    baseline_s = run(None)
    costmodel = CostModel(metrics=Registry())
    costmodel.calibrate("cpu", "cpu")
    # a synthetic sheet generous enough that instantaneous begin/finish
    # never trips the anomaly floor — steady-state cost, not event cost
    costmodel.install_synthetic("prefill", 5.0)
    modeled_s = run(costmodel)
    return {
        "dispatches": n,
        "per_dispatch_us": round(modeled_s / n * 1e6, 4),
        "baseline_per_dispatch_us": round(baseline_s / n * 1e6, 4),
        "overhead_us": round(max(modeled_s - baseline_s, 0.0) / n * 1e6, 4),
        "anomalies": costmodel.ring.total(),  # MUST stay 0 (healthy loop)
    }


def _measure_slo() -> dict:
    """SLO + tenant-metering overhead (slo.py, telemetry.TenantLedger):
    the same flight start/finish loop with and without the bounded
    tenant sketch wired — what per-tenant usage metering adds to every
    request record — plus the wall cost of one SloEngine burn-window
    evaluation over the populated flight ring (the off-hot-path sweep
    the gofr-slo thread runs every SLO_EVAL_INTERVAL_S). The loop is
    all-ok traffic, so burn alerts MUST stay zero — a healthy run that
    pages is the one regression this phase exists to catch. Gated
    loose-first vs bench_baseline.json (``BENCH_GATE_SLO_FACTOR`` on
    ``per_request_us``; ``burn_alerts`` is a hard zero)."""
    from gofr_tpu.metrics import Registry
    from gofr_tpu.slo import SloEngine
    from gofr_tpu.telemetry import (
        FlightRecorder,
        TenantLedger,
        activate_tenant,
    )

    n = int(os.environ.get("BENCH_SLO_REQUESTS", "5000"))

    def run(tenants):
        recorder = FlightRecorder(capacity=512, tenants=tenants)
        start = time.perf_counter()
        for i in range(n):
            # 300 distinct tenants through 256 slots: the eviction
            # path (min-weight roll into ~other) is ON the measured
            # loop, not just the happy dict hit
            activate_tenant(f"bench-t{i % 300}")
            record = recorder.start("echo", "/bench", tokens_in=8)
            record.tokens_out = 4
            recorder.finish(record, status="ok")
        elapsed = time.perf_counter() - start
        return elapsed, recorder

    baseline_s, _ = run(None)
    tenants = TenantLedger(size=256, metrics=Registry())
    metered_s, recorder = run(tenants)
    engine = SloEngine(recorder, metrics=Registry(), interval_s=1.0)
    eval_start = time.perf_counter()
    engine.evaluate()
    evaluate_ms = (time.perf_counter() - eval_start) * 1e3
    activate_tenant(None)  # don't leak a tenant into later phases
    return {
        "requests": n,
        "per_request_us": round(metered_s / n * 1e6, 4),
        "baseline_per_request_us": round(baseline_s / n * 1e6, 4),
        "overhead_us": round(max(metered_s - baseline_s, 0.0) / n * 1e6, 4),
        "evaluate_ms": round(evaluate_ms, 3),
        "tenants_tracked": tenants.stats()["tracked"],
        "burn_alerts": engine.ring.total(),  # MUST stay 0 (healthy loop)
    }


def _measure_shed() -> dict:
    """Deadline-aware serving micro-round (host-side, compile-free):

    - **shed latency** — wall time from submitting an already-expired
      request to its 504-mapped rejection (batcher dequeue shed, stage
      ``queue``): the cost of saying no, which under overload is paid
      far more often than the cost of saying yes;
    - **abandoned-stream reclaim** — from tripping a stream's cancel
      event (the SSE responder's client-abort hook) to the paged-KV
      free-block count returning to baseline: how long an abandoned
      request keeps holding blocks a waiting request could use.

    Gated loose-first vs bench_baseline.json
    (``BENCH_GATE_SHED_FACTOR`` / ``BENCH_GATE_RECLAIM_FACTOR``)."""
    import threading

    from gofr_tpu.config import EnvConfig
    from gofr_tpu.deadline import Deadline, activate_deadline
    from gofr_tpu.errors import DeadlineExceeded
    from gofr_tpu.logging import Level
    from gofr_tpu.metrics import Registry
    from gofr_tpu.testutil import MockLogger
    from gofr_tpu.tpu.device import new_device

    overrides = {
        "MODEL_NAME": "echo",
        "ECHO_STEP_MS": "2",
        "BATCH_TIMEOUT_MS": "1",
        "TIMEBASE_ENABLED": "off",
    }
    old = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        device = new_device(EnvConfig(), MockLogger(Level.FATAL), Registry())
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
    try:
        device.wait_ready(30)
        n = int(os.environ.get("BENCH_SHED_REQUESTS", "50"))
        sheds: list[float] = []
        for _ in range(n):
            expired = Deadline(0.0)
            activate_deadline(expired)
            start = time.perf_counter()
            try:
                device.generate([1, 2, 3], max_new_tokens=8)
            except DeadlineExceeded:
                sheds.append(time.perf_counter() - start)
            finally:
                activate_deadline(None)
        if not sheds:
            raise RuntimeError("no expired request was shed")
        sheds.sort()
        # reclaim: warm the prompt's cache entry first (admission
        # caches a never-seen prompt by design — that is not a leak),
        # then abandon a stream mid-decode and time the blocks back
        prompt = [(3 * i) % 251 + 1 for i in range(96)]
        for _ in device.generate_stream(prompt, 2):
            pass
        kv = device.kv_pool
        baseline_free = kv.stats()["free"] if kv is not None else None
        reclaim_ms = None
        if baseline_free is not None:
            cancel = threading.Event()
            stream = device.generate_stream(prompt, 200, cancel=cancel)
            got = 0
            for _ in stream:
                got += 1
                if got >= 3:
                    break
            start = time.perf_counter()
            cancel.set()  # what the SSE abort hook does on write failure
            stream.close()
            wait_until = time.monotonic() + 10
            while time.monotonic() < wait_until:
                if kv.stats()["free"] >= baseline_free:
                    reclaim_ms = round(
                        (time.perf_counter() - start) * 1e3, 3
                    )
                    break
                time.sleep(0.0005)
        return {
            "shed_requests": n,
            "shed_p50_us": round(sheds[len(sheds) // 2] * 1e6, 1),
            "shed_mean_us": round(sum(sheds) / len(sheds) * 1e6, 1),
            "reclaim_ms": reclaim_ms,
        }
    finally:
        device.close()


def _measure_spec() -> dict:
    """Pooled speculative decoding vs plain pooled decode (host-side,
    compile-free): two echo devices with a real per-dispatch cost
    (``ECHO_STEP_MS``), the same concurrent streams, the same token
    budget. Plain decode pays one dispatch per token; pooled spec pays
    one verify dispatch per accepted-burst (zero-weight n-gram
    drafting costs no dispatch), so the tok/s ratio IS the
    tokens-per-dispatch win the adaptive-k controller settles on.
    Gated: ``speedup >= BENCH_GATE_SPEC_FACTOR`` and
    ``tokens_per_dispatch > 1.5`` (tools/bench_gate.py)."""
    import threading

    from gofr_tpu.config import EnvConfig
    from gofr_tpu.logging import Level
    from gofr_tpu.metrics import Registry
    from gofr_tpu.testutil import MockLogger
    from gofr_tpu.tpu.device import new_device

    streams = int(os.environ.get("BENCH_SPEC_STREAMS", "4"))
    n_tok = int(os.environ.get("BENCH_SPEC_TOKENS", "64"))
    step_ms = os.environ.get("BENCH_SPEC_STEP_MS", "2")
    prompts = [
        [(5 * i + 13 * s) % 241 + 1 for i in range(48)]
        for s in range(streams)
    ]
    out: dict = {"streams": streams, "tokens_per_stream": n_tok}
    for label, extra in (
        ("plain", {"SPEC_POOLED": "off"}),
        ("spec", {"SPEC_POOLED": "on", "SPEC_K_MAX": "4"}),
    ):
        overrides = {
            "MODEL_NAME": "echo",
            "ECHO_STEP_MS": step_ms,
            "BATCH_TIMEOUT_MS": "1",
            "TIMEBASE_ENABLED": "off",
            **extra,
        }
        old = {k: os.environ.get(k) for k in overrides}
        os.environ.update(overrides)
        try:
            device = new_device(
                EnvConfig(), MockLogger(Level.FATAL), Registry()
            )
        finally:
            for k, v in old.items():
                os.environ.pop(k, None) if v is None else (
                    os.environ.__setitem__(k, v)
                )
        try:
            device.wait_ready(30)
            device.generate(prompts[0], max_new_tokens=2)  # warm paths
            stats_before = dict(device.runner.spec_stats)
            stream_errors: list = []

            def run_stream(s: int) -> None:
                # a swallowed stream failure would leave tok/s computed
                # from tokens that were never emitted — and the gate
                # would hold BENCH_GATE_SPEC_FACTOR against a lie
                try:
                    got = device.generate(prompts[s], max_new_tokens=n_tok)
                    if len(got) != n_tok:
                        raise RuntimeError(
                            f"stream {s} emitted {len(got)}/{n_tok} tokens"
                        )
                except BaseException as exc:  # re-raised on the main thread
                    stream_errors.append(exc)

            start = time.perf_counter()
            threads = [
                threading.Thread(
                    target=run_stream, args=(s,),
                    name=f"bench-spec-{label}-{s}",
                )
                for s in range(streams)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - start
            if stream_errors:
                raise RuntimeError(
                    f"{label} phase lost {len(stream_errors)}/{streams} "
                    f"streams: {stream_errors[0]!r}"
                )
            entry: dict = {
                "tok_per_sec": round(streams * n_tok / elapsed, 1),
            }
            if label == "spec":
                with device.runner._spec_lock:
                    stats = dict(device.runner.spec_stats)
                cycles = stats["cycles"] - stats_before["cycles"]
                drafted = stats["drafted"] - stats_before["drafted"]
                accepted = stats["accepted"] - stats_before["accepted"]
                entry["accept_rate"] = (
                    round(accepted / drafted, 4) if drafted else None
                )
                entry["tokens_per_dispatch"] = (
                    round(streams * n_tok / cycles, 3) if cycles else None
                )
            out[label] = entry
        finally:
            device.close()
    out["speedup"] = round(
        out["spec"]["tok_per_sec"] / max(out["plain"]["tok_per_sec"], 1e-9),
        3,
    )
    return out


def _measure_kv_transfer() -> dict:
    """Disaggregated KV handoff, measured end to end on two in-process
    echo replicas over real HTTP (the same chaos-harness replicas the
    fleet e2es use):

    - **transfer latency** — a donor-warmed prompt served by the OTHER
      replica with the router's ``X-KV-Donor`` stamp: pull + verify +
      install + aliased admission (the disaggregated fast path);
    - **local-prefill latency** — the identical-size cold prompt on the
      same replica with no donor: what the fallback costs, and the
      number a transfer must beat on real hardware to pay for itself;
    - **bytes moved** — one pull's wire size off the real
      ``GET /admin/kv/<hash>`` endpoint (header + per-block CRC frames
      + trailer), the cross-replica traffic each handoff costs.

    Echo "KV" is token ids, so the ratio here prices the PROTOCOL
    (HTTP + framing + checksums + install), not saved prefill compute.
    Gated loose-first vs bench_baseline.json
    (``BENCH_GATE_TRANSFER_FACTOR``)."""
    from gofr_tpu.devtools.chaos import chaos_fleet
    from gofr_tpu.fleet import kvwire

    prompt_tokens = int(os.environ.get("BENCH_TRANSFER_PROMPT", "96"))
    rounds = int(os.environ.get("BENCH_TRANSFER_ROUNDS", "8"))
    fleet_env = {
        "ECHO_STEP_MS": "0",
        "KV_BLOCK_TOKENS": "16",  # 96-token prompts span 6 blocks
        "KV_TRANSFER_TIMEOUT_S": "5",
        "WATCHDOG_DISPATCH_TIMEOUT_S": "30",
    }

    def generate_ms(replica, tokens, donor=None):
        headers = {"Content-Type": "application/json"}
        if donor is not None:
            headers["X-KV-Donor"] = donor.address
        req = urllib.request.Request(
            replica.address + "/generate",
            data=json.dumps(
                {"tokens": tokens, "max_new_tokens": 1}
            ).encode(),
            headers=headers,
            method="POST",
        )
        start = time.perf_counter()
        with urllib.request.urlopen(req, timeout=30) as resp:
            resp.read()
        return (time.perf_counter() - start) * 1e3

    with chaos_fleet(2, env=fleet_env) as (donor, receiver):
        transfer_ms: list[float] = []
        local_ms: list[float] = []
        for i in range(rounds):
            # fresh prompts per round: a locally-warm prompt skips the
            # pull, so reuse would measure the cache, not the transfer
            warm = [(j % 251) + 1 for j in range(
                i * prompt_tokens, (i + 1) * prompt_tokens
            )]
            cold = [(j % 251) + 1 for j in range(
                (rounds + i) * prompt_tokens,
                (rounds + i + 1) * prompt_tokens,
            )]
            generate_ms(donor, warm)  # the donor prefills + caches it
            transfer_ms.append(generate_ms(receiver, warm, donor=donor))
            local_ms.append(generate_ms(receiver, cold))
        # one pull's wire bytes, measured off the real endpoint
        probe = [(j % 251) + 1 for j in range(prompt_tokens)]
        with urllib.request.urlopen(
            donor.address + f"/admin/kv/{kvwire.prompt_hash(probe)}",
            timeout=10,
        ) as resp:
            wire_bytes = len(resp.read())
        # the receiver's own ledger proves the fast path actually ran
        with urllib.request.urlopen(
            receiver.address + "/admin/engine", timeout=10
        ) as resp:
            stats = json.loads(resp.read())["data"]["kv_transfer"]
    if stats.get("ok", 0) < rounds:
        raise RuntimeError(
            f"only {stats.get('ok', 0)}/{rounds} pulls took the "
            f"transfer fast path: {stats}"
        )
    transfer_ms.sort()
    local_ms.sort()
    return {
        "prompt_tokens": prompt_tokens,
        "rounds": rounds,
        "transfer_ms_p50": round(transfer_ms[len(transfer_ms) // 2], 3),
        "local_prefill_ms_p50": round(local_ms[len(local_ms) // 2], 3),
        "wire_bytes_per_pull": wire_bytes,
        "pulls_ok": stats.get("ok", 0),
        "fallbacks": stats.get("fallback", 0),
    }


def _measure_trace() -> dict:
    """Fleet-tracing overhead (host-side, compile-free):

    - **stamp cost** — what the hop-correlation layer adds to EVERY
      routed request on the router hot path: sanitize the inbound
      request id, mint the ``X-Gofr-Hop`` value, and parse it back the
      way replica admission does;
    - **assemble cost** — one ``/admin/fleet/trace/<id>`` timeline
      assembly (pure join + latency decomposition over an
      already-scraped 3-attempt route record with flight and transfer
      evidence): the off-hot-path read side.

    Gated loose-first vs bench_baseline.json
    (``BENCH_GATE_TRACE_FACTOR``)."""
    from gofr_tpu.fleet import trace as fleet_trace
    from gofr_tpu.telemetry import format_hop, parse_hop, sanitize_request_id

    n = int(os.environ.get("BENCH_TRACE_ROUNDS", "2000"))
    start = time.perf_counter()
    for i in range(n):
        rid = sanitize_request_id(f"req-bench-{i:08d}")
        hop = format_hop("router-0", i % 3, 0)
        parsed = parse_hop(hop)
        if rid is None or parsed is None:
            raise RuntimeError("hop stamp round-trip failed")
    stamp_us = (time.perf_counter() - start) / n * 1e6
    route = {
        "request_id": "req-bench", "router_id": "router-0",
        "ts": 1000.0, "method": "POST", "path": "/v1/completions",
        "tenant": "t0", "status": 200, "outcome": "ok", "retries": 2,
        "resumes": 1, "stream": True, "resumable": True, "role": "decode",
        "kv_donor": "r0", "elapsed_ms": 180.0,
        "attempts": [
            {"replica": "r1", "status": 503, "error": "saturated",
             "elapsed_ms": 12.0},
            {"replica": "r2", "status": 0, "error": "timeout",
             "elapsed_ms": 30.0},
            {"replica": "r3", "status": 200, "error": None,
             "elapsed_ms": 120.0},
        ],
    }
    flights = {
        "r3": [{
            "request_id": "req-bench",
            "origin": {"router": "router-0", "attempt": 2, "resume_from": 0},
            "queue_wait_s": 0.004, "ttft_s": 0.021, "status": 200,
        }],
    }
    transfers = [{
        "replica": "r3", "side": "receiver", "donor": "r0",
        "outcome": "ok", "request_id": "req-bench", "elapsed_ms": 3.0,
    }]
    start = time.perf_counter()
    for _ in range(n):
        timeline = fleet_trace.assemble(
            "req-bench", route, flights=flights, transfers=transfers,
        )
    assemble_us = (time.perf_counter() - start) / n * 1e6
    if timeline["partial"] or timeline["latency"]["stream_ms"] is None:
        raise RuntimeError(f"bench timeline did not assemble fully: {timeline}")
    return {
        "rounds": n,
        "stamp_us": round(stamp_us, 3),
        "assemble_us": round(assemble_us, 2),
    }


def _measure_recovery() -> dict:
    """Recovery MTTR, measured for real: boot an in-process echo
    engine, wedge a dispatch on a latch, let the watchdog walk
    degraded → wedged and the recovery supervisor rebuild back to
    serving — and stamp the wedge→serving wall time plus the recovery
    counts into the artifact. The watchdog deadline dominates (the
    detection half of MTTR); the rebuild is the repair half."""
    import threading

    from gofr_tpu.config import EnvConfig
    from gofr_tpu.logging import Level
    from gofr_tpu.metrics import Registry
    from gofr_tpu.testutil import MockLogger
    from gofr_tpu.tpu.device import new_device

    watchdog_s = float(os.environ.get("BENCH_RECOVERY_WATCHDOG_S", "0.1"))
    overrides = {
        "MODEL_NAME": "echo",
        "WATCHDOG_DISPATCH_TIMEOUT_S": str(watchdog_s),
        "RECOVERY_BACKOFF_S": "0.05",
        "TIMEBASE_ENABLED": "off",
    }
    old = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        device = new_device(EnvConfig(), MockLogger(Level.FATAL), Registry())
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
    release = threading.Event()
    try:
        device.runner.stall_hook = lambda: release.wait(30)
        wedge_start = time.perf_counter()

        def kick() -> None:
            try:
                device.generate([9], max_new_tokens=2)
            except Exception:
                pass  # the wedged dispatch fails by design

        kicker = threading.Thread(target=kick, name="bench-wedge-kick")
        kicker.start()
        deadline = time.monotonic() + 30
        while not device.recovery.snapshot()["recoveries"].get("recovered"):
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"recovery did not complete: {device.recovery.snapshot()}"
                )
            time.sleep(0.01)
        wall = time.perf_counter() - wedge_start
        release.set()
        kicker.join(10)
        snap = device.recovery.snapshot()
        return {
            "watchdog_timeout_s": watchdog_s,
            # wedge->serving as the supervisor measured it (wedged
            # transition to serving transition)
            "mttr_s": snap["last_mttr_s"],
            # stall-injection->serving as the bench saw it (includes
            # the watchdog's detection window)
            "stall_to_serving_s": round(wall, 3),
            "attempts": snap["attempts"],
            "recoveries": snap["recoveries"],
        }
    finally:
        release.set()
        device.close()


def _scrape_engine(base: str) -> dict:
    """ONE GET /admin/engine snapshot ({} when unreachable) — every
    field the artifact wants (state, kv_blocks, mesh) comes from this
    single fetch."""
    try:
        with urllib.request.urlopen(base + "/admin/engine", timeout=10) as r:
            return json.loads(r.read()).get("data") or {}
    except Exception:
        return {}


def _scrape_engine_state(base: str) -> "str | None":
    """The engine state machine's verdict (when reachable): the emitted
    artifact then says whether the run ended serving or degraded/wedged."""
    return (_scrape_engine(base).get("engine") or {}).get("state")


def _scrape_mfu(base: str, model: str, op: str) -> float | None:
    """Read the device-maintained MFU gauge off /metrics."""
    return _scrape_gauge(base, f'gofr_tpu_mfu{{model="{model}",op="{op}"}}')


def _scrape_gauge(base: str, needle: str) -> float | None:
    try:
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        for line in text.splitlines():
            if line.startswith(needle):
                return round(float(line.rsplit(" ", 1)[1]), 4)
    except Exception:
        pass
    return None


def _measure_decode(post, n_streams: int, prompt_len: int, n_tokens: int) -> float:
    """Aggregate tokens/sec over n_streams concurrent generations, each a
    real POST /generate through the HTTP server (continuous-batching pool
    underneath)."""
    payloads = [
        json.dumps({
            "tokens": [(11 * (i + s)) % 150 + 1 for i in range(prompt_len)],
            "max": n_tokens,
        }).encode()
        for s in range(n_streams)
    ]
    # warm the /generate path (chunk shapes + pool already compiled at boot)
    post("/generate", json.dumps({"tokens": [3, 7, 11, 2], "max": 8}).encode())
    counts = [0] * n_streams
    failures: list[str] = []

    def worker(i):
        try:
            counts[i] = post("/generate", payloads[i], timeout=600)[1]["data"]["n"]
        except Exception as exc:
            failures.append(f"stream {i}: {_describe_http_error(exc)}")

    threads = [
        threading.Thread(target=worker, args=(i,), name=f"bench-stream-{i}")
        for i in range(n_streams)
    ]
    start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - start
    if failures:
        # a silently-deflated tok/s is worse than an error: fail the phase
        raise RuntimeError(
            f"{len(failures)}/{n_streams} decode streams failed: {failures[:3]}"
        )
    return round(sum(counts) / wall, 1)


if __name__ == "__main__":
    sys.exit(main())
