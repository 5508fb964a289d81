"""One run of one cell of the benchmark.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip: it checks that the device is a TPU in the
peaks table (there is no CPU fallback; ``--rehearse`` is the separately
named CPU rehearsal the tests use, and it prints no metric), has the
configuration's architecture (``benchmark/architectures/<name>.py``) register
it and make the weights from the seed, boots the server
in-process the way ``chip_smoke.py`` does with only the cell's buckets and
rows warmed, starts the load generator as a child process that never
imports JAX, lets it offer a short ramp of the schedule and then the window,
and after the window compares every token the window's finished requests
were served with the plain reference. The last line of stdout is the result object; everything
else goes to stderr or to ``benchmark/out/``.

Exit codes: 0 a result was printed; 2 no TPU / unknown device kind / too few
chips; 3 set-up failed; 4 something compiled inside the window; 5 the run
could not be reduced to its metrics.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, as near as Python lets us read it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402

from benchmark import metrics as M  # noqa: E402
from benchmark import spec  # noqa: E402
from benchmark.traffic import build_schedule, rng_for  # noqa: E402

OUT_DIR = os.path.join(spec.HERE, "out")
REHEARSAL_MANIFEST = os.path.join(spec.HERE, "fixtures", "rehearse", "BENCHMARK.json")
HARD_LIMIT_S = 340.0  # the driver allows a warm run 360 s
COLD_LIMIT_S = 1150.0  # and a cell's first run in a checkout 1200 s
READER_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}
BAD_STATES = ("wedged", "recovering", "degraded", "failed")
SERVER_ENV = {
    "GEN_STOP_EOS": "off",  # seeded weights: a chance EOS must not cut a count
    "TPU_BOOT": "background",  # the server listens first; boot shows on /ready
    "LOG_LEVEL": "WARN",
    "PREFIX_CACHE": "0",  # no two prompts share a prefix
    # both rings hold 512 by default and a window overruns them
    "FLIGHT_RECORDER_SIZE": "20000", "DISPATCH_TIMELINE_SIZE": "100000",
}


class RunFailure(Exception):
    def __init__(self, code: int, what: str):
        super().__init__(what)
        self.code = code


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T0:7.2f}s] {msg}", file=sys.stderr, flush=True)


class Run:
    """What the metric readers see of one run (plain attributes)."""

    def __init__(self) -> None:
        self.manifest = self.cell = self.cfg = self.mix = self.load = None
        self.arch = None  # the configuration's architecture module (spec.py)
        self.log = log
        self.seed = 0
        self.seconds = 0.0
        self.trace_on = False
        self.records: list[dict] = []  # every request the generator sent
        self.measured: list[dict] = []  # those that count in this window
        self.w0 = self.w1 = self.deadline = 0.0  # monotonic
        self.wall0 = self.wall1 = 0.0  # the same instants on time.time()
        self.setup_s = 0.0
        self.flights: list[dict] = []  # FlightRecords begun in the window
        self.dispatches: list[dict] = []  # DispatchRecords begun in the window
        self.trace: dict | None = None  # trace_reduce.reduce_trace's result
        self.peaks: dict | None = None
        self.sizes: dict = {}
        self.server_env: dict = {}
        self.device: dict = {}
        self.window_compiles: list[str] = []  # must stay empty


# -- the server, booted the way chip_smoke.py boots it --------------------------

def http_get(base: str, path: str, timeout: float = 10.0):
    try:
        with urllib.request.urlopen(base + path, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as exc:
        status, raw = exc.code, exc.read()
    text = raw.decode("utf-8", "replace")
    try:
        return status, json.loads(text)
    except ValueError:
        return status, text


def host_port(base: str) -> tuple[str, int]:
    host, port = base[len("http://"):].rsplit(":", 1)
    return host, int(port)


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def boot(run: Run, deadline: float):
    import gofr_tpu
    from gofr_tpu.openai import register_openai_routes

    os.environ.update(SERVER_ENV)
    os.environ.update(run.server_env)
    os.environ["HTTP_PORT"] = str(free_port())
    app = gofr_tpu.new()
    if app.container.tpu is None:  # container.py logs and swallows a wiring error
        raise RunFailure(3, "the TPU datasource failed to wire")
    register_openai_routes(app)
    app.start()
    base = f"http://127.0.0.1:{app.http_port}"
    last = None
    while True:
        try:
            status, state = http_get(base, "/.well-known/ready")
        except OSError:
            status, state = 0, {"state": "starting"}
        if status == 200:
            return app, base
        state = state if isinstance(state, dict) else {"state": str(state)}
        detail = state.get("detail") or state.get("state")
        if state.get("state") == "failed":
            raise RunFailure(3, f"boot failed: {detail}")
        if detail != last:
            log(f"boot: {detail}")
            last = detail
        if time.monotonic() > deadline:
            raise RunFailure(3, f"not ready in time (at: {detail})")
        time.sleep(0.25)


def peak_bytes(devices) -> int:
    """Peak device memory so far, on the fullest chip."""
    return max(((d.memory_stats() or {}).get("peak_bytes_in_use") or 0) for d in devices)


def compiles_total(base: str) -> float:
    status, text = http_get(base, "/metrics")
    if status != 200 or not isinstance(text, str):
        raise RunFailure(5, f"GET /metrics -> {status}")
    return sum(
        float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
        if line.startswith("gofr_tpu_compiles_total{")
    )


# -- the window -----------------------------------------------------------------

def warm_requests(run: Run, base: str, model: str, vocab: int, app=None) -> None:
    """Streamed requests before the ramp, in two rounds, so that whatever
    the server builds lazily is built in set-up. First the mix's prompt
    lengths at once (six quantiles, the last the longest): a first cohort
    at every bucket. Then as many at once as the window can have in flight
    when the pool is full (two more than its slots, or the cell's clients
    if those are more), short prompts and answers long enough that the
    first still decodes when the last has prefilled: the pool refuses two
    and they take the solo decode path."""
    import asyncio

    from benchmark.loadgen import one_request
    from benchmark.traffic import quantile_lengths

    slots = int(run.server_env.get("DECODE_SLOTS", run.server_env.get("BATCH_MAX_SIZE", "8")))
    lengths = quantile_lengths(run.mix["prompt_tokens"], 6)
    lengths[-1] = run.mix["prompt_tokens"].get("max", lengths[-1])
    shortest = run.mix["prompt_tokens"].get("min", lengths[0])
    burst = max(slots + 2, int(run.load.get("clients", 0)))
    rng = rng_for(run.seed, "warm")
    host, port = host_port(base)
    telemetry = getattr(getattr(app, "container", None), "telemetry", None)

    def solo() -> int:
        if telemetry is None:
            return 0
        return sum(1 for r in telemetry.records(limit=1 << 30) if r.get("pool_reject_reason"))

    def send(plan: list[tuple[int, int]]) -> None:
        async def go():
            now = time.monotonic()
            return await asyncio.gather(*(
                one_request(host, port, model, {
                    "id": -1 - i, "max_tokens": n_out,
                    "prompt": rng.integers(3, vocab, n_in).tolist(),
                }, now, now + 120.0)
                for i, (n_in, n_out) in enumerate(plan)
            ))

        for rec in asyncio.run(go()):
            if M.is_failed(rec):
                raise RunFailure(3, f"a warm-up request failed: {rec['error']}")

    send([(n_in, 20) for n_in in lengths])
    n_out = 24 + 8 * slots
    send([(shortest, n_out)] * burst)
    log(f"warm-up: {len(lengths)} prompt lengths, then {burst} at once with {n_out} tokens "
        f"each; {solo()} decoded solo after a pool refusal")


def drive_window(run: Run, base: str, model: str, schedule: dict, compiled: list) -> None:
    """Start the load generator, wait out ramp, window and drain, and fill
    ``run`` with the records and the window's marks."""
    tag = f"{run.cell['name']}.{run.seed}.{int(run.trace_on)}"
    sched_path = os.path.join(OUT_DIR, tag + ".schedule.json")
    result_path = os.path.join(OUT_DIR, tag + ".records.json")
    if os.path.exists(result_path):
        os.remove(result_path)
    host, port = host_port(base)
    drain_s = float(run.mix.get("drain_s", 30.0))
    t0 = time.monotonic() + 2.0  # the child needs a moment to start and read
    schedule.update({"t0": t0, "host": host, "port": port, "model": model,
                     "drain_s": drain_s})
    with open(sched_path, "w", encoding="utf-8") as fh:
        json.dump(schedule, fh)
    run.w0 = t0 + schedule["ramp_s"]
    run.w1 = run.w0 + run.seconds
    run.deadline = run.w1 + drain_s
    run.setup_s = run.w0 - T0
    trace_dir = None
    child = subprocess.Popen(
        [sys.executable, "-m", "benchmark.loadgen", sched_path, result_path],
        cwd=spec.ROOT, stdout=sys.stderr, stderr=sys.stderr,
    )
    try:
        time.sleep(max(0.0, run.w0 - time.monotonic()))
        run.wall0 = time.time()  # gofrlint: wall-clock — the program's records carry wall start_ts
        before = (compiles_total(base), len(compiled))
        log(f"window opens (setup_s {run.setup_s:.2f})")
        trace_dir = trace_capture(run, tag) if run.trace_on and run.peaks is not None else None
        time.sleep(max(0.0, run.w1 - time.monotonic()))
        run.wall1 = run.wall0 + run.seconds
        after = (compiles_total(base), len(compiled))
        log("window closes; draining")
        child.wait(timeout=drain_s + 60.0)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0 or not os.path.exists(result_path):
        raise RunFailure(5, f"the load generator exited {child.returncode}")
    run.window_compiles = compiled[before[1]:after[1]]
    if after[0] != before[0]:
        run.window_compiles.append(f"gofr_tpu_compiles_total +{after[0] - before[0]}")
    run.records = spec.load_json(result_path)["records"]
    by_id = {r["id"]: r for r in schedule["requests"]}
    if schedule["loop"] == "open":
        run.measured = [r for r in run.records if by_id[r["id"]]["measured"]]
    else:  # closed: what was sent inside the window
        run.measured = [r for r in run.records
                        if r["sent"] is not None and run.w0 <= r["sent"] < run.w1]
    os.remove(sched_path)  # prompts are large; the seed makes them again
    if trace_dir is not None:
        reduce_capture(run, trace_dir, tag)


def trace_capture(run: Run, tag: str) -> str:
    """Trace ``trace_s`` seconds from one second into the window."""
    import shutil

    import jax

    trace_dir = os.path.join(OUT_DIR, tag + ".trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    time.sleep(max(0.0, run.w0 + 1.0 - time.monotonic()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    time.sleep(float(run.mix.get("trace_s", 4.0)))
    jax.profiler.stop_trace()
    return trace_dir


def reduce_capture(run: Run, trace_dir: str, tag: str) -> None:
    import shutil

    from benchmark import trace_reduce

    start = time.monotonic()
    data = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    run.trace = trace_reduce.reduce_trace(data)
    if os.environ.get("BENCH_DESCRIBE_TRACE"):
        with open(os.path.join(OUT_DIR, tag + ".trace.txt"), "w", encoding="utf-8") as fh:
            fh.write("\n".join(trace_reduce.describe(data, 25)))
    if not os.environ.get("BENCH_KEEP_TRACE"):
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"trace reduced in {time.monotonic() - start:.1f}s: window "
        f"{run.trace['window_s']:.3f}s busy {run.trace['busy_s']:.3f}s")


def collect_program_records(run: Run, app) -> None:
    """FlightRecords and DispatchRecords begun inside the window."""
    inside = lambda r: r.get("start_ts") is not None and run.wall0 <= r["start_ts"] < run.wall1  # noqa: E731
    telemetry = getattr(app.container, "telemetry", None)
    if telemetry is not None:
        run.flights = [r for r in telemetry.records(limit=1 << 30) if inside(r)]
    timeline = getattr(app.container.tpu, "timeline", None)
    if timeline is not None:
        run.dispatches = [r for r in timeline.records(limit=1 << 30) if inside(r)]


def log_requests(run: Run) -> int:
    """The earlier lines: counts, generator lateness, the tails that are not
    judged. -> how many of the window's requests failed."""
    failed = [r for r in run.measured if M.is_failed(r)]
    late = [M.late_s(r) for r in run.measured]
    log(f"requests: attempted {len(run.measured)}, failed {len(failed)}; generator late "
        f"p50 {M.percentile(late, 50) * 1e3:.2f} ms p99 {M.percentile(late, 99) * 1e3:.2f} ms")
    for r in failed[:5]:
        log(f"  failed request {r['id']}: {r['error']} ({len(r['tokens'])}/{r['asked']} tokens)")
    ttfts = [M.ttft_s(r, run.deadline) for r in run.measured]
    tpots = [t for t in (M.tpot_s(r) for r in run.measured) if t is not None] or [0.0]
    log(f"ttft ms mean {sum(ttfts) / len(ttfts) * 1e3:.1f} p50 {M.percentile(ttfts, 50) * 1e3:.1f} "
        f"p90 {M.percentile(ttfts, 90) * 1e3:.1f} max {max(ttfts) * 1e3:.1f}; tpot ms mean "
        f"{(M.tpot_mean_s(run.measured) or 0.0) * 1e3:.2f} p50 {M.percentile(tpots, 50) * 1e3:.2f} "
        f"p90 {M.percentile(tpots, 90) * 1e3:.2f}; tokens in window "
        f"{M.tokens_in_window(run.records, run.w0, run.w1)}; longest silence "
        f"{(M.longest_silence_s(run.records, run.w0, run.w1) or 0.0) * 1e3:.0f} ms")
    return len(failed)


def log_dispatches(run: Run) -> None:
    by_kind: dict[str, list[float]] = {}
    for d in run.dispatches:
        if d["duration_s"] is not None:
            by_kind.setdefault(d["kind"], []).append(d["duration_s"] * 1e3)
    for kind, ms in sorted(by_kind.items()):
        log(f"dispatch {kind}: {len(ms)} in the window, ms p50 {M.percentile(ms, 50):.1f} "
            f"p90 {M.percentile(ms, 90):.1f}")
    rejects: dict[str, int] = {}
    for r in run.flights:
        if r.get("pool_reject_reason"):
            rejects[r["pool_reject_reason"]] = rejects.get(r["pool_reject_reason"], 0) + 1
    log(f"flights {len(run.flights)}, pool rejects {rejects}")


# -- correct ----------------------------------------------------------------------

def check_correct(run: Run, schedule_requests: dict, control: str | None) -> tuple[bool, list[dict]]:
    """Every request the window finished, every token it was served, against
    the plain reference. Prints each number beside its limit."""
    from benchmark.reference import served_gaps

    check = dict(run.mix["check"], **run.load["check"])
    limits = {"served_gap_mean": float(check["served_gap_mean_limit"]),
              "served_gap_max": float(check["served_gap_max_limit"])}
    done = sorted((r for r in run.measured if not M.is_failed(r)), key=lambda r: r["id"])
    if not done:
        log("check: no finished request to compare")
        return False, [{"name": n, "value": None, "limit": v} for n, v in limits.items()]
    pairs = [(schedule_requests[r["id"]]["prompt"], r["tokens"]) for r in done]
    start = time.monotonic()
    got = served_gaps(run.arch.logits_at, run.seed, run.cfg, pairs, check["widths"],
                      check["rows"], check["scored"], control=control)
    took = time.monotonic() - start
    worst = done[int(got["sample"][int(got["gaps"].argmax())])]
    log(f"check: {got['gaps'].size} served tokens of {len(done)} requests, {got['agree']:.4f} are "
        f"the reference's best; the widest gap is in request {worst['id']} "
        f"({worst['n_prompt']}+{len(worst['tokens'])}); the reference took {took:.2f}s")
    numbers, ok = [], True
    for name, value in (("served_gap_mean", float(got["gaps"].mean())),
                        ("served_gap_max", float(got["gaps"].max()))):
        good = value <= limits[name]
        ok = ok and good
        numbers.append({"name": name, "value": value, "limit": limits[name]})
        log(f"check: {name} {value:.6f} limit {limits[name]} -> {'ok' if good else 'NOT CORRECT'}")
    numbers[0].update(tokens=int(got["gaps"].size), requests=len(done),
                      agree_share=got["agree"], seconds=took)
    if control:
        for name, value in ((f"control_{control}_gap_mean", float(got["control_gaps"].mean())),
                            (f"control_{control}_gap_max", float(got["control_gaps"].max()))):
            limit = limits[name.replace(f"control_{control}", "served")]
            numbers.append({"name": name, "value": value, "limit": limit})
            log(f"check: {name} {value:.6f} limit {limit} -> "
                f"{'fails' if value > limit else 'passes'}")
    return ok, numbers


# -- main ---------------------------------------------------------------------------

def read_metrics(run: Run, section: str) -> dict:
    out = {}
    for decl in spec.metrics_of_cell(run.manifest, run.cell["name"], section):
        value = spec.load_module(READER_DIRS[section], decl["name"]).read(run)
        if value is None:
            continue  # a reader that finds nothing to read reports nothing
        out[decl["name"]] = {"value": float(value), "unit": decl["unit"]}
    return out


def prepare(args):
    """Everything before the server boots: files, compile cache, device
    check, configuration and weights registered. -> (run, devices,
    compiled, deadline, model); ``compiled`` grows by one name per XLA
    compile (or cache load) in this process."""
    rehearse = bool(args.rehearse)
    manifest = spec.load_manifest(args.rehearse)
    run = Run()
    run.manifest, run.seed, run.seconds = manifest, args.seed, float(args.seconds)
    run.trace_on = bool(args.trace)
    run.cell = spec.find_cell(manifest, args.workload)
    run.cfg = spec.load_config(manifest, run.cell["config"])
    run.mix = spec.load_mix(manifest, run.cell["traffic"])
    run.load = spec.load_cell_load(manifest, run.cell["name"])
    os.makedirs(OUT_DIR, exist_ok=True)

    # the compile cache: where the caller says, else a fixed path in the
    # checkout (the path is part of the cache's key); the program takes it
    cache_dir = os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(spec.ROOT, ".jax_cache"))
    try:
        cold = not os.listdir(cache_dir)
    except OSError:
        cold = True
    deadline = T0 + getattr(args, "limit_s", COLD_LIMIT_S if cold else HARD_LIMIT_S)
    killer = threading.Timer(deadline - time.monotonic() + 5.0, lambda: os._exit(3))
    killer.daemon = True
    killer.start()
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    if rehearse:  # a CPU cache hit only prints loader warnings
        jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiled: list[str] = []

    def on_duration(event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            # the name, and where in the program it was asked for
            where = [f"{os.path.basename(f.filename)}:{f.lineno}"
                     for f in traceback.extract_stack(limit=40)
                     if "gofr_tpu" in f.filename or "benchmark" in f.filename]
            compiled.append(f"{kw.get('fun_name', '?')} @ {'>'.join(where[-6:])}")

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    run.device = {"platform": platform, "kind": kind, "count": len(devices)}
    if not rehearse:
        peaks = spec.load_json(os.path.join(spec.HERE, "peaks.json"))
        if platform != "tpu":
            raise RunFailure(2, f"JAX found platform {platform!r}, not a TPU")
        if kind not in peaks:
            raise RunFailure(2, f"device kind {kind!r} is not in benchmark/peaks.json")
        if len(devices) < run.cell["chips"]:
            raise RunFailure(2, f"the cell needs {run.cell['chips']} chips, JAX has {len(devices)}")
        run.peaks = peaks[kind]
    log(f"device: {platform} / {kind} x{len(devices)}; cache {cache_dir} "
        f"({'cold' if cold else 'warm'})")

    run.arch = spec.load_architecture(manifest, run.cfg)
    run.sizes = run.arch.sizes_of(run.cfg)
    serving = run.cfg["serving"]
    run.server_env = dict(serving["env"])
    run.server_env.update(run.load.get("env", {}))
    for pair in args.env or ():  # a control run's override, e.g. MODEL_KV_DTYPE=f8
        key, _, value = pair.partition("=")
        run.server_env[key] = value
    model = run.arch.register(run)
    run.server_env["MODEL_NAME"] = model
    run.server_env["MODEL_QUANT"] = serving["quant"]
    return run, devices, compiled, deadline, model


def execute(args, out) -> int:
    run, devices, compiled, deadline, model = prepare(args)
    rehearse = bool(args.rehearse)
    schedule = build_schedule(run.mix, run.load, run.sizes["vocab"], run.seed, run.seconds)
    requests_by_id = {r["id"]: r for r in schedule["requests"]}

    import jax

    app, base = boot(run, deadline)
    stopped = False
    try:
        engine = http_get(base, "/admin/engine")[1]["data"]
        log("ready: " + ", ".join(f"{s['stage']} {s['seconds']}" for s in engine["boot_timeline"]))
        log(f"device memory peak after boot {peak_bytes(devices) / 1e9:.2f} GB")
        warm_requests(run, base, model, run.sizes["vocab"], app)
        log(f"device memory peak after warm-up {peak_bytes(devices) / 1e9:.2f} GB")
        drive_window(run, base, model, schedule, compiled)
        if run.window_compiles:
            raise RunFailure(4, f"compiled inside the window: {run.window_compiles[:20]}")
        collect_program_records(run, app)
        log_dispatches(run)
        stats = [d.memory_stats() or {} for d in devices]
        peak = peak_bytes(devices)
        run.device["memory_peak_bytes"] = peak
        engine = http_get(base, "/admin/engine")[1]["data"]
        history = [h["state"] for h in engine["engine"]["history"]]
        if engine["engine"]["state"] != "serving" or set(history) & set(BAD_STATES):
            raise RunFailure(5, f"engine is {engine['engine']['state']}, history {history}")

        failed = log_requests(run)
        in_use = max((s.get("bytes_in_use") or 0) for s in stats)
        log(f"device memory: peak {peak / 1e9:.2f} GB, in use after the window {in_use / 1e9:.2f} GB")
        # the program's numbers are read: stop it and free its device state,
        # so that the reference fits whatever the cell filled the chip with
        app.shutdown()
        stopped = True
        for array in jax.live_arrays():
            array.delete()
        ok, numbers = check_correct(run, requests_by_id, args.control)
        result = {
            "correct": bool(ok and failed == 0),
            "attempted": len(run.measured), "failed": failed,
            "metrics": {} if rehearse else read_metrics(
                run, "per_layer" if run.trace_on else "end_to_end"),
            "device": dict(run.device),
            "seed": run.seed, "workload": run.cell["name"],
        }
        if rehearse:
            result["rehearse"] = True
            result["counts"] = {
                "flights": len(run.flights), "dispatches": len(run.dispatches),
                "tokens_in_window": M.tokens_in_window(run.records, run.w0, run.w1),
            }
        if run.trace_on and run.trace is not None:
            result["device"]["busy_s"] = run.trace["busy_s"]
            result["device"]["window_s"] = run.trace["window_s"]
            result["breakdown"] = {"device_ops": run.trace["device_ops"],
                                   "idle_gaps": run.trace["idle_gaps"]}
        result["check"] = numbers  # each number compared beside its limit, last on the line
        print(json.dumps(result), file=out, flush=True)
    finally:
        if not stopped:
            try:
                app.shutdown()
            except Exception:  # a shutdown error must not hide the first one
                traceback.print_exc()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", nargs="?", const=REHEARSAL_MANIFEST, default=None,
                    metavar="MANIFEST",
                    help="CPU rehearsal of the control flow on the rehearsal manifest "
                         "(or a copy of it with files added)")
    ap.add_argument("--control", default=None,
                    help="also read the reference computed in this lower precision")
    ap.add_argument("--env", action="append",
                    help="KEY=VALUE for the server, for a control run of the program")
    args = ap.parse_args()
    # stdout is kept for the result line: whatever else this process prints
    # (the server logs to stdout) goes to stderr
    sys.stdout.flush()
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    try:
        return execute(args, out)
    except RunFailure as exc:
        log(f"FAILED ({exc.code}): {exc}")
        return exc.code
    except spec.SpecError as exc:
        log(f"FAILED (3): {exc}")
        return 3


if __name__ == "__main__":
    try:
        status = main()
    except SystemExit as exc:
        status = exc.code if isinstance(exc.code, int) else 1
    except BaseException:
        traceback.print_exc()
        status = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # hard exit: server and pool threads must neither print after the result
    # line nor keep a failed run alive; the child has been waited for
    os._exit(status)
