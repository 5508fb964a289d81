"""On-chip smoke: serve llama3-8b int8 on the local TPU through the normal
entry points and prove, each time it is asked, that the system still starts
and answers correctly there.

    python chip_smoke.py             # one TPU chip (or TPU_MESH=tp=4 on four)
    python chip_smoke.py --dry-run   # development: tiny model, CPU, interpret

One process; it starts no child that needs JAX. Stages, in order, each
failing the run on its first broken check (exit != 0, nothing on stdout):

1. device: ``jax.devices()`` must report a TPU whose ``device_kind`` is in
   the peaks table (gofr_tpu/tpu/flops.py) — checked before any model is
   built. Without ``--dry-run`` any other platform is an error, named.
2. kernels: the Pallas flash-attention family COMPILED for the device
   (``interpret=False``) — GQA 32/8 prefill, ragged ``sq=1`` decode over
   2,048 cached tokens, the fused backward — at head_dim 128, against the
   XLA reference. A lowering error is a failure.
3. boot: ``gofr_tpu.new()`` + ``register_openai_routes`` with
   ``TPU_BOOT=background``, readiness polled on ``/.well-known/ready``;
   ``MODEL_NAME=llama3-8b MODEL_QUANT=int8`` at all 32 layers and published
   widths, seeded random weights quantized during init.
4. evidence: the prefill and pooled-decode jit functions the server holds
   lower, at the shapes just warmed, to programs containing the Mosaic
   custom call — neither the XLA attention path nor interpret mode stood in.
5. requests over HTTP to its own port: ``/v1/completions`` plain and SSE,
   short / mid / longer-than-the-top-bucket prompts, two in flight at once,
   several decode chunks each, one exact repeat (prefix hit, paged gather)
   whose greedy tokens must equal the first answer's.
6. verdict: every request 200 with the token count asked for; zero XLA
   compiles and an unchanged ``gofr_tpu_compiles_total`` across the request
   window; finished ``prefill`` / ``prefill_chunk`` / ``decode_chunk``
   dispatches, a cohort and a pool chunk of more than one row; an engine
   that is ``serving`` with no wedged/recovering/degraded/failed in its
   history.

stdout carries two lines and nothing else (the server's logs are sent to
stderr), both JSON objects, and only when every stage held: the report (model, versions, compile cache, boot timeline, requests,
dispatches, evidence, HBM), then LAST the verdict the driver reads,
``{"ok": true, "device": {"platform", "kind", "count"}}`` with the device as
JAX reports it. Sizes are cut in rows and buckets to fit one 16 GB chip
beside ~8.6 GB of int8 weights (the pooled decode executable holds a second
copy of its slot cache as scratch), never in a width or a layer. Times in
the report are set-up times, not speed claims.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150.0  # the driver allows 1200 s, compilation included
BAD_STATES = ("wedged", "recovering", "degraded", "failed")

# model + request sizes: the chip run, and the same run cut to a tiny size
# for --dry-run (CPU, interpret mode). MODEL_MAX_SEQ 2048 is the smallest
# cache at which sq=1 decode takes the ragged Pallas kernel
# (ops/attention.py::_pallas_ok); the long prompt exceeds the top bucket so
# it prefills chunked.
CHIP = {
    "env": {
        "MODEL_NAME": "llama3-8b", "MODEL_QUANT": "int8",
        "MODEL_MAX_SEQ": "2048", "MODEL_BUCKETS": "256,1024",
        "BATCH_MAX_SIZE": "2", "DECODE_SLOTS": "4", "PREFIX_CACHE": "2",
    },
    "short": 40, "mid": 600, "long": 1500, "max_tokens": 20,
    "prefill": dict(b=2, s=1024, hq=32, hkv=8),
    "decode": dict(b=8, skv=2048, hq=32, hkv=8),
    "bwd": dict(b=1, s=512, hq=8, hkv=2),
}
DRY = {
    "env": {
        "MODEL_NAME": "tiny", "MODEL_QUANT": "int8",
        "MODEL_MAX_SEQ": "128", "MODEL_BUCKETS": "16,32",
        "BATCH_MAX_SIZE": "2", "DECODE_SLOTS": "4", "PREFIX_CACHE": "2",
    },
    "short": 10, "mid": 28, "long": 70, "max_tokens": 20,
    "prefill": dict(b=1, s=256, hq=4, hkv=1),
    "decode": dict(b=2, skv=256, hq=4, hkv=1),
    "bwd": dict(b=1, s=128, hq=2, hkv=1),
}
SERVER_ENV = {
    "TOKENIZER": "byte",  # no tokenizer file; native lib built on demand
    "GEN_STOP_EOS": "off",  # random weights: a chance EOS must not cut a count
    "TPU_BOOT": "background",  # server listens first; boot observable on /ready
    "BATCH_TIMEOUT_MS": "100",  # two requests fired together form one cohort
}


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


# -- stage 2: kernels ---------------------------------------------------------

def kernel_stage(sizes: dict, interpret: bool) -> dict:
    """Forward (prefill GQA, ragged decode) and fused backward of the flash
    kernels against ``flash._reference``, normalized max error per case."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.ops.flash import _reference, flash_attention

    d = 128  # the only head size ops/attention.py::_pallas_ok admits
    scale = d ** -0.5
    rng = np.random.default_rng(0)

    def mk(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    def reference(q, k, v, offs, lens):
        with jax.default_matmul_precision("highest"):
            return _reference(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), offs, lens, True, scale,
            )

    def norm_err(got, want) -> float:
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        require(bool(np.isfinite(got).all()), "kernel output is not finite")
        return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))

    def prefill():
        p = sizes["prefill"]
        q = mk(p["b"], p["s"], p["hq"], d)
        k, v = mk(p["b"], p["s"], p["hkv"], d), mk(p["b"], p["s"], p["hkv"], d)
        out = flash_attention(q, k, v, causal=True, interpret=interpret)
        zeros = jnp.zeros((p["b"],), jnp.int32)
        return norm_err(out, reference(q, k, v, zeros, zeros + p["s"]))

    def ragged_decode():
        p = sizes["decode"]
        q = mk(p["b"], 1, p["hq"], d)
        k, v = mk(p["b"], p["skv"], p["hkv"], d), mk(p["b"], p["skv"], p["hkv"], d)
        # rows at different depths of the cache, the last one full
        offs = jnp.asarray(
            np.linspace(3, p["skv"] - 1, p["b"]).astype(np.int32)
        )
        out = flash_attention(
            q, k, v, causal=True, q_offset=offs, kv_lens=offs + 1,
            interpret=interpret,
        )
        return norm_err(out, reference(q, k, v, offs, offs + 1))

    def fused_backward():
        p = sizes["bwd"]
        q = mk(p["b"], p["s"], p["hq"], d)
        k, v = mk(p["b"], p["s"], p["hkv"], d), mk(p["b"], p["s"], p["hkv"], d)
        zeros = jnp.zeros((p["b"],), jnp.int32)

        def loss_flash(q_, k_, v_):
            return jnp.sum(flash_attention(
                q_, k_, v_, causal=True, interpret=interpret
            ).astype(jnp.float32))

        def loss_ref(q_, k_, v_):
            return jnp.sum(reference(q_, k_, v_, zeros, zeros + p["s"]))

        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        return max(norm_err(g, w) for g, w in zip(got, want))

    cases = {}
    for name, fn in (("prefill_gqa", prefill), ("ragged_decode", ragged_decode),
                     ("fused_backward", fused_backward)):
        start = time.perf_counter()
        err = fn()  # a Mosaic lowering error propagates: that IS a failure
        cases[name] = {"max_err": round(err, 5),
                       "seconds": round(time.perf_counter() - start, 2)}
        log(f"kernel {name}: err {err:.4f} in {cases[name]['seconds']}s")
        require(err < 2e-2, f"kernel {name} off its reference: {err:.4f}")
    return {"interpret": interpret, "head_dim": d, "cases": cases}


# -- HTTP helpers -------------------------------------------------------------

def http(base: str, path: str, body: dict | None = None, timeout: float = 120.0):
    """-> (status, parsed JSON or raw text). Non-2xx does not raise."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        base + path, data=data, headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as exc:
        status, raw = exc.code, exc.read()
    text = raw.decode("utf-8", "replace")
    try:
        return status, json.loads(text)
    except ValueError:
        return status, text


def admin(base: str, path: str) -> dict:
    status, body = http(base, path)
    require(status == 200, f"GET {path} -> {status}: {str(body)[:300]}")
    return body["data"]


def compiles_total(base: str) -> float:
    """Sum of ``gofr_tpu_compiles_total`` over its kinds, off /metrics."""
    status, text = http(base, "/metrics")
    require(status == 200 and isinstance(text, str), f"GET /metrics -> {status}")
    return sum(
        float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
        if line.startswith("gofr_tpu_compiles_total{")
    )


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


# -- stage 3: boot ------------------------------------------------------------

def boot(sizes: dict, deadline: float):
    """Boot the app the way a user does; returns (app, base, engine snapshot)."""
    os.environ.update(sizes["env"])
    os.environ.update(SERVER_ENV)
    os.environ["HTTP_PORT"] = str(free_port())
    os.environ.setdefault("LOG_LEVEL", "WARN")

    import gofr_tpu
    from gofr_tpu.openai import register_openai_routes

    app = gofr_tpu.new()
    # container.py logs and swallows a wiring error: None means it failed
    require(app.container.tpu is not None, "TPU datasource failed to wire")
    register_openai_routes(app)
    app.start()
    base = f"http://127.0.0.1:{app.http_port}"
    last = None
    while True:
        try:
            status, state = http(base, "/.well-known/ready", timeout=10)
        except OSError:  # the listener thread is not accepting yet
            status, state = 0, {"state": "starting"}
        if status == 200:
            break
        state = state if isinstance(state, dict) else {"state": str(state)}
        detail = state.get("detail") or state.get("state")
        require(state.get("state") != "failed", f"boot failed: {detail}")
        if detail != last:
            log(f"boot: {detail}")
            last = detail
        require(time.monotonic() < deadline, f"not ready in time (at: {detail})")
        time.sleep(1.0)
    return app, base, admin(base, "/admin/engine")


# -- stage 4: compiled-kernel evidence ----------------------------------------

def pallas_evidence(tpu) -> dict:
    """Count Mosaic custom calls in what the server's OWN jit functions
    lower to at the shapes it just warmed. The attention path is chosen at
    trace time from the backend and the shapes (ops/attention.py), so the
    lowering of the same function at the same avals is the program the
    warmed executable was compiled from: XLA attention lowers to no custom
    call, interpret mode to plain HLO ops."""
    import jax
    import jax.numpy as jnp

    def avals(tree):
        # an uncommitted array (a fresh jnp.zeros) follows its co-arguments,
        # as it does when the server passes it
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=a.sharding if a.committed else None
            ),
            tree,
        )

    def mosaic_calls(jitted, *args) -> int:
        return jitted.lower(*args).as_text().count("tpu_custom_call")

    from gofr_tpu.tpu.batcher import next_pow2

    runner, pool = tpu.runner, tpu.decode_pool
    require(pool is not None, "decode pool is off")
    params = avals(runner.params)
    out = {}
    # the two prefill shapes served: chunked [1, top] and batched [b, top]
    for b in sorted({1, next_pow2(runner.max_batch)}):
        cache = avals(runner._zero_cache(b))
        tokens = jnp.zeros((b, runner.buckets[-1]), jnp.int32)
        lengths = jnp.ones((b,), jnp.int32)
        if runner._token_sharding is not None:
            tokens = jax.device_put(tokens, runner._token_sharding)
            lengths = jax.device_put(lengths, runner._row_sharding)
        out[f"prefill_b{b}"] = mosaic_calls(
            runner._prefill, params, tokens, cache, lengths
        )
    with pool._work:  # the worker donates the cache: read avals while idle
        pool_args = avals((pool._last_tokens, pool.cache, pool._key))
    n = pool.n_slots
    out["decode_pool"] = mosaic_calls(
        pool._decode, params, *pool_args,
        jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.int32),
        jnp.ones((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
    )
    return out


# -- stage 5: requests --------------------------------------------------------

def prompt(n: int, salt: int) -> str:
    """``n`` printable ASCII bytes (one byte-tokenizer id each), seeded."""
    return "".join(chr(33 + (7 * i + 13 * salt) % 90) for i in range(n))


def complete(base: str, text: str, max_tokens: int, logprobs: bool = False) -> dict:
    body = {"model": os.environ["MODEL_NAME"], "prompt": text,
            "max_tokens": max_tokens, "temperature": 0}
    if logprobs:
        body["logprobs"] = 1  # chosen-token values: the answer's fingerprint
    status, resp = http(base, "/v1/completions", body)
    require(status == 200, f"/v1/completions -> {status}: {str(resp)[:300]}")
    choice = resp["choices"][0]
    return {
        "tokens": resp["usage"]["completion_tokens"],
        "finish": choice["finish_reason"], "text": choice["text"],
        "logprobs": (choice.get("logprobs") or {}).get("token_logprobs"),
    }


def complete_sse(base: str, text: str, max_tokens: int) -> dict:
    body = {"model": os.environ["MODEL_NAME"], "prompt": text,
            "max_tokens": max_tokens, "temperature": 0, "stream": True,
            "stream_options": {"include_usage": True}}
    req = urllib.request.Request(
        base + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    frames, done = [], False
    with urllib.request.urlopen(req, timeout=120) as resp:
        require(resp.status == 200, f"SSE /v1/completions -> {resp.status}")
        for raw in resp:
            line = raw.decode("utf-8").strip()
            if not line.startswith("data:"):
                continue
            payload = line[5:].strip()
            if payload == "[DONE]":
                done = True
                break
            frames.append(json.loads(payload))
    require(done, "SSE stream ended without [DONE]")
    require(not any("error" in f for f in frames), f"SSE error frame: {frames[-1]}")
    usage = next(f["usage"] for f in reversed(frames) if f.get("usage"))
    finish = next(
        f["choices"][0]["finish_reason"] for f in reversed(frames)
        if f.get("choices") and f["choices"][0].get("finish_reason")
    )
    return {"tokens": usage["completion_tokens"], "finish": finish,
            "frames": len(frames)}


def drive_requests(base: str, sizes: dict) -> dict:
    """The request window. Returns per-request results; raises on a bad one."""
    n = sizes["max_tokens"]
    results: dict[str, dict] = {}
    failures: list[str] = []
    sent: list[str] = []

    def run(name: str, fn, *args) -> None:
        sent.append(name)
        try:
            results[name] = fn(base, *args)
        except Exception as exc:  # collected: sent/ok/failed is reported
            failures.append(f"{name}: {type(exc).__name__}: {exc}")

    def together(*calls) -> None:
        threads = [
            threading.Thread(target=run, args=call, name=f"smoke-{call[0]}")
            for call in calls
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            require(not t.is_alive(), f"request {t.name} did not return")

    first = prompt(sizes["short"], 1)
    run("short_plain", complete, first, n, True)
    # its exact repeat while the entry is still cached (PREFIX_CACHE is 2):
    # a prefix hit, served from paged blocks
    run("repeat_plain", complete, first, n, True)
    # same bucket, fired together: one prefill cohort of 2, two pool slots
    together(("pair_plain", complete, prompt(sizes["short"], 2), n),
             ("pair_sse", complete_sse, prompt(sizes["short"], 3), n))
    # the upper bucket beside a streamed short one
    together(("mid_plain", complete, prompt(sizes["mid"], 4), n),
             ("short_sse", complete_sse, prompt(sizes["short"], 5), n))
    # longer than the top bucket: chunked prefill
    run("long_plain", complete, prompt(sizes["long"], 6), n)

    require(not failures, "; ".join(failures))
    for name, res in results.items():
        require(res["tokens"] == n and res["finish"] == "length",
                f"{name}: {res['tokens']} tokens ({res['finish']}), asked {n}")
    a, b = results["short_plain"], results["repeat_plain"]
    require(a["logprobs"] and len(a["logprobs"]) == n
            and all(isinstance(x, float) and x <= 0.0 for x in a["logprobs"]),
            f"short_plain logprobs are not {n} finite values <= 0")
    require(a["text"] == b["text"] and a["logprobs"] == b["logprobs"],
            "the exact repeat (prefix hit) answered differently from the first")
    return {"sent": len(sent), "ok": len(results), "failed": len(failures)}


# -- main ---------------------------------------------------------------------

def versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu}


def run(dry_run: bool) -> dict:
    deadline = time.monotonic() + DEADLINE_S
    sizes = DRY if dry_run else CHIP
    cache_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = cache_env or os.path.join(REPO, ".jax_cache")
    try:  # counted before anything compiles
        cache_entries = len(os.listdir(cache_dir))
    except OSError:
        cache_entries = 0
    if dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported
    sys.path.insert(0, REPO)

    import jax

    # every backend compile (or persistent-cache load) in this process,
    # by function name: the request window must add none
    compiled: list[str] = []

    def on_duration(event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(str(kw.get("fun_name", "?")))

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    # -- stage 1: device, before any model is built ---------------------------
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not dry_run and platform != "tpu":
        raise SmokeFailure(
            f"JAX found platform={platform!r} (device_kind={kind!r}), not a "
            "TPU — the smoke runs on the chip; --dry-run is the CPU rehearsal"
        )
    from gofr_tpu.tpu.flops import device_peak_flops

    device_peak_flops(kind, platform)  # raises on a TPU kind with no peak
    result: dict = {
        "platform": platform, "device_kind": kind, "device_count": len(devices),
        "dry_run": dry_run, "versions": versions(),
    }
    log(f"device: {platform} / {kind} x{len(devices)}")

    result["kernels"] = kernel_stage(sizes, interpret=dry_run)

    app, base, engine = boot(sizes, deadline)
    try:
        tpu = app.container.tpu
        require(engine["platform"] == platform and engine["device_kind"] == kind,
                f"server probed {engine['platform']}/{engine['device_kind']}")
        require(engine["compile_cache_dir"] == cache_dir,
                f"compile cache placed at {engine['compile_cache_dir']}, "
                f"expected {cache_dir}")
        cfg = tpu.runner.cfg
        result.update({
            "model": tpu.model_name, "quant": tpu.quant,
            "layers": cfg.n_layers, "dim": cfg.dim, "heads": cfg.n_heads,
            "kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "max_seq": cfg.max_seq, "buckets": list(tpu.runner.buckets),
            "mesh": engine["mesh"],
            "compile_cache": {"dir": cache_dir, "entries_at_start": cache_entries},
            "boot_timeline": [
                [s["stage"], s["seconds"]] for s in engine["boot_timeline"]
            ],
            "boot_seconds": round(
                sum(s["seconds"] for s in engine["boot_timeline"]), 1
            ),
            "tokenizer": tpu.tokenizer.backend,
        })
        log(f"ready: boot {result['boot_seconds']}s, cache {cache_dir}")

        evidence = pallas_evidence(tpu)
        result["pallas_compiled"] = evidence
        if not dry_run:  # tiny on the CPU runs XLA attention by design
            require(all(v > 0 for v in evidence.values()),
                    f"no Mosaic custom call in a served program: {evidence}")

        # logprobs is an opt-in executable variant that compiles on first
        # use by the repo's policy; the window uses it only as an answer's
        # fingerprint, so that first use is paid here, outside the window
        complete(base, prompt(sizes["short"], 0), 2, logprobs=True)
        compiles_before, xla_before = compiles_total(base), len(compiled)
        result["requests"] = drive_requests(base, sizes)
        window = compiled[xla_before:]
        result["compiles_in_window"] = {
            "gofr_tpu_compiles_total": compiles_total(base) - compiles_before,
            "xla": len(window), "xla_names": window[:40],
        }
        require(result["compiles_in_window"]["gofr_tpu_compiles_total"] == 0
                and not window, f"compiled inside the request window: {window}")

        finished: dict[str, list] = {}
        for dkind in ("prefill", "prefill_chunk", "decode_chunk"):
            records = admin(base, f"/admin/dispatches?kind={dkind}&limit=500")
            finished[dkind] = [
                r for r in records["dispatches"] if r["status"] == "ok"
            ]
            require(finished[dkind], f"no finished {dkind} dispatch")
        result["dispatches"] = {k: len(v) for k, v in finished.items()}
        require(any((r["batch_size"] or 0) > 1 for r in finished["prefill"]),
                "no prefill cohort of more than one request formed")
        require(any((r["batch_size"] or 0) > 1 for r in finished["decode_chunk"]),
                "no pooled decode chunk carried more than one slot")

        engine = admin(base, "/admin/engine")
        history = [h["state"] for h in engine["engine"]["history"]]
        result["engine"] = {"state": engine["engine"]["state"], "history": history}
        require(engine["engine"]["state"] == "serving"
                and not set(history) & set(BAD_STATES),
                f"engine is {engine['engine']['state']}, history {history}")
        result["prefix"] = engine["caches"].get("prefix")
        require((result["prefix"] or {}).get("hits", 0) >= 1,
                f"the exact repeat did not hit the prefix cache: {result['prefix']}")
        stats = [d.memory_stats() or {} for d in devices]
        result["hbm"] = {
            "peak_bytes": [s.get("peak_bytes_in_use") for s in stats],
            "bytes_in_use": [s.get("bytes_in_use") for s in stats],
            "bytes_limit": stats[0].get("bytes_limit"),
        }
    finally:
        app.shutdown()
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="development rehearsal: tiny model, CPU, interpret mode")
    args = ap.parse_args()
    # nothing this process starts may outlive the driver's limit
    killer = threading.Timer(DEADLINE_S + 20, lambda: os._exit(3))
    killer.daemon = True
    killer.start()
    # stdout is kept for the report and the verdict: whatever else this
    # process prints (the server logs to stdout) goes to stderr
    sys.stdout.flush()
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    try:
        report = run(args.dry_run)
    except SmokeFailure as exc:
        log(f"FAILED: {exc}")
        return 1
    print(json.dumps(report), file=out)
    # the verdict line: exactly these keys, the device as JAX reports it
    print(json.dumps({"ok": True, "device": {
        "platform": report["platform"], "kind": report["device_kind"],
        "count": report["device_count"],
    }}), file=out, flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as exc:  # argparse --help / usage errors
        code = exc.code if isinstance(exc.code, int) else 1
    except BaseException:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # hard exit: server and pool threads must neither print after the
    # verdict line nor keep a failed run alive
    os._exit(code)
