"""Prefill MFU profiler: where does the non-MXU time go?

Prefill MFU on the chip is unmeasured on this machine (ROADMAP S5), and no
profile of the serving hot path has been taken. This tool answers the
question two ways:

1. **Shape grid**: times the runner's REAL prefill executable (the same
   ``_prefill`` the serving path dispatches) across bucket x batch shapes,
   reporting ms and MFU per shape. Prefill MFU rises with tokens-per-
   dispatch until the MXU saturates; the grid shows where.
2. **Ablations**: re-times the grid under variants that isolate a cost —
   ``bf16`` (no int8 dequant on the weight path), ``pallas`` / ``xla``
   attention — so the gap to roofline decomposes into named causes
   instead of guesses.

Optionally captures a jax.profiler trace (``--trace DIR``) of one hot
dispatch for TensorBoard's trace viewer (gofr_tpu/profiling.py wraps the
same API for live servers).

    python tools/profile_prefill.py                      # flagship grid
    python tools/profile_prefill.py --model small --platform cpu  # smoke
    python tools/profile_prefill.py --ablate             # + bf16/attn runs

Each config prints one JSON line; stderr carries a ranked summary.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _time_prefill(runner, bucket: int, batch: int, reps: int = 5) -> dict:
    """Times the runner's real prefill at [batch, bucket] two ways.

    ``seconds``: median wall time of one synchronized dispatch — what a
    single request experiences, INCLUDING the host<->device round trip
    (the serving gauge's host-timed prefill MFU includes it too).

    ``pipelined``: per-dispatch time of ``reps`` back-to-back dispatches
    synchronized once at the end — jax's async dispatch queues them so
    the round trip amortizes away; this is the DEVICE throughput
    number, the one comparable to the MXU roofline."""
    import jax
    import jax.numpy as jnp

    tokens = jnp.ones((batch, bucket), jnp.int32)
    lengths = jnp.full((batch,), bucket, jnp.int32)
    if getattr(runner, "_token_sharding", None) is not None:
        tokens = jax.device_put(tokens, runner._token_sharding)
        lengths = jax.device_put(lengths, runner._row_sharding)
    cache = runner._zero_cache(batch)
    runner._prefill(runner.params, tokens, cache, lengths)[1].block_until_ready()
    times = []
    for _ in range(reps):
        start = time.perf_counter()
        _, next_ids, _ = runner._prefill(runner.params, tokens, cache, lengths)
        next_ids.block_until_ready()
        times.append(time.perf_counter() - start)
    times.sort()
    start = time.perf_counter()
    for _ in range(reps):
        _, next_ids, _ = runner._prefill(runner.params, tokens, cache, lengths)
    next_ids.block_until_ready()
    pipelined = (time.perf_counter() - start) / reps
    return {"seconds": times[len(times) // 2], "best": times[0],
            "pipelined": pipelined}


def run_grid(model: str, quant: str, buckets, batches, attn: str | None,
             max_seq: int, trace_dir: str | None) -> list[dict]:
    import jax

    from gofr_tpu.tpu.device import _build_runner
    from gofr_tpu.tpu.flops import device_peak_flops, mfu

    dev = jax.devices()[0]
    # quant-aware: w8a8 measures against the MXU int8 peak (flops.py owns
    # the factor — the serving gauge uses the same call)
    peak = device_peak_flops(
        getattr(dev, "device_kind", dev.platform), dev.platform, quant=quant
    )
    label = f"{model}/{quant or 'bf16'}/{attn or 'auto'}"
    print(f"=== building {label} (buckets={buckets})", file=sys.stderr, flush=True)
    runner = _build_runner(
        model, quant, None, max(batches),
        buckets=tuple(sorted(set(buckets))), max_seq=max_seq, attn_impl=attn,
    )
    out = []
    eff_max = runner.cfg.max_seq
    for bucket in buckets:
        if bucket > eff_max:
            # the runner clamps its compiled buckets to the model's
            # max_seq; timing an unclamped shape would crash the grid
            print(f"skip bucket {bucket} > max_seq {eff_max}",
                  file=sys.stderr, flush=True)
            continue
        for batch in batches:
            t = _time_prefill(runner, bucket, batch)
            tokens = bucket * batch
            rec = {
                "config": label, "bucket": bucket, "batch": batch,
                "ms": round(t["seconds"] * 1e3, 2),
                "best_ms": round(t["best"] * 1e3, 2),
                "pipelined_ms": round(t["pipelined"] * 1e3, 2),
                "tokens": tokens,
                "mfu": round(mfu(runner.n_params, tokens, t["seconds"], peak), 4),
                "mfu_device": round(
                    mfu(runner.n_params, tokens, t["pipelined"], peak), 4
                ),
                "tok_per_sec": round(tokens / t["seconds"], 1),
            }
            out.append(rec)
            print(json.dumps(rec), flush=True)
    if trace_dir and out:
        # trace a shape that was actually measured, from one record
        bucket, batch = out[-1]["bucket"], out[-1]["batch"]
        print(f"=== tracing one [{batch}, {bucket}] dispatch -> {trace_dir}",
              file=sys.stderr)
        jax.profiler.start_trace(trace_dir)
        _time_prefill(runner, bucket, batch, reps=2)
        jax.profiler.stop_trace()
    return out


def summarize_trace(trace_dir: str, top: int = 15) -> list[dict]:
    """Aggregate device-plane op time from a captured .xplane.pb — the
    'where does the non-MXU time go' answer, printable without TensorBoard.
    Uses the ambient tensorflow's xplane proto (parse-only; no TF runtime)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2  # noqa: E501 — env-provided

    paths = []
    for root, _, names in os.walk(trace_dir):
        paths.extend(os.path.join(root, n) for n in names if n.endswith(".xplane.pb"))
    if not paths:
        print(f"no .xplane.pb under {trace_dir}", file=sys.stderr)
        return []
    spaces = []
    for path in paths:
        space = xplane_pb2.XSpace()
        with open(path, "rb") as fh:
            space.ParseFromString(fh.read())
        spaces.append(space)
    # device planes carry the XLA op timeline; host planes carry
    # python/runtime noise. On a CPU smoke there is no device plane —
    # fall back to /host:CPU so the tool is testable without a chip.
    def is_device(name: str) -> bool:
        return "TPU" in name or "/device:" in name
    have_device = any(is_device(p.name) for s in spaces for p in s.planes)
    totals: dict[str, float] = {}
    plane_names = []
    for space in spaces:
        for plane in space.planes:
            if have_device and not is_device(plane.name):
                continue
            if not have_device and plane.name != "/host:CPU":
                continue
            plane_names.append(plane.name)
            meta = plane.event_metadata
            # TPU device planes nest timelines ('XLA Modules' events span
            # their constituent 'XLA Ops' events) — summing every line
            # would double-count, halving each op's reported share. Keep
            # only the op-level line when one exists; host planes (the CPU
            # smoke fallback) have parallel thread lines, not nested ones.
            lines = [ln for ln in plane.lines if ln.name == "XLA Ops"] or plane.lines
            for line in lines:
                for ev in line.events:
                    name = meta[ev.metadata_id].name if ev.metadata_id in meta else "?"
                    totals[name] = totals.get(name, 0.0) + ev.duration_ps / 1e9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    total_ms = sum(totals.values())
    print(f"\n=== device op time ({', '.join(sorted(set(plane_names))) or 'no device plane'}; "
          f"total {total_ms:.1f} ms)", file=sys.stderr)
    out = []
    for name, ms in ranked[:top]:
        pct = 100.0 * ms / total_ms if total_ms else 0.0
        print(f"  {pct:5.1f}%  {ms:9.2f} ms  {name[:90]}", file=sys.stderr)
        out.append({"op": name, "ms": round(ms, 2), "pct": round(pct, 1)})
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=os.environ.get("BENCH_MODEL", "llama3-8b"))
    ap.add_argument("--quant", default="int8")
    ap.add_argument("--buckets", default="64,128,256,512")
    ap.add_argument("--batches", default="1,4,8,16")
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--ablate", action="store_true",
                    help="also run bf16 and explicit xla/pallas attention grids")
    ap.add_argument("--trace", default="", help="capture a profiler trace here")
    ap.add_argument("--platform", default="", help="pin jax platform (cpu smoke)")
    ap.add_argument("--summarize", default="",
                    help="just summarize an existing trace dir and exit")
    args = ap.parse_args()

    if args.summarize:
        # exit 1 on an empty/missing trace so automation can't mistake a
        # typo'd dir for a successful summary
        return 0 if summarize_trace(args.summarize) else 1

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    buckets = [int(b) for b in args.buckets.split(",")]
    batches = [int(b) for b in args.batches.split(",")]
    results = run_grid(args.model, args.quant, buckets, batches, None,
                       args.max_seq, args.trace or None)
    if args.trace:
        # best-effort: a missing tensorflow must not kill the ablation
        # grids below (the trace itself is still on disk for TensorBoard;
        # the explicit --summarize path fails loudly instead)
        try:
            summarize_trace(args.trace)
        except Exception as exc:  # missing tf, truncated .xplane.pb, ...
            print(f"trace summary skipped: {exc!r}", file=sys.stderr)
    if args.ablate and not results:
        # the main grid measured nothing: building more runners to skip
        # the same shapes would waste the whole ablation stage
        print("ablations skipped: the main grid measured nothing",
              file=sys.stderr)
    elif args.ablate:
        # quant ablations at the largest shape the main grid actually
        # MEASURED (its skip logic knows the model's effective max_seq;
        # re-filtering on args.max_seq alone would rebuild multi-GB
        # runners to measure nothing)
        top = [max(r["bucket"] for r in results)]
        for mode in ("", "w8a8"):
            if args.quant != mode:
                results += run_grid(args.model, mode, top,
                                    batches[-1:], None, args.max_seq, None)
        # attention impl: pallas flash vs xla at the largest shape
        for attn in ("xla", "pallas"):
            results += run_grid(args.model, args.quant, top,
                                batches[-1:], attn, args.max_seq, None)
    ranked = sorted(results, key=lambda r: -r["mfu_device"])
    print("\n=== MFU ranking (mfu_device = link-amortized; mfu = one synced"
          " dispatch incl. RTT)", file=sys.stderr)
    for r in ranked[:12]:
        print(
            f"  {r['config']:>24} b{r['bucket']:<4}x{r['batch']:<3}: "
            f"mfu_device {r['mfu_device']:.3f}  mfu {r['mfu']:.3f}  "
            f"{r['pipelined_ms']:8.2f} ms/dispatch",
            file=sys.stderr,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
