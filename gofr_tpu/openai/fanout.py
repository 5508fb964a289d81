"""Generation fan-out: the streaming consumer with host-side stop
matching, and n/best_of candidate generation with mean-logprob ranking."""

from __future__ import annotations

from typing import Any

from gofr_tpu.openai.parse import _StopScanner, _sampler

from gofr_tpu.errors import HTTPError

STREAM_END = object()  # per-index end marker on the multiplex queue


class _LinkedCancel:
    """Event-like stop for ONE fan-out candidate: reads as set when
    either the shared client-abort event or this candidate's own
    teardown tripped. ``set()`` marks only the local side — a finished
    candidate's generator close (``_stream_iter``'s ``finally:
    stop.set()``) must never cancel its still-decoding siblings, while
    a real client abort (the shared event) must cancel all of them.
    The decode paths only ever ``is_set()`` their stop events, so this
    is the full surface they need."""

    __slots__ = ("_shared", "_local")

    def __init__(self, shared: Any):
        import threading

        self._shared = shared
        self._local = threading.Event()

    def set(self) -> None:
        self._local.set()

    def is_set(self) -> bool:
        return self._local.is_set() or (
            self._shared is not None and self._shared.is_set()
        )


def _candidate_samplers(body: dict, count: int) -> list:
    """Per-candidate samplers with the seed+index derivation — THE
    reproducibility contract the stream and non-stream fan-outs share
    (stream candidates must byte-match non-stream candidates)."""
    seed = body.get("seed")
    if seed is not None:
        try:
            seed = int(seed)
        except (TypeError, ValueError):
            raise HTTPError(400, '"seed" must be an integer') from None
    return [
        _sampler({**body, "seed": seed + i} if seed is not None else body)
        for i in range(count)
    ]


def _fanout_workers_override(ctx: Any) -> Any:
    """OPENAI_FANOUT_WORKERS, validated — the operator's explicit
    fan-out concurrency bound (None when unset). Both fan-out paths
    OBEY it in both directions: raising and lowering."""
    raw = ctx.config.get_or_default("OPENAI_FANOUT_WORKERS", "")
    if not raw:
        return None
    try:
        return max(1, int(raw))
    except ValueError:
        raise HTTPError(
            500, "OPENAI_FANOUT_WORKERS must be an integer"
        ) from None


def _fanout_workers(ctx: Any, default_slots: int = 4) -> int:
    """Deployment-scaled fan-out concurrency bound, shared by both
    paths: ~3/4 of the decode pool's slots (one wide request must not
    occupy every slot, nor spawn that many solo seeded decodes);
    OPENAI_FANOUT_WORKERS overrides."""
    override = _fanout_workers_override(ctx)
    if override is not None:
        return override
    slots = getattr(
        getattr(ctx.tpu, "decode_pool", None), "n_slots", None
    ) or default_slots
    return max(1, (slots * 3) // 4 or 1)


def _stream_candidates(
    ctx: Any, body: dict, prompt_ids: list, max_tokens: int,
    sampler: Any, stop_ids: Any, adapter: Any, want_logprobs: bool,
    n: int, cancel: Any = None,
) -> list:
    """Construct the n candidate stream iterators for interleaved SSE.
    Built BEFORE the 200 commits (parameter errors must 400 first).
    Seeded fan-outs derive per-candidate seeds via _candidate_samplers;
    unseeded candidates share the continuous-batching pool. Unlike the
    non-stream path, candidates past the concurrency bound cannot
    serialize (all indexes must progress for interleaved output), so an
    over-wide n is a 400 scaled to the deployment: n may use up to the
    pool's full slot count (OPENAI_FANOUT_WORKERS overrides). The
    caller owns closing every iterator."""
    if n == 1:
        return [ctx.tpu.generate_stream(
            prompt_ids, max_tokens, sampler=sampler, stop_tokens=stop_ids,
            adapter=adapter, logprobs=want_logprobs, cancel=cancel,
        )]
    override = _fanout_workers_override(ctx)
    if override is not None:
        bound = override  # explicit operator bound: obeyed in BOTH directions
    else:
        # default: streamed candidates may use up to the pool's full slot
        # count (they cannot serialize — all indexes must progress)
        bound = getattr(
            getattr(ctx.tpu, "decode_pool", None), "n_slots", None
        ) or 4
    if n > bound:
        raise HTTPError(
            400, f'"n" is capped at {bound} when streaming on this '
            "deployment (candidates stream concurrently and cannot be "
            "serialized; raise DECODE_SLOTS or OPENAI_FANOUT_WORKERS)"
        )
    samplers = _candidate_samplers(body, n)
    iters = []
    try:
        for s in samplers:
            # a client abort must free EVERY candidate's slot/KV — but
            # one candidate finishing first must not cancel the rest:
            # each candidate stops on (shared abort OR its own teardown)
            iters.append(ctx.tpu.generate_stream(
                prompt_ids, max_tokens, sampler=s, stop_tokens=stop_ids,
                adapter=adapter, logprobs=want_logprobs,
                cancel=_LinkedCancel(cancel),
            ))
    except BaseException:
        for it in iters:  # a late candidate failing must free the early ones
            it.close()
        raise
    return iters


def _usage_chunk(
    object_name: str, resp_id: str, created: int, model: str,
    prompt_tokens: int, completion_tokens: int,
) -> str:
    """The ONE pre-[DONE] usage frame both endpoints emit under
    stream_options.include_usage: empty choices + the usage object (a
    shape change here must hit both endpoints' billing identically)."""
    import json as _json

    return _json.dumps({
        "id": resp_id, "object": object_name, "created": created,
        "model": model, "choices": [],
        "usage": {
            "prompt_tokens": prompt_tokens,
            "completion_tokens": completion_tokens,
            "total_tokens": prompt_tokens + completion_tokens,
        },
    })


def _index_feed_text(
    dec: Any, scan: Any, finish: list, i: int, emitted: list, token: int,
) -> tuple:
    """Decode one token for candidate ``i`` through its stop scanner —
    the ONE copy of the per-index feed state machine both endpoints'
    fan-outs share. Returns (text_or_None, stopped): text None means an
    id-only deployment (no tokenizer; the caller emits the token
    extension), stopped True means the stop matched (finish set; the
    returned text is the pre-stop remainder)."""
    emitted[i] += 1
    if dec is None:
        return None, False
    text = dec.feed(token)
    if scan is not None:
        text, done = scan.feed(text)
        if done:
            finish[i] = "stop"
            return text, True
    return text, False


def _index_tail_text(
    dec: Any, scan: Any, finish: list, i: int, emitted: list,
    max_tokens: int,
) -> str:
    """Flush candidate ``i``'s decoder through its stop scanner and
    settle its finish reason — the ONE copy of the per-index tail state
    machine (the subtlest stop/length logic; it must not fork per
    endpoint). Returns the tail text ('' when already finished)."""
    t = dec.flush() if dec is not None else ""
    if finish[i] is not None:
        return ""
    if scan is not None:
        t, done = scan.feed(t)
        if done:
            finish[i] = "stop"
        else:
            t += scan.flush()
    if finish[i] is None:
        finish[i] = "length" if emitted[i] >= max_tokens else "stop"
    return t


def _drive_stream_fanout(
    iters: list, replicate: bool, n: int, finish: list,
    want_logprobs: bool, open_frames: Any, feed: Any, tail: Any,
    error_frame: Any, usage_frames: Any = None, record: Any = None,
) -> Any:
    """The ONE interleaved-SSE driver both endpoints share: replicate
    mode consumes a single iterator and fans frames across indexes;
    multiplex mode merges n pump threads. ``finish`` is the caller's
    per-index finish-reason list — ``feed``/``tail`` mutate it; when a
    feed marks an index finished (stop match), its decode is cancelled
    and anything else that index produces — including an error from the
    cancellation itself — is dropped rather than aborting the healthy
    candidates. Errors from UNFINISHED indexes abort the whole stream
    with one error frame (the transport cannot re-status a committed
    200). ``record`` (the request's FlightRecord) is told when the last
    index's tokens are over, so that the frames after them are not counted
    as some token's."""
    cancels: list = []
    try:
        yield from open_frames()
        if replicate:
            for item in iters[0]:
                token, lp = item if want_logprobs else (item, None)
                for i in range(n):
                    if finish[i] is None:
                        yield from feed(i, token, lp)
                if all(f is not None for f in finish):
                    break
            if record is not None:
                record.end_token_frames()
            for i in range(n):
                yield from tail(i)
        else:
            q, cancels_ = _multiplex(iters)
            cancels.extend(cancels_)
            active = n
            while active:
                i, item = q.get()
                if item is STREAM_END:
                    active -= 1
                    if not active and record is not None:
                        record.end_token_frames()
                    yield from tail(i)
                    continue
                if finish[i] is not None:
                    continue  # stop-matched: drop tokens AND late errors
                if (
                    isinstance(item, tuple) and len(item) == 2
                    and item[0] == "error"
                ):
                    raise item[1]
                token, lp = item if want_logprobs else (item, None)
                yield from feed(i, token, lp)
                if finish[i] is not None:
                    cancels[i].set()  # stop matched: free its decode early
        if usage_frames is not None:
            # stream_options.include_usage: one final pre-[DONE] chunk
            # with empty choices and the usage object
            yield from usage_frames()
        yield "[DONE]"
    except Exception as exc:
        yield error_frame(exc)
    finally:
        if replicate:
            iters[0].close()  # same thread drives it: legal
        else:
            for ev in cancels:
                ev.set()  # pump threads close their own iterators


def _multiplex(iters: list) -> tuple:
    """Merge n token iterators into ONE queue of (index, item) pairs;
    each stream's end posts (index, STREAM_END), an error posts
    (index, ("error", exc)) then STREAM_END. Returns (queue, cancels):
    the PUMP thread owns each iterator's lifecycle — a raw generator
    cannot be close()d from another thread while it executes — so the
    consumer cancels index i by setting cancels[i]; the pump notices at
    its next item, closes the iterator (the device's stop event cancels
    the background decode), and posts STREAM_END."""
    import queue as _queue
    import threading

    out: "_queue.Queue" = _queue.Queue()
    cancels = [threading.Event() for _ in iters]

    def pump(i: int, it: Any) -> None:
        try:
            for item in it:
                if cancels[i].is_set():
                    break
                out.put((i, item))
        except Exception as exc:  # surfaced as an SSE error frame
            out.put((i, ("error", exc)))
        finally:
            # STREAM_END must post even if close() raises (a cancellation
            # tearing down the decode can error): a lost sentinel would
            # wedge the consumer in q.get() forever, hanging the response
            try:
                it.close()  # suspended here, owned by this thread: legal
            except Exception:
                pass  # the index already ended; nothing left to deliver
            finally:
                out.put((i, STREAM_END))

    for i, it in enumerate(iters):
        threading.Thread(
            target=pump, args=(i, it), daemon=True,
            name=f"gofr-sse-fanout-{i}",
        ).start()
    return out, cancels


def _consume_stream(
    ctx: Any, prompt_ids: list, max_tokens: int, sampler: Any,
    stop_ids: Any, stop_strs: list, need_lp: bool, adapter: Any,
) -> tuple[list, Any, str, str]:
    """Generate through the streaming bridge, matching multi-token stop
    strings host-side as text streams off the device and CANCELLING the
    background decode at the first match (closing the iterator frees the
    pool slot — a matched stop must not keep generating to max_tokens).
    Returns (tokens, logprobs_or_None, text, finish_reason); ``text`` is
    truncated before the stop string, tokens/logprobs cover everything
    actually generated (usage accounting)."""
    tok = ctx.tpu.tokenizer  # _parse_stops guarantees one for stop_strs
    dec = tok.stream_decoder()
    scan = _StopScanner(stop_strs)
    it = ctx.tpu.generate_stream(
        prompt_ids, max_tokens, sampler=sampler, stop_tokens=stop_ids,
        adapter=adapter, logprobs=need_lp,
    )
    toks: list = []
    lps: list = []
    parts: list = []
    starts: list = []  # decoded-text offset where each token's text began
    decoded = 0
    finish = None
    try:
        for item in it:
            t, lp = item if need_lp else (item, None)
            toks.append(t)
            if lp is not None:
                lps.append(lp)
            piece = dec.feed(t)
            starts.append(decoded)
            decoded += len(piece)
            emit, done = scan.feed(piece)
            parts.append(emit)
            if done:
                finish = "stop"
                break
        if finish is None:
            emit, done = scan.feed(dec.flush())
            parts.append(emit)
            if done:
                finish = "stop"
            else:
                parts.append(scan.flush())
                finish = "length" if len(toks) >= max_tokens else "stop"
    finally:
        it.close()
    if need_lp and scan.match_pos is not None:
        # align response logprobs with the TRUNCATED text: keep tokens
        # whose text starts before the match (usage still bills the full
        # toks list — the tokens were generated)
        vis = sum(1 for s in starts if s < scan.match_pos)
        lps = lps[:vis]
    return toks, (lps if need_lp else None), "".join(parts), finish


def _fanout_generate(
    ctx: Any, body: dict, prompt_ids: list, max_tokens: int,
    sampler: Any, stop_ids: Any, stop_strs: list, want_logprobs: bool,
    top_n: int, adapter: Any, n: int, best_of: int,
) -> tuple[list, int]:
    """Generate ``best_of`` candidates and keep the ``n`` best. Returns
    ([(tokens, logprobs_or_None, tops_or_None, text_or_None,
    finish_or_None), ...] of length n, total tokens generated across ALL
    candidates — usage must count discarded best_of candidates too, the
    OpenAI accounting).
    ``text``/``finish`` are set only on the multi-token-stop path (the
    host-matched truncation IS the text); otherwise the caller decodes
    the ids itself. ``top_n`` > 0 also collects the top-k alternatives
    per position (tops; None otherwise) — rejected with stop_strs at
    the call sites, so the two never combine here.

    - Deterministic requests (temperature 0) produce identical candidates:
      ONE generation is replicated, not recomputed (and billed once per
      replica, matching what the response carries).
    - Sampled candidates run CONCURRENTLY: the continuous-batching pool
      decodes unseeded requests in one lockstep dispatch, so n streams
      cost ~one stream's wall time. A seeded request derives per-candidate
      seeds (seed + index) so the whole fan-out stays reproducible.
    - best_of > n ranks by mean token logprob (generated with logprobs
      internally; stripped from the response unless requested)."""
    score = best_of > n
    need_lp = want_logprobs or score

    def one(s):
        if stop_strs:
            toks, lps, text, finish = _consume_stream(
                ctx, prompt_ids, max_tokens, s, stop_ids, stop_strs,
                need_lp, adapter,
            )
            return toks, lps, None, text, finish
        if top_n:
            toks, lps, tops = ctx.tpu.generate(
                prompt_ids, max_tokens, sampler=s, stop_tokens=stop_ids,
                adapter=adapter, logprobs=True, top_logprobs=True,
            )
            return toks, lps, tops, None, None
        out = ctx.tpu.generate(
            prompt_ids, max_tokens, sampler=s, stop_tokens=stop_ids,
            adapter=adapter, logprobs=need_lp,
        )
        toks, lps = out if need_lp else (out, None)
        return toks, lps, None, None, None

    if sampler.greedy:
        toks, lps, tops, text, finish = one(sampler)
        if not want_logprobs:
            lps = None
        return [(toks, lps, tops, text, finish)] * n, len(toks) * n

    samplers = _candidate_samplers(body, best_of)
    if best_of == 1:
        results = [one(samplers[0])]
    else:
        import contextvars
        from concurrent.futures import ThreadPoolExecutor

        # concurrency scales with the DEPLOYMENT, not the request
        # (_fanout_workers): candidates beyond the bound serialize
        # through pool.map; a seeded fan-out decodes solo, so the same
        # bound caps its thread count.
        workers = min(best_of, _fanout_workers(ctx))
        # one context COPY per candidate (a single Context cannot run
        # concurrently), snapshotted HERE in the handler thread: pool
        # workers inherit nothing, and without this the request's span
        # and flight record would be invisible to the generation —
        # orphan traces, empty telemetry
        snapshots = [contextvars.copy_context() for _ in samplers]
        with ThreadPoolExecutor(max_workers=workers) as pool:
            results = list(pool.map(
                lambda pair: pair[0].run(one, pair[1]),
                zip(snapshots, samplers),
            ))
    generated = sum(len(r[0]) for r in results)
    if score:
        def mean_lp(item):
            lps = item[1]
            return sum(lps) / len(lps) if lps else float("-inf")

        results = sorted(results, key=mean_lp, reverse=True)[:n]
    if not want_logprobs:
        results = [(toks, None, tops, text, finish)
                   for toks, _, tops, text, finish in results]
    return results, generated
