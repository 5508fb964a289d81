"""POST /v1/completions: prompt in, text out; SSE when streaming;
echo+logprobs teacher-forcing scoring; n/best_of fan-out."""

from __future__ import annotations

import time
import uuid
from collections import deque
from typing import Any

from gofr_tpu.openai.fanout import _fanout_generate
from gofr_tpu.openai.logprobs import _logprobs_obj
from gofr_tpu.openai.parse import (
    _StopScanner,
    _parse_fanout,
    _parse_request,
    _prompt_tokens,
    _stream_usage_opt,
)

from gofr_tpu.errors import HTTPError


def _stream_completion(
    ctx: Any, body: dict, prompt_ids: list, max_tokens: int, sampler: Any,
    stop_ids: Any, stop_strs: list, want_logprobs: bool, top_n: int,
    adapter: Any, n: int, best_of: int, echo: bool,
    cmpl_id: str, created: int, model: str, tok: Any,
    include_usage: bool = False, resume_from: int = 0,
) -> Any:
    """The SSE branch of /v1/completions: per-token text chunks with
    host-side stop matching, terminated by ``data: [DONE]``. ``n`` > 1
    streams candidates CONCURRENTLY as interleaved chunks carrying their
    ``index`` (the OpenAI shape): unseeded candidates share the decode
    pool, seeded ones derive per-candidate seeds, and deterministic
    (greedy) requests replicate one stream across every index — the
    non-stream fan-out's replication rule, so billing and content
    match it."""
    if best_of > n:
        raise HTTPError(
            400, '"best_of" > "n" is not supported when streaming '
            "(candidates cannot be ranked and discarded mid-stream)"
        )
    if max_tokens == 0:
        raise HTTPError(
            400, 'streaming needs "max_tokens" >= 1 (use the '
            "non-stream form for pure echo scoring)"
        )
    if top_n:
        raise HTTPError(
            400, "top-logprob alternatives are not supported when "
            "streaming; drop \"stream\" or request chosen-token "
            "logprobs only"
        )
    if resume_from:
        # resume (X-Resume-From) restores an interrupted stream at a
        # frame offset. n > 1 refuses outright: candidate interleaving
        # is thread-timing-dependent, so the frame sequence is not
        # reproducible and no resume strategy can splice it.
        if n > 1:
            raise HTTPError(
                400, "resume is not supported on n > 1 streams (the "
                "candidate interleave is not reproducible)"
            )
        # the SKIP-AHEAD shortcut (regenerate only positions >= k) is
        # sound only when each frame depends on its own token alone.
        # A tokenizer stream decoder and a stop-sequence scanner carry
        # cross-token state (partial UTF-8 bytes, a half-matched stop
        # string) that a mid-stream restart cannot rebuild, echo
        # prepends replay frames, and logprob values are not journaled
        # — those streams fall back to FULL regeneration from frame 0:
        # deterministic by the resume precondition, renumbered
        # identically, and the router's id filter drops the frames the
        # client already holds. Slower, never wrong.
        if echo or want_logprobs or stop_strs or tok is not None:
            resume_from = 0
    import json as _json

    from gofr_tpu.http.response import Stream

    def chunk(text: str, lp: Any = None, finish: Any = None,
              token: Any = None, index: int = 0) -> str:
        choice: dict[str, Any] = {
            "text": text, "index": index, "finish_reason": finish,
        }
        if token is not None:
            # no tokenizer: bare str(token) text would concatenate
            # ambiguously ("12"+"3" == "1"+"23") — ids ride a tokens
            # extension instead, matching the non-stream path
            choice["tokens"] = [token]
        if want_logprobs:
            choice["logprobs"] = (
                {"token_logprobs": [lp]} if lp is not None else None
            )
        frame = {
            "id": cmpl_id, "object": "text_completion",
            "created": created, "model": model, "choices": [choice],
        }
        if include_usage:
            frame["usage"] = None
        return _json.dumps(frame)

    def usage_frame(completion_tokens: int) -> str:
        from gofr_tpu.openai.fanout import _usage_chunk

        return _usage_chunk("text_completion", cmpl_id, created, model,
                            len(prompt_ids), completion_tokens)

    if n > 1:
        return _stream_completion_fanout(
            ctx, body, prompt_ids, max_tokens, sampler, stop_ids,
            stop_strs, want_logprobs, adapter, n, echo, chunk, tok,
            usage_frame if include_usage else None,
        )

    # constructed OUTSIDE events(): parameter errors (unknown adapter,
    # bad sampler) must 400 before the SSE 200 commits. resume_from is
    # clamped to the token budget: a client interrupted between the
    # last token frame and [DONE] resumes straight into the tail
    from gofr_tpu.openai.parse import _abortable
    from gofr_tpu.telemetry import current_record

    cancel, on_abort = _abortable(ctx)
    stream_iter = ctx.tpu.generate_stream(
        prompt_ids, max_tokens, sampler=sampler, stop_tokens=stop_ids,
        adapter=adapter, logprobs=want_logprobs,
        resume_from=min(resume_from, max_tokens), cancel=cancel,
    )
    record = current_record()  # here, on the handler's thread: events() runs elsewhere
    # token frames built and not yet pulled (``Stream.ready``): the tokens
    # that are there together become frames before the first is handed on,
    # so that one pull of the responder takes them all and none waits
    held: deque = deque()

    def events():
        # a resumed stream's token iterator starts at the resume
        # position; the emitted counter must keep counting ABSOLUTE
        # positions or finish_reason ("length" vs "stop") would drift
        # from the uninterrupted run's
        emitted = min(resume_from, max_tokens)
        finish = None
        dec = tok.stream_decoder() if tok is not None else None
        # stop_strs imply a tokenizer (enforced at parse), so dec
        # is always live when the scanner is
        scan = _StopScanner(stop_strs) if stop_strs else None
        try:
            if echo:
                # prompt replay first, matching the non-stream shape
                if dec is not None:
                    yield chunk(tok.decode(prompt_ids))
                else:
                    for t in prompt_ids:
                        yield chunk("", token=t)
            for item in stream_iter:
                token, lp = item if want_logprobs else (item, None)
                emitted += 1
                if dec is None:
                    held.append(chunk("", lp, token=token))
                else:
                    text = dec.feed(token)
                    if scan is not None:
                        text, done = scan.feed(text)
                        if done:
                            # matched mid-stream: emit up to the stop and
                            # cancel the decode (frees the pool slot). No
                            # lp: the matched token's text is excluded, so
                            # its logprob must not ride this chunk either
                            held.append(chunk(text, None))
                            finish = "stop"
                            break
                    held.append(chunk(text, lp))
                if not stream_iter.ready():  # the next token is a wait away
                    while held:
                        yield held.popleft()
            while held:  # a stop leaves the loop with its frames built
                yield held.popleft()
            if record is not None:
                record.end_token_frames()  # a stop may leave tokens unframed
            tail = dec.flush() if dec is not None else ""
            if finish is None:
                if scan is not None:
                    tail, done = scan.feed(tail)
                    if done:
                        finish = "stop"
                    else:
                        tail += scan.flush()
                if finish is None:
                    finish = "length" if emitted >= max_tokens else "stop"
            else:
                tail = ""
            yield chunk(tail, None, finish)
            if include_usage:
                yield usage_frame(emitted)
            yield "[DONE]"
        except Exception as exc:
            yield _json.dumps({"error": {"message": str(exc)}})
        finally:
            stream_iter.close()  # no-op if already exhausted

    # ids=True: every frame carries its monotonic SSE id (anchored at
    # the resume offset), making the stream resumable through the fleet
    # router's journal — see docs/advanced-guide/fleet.md
    return Stream(events(), ids=True, id_offset=resume_from,
                  on_abort=on_abort, ready=held.__len__)


def _stream_completion_fanout(
    ctx: Any, body: dict, prompt_ids: list, max_tokens: int, sampler: Any,
    stop_ids: Any, stop_strs: list, want_logprobs: bool, adapter: Any,
    n: int, echo: bool, chunk: Any, tok: Any, usage_frame: Any = None,
) -> Any:
    """Interleaved multi-index SSE: n candidates stream concurrently,
    each chunk carrying its choice ``index``. Deterministic (greedy)
    requests run ONE stream replicated across indexes. The shared
    driver (_drive_stream_fanout) owns the replicate/multiplex loops,
    stop-cancellation, and cleanup; this function supplies only the
    completions frame shapes."""
    import json as _json

    from gofr_tpu.http.response import Stream
    from gofr_tpu.openai.fanout import (
        _drive_stream_fanout,
        _index_feed_text,
        _index_tail_text,
        _stream_candidates,
    )
    from gofr_tpu.openai.parse import _abortable, _StopScanner
    from gofr_tpu.telemetry import current_record

    replicate = sampler.greedy
    cancel, on_abort = _abortable(ctx)
    record = current_record()
    if replicate and record is not None:
        record.frames_per_token = n  # one stream's token, a frame an index
    iters = _stream_candidates(
        ctx, body, prompt_ids, max_tokens, sampler, stop_ids, adapter,
        want_logprobs, 1 if replicate else n, cancel=cancel,
    )
    decs = [tok.stream_decoder() if tok is not None else None
            for _ in range(n)]
    scans = [_StopScanner(stop_strs) if stop_strs else None
             for _ in range(n)]
    emitted = [0] * n
    finish: list = [None] * n

    def open_frames():
        if not echo:
            return
        for i in range(n):
            if tok is not None:
                yield chunk(tok.decode(prompt_ids), index=i)
            else:
                for t in prompt_ids:
                    yield chunk("", token=t, index=i)

    def feed(i, token, lp):
        text, stopped = _index_feed_text(
            decs[i], scans[i], finish, i, emitted, token
        )
        if text is None:  # id-only deployment: tokens extension
            return [chunk("", lp, token=token, index=i)]
        if stopped:  # the matched token's lp is excluded with its text
            return [chunk(text, None, index=i)]
        return [chunk(text, lp, index=i)]

    def tail(i):
        t = _index_tail_text(decs[i], scans[i], finish, i, emitted,
                             max_tokens)
        return [chunk(t, None, finish[i], index=i)]

    def error_frame(exc):
        return _json.dumps({"error": {"message": str(exc)}})

    usage_frames = (
        (lambda: [usage_frame(sum(emitted))])
        if usage_frame is not None else None
    )
    return Stream(
        _drive_stream_fanout(
            iters, replicate, n, finish, want_logprobs, open_frames, feed,
            tail, error_frame, usage_frames, record,
        ),
        on_abort=on_abort,
    )


def completions(ctx: Any) -> Any:
    (body, max_tokens, sampler, stop_ids, stop_strs, want_logprobs, top_n,
     adapter) = _parse_request(ctx, default_max=16)
    n, best_of, echo = _parse_fanout(body, allow_best_of=True)
    if echo and want_logprobs and body.get("stream"):
        raise HTTPError(
            400, '"echo" with "logprobs" is not supported when streaming'
        )
    if top_n and stop_strs:
        raise HTTPError(
            400, "top-logprob alternatives with multi-token stop "
            'sequences are not supported; use "stop_token_ids"'
        )
    if "prompt" not in body:
        # a missing prompt is almost always a caller bug (misspelled key):
        # generating from a magic default would 200 on garbage
        raise HTTPError(400, 'missing "prompt"')
    prompt_ids = _prompt_tokens(ctx, body["prompt"])
    model = adapter or ctx.tpu.model_name  # adapters serve under their name
    # gofrlint: wall-clock — OpenAI API `created` is epoch seconds by contract
    created = int(time.time())
    cmpl_id = f"cmpl-{uuid.uuid4().hex[:24]}"
    tok = ctx.tpu.tokenizer

    include_usage = _stream_usage_opt(body)  # validates even sans stream
    # flight record (rides a contextvar so the batcher/pool/device stamp
    # it downstream); the Flight guard owns ok/error/drop semantics
    from gofr_tpu.telemetry import flight

    with flight(
        getattr(ctx.container, "telemetry", None),
        model=model, endpoint="/v1/completions",
        trace_id=ctx.trace_id or "", tokens_in=len(prompt_ids),
        stream=bool(body.get("stream")),
        t_received=getattr(ctx.request, "t_received", None),
    ) as fl:
        if body.get("stream"):
            # X-Resume-From: the fleet router (or a reconnecting
            # client) holds frames 0..k-1 of an interrupted stream and
            # asks for the rest — journal-backed teacher-forced resume
            # when this replica served the original, deterministic
            # replay otherwise (device.generate_stream owns the rules)
            resume_from = 0
            raw_resume = ctx.request.header("X-Resume-From")
            if raw_resume:
                try:
                    resume_from = int(raw_resume)
                except ValueError:
                    raise HTTPError(
                        400, '"X-Resume-From" must be an integer frame '
                        "offset"
                    ) from None
                if resume_from < 0:
                    raise HTTPError(400, '"X-Resume-From" must be >= 0')
            # defer: the record completes when the stream ends
            return fl.defer(_stream_completion(
                ctx, body, prompt_ids, max_tokens, sampler, stop_ids,
                stop_strs, want_logprobs, top_n, adapter, n, best_of, echo,
                cmpl_id, created, model, tok, include_usage, resume_from,
            ))

        prompt_lps = None
        if echo and want_logprobs:
            # teacher-forcing prompt scoring: log p(t_i | t_<i), with null
            # for the first token (no conditional) — the OpenAI convention
            # and the eval-harness loglikelihood pattern. The request's
            # adapter scores too (and an unknown one 400s even on the
            # max_tokens=0 path, where no generation would catch it)
            prompt_lps = [None] + ctx.tpu.score(prompt_ids, adapter=adapter)
        elif max_tokens == 0 and adapter is not None:
            # pure echo without logprobs still must validate the adapter name.
            # list_adapters (not a direct runner read): it waits for readiness,
            # so a request landing mid background-boot blocks like every other
            # path instead of 500ing on a not-yet-built runner
            loaded = ctx.tpu.list_adapters()
            if adapter not in loaded:
                from gofr_tpu.errors import InvalidParamError

                raise InvalidParamError(
                    f"adapter '{adapter}' (loaded: {loaded})"
                )
        if max_tokens == 0:
            # pure scoring (echo-only, enforced at parse): no decode at all
            results = [
                ([], [] if want_logprobs else None, [] if top_n else None,
                 None, "length")
            ] * n
            generated = 0
        else:
            results, generated = _fanout_generate(
                ctx, body, prompt_ids, max_tokens, sampler, stop_ids, stop_strs,
                want_logprobs, top_n, adapter, n, best_of,
            )
    choices = []
    for i, (out, logprobs, tops, text, finish) in enumerate(results):
        if text is None:
            text_ids = (prompt_ids + out) if echo else out
            text_val = tok.decode(text_ids) if tok is not None else ""
            finish = "length" if len(out) >= max_tokens else "stop"
        else:
            # host-matched stop truncation: the scanner's text IS the
            # completion (a tokenizer is guaranteed on this path, so the
            # tokens extension below never applies); echo prepends the
            # decoded prompt
            text_val = (tok.decode(prompt_ids) + text) if echo else text
        lp_list = logprobs
        lp_ids = out
        if prompt_lps is not None:
            lp_list = prompt_lps + (logprobs or [])
            lp_ids = prompt_ids + out
        lp_obj = None
        if lp_list is not None:
            lp_obj = _logprobs_obj(
                tok, lp_list, lp_ids, tops, top_n,
                prompt_positions=len(prompt_ids) if prompt_lps is not None
                else 0,
            )
        choice: dict[str, Any] = {
            "text": text_val,
            "index": i,
            "finish_reason": finish,
            "logprobs": lp_obj,
        }
        if tok is None:
            choice["tokens"] = (prompt_ids + out) if echo else out
        choices.append(choice)
    from gofr_tpu.http.response import Raw

    # OpenAI clients expect the completion object at the top level, not
    # inside this framework's {"data": ...} envelope
    return Raw({
        "id": cmpl_id,
        "object": "text_completion",
        "created": created,
        "model": model,
        "choices": choices,
        "usage": {
            "prompt_tokens": len(prompt_ids),
            "completion_tokens": generated,
            "total_tokens": len(prompt_ids) + generated,
        },
    })
