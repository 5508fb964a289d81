"""POST /v1/chat/completions: messages -> assistant message. Same
generation core as completions; only prompt construction (chat
template) and response shapes differ."""

from __future__ import annotations

import time
import uuid
from collections import deque
from typing import Any

from gofr_tpu.openai.fanout import _fanout_generate
from gofr_tpu.openai.logprobs import _chat_logprobs_obj, _chat_lp_entry
from gofr_tpu.openai.parse import (
    _StopScanner,
    _parse_fanout,
    _parse_request,
    _stream_usage_opt,
)
from gofr_tpu.openai.template import render_chat_prompt

from gofr_tpu.errors import HTTPError


def _stream_chat(
    ctx: Any, body: dict, prompt_ids: list, max_tokens: int, sampler: Any,
    stop_ids: Any, stop_strs: list, want_logprobs: bool, top_n: int,
    adapter: Any, n: int, chat_id: str, created: int, model: str,
    tok: Any, include_usage: bool = False,
) -> Any:
    """The SSE branch of /v1/chat/completions: delta chunks with the
    role first, host-side stop matching, terminated by [DONE]. ``n`` > 1
    streams candidates concurrently as interleaved chunks carrying their
    choice ``index`` (greedy requests replicate one stream — the
    non-stream fan-out's replication rule)."""
    if top_n:
        raise HTTPError(
            400, "top-logprob alternatives are not supported when "
            "streaming; drop \"stream\" or request chosen-token "
            "logprobs only"
        )
    import json as _json

    from gofr_tpu.http.response import Stream

    def chunk(delta: dict, finish: Any = None, lp: Any = None,
              token_id: Any = None, index: int = 0) -> str:
        choice: dict[str, Any] = {
            "index": index, "delta": delta, "finish_reason": finish,
        }
        if want_logprobs:
            if lp is not None and token_id is not None:
                e = _chat_lp_entry(tok, token_id, lp)
                e["top_logprobs"] = []  # alternatives reject with stream
                choice["logprobs"] = {
                    # the modern chat shape stock SDKs parse, plus
                    # the legacy field this server has always sent
                    "content": [e],
                    "token_logprobs": [lp],
                }
            else:
                choice["logprobs"] = None
        frame = {
            "id": chat_id, "object": "chat.completion.chunk",
            "created": created, "model": model, "choices": [choice],
        }
        if include_usage:
            frame["usage"] = None
        return _json.dumps(frame)

    def usage_frame(completion_tokens: int) -> str:
        from gofr_tpu.openai.fanout import _usage_chunk

        return _usage_chunk("chat.completion.chunk", chat_id, created, model,
                            len(prompt_ids), completion_tokens)

    if n > 1:
        return _stream_chat_fanout(
            ctx, body, prompt_ids, max_tokens, sampler, stop_ids,
            stop_strs, want_logprobs, adapter, n, chunk, tok,
            usage_frame if include_usage else None,
        )

    from gofr_tpu.openai.parse import _abortable
    from gofr_tpu.telemetry import current_record

    cancel, on_abort = _abortable(ctx)
    stream_iter = ctx.tpu.generate_stream(
        prompt_ids, max_tokens, sampler=sampler, stop_tokens=stop_ids,
        adapter=adapter, logprobs=want_logprobs, cancel=cancel,
    )
    record = current_record()  # here, on the handler's thread: events() runs elsewhere
    # token frames built and not yet pulled (``Stream.ready``): the tokens
    # that are there together become frames before the first is handed on,
    # so that one pull of the responder takes them all and none waits (a
    # token that decodes to no text leaves no frame to wait behind)
    held: deque = deque()

    def events():
        emitted = 0
        finish = None
        dec = tok.stream_decoder()
        scan = _StopScanner(stop_strs) if stop_strs else None
        yield chunk({"role": "assistant"})  # role arrives first
        try:
            for item in stream_iter:
                token, lp = item if want_logprobs else (item, None)
                emitted += 1
                text = dec.feed(token)
                if scan is not None:
                    text, done = scan.feed(text)
                    if done:
                        if text:
                            # no lp: the matched token's text is
                            # excluded from the stream
                            held.append(chunk({"content": text}))
                        finish = "stop"
                        break
                if text or lp is not None:
                    held.append(chunk({"content": text}, lp=lp, token_id=token))
                elif record is not None:
                    record.note_unframed()  # its text rides a later frame
                if not stream_iter.ready():  # the next token is a wait away
                    while held:
                        yield held.popleft()
            while held:  # a stop leaves the loop with its frames built
                yield held.popleft()
            if record is not None:
                record.end_token_frames()  # a stop may leave tokens unframed
            tail = dec.flush()
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
            if tail:
                yield chunk({"content": tail})
            yield chunk({}, finish)
            if include_usage:
                yield usage_frame(emitted)
            yield "[DONE]"
        except Exception as exc:
            yield _json.dumps({"error": {"message": str(exc)}})
        finally:
            stream_iter.close()  # no-op if already exhausted

    # ids=True: frames carry monotonic SSE ids so the fleet router can
    # resume a deterministic chat stream by replaying from zero and
    # filtering already-delivered frames (chat frames are not 1:1 with
    # tokens, so there is no replica-side X-Resume-From shortcut here)
    return Stream(events(), ids=True, on_abort=on_abort, ready=held.__len__)


def _stream_chat_fanout(
    ctx: Any, body: dict, prompt_ids: list, max_tokens: int, sampler: Any,
    stop_ids: Any, stop_strs: list, want_logprobs: bool, adapter: Any,
    n: int, chunk: Any, tok: Any, usage_frame: Any = None,
) -> Any:
    """Interleaved multi-index chat SSE: n candidates stream
    concurrently, each delta carrying its choice ``index``; every index
    opens with its own role chunk and closes with its own finish. The
    shared driver (_drive_stream_fanout) owns the replicate/multiplex
    loops, stop-cancellation, and cleanup; this function supplies only
    the chat frame shapes."""
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
    decs = [tok.stream_decoder() for _ in range(n)]
    scans = [_StopScanner(stop_strs) if stop_strs else None
             for _ in range(n)]
    emitted = [0] * n
    finish: list = [None] * n

    def open_frames():
        for i in range(n):
            yield chunk({"role": "assistant"}, index=i)

    def feed(i, token, lp):
        text, stopped = _index_feed_text(
            decs[i], scans[i], finish, i, emitted, token
        )
        if stopped:  # the matched token's lp is excluded with its text
            return [chunk({"content": text}, index=i)] if text else []
        if text or lp is not None:
            return [chunk({"content": text}, lp=lp, token_id=token,
                          index=i)]
        if record is not None:
            record.note_unframed()  # its text rides a later frame
        return []

    def tail(i):
        t = _index_tail_text(decs[i], scans[i], finish, i, emitted,
                             max_tokens)
        frames = []
        if t:
            frames.append(chunk({"content": t}, index=i))
        frames.append(chunk({}, finish[i], index=i))
        return frames

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


def chat_completions(ctx: Any) -> Any:
    """Messages -> assistant message. Same generation core as
    ``completions``; only the prompt construction (chat template) and the
    response shapes (chat.completion / chat.completion.chunk with deltas)
    differ."""
    (body, max_tokens, sampler, stop_ids, stop_strs, want_logprobs, top_n,
     adapter) = _parse_request(ctx, default_max=64)
    tok = ctx.tpu.tokenizer
    if tok is None:
        raise HTTPError(
            400, "chat completions need a tokenizer (set TOKENIZER_PATH)"
        )
    prompt_text = render_chat_prompt(ctx, body.get("messages"))
    prompt_ids = tok.encode(prompt_text)
    if not prompt_ids:
        raise HTTPError(400, "messages encoded to zero tokens")
    model = adapter or ctx.tpu.model_name  # adapters serve under their name
    # gofrlint: wall-clock — OpenAI API `created` is epoch seconds by contract
    created = int(time.time())
    chat_id = f"chatcmpl-{uuid.uuid4().hex[:24]}"

    n, _, _ = _parse_fanout(body, allow_best_of=False)
    if top_n and stop_strs:
        raise HTTPError(
            400, "top-logprob alternatives with multi-token stop "
            'sequences are not supported; use "stop_token_ids"'
        )

    include_usage = _stream_usage_opt(body)  # validates even sans stream
    # flight record (rides a contextvar so the batcher/pool/device stamp
    # it downstream); the Flight guard owns ok/error/drop semantics
    from gofr_tpu.telemetry import flight

    with flight(
        getattr(ctx.container, "telemetry", None),
        model=model, endpoint="/v1/chat/completions",
        trace_id=ctx.trace_id or "", tokens_in=len(prompt_ids),
        stream=bool(body.get("stream")),
        t_received=getattr(ctx.request, "t_received", None),
    ) as fl:
        if body.get("stream"):
            # defer: the record completes when the stream ends
            return fl.defer(_stream_chat(
                ctx, body, prompt_ids, max_tokens, sampler, stop_ids,
                stop_strs, want_logprobs, top_n, adapter, n, chat_id,
                created, model, tok, include_usage,
            ))
        results, generated = _fanout_generate(
            ctx, body, prompt_ids, max_tokens, sampler, stop_ids, stop_strs,
            want_logprobs, top_n, adapter, n, n,
        )
    from gofr_tpu.http.response import Raw

    choices = [
        {
            "index": i,
            "message": {
                "role": "assistant",
                "content": text if text is not None else tok.decode(out),
            },
            "finish_reason": (
                finish if finish is not None
                else ("length" if len(out) >= max_tokens else "stop")
            ),
            "logprobs": (
                _chat_logprobs_obj(tok, logprobs, out, tops, top_n)
                if logprobs is not None else None
            ),
        }
        for i, (out, logprobs, tops, text, finish) in enumerate(results)
    ]
    return Raw({
        "id": chat_id,
        "object": "chat.completion",
        "created": created,
        "model": model,
        "choices": choices,
        "usage": {
            "prompt_tokens": len(prompt_ids),
            "completion_tokens": generated,
            "total_tokens": len(prompt_ids) + generated,
        },
    })
