"""Builds the wire response from a handler's (result, error) pair.

Parity: /root/reference/pkg/gofr/http/responder.go:11-62 — the
``{"data": ...}`` / ``{"error": {"message": ...}}`` JSON envelope (:59-62),
``Raw``/``File`` special-casing (:24-37), and status derived from the error
(:43-57 via gofr_tpu.errors.status_from_error). TPU-native addition:
``Stream`` results become chunked SSE responses for token decode endpoints.
"""

from __future__ import annotations

import json
from typing import Any, AsyncIterator, Optional

from gofr_tpu.errors import status_from_error
from gofr_tpu.http.response import File, Held, Raw, Response, Stream

_JSON = "application/json"


def _json_bytes(payload: Any) -> bytes:
    return json.dumps(payload, default=_jsonable, separators=(",", ":")).encode("utf-8")


def _jsonable(obj: Any) -> Any:
    # numpy / jax arrays and scalars serialize as lists / python scalars
    if hasattr(obj, "tolist"):
        return obj.tolist()
    if hasattr(obj, "item") and getattr(obj, "shape", None) == ():
        return obj.item()
    if hasattr(obj, "__dict__"):
        return obj.__dict__
    return str(obj)


def _frame_sse(item: Any, event_id: Optional[int] = None) -> bytes:
    if isinstance(item, bytes):
        data = item.decode("utf-8", "replace")
    elif isinstance(item, str):
        data = item
    else:
        data = json.dumps(item, default=_jsonable)
    prefix = f"id: {event_id}\n" if event_id is not None else ""
    return (prefix + "data: " + data + "\n\n").encode("utf-8")


async def _sse_iter(stream: Stream, executor: Any = None) -> AsyncIterator[bytes]:
    events = stream.events
    # resumable-stream numbering (Stream.ids): every frame carries a
    # monotonic SSE `id:` line anchored at id_offset, so a proxy (the
    # fleet router) can journal the last delivered offset and resume a
    # broken stream without missing or duplicated events
    next_id = stream.id_offset if stream.ids else None
    # client-abort detection: if this async generator is finalized
    # before the events exhausted — a write failure aborted the
    # connection, or the connection task was cancelled — the stream's
    # abort hook fires DIRECTLY (never via the events generator, which
    # may be suspended mid-next on a pool thread), so the generation's
    # stop event trips and its slot/KV free within one chunk
    completed = False
    try:
        if hasattr(events, "__aiter__"):
            async for item in events:  # type: ignore[union-attr]
                if stream.sse:
                    yield _frame_sse(item, next_id)
                    if next_id is not None:
                        next_id += 1
                else:
                    yield _to_bytes(item)
                # resumed: the server wrote that frame and asked for more
                if stream.on_write is not None:
                    stream.on_write(1)
        else:
            # Sync generators (e.g. blocking token decode) must not stall the
            # event loop between yields; pull on a worker thread —
            # the CALLER-provided pool (container.handler_executor), because a
            # stream's blocking next() holds its thread for the full
            # inter-token wait and asyncio's cpu_count+4 default executor
            # caps concurrent streams at a handful on small serving VMs.
            import asyncio

            loop = asyncio.get_running_loop()
            iterator = iter(events)  # type: ignore[arg-type]
            ready = stream.ready
            sentinel = object()

            def pull() -> list:
                # ONE trip to the thread for whatever the stream has: it
                # waits for the first item as ever, then takes each further
                # one that is ALREADY there (``Stream.ready`` says so) and
                # never waits for a second. Framed here, off the loop.
                nonlocal next_id
                frames: list = []
                item = next(iterator, sentinel)
                while item is not sentinel:
                    if stream.sse:
                        frames.append(_frame_sse(item, next_id))
                        if next_id is not None:
                            next_id += 1
                    else:
                        frames.append(_to_bytes(item))
                    if ready is None or not ready():
                        return frames
                    item = next(iterator, sentinel)
                frames.append(sentinel)
                return frames

            while True:
                frames = await loop.run_in_executor(executor, pull)
                ended = frames[-1] is sentinel
                if ended:
                    frames.pop()
                # all but the last are Held: the server writes them with it
                for frame in frames[:-1]:
                    yield Held(frame)
                if frames:
                    yield frames[-1]
                    # resumed: the server wrote them and asked for more
                    if stream.on_write is not None:
                        stream.on_write(len(frames))
                if ended:
                    break
        completed = True
    finally:
        if not completed and stream.on_abort is not None:
            try:
                stream.on_abort()
            except Exception:
                pass  # an abort hook must never mask the teardown


def _to_bytes(item: Any) -> bytes:
    if isinstance(item, bytes):
        return item
    if isinstance(item, str):
        return item.encode("utf-8")
    return _json_bytes(item)


def respond(
    result: Any, error: Optional[BaseException], executor: Any = None
) -> Response:
    """Parity: http/responder.go:19-41 (Respond's type switch).
    ``executor``: thread pool for pulling sync Stream items (the handler
    adapter passes the container's I/O-sized pool)."""
    if error is not None:
        status = status_from_error(error)
        if status == 500 and not hasattr(error, "status_code"):
            # Hide internals for unexpected errors (parity: the reference's
            # recovery path returns a generic message, middleware/logger.go:104).
            message = "some unexpected error has occurred"
        else:
            message = str(error) or error.__class__.__name__
        payload: dict[str, Any] = {"message": message}
        # shed verdicts echo the HASHED tenant id the admission gate
        # derived (never the raw key), so a 429'd client can quote the
        # exact id /admin/tenants and /admin/requests?tenant= rank under
        tenant = getattr(error, "tenant", None)
        if tenant:
            payload["tenant"] = tenant
        body = _json_bytes({"error": payload})
        headers = {"Content-Type": _JSON}
        # overload verdicts (brownout 429s, admission sheds) carry an
        # explicit backoff hint — bounded-queue discipline end to end
        retry_after = getattr(error, "retry_after_s", None)
        if isinstance(retry_after, (int, float)) and retry_after > 0:
            headers["Retry-After"] = str(max(1, int(retry_after + 0.999)))
        return Response(status=status, headers=headers, body=body)

    if isinstance(result, Response):
        return result
    if isinstance(result, Raw):
        return Response(status=200, headers={"Content-Type": _JSON}, body=_json_bytes(result.data))
    if isinstance(result, File):
        return Response(
            status=200, headers={"Content-Type": result.content_type}, body=result.content
        )
    if isinstance(result, Stream):
        headers = {
            "Content-Type": result.content_type,
            "Cache-Control": "no-cache",
            "X-Accel-Buffering": "no",
        }
        return Response(status=200, headers=headers, stream=_sse_iter(result, executor))

    body = _json_bytes({"data": result})
    return Response(status=200, headers={"Content-Type": _JSON}, body=body)
