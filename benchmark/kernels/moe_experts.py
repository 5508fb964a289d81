"""The routed expert product of a ``cca_moe`` model (the operations named
``moe_experts*`` in a trace: ``gofr_tpu/ops/experts.py``), from what routing
did (DispatchRecord ``experts_read`` and ``expert_tokens``, summed over a
dispatch's steps and layers): every expert that got a token is read once
(gate, up and down: 3 x hidden x width weights) and every routed token
multiplies one expert's. What the product MUST do, whatever implements it:
an expert without a token owes nothing, a token pays for one expert."""

from __future__ import annotations


def expert_bytes(sz: dict) -> float:
    """One expert's three matrices as served (bf16)."""
    return 2.0 * 3 * sz["dim"] * sz["ffn"]


def token_flops(sz: dict) -> float:
    """One token through one expert."""
    return 2.0 * 3 * sz["dim"] * sz["ffn"]


def traced_span(run) -> tuple[float, float] | None:
    """(from, to) in seconds after the window's opening of what a traced
    run's profiler held (``run.py::trace_capture``: ``trace_s`` seconds from
    one second in); None for a run that does not say."""
    mix = getattr(run, "mix", None)
    if getattr(run, "trace", None) is None or not mix:
        return None
    return 1.0, 1.0 + float(mix.get("trace_s", 4.0))


def routed(run, kinds: tuple[str, ...], traced: bool = False) -> list[dict]:
    """The window's finished dispatches of ``kinds`` that carry the counters;
    ``traced``: of those, the ones begun while the profiler ran (a window's
    first seconds hold fewer live rows than its mean: the sheet then counts
    the routing of the steps whose time the trace holds), all of them where
    the run does not say when that was."""
    records = [d for d in run.dispatches if d["kind"] in kinds and d["status"] == "ok"
               and d.get("experts_read") is not None]
    span, wall0 = traced_span(run), getattr(run, "wall0", None)
    if traced and span and wall0:
        records = [d for d in records
                   if wall0 + span[0] <= (d.get("start_ts") or 0.0) < wall0 + span[1]] or records
    return records


def mean_work(run, kinds: tuple[str, ...], traced: bool = False) -> tuple[float, float]:
    """(flops, bytes) of the product in ONE dispatch of ``kinds``, the
    window's mean (``traced``: the mean of those begun under the profiler)."""
    records = routed(run, kinds, traced)
    if not records:
        return 0.0, 0.0
    return (token_flops(run.sizes) * sum(d["expert_tokens"] for d in records) / len(records),
            expert_bytes(run.sizes) * sum(d["experts_read"] for d in records) / len(records))


def work(run, decode_runs: int, prefill_runs: int) -> tuple[float, float]:
    """(flops, bytes) of the product in the traced runs of the pooled decode
    program and of the prefill programs."""
    decode = mean_work(run, ("decode_chunk",), traced=True)
    prefill = mean_work(run, ("prefill", "prefill_chunk"))
    return (decode_runs * decode[0] + prefill_runs * prefill[0],
            decode_runs * decode[1] + prefill_runs * prefill[1])
