"""The pooled decode program of a retention model: DECODE_CHUNK steps a run,
each reading every served weight once and reading and writing the state of
every live row once. What a step MUST move: a row that is not live owes
nothing, whatever the program does with it."""

from __future__ import annotations

from benchmark import model_work as mw


def state_row_bytes(run) -> int:
    """One row's state in one layer, S [d, phi] and z [phi] per kv head."""
    sz = run.sizes
    width = 2 if run.server_env.get("MODEL_KV_DTYPE", "") in ("bf16", "bfloat16") else 4
    return sz["kv_heads"] * (sz["head_dim"] + 1) * sz["phi"] * width


def gate_bytes(sz: dict) -> float:
    return 2.0 * sz["layers"] * sz["dim"] * sz["kv_heads"]


def live_rows(run) -> float:
    chunks = [d for d in run.dispatches if d["kind"] == "decode_chunk"]
    return sum(d["batch_size"] or 0 for d in chunks) / max(len(chunks), 1)


def step_work(run) -> tuple[float, float, float]:
    """(flops, weight bytes, state bytes) of ONE step at the window's mean
    live rows."""
    sz, rows = run.sizes, live_rows(run)
    state = rows * sz["layers"] * 2 * state_row_bytes(run)
    # the update v phi(k)^T and the read-out S phi(q), z . phi(q), per kv head
    per_head = 2.0 * (sz["head_dim"] + 1) * sz["phi"] * (1 + sz["heads"] // sz["kv_heads"])
    flops = mw.forward_flops(sz, rows, rows) + rows * sz["layers"] * sz["kv_heads"] * per_head
    return flops, mw.weight_bytes(sz) + gate_bytes(sz), state


def work(run, runs: int) -> tuple[float, float]:
    """(flops, bytes) the traced ``runs`` of the program had to do."""
    steps = runs * int(run.server_env.get("DECODE_CHUNK", "8"))
    flops, weights, state = step_work(run)
    return steps * flops, steps * (weights + state)
