"""CI smoke for the postmortem black box: boot the echo runner, inject
a synthetic device stall, and assert that a PARSEABLE postmortem bundle
lands on disk with the forensics an operator needs — the stalling
dispatch visible, thread stacks, timebase snapshots, and flight data.

    python tools/postmortem_smoke.py          # exit 0 = black box works

Compile-free (MODEL_NAME=echo, no XLA): safe for CPU-only CI runners.
Unlike the unit/e2e tests this exercises the FULL out-of-process
contract — the same bundle file a wedged process leaves on disk,
validated through tools/postmortem_view.py, the same way a human (or
the driver) would read it after the process is gone.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg: str) -> None:
    print(f"[pm-smoke] {msg}", flush=True)


def main() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    pm_dir = tempfile.mkdtemp(prefix="gofr_pm_smoke_")
    os.environ.update(
        HTTP_PORT=str(port),
        LOG_LEVEL="ERROR",
        MODEL_NAME="echo",
        TOKENIZER="byte",
        POSTMORTEM_DIR=pm_dir,
        TIMEBASE_INTERVAL_S="0.05",
        # 0.7s injected stall vs 0.1s deadline: degraded at 0.1s,
        # wedged (3x) at 0.3s — the wedge transition writes the bundle
        WATCHDOG_DISPATCH_TIMEOUT_S="0.1",
    )

    import gofr_tpu
    from gofr_tpu.openai_compat import register_openai_routes

    app = gofr_tpu.new()
    register_openai_routes(app)
    app.start()
    base = f"http://127.0.0.1:{port}"
    tpu = app.container.tpu
    assert tpu is not None, "echo TPU datasource failed to wire"
    try:
        # let the timebase accumulate pre-incident snapshots
        time.sleep(0.2)
        log("injecting 0.7s device stall")
        tpu.runner.stall_hook = lambda: time.sleep(0.7)

        def fire() -> None:
            req = urllib.request.Request(
                base + "/v1/chat/completions",
                data=json.dumps(
                    {"messages": [{"role": "user", "content": "stall"}],
                     "max_tokens": 1, "temperature": 0}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            urllib.request.urlopen(req, timeout=30).read()

        worker = threading.Thread(target=fire, name="pm-smoke-fire")
        worker.start()

        bundle_path = None
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline and bundle_path is None:
            bundles = sorted(
                n for n in os.listdir(pm_dir)
                if n.startswith("postmortem-") and n.endswith(".json")
            )
            if bundles:
                bundle_path = os.path.join(pm_dir, bundles[0])
                break
            time.sleep(0.05)
        worker.join()
        tpu.runner.stall_hook = None
        assert bundle_path, f"no bundle appeared in {pm_dir} within 15s"
        log(f"bundle written: {bundle_path}")

        # validate THROUGH the viewer — the same parser a human uses
        from tools import postmortem_view

        bundle = postmortem_view.load_bundle(bundle_path)
        d = postmortem_view.digest(bundle)
        log(f"digest: {json.dumps(d)}")
        assert bundle["reason"] == "wedged", bundle["reason"]
        assert d["engine_state"] == "wedged", d["engine_state"]
        assert d["stalled_watches"], "no stalled watchdog entry in bundle"
        stalled_ids = {w["dispatch_id"] for w in d["stalled_watches"]}
        running = set(d["dispatches_running"])
        assert stalled_ids & running, (
            f"stalling dispatch {stalled_ids} not visible as running "
            f"in the timeline ({running})"
        )
        assert d["timebase_snapshots"] >= 2, d["timebase_snapshots"]
        assert d["threads"] >= 2, d["threads"]
        assert d["requests_in_flight"] >= 1, "wedged request not in bundle"
        rc = postmortem_view.main([bundle_path])
        assert rc == 0, f"postmortem_view exited {rc}"
        log("postmortem black box OK")
        return 0
    finally:
        app.shutdown()


if __name__ == "__main__":
    sys.exit(main())
