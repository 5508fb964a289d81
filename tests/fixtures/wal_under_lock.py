"""Regression fixture: the PR 14 journal/WAL hazard, committed so the
interprocedural GFL004 pass can never silently lose the shape that
motivated it (tests/test_gofrlint.py asserts this file IS flagged).

The hazard: ``Journal.record`` holds the per-journal lock while calling
``self._wal.append_tokens`` — a method on a DIFFERENT object whose body
reaches ``os.fsync`` two hops down. No single function both holds the
lock and blocks, so the per-file rule is structurally blind to it; the
whole-program pass resolves ``self._wal`` to :class:`WalWriter` from
the ``__init__`` assignment and carries may-block through the chain.

(The fsync inside :class:`WalWriter` under WalWriter's OWN lock is the
resource-guard shape the analysis deliberately exempts — the finding
must land on the cross-object reach-through in ``Journal.record``.)

This file is a lint fixture, not production code: it lives outside the
tree gate's paths (gofr_tpu/, tools/) and is linted only by
its own test.
"""

import os
import threading


class WalWriter:
    """Minimal segmented-WAL stand-in: append then durability barrier."""

    def __init__(self, path):
        self._fd = os.open(path, os.O_WRONLY | os.O_CREAT, 0o600)
        self._lock = threading.Lock()

    def append_tokens(self, payload):
        with self._lock:
            self._write(payload)
            self._sync()

    def _write(self, payload):
        os.write(self._fd, payload)

    def _sync(self):
        os.fsync(self._fd)


class Journal:
    """Minimal generation-journal stand-in with the hazardous shape."""

    def __init__(self, path):
        self._lock = threading.Lock()
        self._entries = {}
        self._wal = WalWriter(path)

    def record(self, request_id, payload):
        with self._lock:
            self._entries[request_id] = payload
            # HAZARD (intentional): a device-speed durability barrier
            # runs while every other journal operation is locked out
            self._wal.append_tokens(payload)
