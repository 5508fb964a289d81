"""Median host time to prepare and enqueue a prefill program
(DispatchRecord ``issue_s``, kinds prefill and prefill_chunk); the rest of
``step.prefill_p50_ms`` is the wait for the device."""
from benchmark.span_readers import prefill_issue_p50_ms as read  # noqa: F401
