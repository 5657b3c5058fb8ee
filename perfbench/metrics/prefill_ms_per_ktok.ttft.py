"""prefill_ms_per_ktok.ttft: read in the traced window; moves ttft_p95_ms."""

from perfbench.readers import prefill_ms_per_ktok as read  # noqa: F401
