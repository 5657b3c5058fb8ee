"""prefill_ms_per_ktok.tps: read in the traced window; moves tokens_per_s."""

from perfbench.readers import prefill_ms_per_ktok as read  # noqa: F401
