"""decode_step_ms.tps: read in the traced window; moves tokens_per_s."""

from perfbench.readers import decode_step_ms as read  # noqa: F401
