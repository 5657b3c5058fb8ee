"""decode_step_ms.tpot: read in the traced window; moves tpot_p95_ms."""

from perfbench.readers import decode_step_ms as read  # noqa: F401
