"""mfu.tpot: read in the traced window; moves tpot_p95_ms."""

from perfbench.readers import mfu as read  # noqa: F401
