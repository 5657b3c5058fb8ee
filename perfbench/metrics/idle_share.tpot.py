"""idle_share.tpot: read in the traced window; moves tpot_p95_ms."""

from perfbench.readers import idle_share as read  # noqa: F401
