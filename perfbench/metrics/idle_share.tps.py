"""idle_share.tps: read in the traced window; moves tokens_per_s."""

from perfbench.readers import idle_share as read  # noqa: F401
