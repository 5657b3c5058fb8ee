"""mfu.tps: read in the traced window; moves tokens_per_s."""

from perfbench.readers import mfu as read  # noqa: F401
