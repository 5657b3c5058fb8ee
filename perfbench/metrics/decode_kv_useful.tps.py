"""decode_kv_useful.tps: read from the program's span log; moves tokens_per_s."""

from perfbench.spanlog import decode_kv_useful as read  # noqa: F401
