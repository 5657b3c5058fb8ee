"""host_idle_ms.tps: read from the program's span log; moves tokens_per_s."""

from perfbench.spanlog import host_idle_ms as read  # noqa: F401
