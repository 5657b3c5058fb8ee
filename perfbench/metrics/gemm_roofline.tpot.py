"""gemm_roofline.tpot: read in the traced window; moves tpot_p95_ms."""

from perfbench.readers import gemm_roofline as read  # noqa: F401
