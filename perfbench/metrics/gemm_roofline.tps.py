"""gemm_roofline.tps: read in the traced window; moves tokens_per_s."""

from perfbench.readers import gemm_roofline as read  # noqa: F401
