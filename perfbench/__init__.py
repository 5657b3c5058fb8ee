"""On-chip benchmark of photonic-GEMM LM serving (see ``perfbench/run.py``)."""
