"""RPR003 — models/runtime route every GEMM through the engine surface.

The sanctioned entry points are ``repro.models.common.dense(site=...)``,
``engine.matmul``/``matmul_float`` and the ``repro.photonic.sharded``
contexts. Direct calls into the kernel backends (Pallas kernel, reference
int GEMM) from model or runtime code bypass routing policy, seed
derivation, and prepacking — the exact machinery the PR-3/PR-4 results
depend on.
"""

from __future__ import annotations

import ast
from typing import Iterable

from repro.analysis.core import Finding, Rule, dotted_name, register_rule

# Backend entry points that only repro.photonic / repro.kernels may touch.
_BACKEND_NAMES = frozenset(
    {
        "photonic_gemm_pallas",
        "photonic_gemm_ref",
        "exact_int_gemm",
        "int_gemm",
        "psum_int_gemm",
        "_packed_matmul",
    }
)

_SCOPED_PREFIXES = ("src/repro/models/", "src/repro/runtime/")


@register_rule
class EngineRoutingRule(Rule):
    id = "RPR003"
    summary = "direct kernel-backend call outside repro.photonic"
    rationale = (
        "Models and runtime must route GEMMs via dense(site=...) or "
        "engine.matmul*; calling kernel backends directly skips the "
        "engine's routing policy, per-site seed derivation, and the "
        "weight-stationary prepacked path."
    )

    def applies_to(self, relpath: str) -> bool:
        return relpath.startswith(_SCOPED_PREFIXES)

    def check(self, tree: ast.Module, text: str, relpath: str) -> Iterable[Finding]:
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod == "repro.kernels" or mod.startswith("repro.kernels."):
                    yield self.finding(
                        relpath,
                        node,
                        f"import from kernel backend {mod!r}; route via "
                        "dense(site=...) / engine.matmul*",
                    )
                    continue
                for alias in node.names:
                    if alias.name in _BACKEND_NAMES:
                        yield self.finding(
                            relpath,
                            node,
                            f"import of backend entry point {alias.name!r}; "
                            "route via dense(site=...) / engine.matmul*",
                        )
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.startswith("repro.kernels"):
                        yield self.finding(
                            relpath,
                            node,
                            f"import of kernel backend {alias.name!r}; "
                            "route via dense(site=...) / engine.matmul*",
                        )
            elif isinstance(node, ast.Call):
                func = node.func
                name = None
                if isinstance(func, ast.Attribute):
                    name = func.attr
                elif isinstance(func, ast.Name):
                    name = func.id
                if name in _BACKEND_NAMES:
                    dotted = dotted_name(func) or name
                    yield self.finding(
                        relpath,
                        node,
                        f"direct kernel-backend call {dotted}(); route via "
                        "dense(site=...) / engine.matmul*",
                    )
