"""RPR001 — JAX compat-sensitive symbols only inside ``repro/compat.py``.

The symbol inventory is imported from :mod:`repro.compat` itself (the
``COMPAT_SENSITIVE_*`` registry), so adding a shim and banning direct use
of the raw symbol are one edit. Replaces the ROADMAP ``rg`` spot-check.
"""

from __future__ import annotations

import ast
from typing import Iterable

from repro.analysis.core import Finding, Rule, dotted_name, register_rule
from repro.compat import (
    COMPAT_SENSITIVE_ATTRS,
    COMPAT_SENSITIVE_MODULES,
    COMPAT_SENSITIVE_NAMES,
)

# compat.py holds the wrappers; test_compat.py exercises them on purpose.
_EXEMPT = ("src/repro/compat.py", "tests/test_compat.py")


@register_rule
class CompatIsolationRule(Rule):
    id = "RPR001"
    summary = "version-sensitive JAX symbol referenced outside repro.compat"
    rationale = (
        "JAX symbols that moved or changed signature in past releases "
        "(shard_map, AxisType, AbstractMesh, make_mesh, axis_size) are "
        "reached through repro.compat, so the next move is a one-file edit."
    )

    def applies_to(self, relpath: str) -> bool:
        return relpath not in _EXEMPT

    def check(self, tree: ast.Module, text: str, relpath: str) -> Iterable[Finding]:
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name in COMPAT_SENSITIVE_MODULES:
                        yield self.finding(
                            relpath,
                            node,
                            f"import of version-sensitive module "
                            f"{alias.name!r}; use repro.compat",
                        )
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod in COMPAT_SENSITIVE_MODULES:
                    yield self.finding(
                        relpath,
                        node,
                        f"import from version-sensitive module {mod!r}; "
                        "use repro.compat",
                    )
                elif mod == "jax" or mod.startswith("jax."):
                    for alias in node.names:
                        if alias.name in COMPAT_SENSITIVE_NAMES:
                            yield self.finding(
                                relpath,
                                node,
                                f"from-import of version-sensitive "
                                f"{alias.name!r} from {mod!r}; import it "
                                "from repro.compat",
                            )
            elif isinstance(node, ast.Attribute):
                dotted = dotted_name(node)
                if dotted in COMPAT_SENSITIVE_ATTRS:
                    yield self.finding(
                        relpath,
                        node,
                        f"{dotted} is version-sensitive; use the "
                        "repro.compat equivalent",
                    )
