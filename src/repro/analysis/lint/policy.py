"""Repo-policy rules: the ROADMAP conventions, promoted from the CI
``policy`` job's shell greps to import-graph analysis.

Each rule documents the convention it enforces and the PR that
motivated it; the catalog with suppression guidance is docs/lint.md.
"""
from __future__ import annotations

import ast
from typing import List

from repro.analysis.lint.base import (ModuleCtx, Rule, Violation, dotted,
                                      function_scoped_nodes,
                                      under_type_checking, walk_imports)

# The only module allowed to import jax.experimental.pallas.tpu (PR 1:
# version-portable TPU symbol resolution lives in exactly one place).
TPU_IMPORTER = "repro/kernels/compat.py"

# Deprecated round factories (PR 2): everything constructs rounds via
# repro.api.fed_round.  Their own module and the shim==facade tests are
# the only legitimate references.
DEPRECATED_FACTORIES = {"make_window_fed_round", "make_mask_fed_round"}
FACTORY_HOME = "repro/core/fedavg.py"

# Layering (PR 7): repro.fleet drives the round object handed to it and
# never constructs rounds — importing the facade or the round factories
# from inside the package would invert the layering.
FLEET_PKG = "repro/fleet/"
FLEET_FORBIDDEN = ("repro.api", "repro.core.fedavg")

# Modules that are numpy-only by contract: importing jax at module scope
# would make their consumers (subprocess samplers, checkpoint inspection,
# the no-install CI policy job) pay a jax import they never use.  The
# linter package itself is on the list — it must stay stdlib-only.
NUMPY_ONLY = {
    "repro/fleet/__init__.py",
    "repro/fleet/sampler.py",
    "repro/fleet/buffer.py",
    "repro/fleet/simulator.py",
    "repro/data/federated.py",
    "repro/data/synthetic.py",
    "repro/analysis/hlo_check.py",
    "repro/checkpoint/checkpoint.py",
}
NUMPY_ONLY_PREFIXES = ("repro/analysis/lint/",)
LAZY_FORBIDDEN_ROOTS = ("jax", "jaxlib")


def _is_pallas_tpu_import(module: str, names: List[str]) -> bool:
    if module.startswith("jax.experimental.pallas.tpu"):
        return True
    return module == "jax.experimental.pallas" and "tpu" in names


def check_sole_tpu_importer(ctx: ModuleCtx) -> List[Violation]:
    if ctx.module == TPU_IMPORTER:
        return []
    out = []
    for node, module, names in walk_imports(ctx.tree):
        if _is_pallas_tpu_import(module, names):
            out.append(ctx.violation(
                node, "sole-tpu-importer",
                "jax.experimental.pallas.tpu imported outside "
                "kernels/compat.py; route TPU symbols through "
                "repro.kernels.compat"))
    return out


def check_api_facade(ctx: ModuleCtx) -> List[Violation]:
    if ctx.module == FACTORY_HOME or ctx.is_test():
        return []
    out = []
    for node, module, names in walk_imports(ctx.tree):
        hit = sorted(DEPRECATED_FACTORIES & set(names))
        if hit:
            out.append(ctx.violation(
                node, "api-facade",
                f"deprecated round factory import ({', '.join(hit)}); "
                "construct rounds via repro.api.fed_round"))
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            fn = node.func
            name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            if name in DEPRECATED_FACTORIES:
                out.append(ctx.violation(
                    node, "api-facade",
                    f"deprecated round factory call {name}(); construct "
                    "rounds via repro.api.fed_round"))
    return out


def check_fleet_layering(ctx: ModuleCtx) -> List[Violation]:
    if not (ctx.module or "").startswith(FLEET_PKG):
        return []
    out = []
    for node, module, names in walk_imports(ctx.tree):
        bad = None
        for target in FLEET_FORBIDDEN:
            if module == target or module.startswith(target + "."):
                bad = target
        if module == "repro" and "api" in names:
            bad = "repro.api"
        if module == "repro.core" and "fedavg" in names:
            bad = "repro.core.fedavg"
        if bad:
            out.append(ctx.violation(
                node, "fleet-layering",
                f"repro.fleet imports {bad}: fleet/ drives round objects "
                "built by repro.api.fed_round and must never construct "
                "them"))
    return out


def check_lazy_jax_import(ctx: ModuleCtx) -> List[Violation]:
    mod = ctx.module or ""
    if mod not in NUMPY_ONLY and not mod.startswith(NUMPY_ONLY_PREFIXES):
        return []
    inner = function_scoped_nodes(ctx.tree)
    typing_only = under_type_checking(ctx.tree)
    out = []
    for node, module, names in walk_imports(ctx.tree):
        if id(node) in inner or id(node) in typing_only:
            continue
        root = module.split(".", 1)[0]
        if root in LAZY_FORBIDDEN_ROOTS:
            out.append(ctx.violation(
                node, "lazy-jax-import",
                f"module-scope import of {module or root} in the "
                "numpy-only module "
                f"{mod}; defer it into the function that needs it so "
                "jax-free consumers never pay the import"))
    return out


RULES = [
    Rule("sole-tpu-importer",
         "kernels/compat.py is the only importer of "
         "jax.experimental.pallas.tpu",
         check_sole_tpu_importer),
    Rule("api-facade",
         "no imports/calls of the deprecated make_*_fed_round factories "
         "outside core/fedavg.py",
         check_api_facade),
    Rule("fleet-layering",
         "repro.fleet never imports repro.api or repro.core.fedavg",
         check_fleet_layering),
    Rule("lazy-jax-import",
         "declared numpy-only modules must not import jax at module "
         "scope",
         check_lazy_jax_import),
]
