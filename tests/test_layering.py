"""The package's layers import one way.  Every module of the hot path's
sub-packages is held, by ``ast`` (imports inside functions included), to
the sub-packages its layer may know.  The table below is the only place
the layering is written down."""

import ast
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "chainermn_tpu"

#: sub-package -> the other sub-packages its modules may import.
ALLOWED = {
    "observability": set(),
    "sharding": set(),
    "communicators": {"observability"},
    "ops": {"observability"},
    "parallel": {"communicators", "ops", "sharding"},
    "models": {"ops", "parallel", "observability"},
}

#: the known upward imports, each a named debt in ROADMAP.md:
#: module -> the one ``chainermn_tpu.*`` module it may reach outside its
#: layer's edges.
EXCEPTIONS = {
    "observability/exporter.py": "chainermn_tpu.tools.obs",
    "ops/decode_attention.py": "chainermn_tpu.communicators.quant",
    "models/transformer.py": "chainermn_tpu.communicators.quant",
}


def _modules():
    for layer in sorted(ALLOWED):
        for name in sorted(os.listdir(os.path.join(ROOT, PACKAGE, layer))):
            if name.endswith(".py"):
                yield f"{layer}/{name}"


def _package_imports(module):
    """Absolute names of every ``chainermn_tpu.*`` module that the file
    imports, relative imports resolved."""
    here = [PACKAGE] + module.split("/")[:-1]
    with open(os.path.join(ROOT, PACKAGE, module)) as f:
        tree = ast.parse(f.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - (node.level - 1)] if node.level else []
            stem = ".".join(base + ([node.module] if node.module else []))
            if node.module is None or stem == PACKAGE:
                found.update(f"{stem}.{a.name}" for a in node.names)
            else:
                found.add(stem)
    return {m for m in found if m.split(".")[0] == PACKAGE}


@pytest.mark.parametrize("module", list(_modules()))
def test_module_imports_stay_inside_its_layer(module):
    layer = module.split("/")[0]
    outside = {
        m for m in _package_imports(module)
        if (m.split(".") + [""])[1] not in ALLOWED[layer] | {layer}
    }
    excepted = {EXCEPTIONS[module]} if module in EXCEPTIONS else set()
    assert outside == excepted, (
        f"{module} imports {sorted(outside)}: {layer} may import only "
        f"{sorted(ALLOWED[layer])}, and this module {sorted(excepted)}"
    )
