"""The arrows point one way (ROADMAP D19, D16): ``models/`` -> the kernels
of ``ops/`` -> ``ops/kernel_parts.py``. No file under ``ops/`` takes an
underscore name from a sibling (what two kernel files share has a public
name in ``kernel_parts``), and neither the kernels nor the models know the
metrics plane: an instrument lands with its reader or not at all, and a
test asks the planning function. A file is a case."""

import ast
import glob
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "horovod_tpu")
OPS = sorted(glob.glob(os.path.join(PACKAGE, "ops", "*.py")))
KERNELS = [os.path.join(PACKAGE, "ops", name + ".py") for name in (
    "attention", "linear_attention", "ssd", "rotary_split", "kernel_parts")]
MODELS = sorted(glob.glob(os.path.join(PACKAGE, "models", "*.py")))


def imports(path):
    """``(module as written, name or None)`` of every import of ``path``,
    at its top level or inside a function: ``from .a import b`` gives
    ``(".a", "b")``, ``import a.b`` gives ``("a.b", None)``."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from ((alias.name, None) for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            origin = "." * node.level + (node.module or "")
            yield from ((origin, alias.name) for alias in node.names)


def short(path):
    return os.path.relpath(path, PACKAGE)


@pytest.mark.parametrize("path", OPS, ids=short)
def test_no_file_of_ops_takes_a_private_name_from_a_sibling(path):
    siblings = {os.path.splitext(os.path.basename(other))[0]
                for other in OPS}
    taken = [(module, name) for module, name in imports(path)
             if name and name.startswith("_") and not name.startswith("__")
             and module.startswith(".") and not module.startswith("..")
             and module.lstrip(".").split(".")[0] in siblings | {""}]
    assert not taken, taken


@pytest.mark.parametrize("path", KERNELS + MODELS, ids=short)
def test_no_kernel_and_no_model_knows_the_metrics_plane(path):
    assert os.path.exists(path), path
    known = [(module, name) for module, name in imports(path)
             if "metrics" in (module.split(".") + [name])]
    assert not known, known
