"""The serving stack's imports go one way (PR 42):

    serving/ -> engine_v2 -> model_implementations/ -> modules/
             -> kernels/, ops/, ragged/        (ragged/ imports none of them)

read from the source with ``ast``, a case a file, so that the next family
(or the next shared layer) that reaches sideways or upwards fails by name:

* a ``model_implementations/ragged_<family>.py`` imports no sibling but for
  a class it subclasses;
* ``kernels/`` and ``ragged/`` import nothing from ``modules/`` or
  ``model_implementations/``, ``ragged/`` nothing from ``kernels/`` either,
  ``modules/`` nothing from ``model_implementations/``, and none of them
  the engine or ``serving/``;
* ``engine_v2.py`` imports no single family's file at module level;
* ``serving/scheduler.py`` asks the engine no capability through an
  underscore attribute.
"""

import ast
import glob
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
V2 = os.path.join(ROOT, "deepspeed_tpu", "inference", "v2")
PKG = "deepspeed_tpu.inference.v2."


def _tree(path):
    with open(path) as f:
        return ast.parse(f.read(), path)


def _imports(tree, module_level_only=False):
    """``(module, name)`` of every import in ``tree`` (``import a.b`` gives
    ``("a.b", None)``); with ``module_level_only`` those of the module's own
    body, not of its functions."""
    nodes = tree.body if module_level_only else ast.walk(tree)
    out = []
    for node in nodes:
        if isinstance(node, ast.Import):
            out += [(a.name, None) for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            out += [(node.module, a.name) for a in node.names]
    return out


def _files(*parts):
    return sorted(glob.glob(os.path.join(V2, *parts)))


def _rel(path):
    return os.path.relpath(path, V2)


#: directory -> the packages under inference/v2 (and beside it) that none of
#: its files may import
LOWER_LAYERS = {
    "kernels": ("modules", "model_implementations", "engine_v2"),
    "ragged": ("modules", "model_implementations", "engine_v2", "kernels"),
    "modules": ("model_implementations", "engine_v2"),
    "model_implementations": ("engine_v2",),
}
LOWER_FILES = [(d, p) for d in LOWER_LAYERS for p in _files(d, "*.py")]


@pytest.mark.parametrize("layer,path", LOWER_FILES,
                         ids=[_rel(p) for _, p in LOWER_FILES])
def test_a_lower_layer_imports_nothing_above_it(layer, path):
    for module, name in _imports(_tree(path)):
        assert not module.startswith("deepspeed_tpu.serving"), (module, name)
        for above in LOWER_LAYERS[layer]:
            assert not (module + ".").startswith(PKG + above + "."), \
                f"{_rel(path)} imports {module} ({name})"
            assert not (module + "." == PKG and name == above), \
                f"{_rel(path)} imports {above} from the package"


FAMILIES = _files("model_implementations", "ragged_*.py")


@pytest.mark.parametrize("path", FAMILIES, ids=[_rel(p) for p in FAMILIES])
def test_a_family_file_imports_no_sibling_but_a_base_class(path):
    tree = _tree(path)
    bases = {b.id for node in ast.walk(tree) if isinstance(node, ast.ClassDef)
             for b in node.bases if isinstance(b, ast.Name)}
    mi = PKG + "model_implementations"
    for module, name in _imports(tree):
        if module == mi or module.startswith(mi + "."):
            assert name in bases, \
                f"{_rel(path)} imports {name} from {module}: the layers " \
                f"families share live in inference/v2/modules/"


def test_the_engine_imports_no_single_family_at_module_level():
    tree = _tree(os.path.join(V2, "engine_v2.py"))
    for module, name in _imports(tree, module_level_only=True):
        assert "model_implementations.ragged_" not in module + ".", \
            (module, name)
    # and below module level it reaches families through the package alone
    for module, name in _imports(tree):
        assert "model_implementations.ragged_" not in module + ".", \
            (module, name)
        assert not module.startswith("deepspeed_tpu.serving"), (module, name)


def test_no_moved_function_is_importable_from_its_old_path():
    """(But for a name the old file still uses itself, which its own import
    binds there: no re-export is kept for the callers that moved.)"""
    from deepspeed_tpu.inference.v2.kernels import blocked_flash
    from deepspeed_tpu.inference.v2.model_implementations import (
        ragged_llama, ragged_mixtral)

    moved = {
        ragged_llama: ("_paged_attention", "_head_view", "_big_pool",
                       "_single_row_read", "_dense_pool_read",
                       "_gather_read", "_rope_insert", "insert_kv",
                       "_rms_norm_1p", "_layer_norm",
                       "shard_ragged_params", "on_tpu"),
        ragged_mixtral: ("moe_router", "_shared_expert"),
        blocked_flash: ("two_segment_case",),
    }
    for module, names in moved.items():
        for name in names:
            assert not hasattr(module, name), (module.__name__, name)


def test_the_scheduler_asks_no_capability_through_an_underscore_attribute():
    """What an engine's cache layout can serve is one call on its state
    manager (``require``).  The one underscore attribute of the engine the
    scheduler touches is the recovery of a donated cache, a method call."""
    tree = _tree(os.path.join(ROOT, "deepspeed_tpu", "serving",
                              "scheduler.py"))

    def is_engine(node):
        return (isinstance(node, ast.Name) and node.id == "engine") or (
            isinstance(node, ast.Attribute) and node.attr == "engine")

    private = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr.startswith("_") \
                and is_engine(node.value):
            private.add(node.attr)
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id in ("getattr", "hasattr") \
                and len(node.args) >= 2 and is_engine(node.args[0]) \
                and isinstance(node.args[1], ast.Constant) \
                and str(node.args[1].value).startswith("_"):
            private.add(node.args[1].value)
    assert private <= {"_recover_donated_cache"}, private
