"""The arrows of the serving path point one way: ``kernels/``, ``ops/`` <-
the slot-mode core, the layer kinds, the shared blocks <- the decoders <-
``serving/kv_cache.py`` <- ``serving/engine.py``.  Read off the package's
sources (``ast``: every import, the ones inside functions too), so a new
architecture that reaches sideways or upwards fails here and not in a
cycle at import time."""

import ast
import pathlib

import pytest

PKG = "easyparallellibrary_tpu"
ROOT = pathlib.Path(__file__).resolve().parents[1] / PKG
DECODERS = ("gpt", "jamba", "glm_moe", "lfm2_moe", "dots3_note",
            "smallthinker", "gigachat")
BELOW_THE_DECODERS = ("slot_core", "blocks", "layer_kinds")
# The slot-mode core's names (models/slot_core.py).
CORE = {"slot_cache_attend", "PagedInfo", "paged_cache_attend",
        "paged_step_logits", "SlotRows", "child_of", "SplitLayer",
        "slot_layers", "slot_rows", "flat_ids", "slot_step_logits",
        "missing_slot_cache", "_missing_slot_cache"}


def _tree(path: pathlib.Path) -> ast.Module:
  return ast.parse(path.read_text(), filename=str(path))


def imports(path: pathlib.Path) -> set:
  """Every module ``path`` imports, as dotted names (``from a.b import c``
  gives ``a.b`` and ``a.b.c``: ``c`` may be a module)."""
  here = path.relative_to(ROOT.parent).with_suffix("").parts
  out = set()
  for node in ast.walk(_tree(path)):
    if isinstance(node, ast.Import):
      out.update(alias.name for alias in node.names)
    elif isinstance(node, ast.ImportFrom):
      base = node.module or ""
      if node.level:
        base = ".".join(here[:len(here) - node.level] + ((base,) if base
                                                         else ()))
      out.add(base)
      out.update(f"{base}.{alias.name}" for alias in node.names)
  return out


def _decoder_imports(path: pathlib.Path) -> set:
  decoders = {f"{PKG}.models.{name}" for name in DECODERS}
  return imports(path) & (decoders - {f"{PKG}.models.{path.stem}"})


def test_no_model_imports_serving():
  for path in sorted((ROOT / "models").glob("*.py")):
    up = {m for m in imports(path) if m.startswith(f"{PKG}.serving")}
    assert not up, f"models/{path.name} imports {sorted(up)}"


def test_the_cache_imports_no_decoder():
  assert not _decoder_imports(ROOT / "serving" / "kv_cache.py")


@pytest.mark.parametrize("name", DECODERS + ("moe",) + BELOW_THE_DECODERS)
def test_no_decoder_imports_another_and_what_they_share_imports_none(name):
  sideways = _decoder_imports(ROOT / "models" / f"{name}.py")
  assert not sideways, f"models/{name}.py imports {sorted(sideways)}"


def test_gpt_defines_none_of_the_slot_mode_core():
  """``models/gpt.py`` imports the core like any other decoder; the one
  name it hands on is ``slot_step_logits``, which
  perfbench/selection_witness.py imports from it (ROADMAP D17)."""
  defined = {node.name for node in _tree(ROOT / "models" / "gpt.py").body
             if isinstance(node, (ast.FunctionDef, ast.ClassDef))}
  assert not defined & CORE
  core = {node.name for node in _tree(ROOT / "models" / "slot_core.py").body
          if isinstance(node, (ast.FunctionDef, ast.ClassDef))}
  assert CORE - {"_missing_slot_cache"} <= core
