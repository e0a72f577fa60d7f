"""What the repository says about itself must point at files that exist:
a Makefile recipe that runs a deleted script, or a document that sends
the reader to one, is how a retired record of speed keeps being cited.
"""

import glob
import importlib.util
import os
import re
import shlex

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Paths in the documents that are not files of this tree: the TF
# reference's own layout, and artifacts a run writes.
REFERENCE_PREFIXES = ("epl/",)
RUNTIME_ARTIFACTS = {"index.json", "state.json", "slo_events.jsonl",
                     "params/"}
# Where a document may root a relative path.
ROOTS = ("", "easyparallellibrary_tpu/", "perfbench/", "docs/")

_SKIP_DIRS = {"__pycache__", "chiprun_out"}


def _tree():
  """(files, directories, basenames) of the checkout, repo-relative."""
  files, dirs = set(), set()
  for top, subdirs, names in os.walk(REPO):
    subdirs[:] = [d for d in subdirs
                  if not d.startswith(".") and d not in _SKIP_DIRS]
    rel = os.path.relpath(top, REPO)
    rel = "" if rel == "." else rel + "/"
    dirs.update(rel + d for d in subdirs)
    files.update(rel + n for n in names)
  return files, dirs, {os.path.basename(f) for f in files}


def _recipes():
  """``(target, command words)`` of every recipe line of the Makefile."""
  target = None
  with open(os.path.join(REPO, "Makefile")) as f:
    for line in f:
      if line.startswith("\t"):
        yield target, shlex.split(line.strip().lstrip("@"))
      elif re.match(r"^[\w.-]+:", line):
        target = line.split(":")[0]


def test_makefile_recipes_name_files_that_exist():
  checked = 0
  for target, words in _recipes():
    for i, word in enumerate(words):
      where = f"make {target}: {' '.join(words)}"
      if word == "-m" and i and words[i - 1].startswith("python"):
        assert importlib.util.find_spec(words[i + 1]) is not None, where
        checked += 1
      elif word == "-C":
        assert os.path.isdir(os.path.join(REPO, words[i + 1])), where
        checked += 1
      elif re.search(r"\.(py|sh)$", word) or (
          i and words[i - 1] in ("python", "python3", "bash", "sh")
          and not word.startswith("-")):
        assert os.path.isfile(os.path.join(REPO, word)), where
        checked += 1
  assert checked >= 10, "the Makefile's recipes were not parsed"


def test_documents_name_files_that_exist():
  files, dirs, basenames = _tree()
  looks_like_path = re.compile(
      r"^[\w.\-/]+(\.(py|md|json|jsonl|sh|cc|h)|/)$")
  missing, checked = [], 0
  docs = [os.path.join(REPO, "README.md")] + sorted(
      glob.glob(os.path.join(REPO, "docs", "*.md")))
  for doc in docs:
    with open(doc) as f:
      text = f.read()
    # Every word of a back-ticked span: `python chip_smoke.py` names one.
    for token in (word for span in re.findall(r"`([^`\n]+)`", text)
                  for word in span.split()):
      # `path.py::test_name`, `path.py:12-30`, a trailing full stop
      path = re.sub(r":\d+(-\d+)?(,\d+(-\d+)?)*$", "",
                    token.split("::")[0])
      path = path.rstrip(".,;")
      if not looks_like_path.match(path) or path.startswith("/"):
        continue
      if path in RUNTIME_ARTIFACTS or path.startswith(REFERENCE_PREFIXES):
        continue
      checked += 1
      stem = path.rstrip("/")
      if "/" in stem:
        found = any(r + stem in files or r + stem in dirs for r in ROOTS)
      else:
        found = stem in basenames or any(r + stem in dirs for r in ROOTS)
      if not found:
        missing.append(f"{os.path.relpath(doc, REPO)}: `{token}`")
  assert checked >= 100, "the documents' paths were not parsed"
  assert not missing, "documents name files that do not exist:\n" + \
      "\n".join(missing)
