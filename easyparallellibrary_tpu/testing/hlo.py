"""Where an operation sits in a compiled program's control flow, read
from its optimised HLO text (``compiled.as_text()``).

The serving step's sampling tail chooses its work with ``lax.cond``
(serving/engine.py ``sample_token_slots``); that is worth something only
while the compiler keeps the conditional and leaves the vocabulary sort
inside a branch.  The tests and ``chip_smoke.py`` ask that question of
the program the backend really built, with this one reader.
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_BRANCHES = re.compile(
    r"branch_computations=\{[^}]*\}|(?:true|false)_computation=%?[\w.\-]+")
_NAME = re.compile(r"%([\w.\-]+)")


def op_sites(hlo_text: str, op: str) -> Tuple[List[str], List[str]]:
  """``(unconditional, conditional)``: for every ``op`` instruction (an
  HLO opcode, e.g. ``"sort"``) of the program, the name of the
  computation that holds it.  ``unconditional`` are those that run
  whenever the program runs: in the entry computation or reached from it
  through calls, fusions and loops alone.  ``conditional`` are those
  reached only through a branch of a ``conditional``."""
  # computation -> the right-hand side of each of its instructions
  rhs: Dict[str, List[str]] = {}
  entry = name = None
  for line in hlo_text.splitlines():
    if name is None:
      m = _COMPUTATION.match(line)
      if m:
        name = m.group(2)
        rhs[name] = []
        if m.group(1):
          entry = name
    elif line.startswith("}"):
      name = None
    else:
      rhs[name].append(line.split(" = ", 1)[-1])
  if entry is None:
    raise ValueError("no ENTRY computation in the HLO text")

  always, todo = {entry}, [entry]
  while todo:
    for text in rhs[todo.pop()]:
      # What a conditional names as its branches runs only when chosen;
      # every other computation an instruction names runs with it.
      for callee in _NAME.findall(_BRANCHES.sub("", text)):
        if callee in rhs and callee not in always:
          always.add(callee)
          todo.append(callee)

  is_op = re.compile(rf"\s{re.escape(op)}\(")
  unconditional, conditional = [], []
  for comp, texts in rhs.items():
    held = sum(1 for text in texts if is_op.search(text))
    (unconditional if comp in always else conditional).extend([comp] * held)
  return unconditional, conditional
