"""Operations and bytes a dropless expert layer's grouped matmuls REQUIRE,
from shapes alone.

The kernel's contract (``easyparallellibrary_tpu/kernels/moe_gmm.py``):
each sorted assignment row times its expert's matrix, twice a layer (gate
and up as one ``[D, 2 F]`` matrix, then down ``[F, D]``).  Counted is only
what ANY implementation of an expert layer must move in a step: the three
matrices of every expert that has a row, read once, and each live
assignment's rows in and out of the two products (``D`` in and ``2 F`` out,
``F`` in and ``D`` out).  Dead rows (positions beyond a slot's
``num_valid``, idle slots) need nothing.

The count ASSUMES EVERY EXPERT IS TOUCHED in every layer of every step:
with ``a`` live assignments spread over ``E`` experts an expert goes
untouched with probability about ``exp(-a / E)``; at the ~1,490 assignments
a layer of the cell this was written for that is once in ~1e10
layer-steps.  A mix with under ~300 live assignments a layer (``E`` 64)
would leave experts untouched often enough to matter, and this function
would then need the touched count (which the runners do not hand over:
PERF.md section 7, item 12); until then it would OVERSTATE the requirement
there and must not be used.

Arithmetic per assignment: ``2 D (2 F) + 2 F D = 6 D F`` flops.  At tens of
rows an expert the weights' bytes bound the time by a factor of ~50.
"""

from __future__ import annotations

KERNEL = "moe_gmm"


def expert_layers(config: dict) -> int:
  """How many layers of the configuration are expert layers."""
  return config["num_hidden_layers"] - config["first_k_dense_replace"]


def layer_cost(assignments: float, experts: int, d_model: int, d_expert: int,
               act_bytes: int = 2):
  """(flops, bytes) one expert layer's two grouped matmuls require for
  ``assignments`` live (position, expert) pairs, every one of ``experts``
  experts touched."""
  weights = experts * 3 * d_model * d_expert * act_bytes
  rows = assignments * (2 * d_model + 3 * d_expert) * act_bytes
  return assignments * 6 * d_model * d_expert, weights + rows


def step_cost(config: dict, model_opts: dict, live_slots: float):
  """(flops, bytes) of one serving step's grouped matmuls: every expert
  layer once, ``live_slots`` slots feeding AT LEAST one position each (a
  decode slot's one token; a prefill slot's chunk is more, which only
  raises the requirement, so this is a floor), each position going to
  ``num_experts_per_tok`` experts."""
  act = 2 if model_opts.get("dtype", "bfloat16") == "bfloat16" else 4
  f, b = layer_cost(
      live_slots * config["num_experts_per_tok"], config["n_routed_experts"],
      config["hidden_size"], config["moe_intermediate_size"], act)
  n = expert_layers(config)
  return n * f, n * b
