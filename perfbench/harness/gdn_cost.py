"""Operations and bytes the gated delta rule REQUIRES, from shapes alone.

The kernel's contract (``easyparallellibrary_tpu/kernels/gdn_scan.py``):
per slot, the float32 state ``[Hv, dk, dv]`` comes in and goes out, and per
live position of the slot's chunk come its row of the convolution's output
(``2 Hk dk + Hv dv`` values in the compute dtype: queries, keys, values),
``g`` and ``beta`` (float32 ``[Hv]`` each) and goes the output (``Hv dv``
values in the compute dtype).  Counted is only what ANY implementation of
that contract must move: the state of a slot that advances read once and
written once, each activation of a LIVE position once.  A slot that does
not advance needs nothing, and the positions of a chunk beyond
``num_valid`` need not be read; the kernel at hand moves a chunk's whole
block anyway, so its share reads low, never above 100%.  The same work
whatever lowering ran and whichever of the update's two forms (one position,
a chunk) a slot took.

Arithmetic per live position, value head and element of the ``[dk, dv]``
state: the decay (1), ``S^T k`` (2), the rank-one update (2), ``S^T q``
(2): 7 flops; the chunk's form does more arithmetic for the same result
(its triangular solve), which is the implementation's and is not counted.
At one position a slot the bytes bound the time by a factor of ~100 on any
chip whose peak is counted in matmul flops.
"""

from __future__ import annotations

KERNEL = "gdn_scan"
FLOPS_PER_ELEMENT = 7


def gdn_scan_cost(slots: float, live_positions: float, key_heads: int,
                  value_heads: int, key_dim: int, value_dim: int,
                  act_bytes: int = 2):
  """(flops, bytes) one call requires when ``slots`` slots advance by
  ``live_positions`` positions in all."""
  state = 2 * slots * value_heads * key_dim * value_dim * 4
  row = 2 * key_heads * key_dim + value_heads * value_dim
  per_position = ((row + value_heads * value_dim) * act_bytes
                  + 2 * value_heads * 4)
  flops = (live_positions * value_heads * key_dim * value_dim
           * FLOPS_PER_ELEMENT)
  return flops, state + live_positions * per_position


def linear_layers(config: dict) -> int:
  """How many layers of a GigaChat 3.5 configuration run the delta rule."""
  return config["num_hidden_layers"] - len(config["full_attention_layers"])


def step_cost(config: dict, model_opts: dict, live_slots: float):
  """(flops, bytes) of one serving step's delta rules: every linear layer
  once, ``live_slots`` slots advancing by AT LEAST one position each (a
  decode slot's one token; a prefill slot's chunk is more, which only
  raises the requirement, so this is a floor)."""
  act = 2 if model_opts.get("dtype", "bfloat16") == "bfloat16" else 4
  f, b = gdn_scan_cost(
      live_slots, live_slots, config["linear_num_key_heads"],
      config["linear_num_value_heads"], config["linear_key_head_dim"],
      config["linear_value_head_dim"], act)
  n = linear_layers(config)
  return n * f, n * b
