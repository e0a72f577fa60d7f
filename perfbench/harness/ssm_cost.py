"""Operations and bytes the selective scan REQUIRES, from shapes alone.

The kernel's contract (``easyparallellibrary_tpu/kernels/ssm_scan.py``):
per slot, the float32 state ``[d_state, d_inner]`` comes in and goes out,
and per live position of the slot's chunk come ``u`` and ``z`` (compute
dtype), ``delta`` (float32), ``B`` and ``C`` (float32 ``[d_state]``) and
goes the gated output (compute dtype); ``A`` ``[d_state, d_inner]`` and
``D`` ``[d_inner]`` come in once a call.  Counted is only what ANY
implementation of that contract must move: the state of a slot that
advances read once and written once, each activation of a LIVE position
once.  A slot that does not advance needs nothing, and the positions of a
chunk beyond ``num_valid`` need not be read; the kernel at hand moves
them anyway, so its share reads low, never above 100%.

Arithmetic per live position, channel and state: ``exp(delta A)`` (one
transcendental, not counted), ``* s``, ``delta u`` (shared by the states),
``* B``, ``+``, ``* C``, the sum over the states: 6 flops.  At 16 states
the bytes bound the time on any chip whose peak is counted in matmul
flops.
"""

from __future__ import annotations

KERNEL = "ssm_scan"
FLOPS_PER_ELEMENT = 6


def ssm_scan_cost(slots: float, live_positions: float, d_state: int,
                  d_inner: int, act_bytes: int = 2):
  """(flops, bytes) one call requires when ``slots`` slots advance by
  ``live_positions`` positions in all."""
  state = 2 * slots * d_state * d_inner * 4
  per_position = d_inner * (3 * act_bytes + 4) + 2 * d_state * 4
  once = d_state * d_inner * 4 + d_inner * 4
  nbytes = state + live_positions * per_position + once
  flops = live_positions * d_state * d_inner * FLOPS_PER_ELEMENT
  return flops, nbytes


def mamba_layers(config: dict) -> int:
  """How many layers of a Jamba configuration run the scan."""
  period, offset = config["attn_layer_period"], config["attn_layer_offset"]
  return sum(1 for i in range(config["num_hidden_layers"])
             if i % period != offset)


def step_cost(config: dict, model_opts: dict, live_slots: float):
  """(flops, bytes) of one serving step's scans: every Mamba layer once,
  ``live_slots`` slots advancing by AT LEAST one position each (a decode
  slot's one token; a prefill slot's chunk is more, which only raises the
  requirement, so this is a floor)."""
  act = 2 if model_opts.get("dtype", "bfloat16") == "bfloat16" else 4
  f, b = ssm_scan_cost(
      live_slots, live_slots, config["mamba_d_state"],
      config["mamba_expand"] * config["hidden_size"], act)
  n = mamba_layers(config)
  return n * f, n * b
