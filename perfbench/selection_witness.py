"""A second witness for a cell whose ``correct`` rests on attention that
SELECTS its rows: the program wholly in float32, through the slot cache and
the lowerings the engine resolves, against the plain reference at the
configuration's published widths, below and beyond the position where the
selection starts to discard.

    python3 perfbench/selection_witness.py --workload <cell> \
        [--positions 4096] [--draw file|uniform] \
        [--controls recent,loose,fp8] [--seed n]

A cell's own check compares a bfloat16 program's served tokens; with an
exact top-k over thousands of rows those hinge on near-ties, so a gap there
does not say whether the selection is RIGHT.  Here both sides compute in
float32 from the same weights: every position's logits are compared
(``dlogit``: the largest absolute difference; ``gap``: how far the
reference logit of the program's best token lies below the reference's
best), ``positions`` fed ``prefill_chunk`` at a time through
``slot_step_logits``, the call the engine steps through.  The chip holds
``HELD`` experts here (scale, not a width), so that float32 weights fit
beside the reference's on one chip; ``--draw uniform`` widens what the reference's
draw narrows (``reference/dots3_note.py:init_attention``) back to the
configuration's ``initializer_range``: every matrix drawn alike.
``--controls`` also computes the reference under each named control in the
program's place, read the same way: what the comparison sees of a planted
fault.  One JSON line, the last of standard output; the benchmark's own
runs never call this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

from perfbench.harness import manifest as manifest_lib  # noqa: E402
from perfbench.harness.result import say  # noqa: E402

HELD = 8


def _widen(cfg, tree):
  """``tree`` (the reference's or the program's) with the two matrices the
  reference's draw narrows in a full layer multiplied back to the common
  range and rounded to bfloat16 again, in the leaf's own dtype."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  rank = {"q_b": cfg.full.q_rank, "kv_b": cfg.full.kv_rank}

  def one(path, leaf):
    keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
    name = next((k for k in keys if k in rank), None)
    layer = next((k if isinstance(k, int) else int(k[len("block_"):])
                  for k in keys if isinstance(k, int)
                  or (isinstance(k, str) and k.startswith("block_"))), None)
    if name is None or layer is None or leaf.ndim != 2 or (
        cfg.layer_types[layer] != "full_attention"):
      return leaf
    wide = leaf.astype(jnp.float32) * np.sqrt(cfg.hidden_size / rank[name])
    return wide.astype(jnp.bfloat16).astype(leaf.dtype)

  return jax.tree_util.tree_map_with_path(one, tree)


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("--workload", required=True)
  parser.add_argument("--positions", type=int, default=4096)
  parser.add_argument("--draw", choices=("file", "uniform"), default="file")
  parser.add_argument("--controls", default="")
  parser.add_argument("--seed", type=int, default=2147630101)
  args = parser.parse_args(argv)

  man = manifest_lib.Manifest(ROOT)
  cell = man.workload(args.workload)
  cell_file = man.cell_file(cell["name"])
  doc = dict(man.config_file(cell["config"]))
  chunk = cell_file["engine"]["prefill_chunk"]
  doc["n_routed_experts"] = HELD
  doc["assumed"] = dict(doc["assumed"], served_context=args.positions + chunk)
  family = cell_file["family"]

  import jax
  import jax.numpy as jnp
  import numpy as np
  import easyparallellibrary_tpu as epl
  from easyparallellibrary_tpu.models.gpt import slot_step_logits
  from easyparallellibrary_tpu.serving import kv_cache as kv_lib
  ref = importlib.import_module(f"perfbench.reference.{family}")
  glue = importlib.import_module(f"perfbench.runners.epl_{family}")

  cfg = glue.ref_config(doc)
  edge = cfg.index_topk
  T = args.positions
  if T <= edge:
    raise SystemExit(f"--positions {T} never passes the selection's start "
                     f"({edge})")
  key = ref.seed_key(args.seed)
  ids = jnp.asarray(np.random.default_rng([args.seed, 7]).integers(
      0, cfg.vocab_size, (1, T)), jnp.int32)

  epl.init(epl.Config({}), devices=jax.devices()[:1])
  f32 = {"dtype": "float32", "param_dtype": "float32"}
  model, shell_of = glue.build_model(cfg, f32)
  # The weights are the CELL's, made in the dtypes the cell holds them in
  # and widened leaf by leaf outside any compiled program: drawn straight
  # into a float32 tree, the draw's rounding to bfloat16 and the cast back
  # meet inside one program, where the rounding does not survive on the
  # chip (it does on the CPU): every weight lay up to half a bfloat16 step
  # off the reference's (measured: PERF.md section 6, PR 39).
  _, held_shell_of = glue.build_model(cfg, cell_file["model"])
  held = glue.program_params(cfg, key, held_shell_of(ids[:, :8]))
  ref_params = jax.jit(lambda k: ref.init_params(cfg, k))(key)
  if args.draw == "uniform":
    ref_params, held = _widen(cfg, ref_params), _widen(cfg, held)
  params = jax.tree_util.tree_map(
      lambda leaf, like: leaf.astype(like.dtype), held, shell_of(ids[:, :8]))
  del held
  sq, ref_sq = (float(jax.jit(glue.sum_of_squares)(t))
                for t in (params, ref_params))
  if abs(sq - ref_sq) > 1e-3 * ref_sq:
    raise SystemExit(f"program and reference differ in their weights: sums "
                     f"of squares {sq:.6g} and {ref_sq:.6g}")

  impls = {
      "kv_write_impl": kv_lib.kv_write_impl(model.cfg, 1, chunk),
      "slot_attn_impl": kv_lib.slot_attn_impl(model.cfg, 1, chunk),
      "dsa_index_impl": kv_lib.dsa_index_impl(model.cfg, 1, chunk),
      "moe_gmm_impl": kv_lib.moe_gmm_impl(model.cfg, 1, chunk),
  }
  say(f"witness: {T} positions in chunks of {chunk}, {HELD} experts "
      f"held, draw {args.draw}, lowerings {impls}")
  kv, cursors = kv_lib.allocate_kv_cache(model.cfg, 1, chunk)
  step = jax.jit(lambda p, kv, block, cur, nv: slot_step_logits(
      model, p, kv, block, cur, num_valid=nv, **impls))
  outs = []
  with jax.default_matmul_precision("highest"):
    for s in range(0, T, chunk):
      n = min(chunk, T - s)
      nv = jnp.full((1,), n, jnp.int32)
      block = jnp.zeros((1, chunk), jnp.int32).at[:, :n].set(ids[:, s:s + n])
      lg, kv = step(params, kv, block, cursors, nv)
      cursors = cursors + nv
      outs.append(lg[0, :n].astype(jnp.float32))
  got = jnp.concatenate(outs, 0)
  del kv, params

  want = jax.jit(lambda p, i: ref.logits(cfg, p, i)[0])(ref_params, ids)
  best = jnp.max(want, -1)

  def read(other):
    """Below and beyond the selection's start: the largest logit
    difference and the largest gap of ``other``'s best token."""
    tok = jnp.argmax(other, -1)
    gap = np.asarray(best - jnp.take_along_axis(want, tok[:, None], -1)[:, 0])
    diff = np.asarray(jnp.max(jnp.abs(other - want), -1))
    part = lambda rows: {"dlogit": float(diff[rows].max()),
                         "dlogit_median": float(np.median(diff[rows])),
                         "gap": float(gap[rows].max()),
                         "tokens_moved": int((gap[rows] > 0).sum())}
    return {"below": part(slice(0, edge)), "beyond": part(slice(edge, T))}

  out = {"workload": cell["name"], "positions": T, "edge": edge,
         "held": HELD, "draw": args.draw, "seed": args.seed,
         "platform": jax.devices()[0].platform, "lowerings": impls,
         "program_float32": read(got), "controls": {}}
  for control in [c for c in args.controls.split(",") if c]:
    low = jax.jit(lambda p, i, c=control: ref.logits(cfg, p, i, c)[0])(
        ref_params, ids)
    out["controls"][control] = read(low)
  print("WITNESS " + json.dumps(out), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
