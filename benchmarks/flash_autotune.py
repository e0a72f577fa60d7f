"""Flash-attention block autotune sweep (VERDICT r3 item 6).

On real TPU hardware, times the flash kernels' fused fwd+bwd across
candidate block widths per (S, D) and writes the winners into
``easyparallellibrary_tpu/kernels/flash_block_table.json`` — the table
``_default_block`` consults, so every flash user (models, ring
attention, bench.py) picks the tuned widths up automatically.  Only
entries that beat the built-in 512/1024 heuristic by >3% are written
(the heuristic stays the fallback for everything unswept).

Timing: warm, then chain the grad through q so the whole sequence must
execute, fetch one scalar, subtract the measured null round-trip (see
benchmarks/_common.py).

Off-TPU this prints a note and exits 0: interpret-mode timing would
tune for the interpreter, not the chip.

Prints one JSON line per (S, D) plus a summary line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmarks._common import force, null_round_trip  # noqa: E402

import importlib  # noqa: E402

# The kernels package re-exports the flash_attention FUNCTION under
# the same name, shadowing attribute access to the module.
fa = importlib.import_module(
    "easyparallellibrary_tpu.kernels.flash_attention")

CANDIDATES = (256, 512, 1024, 2048)
SWEEP = [
    # (S, D, batch, heads) — batch halves as S doubles to bound memory.
    (1024, 64, 8, 16),
    (2048, 64, 8, 16),
    (4096, 64, 8, 16),
    (8192, 64, 4, 16),
    (16384, 64, 2, 16),
    (32768, 64, 1, 16),
    (2048, 128, 4, 16),
    (4096, 128, 2, 16),
    (8192, 128, 1, 16),
]


def _time_grad(want, q, k, v, reps=8):
  import functools
  S, D = q.shape[2], q.shape[3]
  bq = bk = fa._default_block(S, want, d=D, itemsize=q.dtype.itemsize)
  if not bq:
    return None

  def attn(q, k, v):
    o, _ = fa._fwd(q, k, v, True, bq, bk)
    return o

  g = jax.jit(jax.grad(lambda *a: jnp.sum(attn(*a) ** 2)))
  out = g(q, k, v)
  force(out[0, 0, 0])
  null = null_round_trip()
  t0 = time.perf_counter()
  acc = q
  for _ in range(reps):
    acc = g(acc, k, v)
  force(acc[0, 0, 0])
  return max(time.perf_counter() - t0 - null, 1e-9) / reps


def main():
  if jax.devices()[0].platform != "tpu":
    print(json.dumps({"metric": "flash_autotune", "skipped": True,
                      "reason": "no TPU: interpret-mode timing would "
                                "tune for the interpreter"}))
    return

  device = jax.devices()[0].device_kind
  # Merge semantics: keep prior same-device entries for shapes NOT in
  # this sweep; every swept shape is re-decided from scratch (so a
  # previously-tuned width that no longer beats the heuristic is
  # dropped, and re-runs never compare against their own prior output).
  old_entries = {}
  had_file = False
  try:
    with open(fa._BLOCK_TABLE_PATH) as f:
      raw = json.load(f)
    had_file = True
    if isinstance(raw, dict) and raw.get("device") == device \
        and isinstance(raw.get("entries"), dict):
      old_entries = dict(raw["entries"])
  except Exception:
    pass
  for S, D, _, _ in SWEEP:
    old_entries.pop(f"{S}:{D}:2", None)

  table = {}
  rows = []
  for S, D, B, H in SWEEP:
    r = np.random.RandomState(0)
    mk = lambda: jnp.asarray(r.randn(B, H, S, D), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    # Default from the HEURISTIC, not the loaded table — comparing
    # against our own prior output would silently drop valid entries.
    default_want = fa._default_block(S, fa._heuristic_want(S, D, 2),
                                     d=D, itemsize=2)
    times = {}
    for want in CANDIDATES:
      try:
        t = _time_grad(want, q, k, v)
      except Exception as e:
        t = None
        print(f"autotune: S={S} D={D} want={want} failed: "
              f"{type(e).__name__}", file=sys.stderr)
      if t is not None:
        times[want] = t
    if not times:
      continue
    best_want = min(times, key=times.get)
    t_default = times.get(default_want) or min(times.values())
    gain = t_default / times[best_want]
    row = {"S": S, "D": D, "batch": B,
           "times_ms": {str(w): round(1e3 * t, 3)
                        for w, t in times.items()},
           "default_want": default_want, "best_want": best_want,
           "gain_vs_default": round(gain, 3)}
    rows.append(row)
    print(json.dumps(row), flush=True)
    if best_want != default_want and gain > 1.03:
      table[f"{S}:{D}:2"] = best_want

  final = {**old_entries, **table}
  if final or had_file:
    # Rewrite even when empty: a re-run that rejects every prior entry
    # must not leave the stale table serving rejected widths.
    with open(fa._BLOCK_TABLE_PATH, "w") as f:
      json.dump({"device": device, "entries": final}, f, indent=1)
  print(json.dumps({
      "metric": "flash_autotune", "value": len(table),
      "unit": "tuned_entries",
      "detail": {"new_entries": table, "kept_entries": old_entries,
                 "table_path": fa._BLOCK_TABLE_PATH,
                 "device": device,
                 "rows": len(rows)}}), flush=True)


if __name__ == "__main__":
  main()
