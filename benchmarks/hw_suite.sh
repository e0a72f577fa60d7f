#!/bin/bash
# Hardware measurement suite: one process after another, each owning the
# chip alone.  Fills the BASELINE.md matrix: every row gets a real-chip
# number and bench.py persists raw chain timings into BENCH_EVIDENCE.json.
# Outputs land under chiprun_out/hw_suite/ (git-ignored).
cd "$(dirname "$0")/.." || exit 1
OUT=chiprun_out/hw_suite
mkdir -p "$OUT"

# run <timeout_s> <json_out> <cmd...>: full stdout goes to <json_out>.raw,
# the LAST line (the JSON report; progress lines go first or to stderr)
# to <json_out>, so consumers can json.load every artifact.
run() {
  local t="$1" out="$2"; shift 2
  timeout "$t" "$@" > "$out.raw" 2>> "$OUT/suite.err"
  local rc=$?
  tail -n 1 "$out.raw" > "$out"
  echo "[$(date -u +%FT%TZ)] $* -> rc=$rc $(cat "$out")"
}

echo "=== hw_suite start $(date -u +%FT%TZ) ==="

echo "--- bench.py (GPT-350M headline, raw timings -> BENCH_EVIDENCE) ---"
run 3600 "$OUT/bench_gpt350m.json" python bench.py

echo "--- single_chip_models: resnet50 (row 1) ---"
run 1800 "$OUT/row1_resnet50.json" python benchmarks/single_chip_models.py resnet50

echo "--- single_chip_models: bert_large (row 2) ---"
run 1800 "$OUT/row2_bert_large.json" python benchmarks/single_chip_models.py bert_large

echo "--- single_chip_models: tp_head (row 3 model) ---"
run 1800 "$OUT/row3_tp_head.json" python benchmarks/single_chip_models.py tp_head

echo "--- single_chip_models: gpt_moe (row 5 model) ---"
run 1800 "$OUT/row5_gpt_moe.json" python benchmarks/single_chip_models.py gpt_moe

echo "--- flash autotune sweep ---"
run 2400 "$OUT/flash_autotune.json" python benchmarks/flash_autotune.py

echo "--- zigzag ring compiled-mode check ---"
run 1800 "$OUT/ring_zigzag.json" python benchmarks/ring_layout.py

echo "--- smap boundary-collective overhead ---"
run 1800 "$OUT/smap_overhead.json" python benchmarks/smap_overhead.py

echo "--- MoE a2a time share ---"
run 1800 "$OUT/moe_a2a_share.json" python benchmarks/moe_a2a_share.py

echo "--- MFU tuning sweep ---"
timeout 5400 bash benchmarks/mfu_sweep.sh > "$OUT/mfu_sweep.txt" 2>&1
echo "[$(date -u +%FT%TZ)] mfu_sweep rc=$? ($OUT/mfu_sweep.txt)"

echo "=== hw_suite done $(date -u +%FT%TZ) ==="
