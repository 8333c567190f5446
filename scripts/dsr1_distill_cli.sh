#!/usr/bin/env bash
# BASELINE config 1's exact model (DeepSeek-R1-Distill-Llama-8B,
# architecturally llama3-8b) served end-to-end through the canonical
# `run in=http out=jax` pipeline. On the CPU (JAX_PLATFORMS=cpu) this
# proves the CLI path + preset + model card; on the chip:
# BENCH_MODEL=deepseek-r1-distill-llama-8b python bench.py.
set -u
cd "$(dirname "$0")/.."
OUT=artifacts/dsr1_distill_cli.json
PORT=8871
LOG=/tmp/dsr1_serve.log
env PYTHONPATH=. JAX_PLATFORMS=cpu python -u -m dynamo_tpu.cli.run run \
  in=http out=jax --model deepseek-r1-distill-llama-8b --dtype bfloat16 \
  --page-size 16 --num-pages 96 --max-context 256 --max-seqs 2 \
  --port $PORT > "$LOG" 2>&1 &
SRV=$!
trap 'kill $SRV 2>/dev/null' EXIT
for i in $(seq 1 240); do
  grep -q "listening on" "$LOG" && break
  sleep 5
done
T0=$(date +%s)
RESP=$(curl -s -m 1800 http://127.0.0.1:$PORT/v1/chat/completions \
  -H 'Content-Type: application/json' \
  -d '{"model":"deepseek-r1-distill-llama-8b","messages":[{"role":"user","content":"Hi"}],"max_tokens":2,"temperature":0}')
T1=$(date +%s)
python - "$RESP" "$((T1-T0))" << 'PY' > "$OUT"
import json, sys
resp = json.loads(sys.argv[1])
print(json.dumps({
  "what": "DeepSeek-R1-Distill-Llama-8B (BASELINE config 1) served "
          "end-to-end via `run in=http out=jax` (CPU, random weights - "
          "8B bf16 arch proof)",
  "model": resp.get("model"),
  "usage": resp.get("usage"),
  "finish_reason": resp["choices"][0].get("finish_reason"),
  "wall_s_request": int(sys.argv[2]),
  "platform": "cpu-1core-fallback",
  "date": "2026-07-31",
}, indent=1))
PY
cat "$OUT"
