#!/usr/bin/env bash
# End-to-end tour of the gateway against the built-in fake provider —
# zero API keys, runs anywhere JAX runs (CPU fine).  Exercises: scoring
# with static and trained weights, streaming, multichat with live
# consensus frames, embeddings, archive (reference + rescore + snapshot),
# learning, metrics, and the profiler.
#
#   bash examples/demo.sh [port]
set -euo pipefail
PORT="${1:-5055}"
ROOT="$(cd "$(dirname "$0")/.." && pwd)"
WORK="$(mktemp -d)"
GW_PID=""
trap 'kill "${GW_PID:-}" 2>/dev/null || true; rm -rf "$WORK"' EXIT

say() { printf '\n\033[1m== %s ==\033[0m\n' "$*"; }

say "starting gateway (fake upstream; archive + tables + profiler armed)"
cd "$ROOT"
# the demo is a functional tour — it runs on CPU by default; set
# LWC_DEMO_PLATFORM (e.g. tpu) to tour on real hardware instead.
JAX_PLATFORMS="${LWC_DEMO_PLATFORM:-cpu}" \
EMBEDDER_MODEL=test-tiny EMBEDDER_MAX_TOKENS=32 \
WARMUP=3x16 \
RM_MODEL=deberta-test-tiny RM_MAX_TOKENS=32 \
ARCHIVE_PATH="$WORK/archive.json" TABLES_PATH="$WORK/tables.npz" \
PROFILE_DIR="$WORK/traces" \
python -m llm_weighted_consensus_tpu.serve --port "$PORT" --fake-upstream &
GW_PID=$!
for _ in $(seq 120); do
  curl -sf "localhost:$PORT/healthz" > /dev/null 2>&1 && break
  sleep 0.5
done
curl -sf "localhost:$PORT/healthz"

MODEL='{"llms": [
  {"model": "judge-a", "weight": {"type": "training_table", "base_weight": 1, "min_weight": 1, "max_weight": 5}},
  {"model": "judge-b", "weight": {"type": "training_table", "base_weight": 1, "min_weight": 1, "max_weight": 5}}
], "weight": {"type": "training_table", "embeddings": {"model": "test-tiny", "max_tokens": 32}, "top": 3}}'

say "score: 3 candidates, 2 judges, trained weights (base for now)"
CID=$(curl -s "localhost:$PORT/score/completions" -H 'content-type: application/json' -d "{
  \"messages\": [{\"role\": \"user\", \"content\": \"which answer is best?\"}],
  \"model\": $MODEL,
  \"choices\": [\"the first answer\", \"the second answer\", \"a third answer\"]
}" | python -c 'import json,sys; d=json.load(sys.stdin); print(d["id"])
conf=[(c["index"], c.get("confidence")) for c in d["choices"] if c["index"]<3]
print("candidate confidences:", conf, file=sys.stderr)')
echo "archived as: $CID"

say "score: STREAMING (initial candidates frame ... judges ... final tally ... [DONE])"
curl -sN "localhost:$PORT/score/completions" -H 'content-type: application/json' -d "{
  \"stream\": true,
  \"messages\": [{\"role\": \"user\", \"content\": \"best?\"}],
  \"model\": $MODEL,
  \"choices\": [\"alpha\", \"beta\"]
}" | tail -4

say "multichat with live consensus frames"
curl -sN "localhost:$PORT/multichat/completions" -H 'content-type: application/json' -d '{
  "stream": true, "consensus": true,
  "messages": [{"role": "user", "content": "answer please"}],
  "model": {"llms": [{"model": "gen-a"}, {"model": "gen-b"}, {"model": "gen-c"}]}
}' | { grep -c "multichat.consensus" || true; } | xargs echo "consensus frames:"

say "embeddings (on-device encoder)"
curl -s "localhost:$PORT/embeddings" -H 'content-type: application/json' \
  -d '{"model": "test-tiny", "input": ["hello tpu"]}' \
  | python -c 'import json,sys; d=json.load(sys.stdin); print("dims:", len(d["data"][0]["embedding"]), "tokens:", d["usage"]["total_tokens"])'

say "device self-consistency scorer as a service (POST /consensus)"
curl -s "localhost:$PORT/consensus" -H 'content-type: application/json' \
  -d '{"input": ["the answer is 42", "the answer is 42!", "cabbage"]}' \
  | python -c 'import json,sys; d=json.load(sys.stdin); print("confidence:", [round(c, 3) for c in d["confidence"]], "tokens:", d["usage"]["prompt_tokens"])'

say "reward-model re-ranking on the same route (scorer: rm)"
curl -s "localhost:$PORT/consensus" -H 'content-type: application/json' \
  -d '{"input": ["the answer is 42", "probably 41"], "scorer": "rm", "prompt": "what is the answer?"}' \
  | python -c 'import json,sys; d=json.load(sys.stdin); print("scorer:", d["scorer"], "model:", d["model"], "confidence:", [round(c, 3) for c in d["confidence"]])'

say "archived completion as a candidate in a NEW request"
curl -s "localhost:$PORT/score/completions" -H 'content-type: application/json' -d "{
  \"messages\": [{\"role\": \"user\", \"content\": \"re-judge\"}],
  \"model\": $MODEL,
  \"choices\": [{\"type\": \"score_completion\", \"id\": \"$CID\", \"choice_index\": 0}, \"a fresh candidate\"]
}" | python -c 'import json,sys; d=json.load(sys.stdin); print("ok, id:", d["id"])'

say "learn judge weights from the archived outcomes"
curl -s -X POST "localhost:$PORT/weights/learn" -H 'content-type: application/json' -d "{\"model\": $MODEL}"
echo

say "batch re-score the archive on device and write the tally back"
# (pass weight_overrides: {<judge id>: w} to re-weight judges; ids are the
# hashed judge identities echoed in each choice's "model" field)
curl -s -X POST "localhost:$PORT/archive/rescore" -H 'content-type: application/json' \
  -d '{"apply": true}' ; echo

say "profiler round trip"
curl -s -X POST "localhost:$PORT/profile/start" > /dev/null
curl -s "localhost:$PORT/embeddings" -H 'content-type: application/json' \
  -d '{"model": "test-tiny", "input": ["traced"]}' > /dev/null
curl -s -X POST "localhost:$PORT/profile/stop"
echo " -> $(find "$WORK/traces" -type f | wc -l) trace file(s)"

say "service metrics"
# sed -n drains stdin (head would SIGPIPE json.tool under pipefail)
curl -s "localhost:$PORT/metrics" | python -m json.tool | sed -n '1,20p'

say "graceful shutdown persists archive + tables snapshots"
kill -INT "$GW_PID"; wait "$GW_PID" 2>/dev/null || true
python - << EOF
import json, numpy as np
a = json.load(open("$WORK/archive.json"))
print("archive snapshot:", {k: len(v) for k, v in a.items() if isinstance(v, dict)})
with np.load("$WORK/tables.npz") as d:
    print("tables snapshot entries:", len(d.files))
EOF

say "demo complete"
