#!/usr/bin/env bash
# Pre-merge static gate: ruff -> analysis CLI -> strict trace
# validation -> request-path doctor -> autotune smoke -> multi-host
# smoke. Run from anywhere; every step must pass (ruff is skipped with
# a note on hosts that don't have it — the [tool.ruff] config in
# pyproject.toml still applies wherever ruff exists, e.g. CI). No step
# compares a time: a performance number comes from benchmark/run.py on
# the chip (PERF.md).
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== ruff =="
if command -v ruff >/dev/null 2>&1; then
    ruff check .
else
    echo "ruff not installed — skipped (config lives in pyproject.toml [tool.ruff])"
fi

echo "== analysis (AST linter + compiled-program audit) =="
python -m deeperspeed_tpu.analysis

echo "== strict trace validation =="
for trace in traces/*.json; do
    [ -e "$trace" ] || continue
    JAX_PLATFORMS=cpu python -m deeperspeed_tpu.monitor.validate --strict "$trace"
    echo "  $trace OK"
done

echo "== request-path doctor (tail-latency attribution gate) =="
# the doctor must be able to explain >= 95% of every request's TTFT on
# the committed drill traces — if attribution stops covering the tail,
# the build fails, not the postmortem
for trace in traces/serving_bench_trace.json traces/obs_drill_merged.json; do
    [ -e "$trace" ] || continue
    JAX_PLATFORMS=cpu python -m deeperspeed_tpu.monitor.slo \
        --max-residual 0.05 "$trace"
done

echo "== autotune smoke (quick space, rank-only) =="
# the config-search pipeline end to end on a small space: enumerate ->
# AOT-price -> emit + provenance self-check (<60s; the measured confirm
# phase is the CLI's default and is left out of the gate)
JAX_PLATFORMS=cpu python -m deeperspeed_tpu.autotune --devices 8 --quick \
    --no-confirm --out /tmp/autotune_smoke.json
python - <<'EOF'
import json
from deeperspeed_tpu.autotune.provenance import verify_provenance
cfg = json.load(open("/tmp/autotune_smoke.json"))
ok, why = verify_provenance(cfg)
assert ok, why
print(f"  emitted config verifies: {why}")
EOF

echo "== multi-host smoke (2-process localhost mesh, probe-guarded) =="
# a REAL 2-process jax.distributed rendezvous on this host: bootstrap
# both workers over the gloo coordinator, build the process-spanning
# mesh, run one psum across hosts. Skipped (with a note) where the
# jaxlib lacks multiprocess CPU collectives — the probe IS the gate's
# skip condition, same as tests/test_multihost.py
if JAX_PLATFORMS=cpu python -m deeperspeed_tpu.distributed.bootstrap \
        >/dev/null 2>&1; then
    JAX_PLATFORMS=cpu python - <<'EOF'
from deeperspeed_tpu.distributed.bootstrap import multiprocess_cpu_probe
assert multiprocess_cpu_probe(), "probe passed as CLI but not as API"
print("  2-process localhost rendezvous + cross-host psum OK")
EOF
else
    echo "  no multiprocess CPU collectives in this jaxlib — skipped"
fi

echo "check.sh: all gates passed"
