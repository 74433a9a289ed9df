#!/usr/bin/env bash
# One-command static gate: style (ruff, when installed) + concurrency lint
# + graph verification over every shipped model (docs/ANALYSIS.md).
#
#   scripts/check.sh            # the full gate
#   scripts/check.sh --fast     # lint only, skip the model-graph sweep
#
# Exit nonzero on the first failing stage.  The same checks run inside the
# default pytest invocation via tests/test_analysis.py (marker: analysis),
# so CI needs nothing beyond tier-1; this script is the local loop.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== ruff (style) =="
if python -m ruff --version >/dev/null 2>&1; then
    python -m ruff check parsec_tpu tests examples
elif command -v ruff >/dev/null 2>&1; then
    ruff check parsec_tpu tests examples
else
    echo "ruff not installed — skipping style stage (config lives in" \
         "pyproject.toml [tool.ruff])"
fi

if [[ "${1:-}" == "--fast" ]]; then
    echo "== runtimelint (concurrency + hygiene) =="
    python -m parsec_tpu.analysis --self-lint
else
    echo "== runtimelint + graphcheck (every shipped model graph) =="
    python -m parsec_tpu.analysis

    echo "== commcheck (static comm-pattern derivation: model sweep" \
         "classified at 4 ranks + built-in invariants) =="
    python -m parsec_tpu.analysis --comm
    python -m parsec_tpu.analysis.commcheck --self-test

    echo "== tracemerge (cross-rank trace stitching self-test) =="
    python -m parsec_tpu.prof.tracemerge --self-test

    echo "== critpath (critical-path attribution self-test: additive" \
         "sweep, overlap_lost, chrome round-trip, DAG, cycle-safety) =="
    python -m parsec_tpu.prof.critpath --self-test

    echo "== perfdb (perf ledger + regression sentinel: EWMA verdicts," \
         "cross-instance accrual) =="
    python -m parsec_tpu.prof.perfdb --self-test
    python -m pytest tests/test_critpath.py tests/test_perf_smoke.py -q \
        -k "perfdb or critpath" -p no:cacheprovider

    echo "== tune (closed-loop autotuner self-test: quadratic-basin" \
         "search, scoped override restore, tunedb round-trip + ambient" \
         "consult) =="
    python -m parsec_tpu.tune --self-test
    python -m pytest tests/test_tune.py -q -p no:cacheprovider

    echo "== tracing overhead gate (uninstalled, the recorder holds no" \
         "PINS chain and the phase plane builds nothing; installed, n" \
         "tasks leave n exec and n release spans; allocation-free) =="
    python -m pytest tests/test_tracing.py -q \
        -k "tracing_overhead or allocation_free" -p no:cacheprovider

    echo "== prefix-cache trie unit tests (radix tree vs the brute-force" \
         "LCP oracle + LRU/byte-budget eviction + CoW pin semantics) =="
    python -m pytest tests/test_llm_prefix.py -q -k "trie or privatize" \
        -p no:cacheprovider

    echo "== speculative decode unit tests (VERIFY incarnation trios," \
         "spec pools vs the greedy oracle at acceptance 0/partial/1.0," \
         "tail rollback across page boundaries + device-copy" \
         "invalidation) =="
    python -m pytest tests/test_llm_spec.py -q \
        -k "incarnations or rollback or acceptance_sweep or rejected" \
        -p no:cacheprovider

    echo "== sharded serving plane (2-rank acceptance: token-for-token" \
         "oracle-equal decode on both ranks + bucket-exact cross-rank" \
         "SLO metrics merge) =="
    python -m pytest tests/test_serve_sharded.py -q \
        -k "oracle_equal_and_metrics_merge" -p no:cacheprovider

    echo "== llm decode superpools (submits per token swept over" \
         "llm_steps_per_pool and streams, tokens oracle-equal) =="
    python -m pytest tests/test_llm.py -q -k "superpool_pays" \
        -p no:cacheprovider

    echo "== lowering (XLA calls per DAG by emission: chain/wavefront/" \
         "unrolled/regions/scan; the region path's drop against" \
         "task-per-dispatch; a warm plan compiles nothing) =="
    python -m pytest tests/test_lowering.py tests/test_lowering_regions.py \
        -q -k "xla_call" -p no:cacheprovider
fi

echo "check.sh: all stages green"
