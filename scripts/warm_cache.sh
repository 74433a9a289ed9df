#!/usr/bin/env bash
# AOT lowering/compile cache warmer (ISSUE 8): populate the persistent
# lowering + XLA compilation caches BEFORE a bench run, so its stages pay
# deserialization instead of minutes of XLA compile inside a stage
# deadline.  See docs/PERF.md, "Region lowering & compile budgets".
#
#   scripts/warm_cache.sh                        # default workload set
#   scripts/warm_cache.sh cholesky gemm          # named workloads
#   WARM_N=8192 WARM_NB=512 scripts/warm_cache.sh cholesky
#   WARM_MODES=region WARM_BUDGET=120 scripts/warm_cache.sh cholesky
#
# The cache directory is JAX_COMPILATION_CACHE_DIR when set (JAX reads it
# itself), else <checkout>/.jax_cache (parsec_tpu/device/compile_cache.py).
# JAX keys entries by backend and version, so CPU and TPU processes share
# one directory safely.
set -euo pipefail
cd "$(dirname "$0")/.."

# llm_decode_k is the k-step decode superpool's region program (ISSUE 9):
# warming it is what keeps a region-lowered serving path
# (--mca llm_lower_regions 1) from paying XLA at first-token time.
# llm_prefill_tail is the prefix-cache admission shape (ISSUE 11): a
# trie-hit stream prefills only its unmatched tail, and warming that
# pool geometry keeps cache hits from paying cold compile at admission.
# llm_spec_k is the batched speculative superpool (ISSUE 12): warming it
# keeps the spec serving path (--mca llm_spec_k N) from hitting cold XLA
# at first-draft time in bench/tier-1.
WORKLOADS=("$@")
if [[ ${#WORKLOADS[@]} -eq 0 ]]; then
    WORKLOADS=(gemm cholesky lu stencil llm_decode_k llm_spec_k
               llm_prefill_tail)
fi

ARGS=()
[[ -n "${WARM_N:-}" ]] && ARGS+=(--n "$WARM_N")
[[ -n "${WARM_NB:-}" ]] && ARGS+=(--nb "$WARM_NB")
[[ -n "${WARM_NT:-}" ]] && ARGS+=(--nt "$WARM_NT")
[[ -n "${WARM_MODES:-}" ]] && ARGS+=(--modes "$WARM_MODES")
[[ -n "${WARM_BUDGET:-}" ]] && ARGS+=(--budget "$WARM_BUDGET")

for w in "${WORKLOADS[@]}"; do
    echo "== warm: $w ==" >&2
    python -m parsec_tpu.ptg.lowering --warm "$w" "${ARGS[@]}"
done
