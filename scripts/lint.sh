#!/usr/bin/env bash
# Repo lint gate: all four rule families (flow, dev, proto, nat) over the
# default target set (foundationdb_tpu/ + scripts/ + native/fdb_native.c),
# then baseline drift detection, then the CHANGES.md row-alignment check —
# with ONE merged exit code, so CI reports every failing gate in a single
# run instead of stopping at the first.
#
#   scripts/lint.sh             # human output
#   scripts/lint.sh --github    # ::error annotations for CI runners
#
# Exit non-zero on any new violation OR when the committed baseline no
# longer matches current findings (stale/renamed entries someone forgot
# to regenerate with --update-baseline).
set -uo pipefail
cd "$(dirname "$0")/.."

FORMAT=text
if [[ "${1:-}" == "--github" ]]; then
    FORMAT=github
fi

# Keep the gate itself off the accelerator: the analyzer is pure AST work,
# and must not take the chip from a process that serves with it.
export JAX_PLATFORMS=cpu

status=0
python -m foundationdb_tpu.analysis --family all --format "$FORMAT" \
    || status=$?
python -m foundationdb_tpu.analysis --family all --update-baseline --check \
    || status=$?
python scripts/changes_check.py || status=$?
exit "$status"
