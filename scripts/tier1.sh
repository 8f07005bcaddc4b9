#!/usr/bin/env bash
# tier1.sh — the blessed tier-1 entry points.
#
# Run in ONE process (`-p no:xdist`, as below), the full tier-1 suite
# does not fit an 870s per-invocation cap; the driver instead runs it
# under pytest-xdist (`-p xdist -n 6 --dist loadfile`, 8 cores, ~8 min).
# For single-process runs this script splits the suite
# DETERMINISTICALLY:
# `tests/test_*.py` are sorted lexically and alternated by index, and
# the `-m multiprocess` pod legs (real 2-process gloo clouds — minutes
# each, clustered in a few files) are carved out into their own target
# so neither half busts the cap as pods are added. The three targets
# together cover exactly the whole suite.
#
#   scripts/tier1.sh part1        # even-indexed files, minus pod legs
#   scripts/tier1.sh part2        # odd-indexed files, minus pod legs
#   scripts/tier1.sh multiprocess # pod smoke: ONLY -m multiprocess legs
#                                 # (cloud formation, durability, fleet,
#                                 # tracing, global fit)
#   scripts/tier1.sh full         # the ROADMAP.md one-shot (needs >870s)
#   scripts/tier1.sh perfguard    # benchdiff gate vs committed BENCH
#                                 # snapshot (jax-free, <10s)
#
# Every mode mirrors the ROADMAP.md tier-1 flags exactly; each capped
# mode runs under `timeout -k 10 870`.
set -u -o pipefail

cd "$(dirname "$0")/.."
MODE="${1:-full}"

PYTEST=(env JAX_PLATFORMS=cpu python -m pytest -q \
        --continue-on-collection-errors \
        -p no:cacheprovider -p no:xdist -p no:randomly)

mapfile -t ALL < <(ls tests/test_*.py | sort)

half() {  # half <parity>: every 2nd file starting at index $1
    local parity="$1" i
    for i in "${!ALL[@]}"; do
        if (( i % 2 == parity )); then printf '%s\n' "${ALL[$i]}"; fi
    done
}

case "$MODE" in
    part1|part2)
        parity=0; [[ "$MODE" == part2 ]] && parity=1
        mapfile -t FILES < <(half "$parity")
        echo "# tier1 $MODE: ${#FILES[@]}/${#ALL[@]} test files" >&2
        timeout -k 10 870 "${PYTEST[@]}" \
            -m 'not slow and not multiprocess' "${FILES[@]}"
        ;;
    full)
        timeout -k 10 870 "${PYTEST[@]}" -m 'not slow' tests/
        ;;
    multiprocess)
        timeout -k 10 870 "${PYTEST[@]}" -m 'multiprocess and not slow' \
            tests/
        ;;
    perfguard)
        # perf-regression gate (ISSUE 20): diff the committed BENCH
        # snapshot against itself through scripts/benchdiff.py — proves
        # the gate's parse/compare path end-to-end, jax-free, <10s.
        # An identical pair MUST pass; a broken parser fails loudly.
        timeout -k 10 60 env JAX_PLATFORMS='' python \
            scripts/benchdiff.py BENCH_r05.json BENCH_r05.json
        ;;
    *)
        echo "usage: $0 {part1|part2|full|multiprocess|perfguard}" >&2
        exit 2
        ;;
esac
