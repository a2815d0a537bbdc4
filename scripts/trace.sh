#!/usr/bin/env bash
# One-command flight-recorder run: journal-enabled local bench, merged
# cross-node trace in the SUMMARY, Chrome trace JSON for Perfetto.
#
#   scripts/trace.sh                         # 4 nodes, 500 tx/s, 10 s
#   scripts/trace.sh --nodes 8 --rate 1000   # extra args pass through
#
# Output: logs/journals/ (per-node JSONL ring segments) and
# logs/trace.json — open the latter at https://ui.perfetto.dev.
# Timeout-bounded so a hung committee cannot wedge a CI job.
#
#   BYZ=1 scripts/trace.sh        # ONLY the Byzantine adversary matrix
#                                 # (scripts/byz_check.py): equivocation
#                                 # caught-and-attributed, collusion
#                                 # FAILs with non-zero exit, withholding
#                                 # recovers liveness
#   AGG=1 scripts/trace.sh        # ONLY the compact-certificate sweep
#                                 # (scripts/agg_check.py): compact vs
#                                 # vote-list QC parity + one-pairing
#                                 # flatness across committee sizes,
#                                 # non-zero exit on any divergence
#   LOAD=1 scripts/trace.sh       # ONLY the admission-plane load check
#                                 # (scripts/load_check.py): open-loop
#                                 # saturation sweep + 2x-saturation
#                                 # overload with a squeezed proposer
#                                 # buffer, non-zero exit on any silent
#                                 # drop-newest
#   STATE=1 scripts/trace.sh      # ONLY the replicated execution-layer
#                                 # check (scripts/state_check.py):
#                                 # SIGKILLed node rejoins via snapshot
#                                 # state-sync with a converging root,
#                                 # byz-collude FAILs full-history root
#                                 # agreement while the trusted subset
#                                 # PASSes, non-zero exit on any break
#   HEALTH=1 scripts/trace.sh     # ONLY the live health-plane check
#                                 # (scripts/health_check.py): fleet
#                                 # watch attaches to a healthy 4-node
#                                 # committee with quiet detectors,
#                                 # leader-isolation trips leader_stall
#                                 # in the live view AND the + HEALTH
#                                 # SUMMARY
#   RECONFIG=1 scripts/trace.sh   # ONLY the live-reconfiguration check
#                                 # (scripts/reconfig_check.py): rotate
#                                 # joins node 4 / retires node 0 with
#                                 # epoch agreement + bounded handoff
#                                 # gap, the rotation survives a
#                                 # SIGKILL+rejoin across the boundary,
#                                 # and byz-reconfig FAILs full-history
#                                 # epoch agreement (trusted subset
#                                 # PASSes); non-zero exit on any break
#   SIM=1 scripts/trace.sh        # ONLY the deterministic-simulator
#                                 # sweep (scripts/sim_check.py): a
#                                 # 500-seed virtual-time explore at
#                                 # n=4 (faults+crashes+byz mix), zero
#                                 # honest invariant failures, the
#                                 # byz-collude family FAILs
#                                 # full-history / PASSes
#                                 # trusted-subset, and a double-run
#                                 # determinism probe; non-zero exit on
#                                 # any break
#   ADAPT=1 scripts/trace.sh      # ONLY the adaptive-adversary check
#                                 # (scripts/adapt_check.py): guided
#                                 # schedule search beats the flat sweep
#                                 # on invariant-threatening schedules
#                                 # at equal budget, honest seeds stay
#                                 # green, and every promoted corpus
#                                 # schedule replays to the same verdict
#                                 # with a byte-identical journal digest
#   CRIT=1 scripts/trace.sh       # ONLY the commit critical-path check
#                                 # (scripts/critpath_check.py): a
#                                 # journaled 4-node run must attribute
#                                 # with >= 90% coverage and print the
#                                 # + CRITPATH block, the --diff gate
#                                 # passes unchanged / fails a planted
#                                 # stage-share regression, and the
#                                 # regime classification is stable
#                                 # across two identical runs
#   NET=1 scripts/trace.sh        # ONLY the wire-level flow accounting
#                                 # check (scripts/net_check.py): a
#                                 # 4-node run must print + NET with
#                                 # propose amplification ~ n-1, class
#                                 # shares covering >= 95% of egress,
#                                 # compact QCs beating the vote list
#                                 # on the wire and zero clean-link
#                                 # retransmits; same-seed sim runs
#                                 # must produce byte-identical flow
#                                 # tables and amp stays sane under
#                                 # flapping-link chaos
#   INGEST=1 scripts/trace.sh     # ONLY the zero-copy ingest check
#                                 # (scripts/ingest_check.py): signed
#                                 # votes over the native reactor
#                                 # transport must verify straight from
#                                 # the staging arenas — every verdict
#                                 # True, zero-copy hit rate >= 90%,
#                                 # e2e sigs/s reported; non-zero exit
#                                 # if the pack/claim streams desync
#   LINT=1 scripts/trace.sh       # ONLY the static analysis plane
#                                 # (scripts/analysis_check.py): every
#                                 # hotstuff_tpu/analysis lint rule,
#                                 # docs/KNOBS.md freshness, and the
#                                 # native TSan/ASan reactor + store
#                                 # stress (skip-if-unsupported),
#                                 # non-zero exit on any finding
set -euo pipefail

cd "$(dirname "$0")/.."

if [ "${AGG:-0}" = "1" ]; then
    exec timeout -k 10 1800 env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python scripts/agg_check.py "$@"
fi

if [ "${BYZ:-0}" = "1" ]; then
    exec timeout -k 10 1800 env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python scripts/byz_check.py "$@"
fi

if [ "${LOAD:-0}" = "1" ]; then
    exec timeout -k 10 1800 env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python scripts/load_check.py "$@"
fi

if [ "${STATE:-0}" = "1" ]; then
    exec timeout -k 10 1800 env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python scripts/state_check.py "$@"
fi

if [ "${HEALTH:-0}" = "1" ]; then
    exec timeout -k 10 1800 env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python scripts/health_check.py "$@"
fi

if [ "${RECONFIG:-0}" = "1" ]; then
    exec timeout -k 10 1800 env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python scripts/reconfig_check.py "$@"
fi

if [ "${SIM:-0}" = "1" ]; then
    exec timeout -k 10 1800 env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python scripts/sim_check.py "$@"
fi

if [ "${ADAPT:-0}" = "1" ]; then
    exec timeout -k 10 1800 env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python scripts/adapt_check.py "$@"
fi

if [ "${CRIT:-0}" = "1" ]; then
    exec timeout -k 10 1800 env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python scripts/critpath_check.py "$@"
fi

if [ "${NET:-0}" = "1" ]; then
    exec timeout -k 10 1800 env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python scripts/net_check.py "$@"
fi

if [ "${INGEST:-0}" = "1" ]; then
    exec timeout -k 10 1800 env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python scripts/ingest_check.py "$@"
fi

if [ "${LINT:-0}" = "1" ]; then
    # stdlib-only: the analysis plane never imports jax, so this gate
    # also runs in the bare CI lint venv
    exec timeout -k 10 1800 python scripts/analysis_check.py "$@"
fi

timeout -k 10 240 env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m benchmark local \
    --nodes 4 --rate 500 --duration 10 --journal "$@"
