#!/usr/bin/env bash
# CI gate, in dependency order (cheapest signal first):
#   1. raylint         — static invariants, JAX-free, ~5s
#   2. drill gate      — one bounded, seeded resilience drill; fails on an
#                        SLO regression (MTTR/availability/request-loss
#                        thresholds in ray_tpu/drills/thresholds.json)
#   3. overload gate   — the overload_storm drill: >=3x offered load +
#                        task flood; goodput floor, zero lost-accepted,
#                        post-storm recovery (anti-metastable-collapse)
#   4. controller gate — the controller_kill drill: serve controller
#                        dies under load; the restarted incarnation must
#                        recover from its GCS-KV checkpoint and ADOPT
#                        every live replica (zero restarts, zero
#                        lost-accepted, bounded MTTR)
#   5. rl storm gate   — the rl_rollout_storm drill: rollout-runner
#                        kills + a node preemption mid-decoupled-RL-
#                        training; learner cadence, zero stale batches
#                        trained, zero lost progress, slot-keyed
#                        respawn MTTR
#   6. dataplane smoke — one >2x-chunk-size jax.Array put/get across a
#                        2-node in-process cluster: value integrity, a
#                        conservative bandwidth floor, and ZERO
#                        whole-payload copies (serialization.COPY_STATS)
#   7. memory smoke    — put/transfer/free churn across a 2-node
#                        in-process cluster: every node+worker answers
#                        the memory fan-out, the leak sweep stays at
#                        ZERO suspects, no object.leak_suspect events,
#                        arena bytes back to the pre-churn baseline
#   8. health smoke    — a typed-shed burst on a 2-node cluster must
#                        fire the production overload_shed_burst SLO
#                        rule (compressed windows) and RESOLVE after
#                        the burst, with alert.firing/alert.resolved
#                        in the cluster event log and a live scorecard
#   9. tier-1 tests    — the full `not slow` suite
#
# Usage: tools/ci.sh [--skip-tests]
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== raylint =="
# human format on stdout; machine-readable report for CI artifact upload
python -m tools.raylint ray_tpu/ tests/ \
    --json-out "${TMPDIR:-/tmp}/ci_raylint.json"

echo "== drill gate (bounded, seeded) =="
JAX_PLATFORMS=cpu python -m ray_tpu drill run \
    --scenario replica_kill --budget 120s --seed 0 \
    --report "${TMPDIR:-/tmp}/ci_drill_report.json" --gate

echo "== overload_storm drill gate =="
JAX_PLATFORMS=cpu python -m ray_tpu drill run \
    --scenario overload_storm --budget 120s --seed 0 \
    --report "${TMPDIR:-/tmp}/ci_overload_report.json" --gate

echo "== controller_kill drill gate =="
JAX_PLATFORMS=cpu python -m ray_tpu drill run \
    --scenario controller_kill --budget 120s --seed 0 \
    --report "${TMPDIR:-/tmp}/ci_controller_report.json" --gate

echo "== rl_rollout_storm drill gate =="
JAX_PLATFORMS=cpu python -m ray_tpu drill run \
    --scenario rl_rollout_storm --budget 240s --seed 0 \
    --report "${TMPDIR:-/tmp}/ci_rl_storm_report.json" --gate

echo "== dataplane smoke (bounded) =="
JAX_PLATFORMS=cpu python -m tools.dataplane_smoke --budget 120

echo "== memory smoke (bounded) =="
JAX_PLATFORMS=cpu python -m tools.memory_smoke --budget 120

echo "== health smoke (bounded) =="
JAX_PLATFORMS=cpu python -m tools.health_smoke --budget 120

if [[ "${1:-}" != "--skip-tests" ]]; then
    echo "== tier-1 tests =="
    JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "not slow" \
        -p no:cacheprovider
fi
