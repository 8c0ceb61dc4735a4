#!/bin/bash
# The one-command merge gate (ISSUE 10): native build + C++ test suites
# (plain AND under TSan) + the Python extension, then the full static
# analysis lane — repo-wide beastlint in CI mode (18 rules incl. the
# C++ frontend and the fleet/telemetry tier), the rule-fixture
# selftest, and the exhaustive model checks for both protocol specs
# (shm ring + doorbell, and the fleet control plane; shipped specs
# verify, seeded mutants must produce counterexample traces).
#
#   scripts/check.sh            # everything
#   scripts/check.sh --fast     # skip the native build (analysis only)
#
# Exit: nonzero on the first failing stage; each stage prints its own
# verdict line.
set -euo pipefail
cd "$(git -C "$(dirname "$0")" rev-parse --show-toplevel)"

FAST=0
for arg in "$@"; do
    case "$arg" in
        --fast) FAST=1 ;;
        *)
            echo "unknown argument: $arg" >&2
            exit 2
            ;;
    esac
done

if [[ "$FAST" -eq 0 ]]; then
    echo "== check: native smoke (build + C++ tests, plain + TSan, extension)"
    bash scripts/build_native.sh --smoke
fi

echo "== check: beastlint --ci (repo-wide, C++ frontend active)"
ci_start=$(date +%s)
python -m torchbeast_tpu.analysis --ci
ci_elapsed=$(( $(date +%s) - ci_start ))
# The CI-budget pin (ISSUE 10, re-anchored ISSUE 12): the full
# static-analysis lane must stay under 20s or it stops being a
# pre-commit-speed gate.
if [[ "$ci_elapsed" -gt 20 ]]; then
    echo "beastlint --ci took ${ci_elapsed}s (> 20s CI budget)" >&2
    exit 1
fi

echo "== check: beastlint --selftest (rule fixtures)"
python -m torchbeast_tpu.analysis --selftest

echo "== check: protocol model check (shm ring + doorbell)"
python -m torchbeast_tpu.analysis --check-protocol

echo "== check: fleet protocol model check (control plane under crash/wedge)"
python -m torchbeast_tpu.analysis --check-fleet

if [[ "$FAST" -eq 0 ]]; then
    echo "== check: chaos selftest, scaled (x2 fleet + x2 fault plan, shed audit)"
    JAX_PLATFORMS=cpu python scripts/chaos_run.py --selftest --scale 2

    echo "== check: IMPACT smoke (Catch, lag budget 10x, replay reuse 2)"
    # The lag-tolerant learner end to end (ISSUE 18): --loss impact
    # must LEARN Catch with the policy-lag budget at 10x the default
    # (replicas on the impact-relaxed refresh-every-10 cadence) while
    # reusing every batch twice — and the throughput/cadence accounting
    # that justifies the mode must be in the telemetry: the
    # env_sps/learn_sps split at the configured reuse factor, and the
    # target-network store publishing on its own cadence.
    JAX_PLATFORMS=cpu python -m torchbeast_tpu.polybeast \
        --env Catch --total_steps 40000 --num_servers 2 --num_actors 4 \
        --batch_size 4 --unroll_length 20 \
        --learning_rate 2e-3 --entropy_cost 0.01 \
        --loss impact --replay_reuse 2 --target_refresh_updates 8 \
        --max_policy_lag 200 --env_seed 1 \
        --xpid impact-smoke --savedir /tmp/tbt_impact_smoke \
        > /tmp/tbt_impact_smoke.log 2>&1 \
        || { tail -20 /tmp/tbt_impact_smoke.log; exit 1; }
    python - <<'EOF'
import csv, json
run = "/tmp/tbt_impact_smoke/impact-smoke"
ret = None
for row in csv.DictReader(open(run + "/logs.csv")):
    if row.get("mean_episode_return"):
        ret = float(row["mean_episode_return"])
assert ret is not None and ret >= 0.5, f"impact Catch final return {ret} < 0.5"
snap = json.loads(open(run + "/telemetry.jsonl").read().strip().splitlines()[-1])
g, c = snap["gauges"], snap["counters"]
assert g.get("learner.sample_reuse") == 2.0, g.get("learner.sample_reuse")
env_sps, learn_sps = g.get("learner.env_sps"), g.get("learner.learn_sps")
assert env_sps and learn_sps and learn_sps > env_sps, (env_sps, learn_sps)
assert c.get("learner.target.snapshots_published", 0) >= 1, \
    c.get("learner.target.snapshots_published")
assert c.get("learner.target.snapshot_bytes_published", 0) > 0
assert c.get("serving.snapshots_published", 0) >= 1, \
    c.get("serving.snapshots_published")
print("impact-smoke: PASS (return", ret, "env_sps", round(env_sps, 1),
      "learn_sps", round(learn_sps, 1), ")")
EOF

    # The device split, the two-host fleet and the native serving plane
    # have no smoke here: tier-1 drives them end to end
    # (tests/test_sebulba.py::test_polybeast_device_split_e2e: polybeast
    # under `--device_split inf=1,learn=rest` on forced host devices;
    # tests/test_fleet.py: the control plane and wire-delivered
    # snapshots; tests/test_native_routing.py: the C++ slice and replica
    # routing with admission armed).
fi

echo "== check: PASS"
