#!/usr/bin/env bash
# Tier-1 verify wrapper (ROADMAP.md) with a fast collection gate.
#
# The gate runs `pytest --collect-only` first: an import break (like the
# seed's `from jax import shard_map` failure on older JAX) fails in seconds
# with the real traceback instead of surfacing as per-file collection
# errors mid-suite.  The full suite then runs partitioned into
# process-isolated pytest groups (see the comment above the loop): one
# process accumulating every suite's XLA compilations hits a pre-existing
# XLA:CPU backend_compile segfault around ~550 programs.
#
# Usage: scripts/t1.sh            # gate + full tier-1 suite (partitioned)
#        scripts/t1.sh --collect  # gate only (seconds)
#        T1_GROUPS=8 scripts/t1.sh  # override the partition count
set -u -o pipefail
cd "$(dirname "$0")/.."

echo "== t1: jax lint gate =="
# pure-AST lint (no JAX import, sub-second): jitted step/update functions
# must donate, no host syncs inside jitted bodies, no stray jax.debug.print
if ! timeout -k 10 60 python scripts/lint_jax.py; then
    echo "t1: LINT FAILED (scripts/lint_jax.py)" >&2
    exit 2
fi

echo "== t1: concurrency static gates =="
# (a) the lint above also enforces bare-lock / blocking-in-lock /
# wall-clock-interval; (b) this gate checks the lockdep waiver file is
# strict-valid and the fleet frame protocol is exhaustive: every
# {"op"/"ev": ...} literal sent across transport/worker/remote has a
# handler comparing against it, and no handler is dead (pure AST)
if ! timeout -k 10 60 python -m deepspeed_tpu.analysis.concurrency; then
    echo "t1: CONCURRENCY GATE FAILED (deepspeed_tpu/analysis/concurrency.py)" >&2
    exit 2
fi

echo "== t1: collection gate =="
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly > /tmp/_t1_collect.log 2>&1
then
    echo "t1: COLLECTION FAILED" >&2
    grep -aE "ERROR|error" /tmp/_t1_collect.log | head -20 >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi
tail -1 /tmp/_t1_collect.log
# the PEFT subsystem suite must be visible to collection — a linear/ import
# break would otherwise hide all its tests behind a collection error
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_linear.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_linear.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# same for the serving suite — its imports pull in the whole stack
# (inference/v2, elasticity teardown helper, monitor, HTTP front)
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_serving.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_serving.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# fault-tolerance suite: its imports pull in the durability stack
# (faults harness, checkpoint commit protocol, elastic agent)
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_fault_tolerance.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_fault_tolerance.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# prefix-cache suite: imports the radix tree, refcounted allocator, and
# the serving metrics/broker integration
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_prefix_cache.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_prefix_cache.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# speculative-decoding suite: imports the in-graph draft/verify step
# (inference/v2/spec.py), the self-draft heads (linear/spec_heads.py), and
# the broker's multi-token dispatch path
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_spec_decode.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_spec_decode.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# observability suite: imports the tracer/recorder/prometheus package, the
# /debug server surfaces, and the flight-dump fault plumbing
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_observability.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_observability.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# serving-fleet suite: imports the replica transport, the worker process
# entrypoint, and the supervisor (chaos/fault-isolation stack)
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_fleet.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_fleet.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# replay suite: imports the workload schema and the broker's capture
# (observability/replay.py) and the fleet's trace stitching
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_replay.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_replay.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# mixed-GEMM path suite: imports the Pallas kernel wiring (linear/ frozen
# base, models/ scan path, inference/v2 quantized serving)
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_mixed_gemm_path.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_mixed_gemm_path.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# multi-host fleet suite: imports the network transport (remote registry,
# fenced registration), the autoscaler, and the rolling-rollout controller
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_remote_fleet.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_remote_fleet.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# disaggregated-serving suite: imports the phase-class balancer routing,
# the KV prefix-handoff path, and the per-tenant SLO accounting
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_disagg.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_disagg.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# paging suite: imports the host-DRAM/spill block pager (inference/v2/
# paging.py), the tiered radix-tree demote/promote path, and the
# FastPersist O_DIRECT spill writer
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_paging.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_paging.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# adapter suite: imports the multi-tenant LoRA registry (serving/
# adapters.py), the heterogeneous-adapter decode path, and the merged-
# weight export seam
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_adapters.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_adapters.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

# rehydration suite: imports the crash-durable cold tier (inference/v2/
# coldstore.py), the restart rehydration paths (engine + adapter
# registry), and the fault-injection harness
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_rehydrate.py -q --collect-only \
    -p no:cacheprovider -p no:xdist -p no:randomly >> /tmp/_t1_collect.log 2>&1
then
    echo "t1: test_rehydrate.py COLLECTION FAILED" >&2
    tail -30 /tmp/_t1_collect.log >&2
    exit 2
fi

if [ "${1:-}" = "--collect" ]; then
    exit 0
fi

# -- full suite, partitioned into process-isolated pytest runs ------------
#
# One monolithic pytest process accumulates every suite's XLA compilations
# in a single CPU client; around ~550 programs the XLA:CPU backend_compile
# segfaults (pre-existing upstream issue, reproducible at the seed).
# Round-robin the test files into $T1_GROUPS groups, each its own pytest
# process, so no single process approaches the cliff.  Per-file pass/fail
# is unaffected (tier-1 tests are file-independent; conftest re-creates
# fixtures per process); DOTS_PASSED aggregates across groups.
T1_GROUPS=${T1_GROUPS:-6}
# test_remote_fleet gets its own partition (appended below): its loopback-
# TCP fleets bind ephemeral registry ports and spawn scripted worker
# processes, and must not share a pytest process with engine-heavy suites.
# test_disagg likewise: its multi-replica pools compile several engine
# variants (prefix cache on/off, max_seqs overrides) in one process.
# test_fleet gets its own partition too so the three chaos-heavy suites
# (fleet/remote-fleet/disagg) can run under DSTPU_LOCKDEP=1 — every
# failover/fencing/autoscale path is lock-order-checked on every CI run
# (conftest.pytest_sessionfinish asserts the report empty mod waivers).
# test_paging joins them: the pager's promote-ahead thread and spill
# writer interleave with the broker/engine locks, so the whole tiered-KV
# suite runs lock-order-checked too.
# test_adapters likewise: the adapter registry lock nests against the
# broker/engine/pager locks on the admission and retire paths, so the
# multi-tenant suite is lock-order-checked on every CI run.
# test_rehydrate likewise: the cold-store counter lock nests against the
# pager/prefix-cache/broker locks on the demote and rehydrate paths, and
# its fleet test SIGKILLs a live worker — lock-order-checked every run.
mapfile -t T1_FILES < <(ls tests/test_*.py \
    | grep -v -e 'test_remote_fleet' -e 'test_disagg' -e 'test_fleet\.py' \
        -e 'test_paging' -e 'test_adapters' -e 'test_rehydrate' \
    | sort)
rc=0
rm -f /tmp/_t1.log
for ((g = 0; g < T1_GROUPS; g++)); do
    group=()
    for i in "${!T1_FILES[@]}"; do
        if [ $((i % T1_GROUPS)) -eq "$g" ]; then
            group+=("${T1_FILES[$i]}")
        fi
    done
    [ ${#group[@]} -eq 0 ] && continue
    echo "== t1: group $((g + 1))/${T1_GROUPS}: ${group[*]} =="
    timeout -k 10 1800 env JAX_PLATFORMS=cpu \
        python -m pytest "${group[@]}" -q -m 'not slow' \
        --continue-on-collection-errors \
        -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee -a /tmp/_t1.log
    grc=${PIPESTATUS[0]}
    # rc 5 = "no tests collected" (a group of only slow/skipped files): pass
    if [ "$grc" -ne 0 ] && [ "$grc" -ne 5 ]; then
        rc=$grc
    fi
done
echo "== t1: group fleet (lockdep): tests/test_fleet.py =="
timeout -k 10 1800 env JAX_PLATFORMS=cpu DSTPU_LOCKDEP=1 \
    python -m pytest tests/test_fleet.py -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee -a /tmp/_t1.log
grc=${PIPESTATUS[0]}
if [ "$grc" -ne 0 ] && [ "$grc" -ne 5 ]; then
    rc=$grc
fi
echo "== t1: group disagg (lockdep): tests/test_disagg.py =="
timeout -k 10 1800 env JAX_PLATFORMS=cpu DSTPU_LOCKDEP=1 \
    python -m pytest tests/test_disagg.py -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee -a /tmp/_t1.log
grc=${PIPESTATUS[0]}
if [ "$grc" -ne 0 ] && [ "$grc" -ne 5 ]; then
    rc=$grc
fi
echo "== t1: group paging (lockdep): tests/test_paging.py =="
timeout -k 10 1800 env JAX_PLATFORMS=cpu DSTPU_LOCKDEP=1 \
    python -m pytest tests/test_paging.py -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee -a /tmp/_t1.log
grc=${PIPESTATUS[0]}
if [ "$grc" -ne 0 ] && [ "$grc" -ne 5 ]; then
    rc=$grc
fi
echo "== t1: group adapters (lockdep): tests/test_adapters.py =="
timeout -k 10 1800 env JAX_PLATFORMS=cpu DSTPU_LOCKDEP=1 \
    python -m pytest tests/test_adapters.py -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee -a /tmp/_t1.log
grc=${PIPESTATUS[0]}
if [ "$grc" -ne 0 ] && [ "$grc" -ne 5 ]; then
    rc=$grc
fi
echo "== t1: group rehydrate (lockdep): tests/test_rehydrate.py =="
timeout -k 10 1800 env JAX_PLATFORMS=cpu DSTPU_LOCKDEP=1 \
    python -m pytest tests/test_rehydrate.py -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee -a /tmp/_t1.log
grc=${PIPESTATUS[0]}
if [ "$grc" -ne 0 ] && [ "$grc" -ne 5 ]; then
    rc=$grc
fi
echo "== t1: group remote-fleet (lockdep): tests/test_remote_fleet.py =="
timeout -k 10 1800 env JAX_PLATFORMS=cpu DSTPU_LOCKDEP=1 \
    python -m pytest tests/test_remote_fleet.py -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee -a /tmp/_t1.log
grc=${PIPESTATUS[0]}
if [ "$grc" -ne 0 ] && [ "$grc" -ne 5 ]; then
    rc=$grc
fi
# lockdep aggregate: sum the per-process "LOCKDEP locks=..." lines the
# conftest sessionfinish hook printed in the DSTPU_LOCKDEP=1 partitions
echo "LOCKDEP_SUMMARY $(grep -a '^LOCKDEP locks=' /tmp/_t1.log \
    | awk -F'[= ]' '{l+=$3; e+=$5; c+=$7; b+=$9; w+=$11} END {
        printf "locks=%d edges=%d cycles=%d blocking=%d waived=%d runs=%d", l, e, c, b, w, NR}')"
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
