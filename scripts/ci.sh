#!/usr/bin/env bash
# Tier-1 CI gate: run the full suite and fail on any regression vs the
# known-failures baseline (scripts/known_failures.txt).  Collection errors
# always fail.  Tests newly fixed show up as a friendly note — update the
# baseline when that happens.
set -uo pipefail
cd "$(dirname "$0")/.."

# The OOC parity suite (tests/test_chunkstore.py) writes chunk stores and
# vertex spills via pytest's tmp factory; point TMPDIR at a dedicated
# scratch dir so every byte is reclaimed even if pytest is killed mid-run.
SCRATCH=$(mktemp -d)
trap 'rm -rf "$SCRATCH"' EXIT
export TMPDIR="$SCRATCH"

# Per-suite wall time is printed after each pytest run so slow regressions
# are visible in the CI log history.
suite_timer_start() { SUITE_T0=$(date +%s); }
suite_timer_end() { echo "suite timing: $1 took $(( $(date +%s) - SUITE_T0 ))s"; }

OUT=$(mktemp)
suite_timer_start
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    --continue-on-collection-errors 2>&1 | tee "$OUT"
STATUS=${PIPESTATUS[0]}
suite_timer_end "full suite"

# pytest: 0 = all passed, 1 = some tests failed (gated by the baseline
# below); anything else (interrupted, internal error, usage error, no
# tests collected) means the run itself is broken.
if [ "$STATUS" -ne 0 ] && [ "$STATUS" -ne 1 ]; then
    echo "CI FAIL: pytest exited with status $STATUS (crashed/aborted run)" >&2
    exit 1
fi
if ! grep -qE "[0-9]+ passed" "$OUT"; then
    echo "CI FAIL: no test summary found (aborted run?)" >&2
    exit 1
fi
if grep -qE "^ERROR " "$OUT"; then
    echo "CI FAIL: collection errors" >&2
    exit 1
fi

BASELINE=scripts/known_failures.txt
CURRENT=$(mktemp)
grep -E "^FAILED " "$OUT" | awk '{print $2}' | sort -u > "$CURRENT"

NEW=$(comm -13 <(sort -u "$BASELINE") "$CURRENT")
FIXED=$(comm -23 <(sort -u "$BASELINE") "$CURRENT")

if [ -n "$FIXED" ]; then
    echo "note: tests fixed vs baseline (consider updating $BASELINE):"
    echo "$FIXED"
fi
if [ -n "$NEW" ]; then
    echo "CI FAIL: new failures vs baseline:" >&2
    echo "$NEW" >&2
    exit 1
fi

# The OOC measured-vs-modeled parity suite is the fully-out-of-core gate;
# run it standalone so a regression there fails loudly even when someone
# edits the baseline file.
suite_timer_start
if ! PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    tests/test_chunkstore.py; then
    echo "CI FAIL: OOC parity suite (tests/test_chunkstore.py)" >&2
    exit 1
fi
suite_timer_end "OOC parity suite"

# The codec + compression-parity suite is the compression-tier gate
# (DESIGN.md §9): varint/delta round trips, every compressed read's length
# == the byte model, and bit-identical results with the compression knob
# on vs off across the executors; standalone for the same
# baseline-can't-hide-it reason as above.
suite_timer_start
if ! PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    tests/test_codec.py; then
    echo "CI FAIL: codec + compression parity suite (tests/test_codec.py)" >&2
    exit 1
fi
suite_timer_end "codec + compression parity suite"

# The distributed parity suite (dist_ooc worker shards + sparse exchange,
# shard_map-vs-local, filter-never-drops property) is the distributed
# fully-out-of-core gate; 8 forced host devices so the shard_map paths run
# on a real (emulated) mesh.  REPRO_DIST_PARALLEL=1 flips every dist_ooc
# engine in the suite onto the thread-pooled parallel-worker path
# (EngineConfig.parallel_workers, DESIGN.md §8), so the parity gate proves
# the concurrent pipeline, not just the sequential reference; compare this
# suite's timing line against the full-suite run above to see the overlap
# win in the CI log history.
suite_timer_start
DIST_OUT=$(mktemp)
if ! XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    REPRO_DIST_PARALLEL=1 \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    tests/test_dist_ooc.py tests/test_distributed_engine.py \
    tests/test_filter_property.py 2>&1 | tee "$DIST_OUT"; then
    echo "CI FAIL: distributed parity suite (tests/test_dist_ooc.py," \
         "tests/test_distributed_engine.py, tests/test_filter_property.py," \
         "parallel_workers on)" >&2
    exit 1
fi
# The hypothesis-based filter property suite importorskips when the module
# is absent (some dev containers cannot pip install); make that loud so a
# broken hypothesis install on a real CI host cannot silently skip the
# never-drop-a-message property.
if grep -q "skipped" "$DIST_OUT" && \
   ! python -c "import hypothesis" 2>/dev/null; then
    echo "CI WARNING: hypothesis not installed —" \
         "tests/test_filter_property.py was SKIPPED, the filter" \
         "never-drops property did not run" >&2
fi
suite_timer_end "distributed parity suite"

# The device-decode parity suite (DESIGN.md §10): Pallas varint/delta
# kernels bit-identical to the numpy codec, per-chunk device decode ==
# host decode, and EngineConfig.device_decode on/off bit-identity across
# all four executors (shard_map in a subprocess on 8 forced host
# devices); standalone for the same baseline-can't-hide-it reason.
suite_timer_start
if ! PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    tests/test_varint_kernels.py; then
    echo "CI FAIL: device-decode parity suite" \
         "(tests/test_varint_kernels.py)" >&2
    exit 1
fi
suite_timer_end "device-decode parity suite"

# Streaming gate (ROADMAP "larger-than-host graphs in CI"): push an RMAT
# graph through dist_ooc with compression on; verify_io raises inside
# every call on any measured/model byte mismatch, and the driver asserts
# compression strictly reduced disk+net traffic.  The small configuration
# (scale 12) runs on every CI invocation — the vectorized store build made
# it cheap; REPRO_SLOW=1 switches to the large configuration (scale 16+).
suite_timer_start
if ! PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} \
    python benchmarks/rmat_stream.py; then
    echo "CI FAIL: RMAT streaming benchmark (benchmarks/rmat_stream.py)" >&2
    exit 1
fi
if [ "${REPRO_SLOW:-0}" = "1" ]; then
    suite_timer_end "RMAT streaming benchmark (REPRO_SLOW)"
else
    suite_timer_end "RMAT streaming benchmark (small config)"
fi

# Kernel microbenchmarks: oracle-agreement gates inside the script (it
# asserts decode parity and kernel error bounds) + the BENCH_kernels.json
# perf trajectory (host vs device varint MB/s, DESIGN.md §10) that every
# default CI run must produce so the curve is diffable across commits.
suite_timer_start
if ! PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} \
    python benchmarks/kernels_micro.py; then
    echo "CI FAIL: kernel microbenchmarks (benchmarks/kernels_micro.py)" >&2
    exit 1
fi
if [ ! -s "${REPRO_BENCH_DIR:-.}/BENCH_kernels.json" ]; then
    echo "CI FAIL: benchmarks/kernels_micro.py did not write" \
         "BENCH_kernels.json" >&2
    exit 1
fi
suite_timer_end "kernel microbenchmarks + BENCH_kernels.json"

# The multi-query parity suite (DESIGN.md §11): Q-batched execution
# bit-identical to Q independent runs on all four executors, per-query
# convergence, batched measured bytes <= the sum of solo runs, and the
# serving session.  8 forced host devices for the shard_map panel path;
# REPRO_DIST_PARALLEL=1 so the dist_ooc W=2 parity cases run the
# thread-pooled parallel-worker pipeline, not just the sequential
# reference.  Standalone for the baseline-can't-hide-it reason above.
suite_timer_start
if ! XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    REPRO_DIST_PARALLEL=1 \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    tests/test_multiquery.py; then
    echo "CI FAIL: multi-query parity suite (tests/test_multiquery.py," \
         "parallel_workers on)" >&2
    exit 1
fi
suite_timer_end "multi-query parity suite"

# The serving amortization gate (DESIGN.md §11): run fig5's serving
# section (reduced scale — the curve's shape, not its magnitude, is the
# gate) and re-check from BENCH_serving.json that serving 8 queries in
# one batch costs < 0.5x the per-query bytes of serving them one at a
# time.  The section's own in-script asserts additionally cover
# bit-identical answers across every Q.
suite_timer_start
if ! REPRO_FIG5_SECTIONS=serving REPRO_BENCH_DIR="$SCRATCH/serving" \
    PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} \
    python -c "from benchmarks import fig5_traffic; fig5_traffic.main(scale=9)"; then
    echo "CI FAIL: fig5 serving section (benchmarks/fig5_traffic.py)" >&2
    exit 1
fi
if ! python - "$SCRATCH/serving/BENCH_serving.json" <<'EOF'
import json, sys
recs = json.load(open(sys.argv[1]))
per_q = {r["config"]: r["value"] for r in recs
         if r["metric"] == "bytes_per_query"}
q1, q8 = per_q["ooc/Q=1/queries=8"], per_q["ooc/Q=8/queries=8"]
ratio = q8 / q1
print(f"serving gate: bytes/query Q=8 is {ratio:.3f}x Q=1")
sys.exit(0 if ratio < 0.5 else 1)
EOF
then
    echo "CI FAIL: serving amortization gate —" \
         "bytes/query(Q=8) >= 0.5x bytes/query(Q=1)" >&2
    exit 1
fi
suite_timer_end "serving amortization gate + BENCH_serving.json"

# The shard_map sparse-exchange parity suite (DESIGN.md §12): compacted
# collectives' padding/overflow contracts, compaction + scatter-back ==
# the dense filtered exchange bit-for-bit, and the
# physical_sparse_exchange knob bit-identical to the dense slab for all
# four algorithms + multi-BFS with the measured==model payload audit.
# Standalone for the baseline-can't-hide-it reason above; 8 forced host
# devices so the collectives run on a real (emulated) mesh.
suite_timer_start
if ! XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    tests/test_shardmap_exchange.py; then
    echo "CI FAIL: shard_map sparse-exchange parity suite" \
         "(tests/test_shardmap_exchange.py)" >&2
    exit 1
fi
if ! python -c "import hypothesis" 2>/dev/null; then
    echo "CI WARNING: hypothesis not installed —" \
         "tests/test_sparse_collectives.py's compacted round-trip" \
         "property suite was SKIPPED (its deterministic twins in" \
         "tests/test_shardmap_exchange.py did run)" >&2
fi
suite_timer_end "shard_map sparse-exchange parity suite"

# The physical-exchange payload gate (DESIGN.md §12): run fig5's shardmap
# section (reduced scale) and re-check from BENCH_shardmap.json that the
# compacted collective shipped strictly fewer payload elements than the
# dense slab on BFS while never exceeding it on PageRank.
suite_timer_start
if ! REPRO_FIG5_SECTIONS=shardmap REPRO_BENCH_DIR="$SCRATCH/shardmap" \
    PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} \
    python -c "from benchmarks import fig5_traffic; fig5_traffic.main(scale=9)"; then
    echo "CI FAIL: fig5 shardmap section (benchmarks/fig5_traffic.py)" >&2
    exit 1
fi
if ! python - "$SCRATCH/shardmap/BENCH_shardmap.json" <<'EOF'
import json, sys
recs = json.load(open(sys.argv[1]))
vals = {(r["config"], r["metric"]): r["value"] for r in recs
        if r["benchmark"] == "fig5_shardmap"}
tag = "p8/cpu-host-devices"
bfs, bfs_d = vals[(f"bfs/{tag}", "payload_elems")], \
    vals[(f"bfs/{tag}", "payload_elems_dense")]
pr, pr_d = vals[(f"pagerank/{tag}", "payload_elems")], \
    vals[(f"pagerank/{tag}", "payload_elems_dense")]
print(f"shardmap gate: bfs {bfs:.0f}/{bfs_d:.0f} elems,"
      f" pagerank {pr:.0f}/{pr_d:.0f} elems")
sys.exit(0 if bfs < bfs_d and pr <= pr_d else 1)
EOF
then
    echo "CI FAIL: physical-exchange payload gate —" \
         "compacted did not beat the dense slab on BFS" >&2
    exit 1
fi
suite_timer_end "physical-exchange payload gate + BENCH_shardmap.json"

# The process-transport gate (DESIGN.md §13): wire-format framing
# round-trips + truncation error paths, and the loopback parity runs that
# prove a real multi-process dist_ooc run over localhost sockets is
# bit-identical to the in-thread Exchange (counters, worker totals, and
# the measured==model byte audit included).  Standalone for the
# baseline-can't-hide-it reason above.
suite_timer_start
if ! PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    tests/test_transport.py; then
    echo "CI FAIL: process-transport suite (tests/test_transport.py)" >&2
    exit 1
fi
suite_timer_end "process-transport suite"

# The crash-recovery gate (DESIGN.md §13): the fault-injection matrix —
# kill a worker process at chosen ProcessEdges calls/phases on all four
# algorithms, drop and delay cross-rank batches — asserting every
# recovered run is bit-identical to the failure-free reference.
# REPRO_FAULT_FULL=1 expands the kill matrix to every ProcessEdges call
# index; the default representative subset runs on every CI invocation.
suite_timer_start
if ! PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    tests/test_fault_injection.py; then
    echo "CI FAIL: crash-recovery fault-injection suite" \
         "(tests/test_fault_injection.py)" >&2
    exit 1
fi
if ! python -c "import hypothesis" 2>/dev/null; then
    echo "CI WARNING: hypothesis not installed —" \
         "tests/test_fault_injection.py ran the pinned-seed random-" \
         "schedule sweep instead of the hypothesis property" >&2
fi
suite_timer_end "crash-recovery fault-injection suite"

# The storage-integrity gate (DESIGN.md §14): every persisted artifact —
# chunk sections, vertex-spill batches, checkpoint blocks, serialized
# edge lists — carries a CRC that is verified on read; a single flipped
# byte raises a typed IntegrityError naming the damaged file, and
# scripts/fsck.py finds it offline.
suite_timer_start
if ! PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    tests/test_integrity.py; then
    echo "CI FAIL: storage-integrity suite (tests/test_integrity.py)" >&2
    exit 1
fi
suite_timer_end "storage-integrity suite"

# The durable-restart gate (DESIGN.md §14): kill EVERY rank mid-run,
# relaunch with resume=True, and require the finished job to be
# bit-identical to a failure-free run — values, counters, and the
# measured==model byte audit included.  Also gates the run-log CRC and
# run-id guards (a tampered or foreign run log is a typed fatal, never a
# silently-wrong resume).
suite_timer_start
if ! PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    tests/test_restart.py; then
    echo "CI FAIL: durable-restart suite (tests/test_restart.py)" >&2
    exit 1
fi
suite_timer_end "durable-restart suite"

# Crash-restart smoke (REPRO_FAULT_FULL=1 only): one extra end-to-end
# run on a freshly built store — kill all ranks at a randomly drawn
# ProcessEdges call on a randomly drawn algorithm, resume, and require
# bit-identity.  Randomized on purpose: over CI history this walks crash
# points the fixed restart matrix does not pin.
if [ "${REPRO_FAULT_FULL:-0}" = "1" ]; then
    suite_timer_start
    if ! PYTHONPATH=src:tests${PYTHONPATH:+:$PYTHONPATH} \
        python - <<'EOF'
import os, random, tempfile

import prochelp
from repro.runtime.faults import FAULT_EXIT, FaultPlan

root = tempfile.mkdtemp(prefix="restart_smoke_")
prob = prochelp.build_problem(os.path.join(root, "store"), workers=(2,))
alg = random.choice(["pagerank", "bfs", "sssp", "wcc"])
pe = random.randint(1, 3)   # always within the shortest run's op count
print(f"crash-restart smoke: alg={alg}, kill all ranks at pe={pe}",
      flush=True)
base = prochelp.run_threads(prob, 2, alg)
plan = FaultPlan([FaultPlan.kill(r, pe, "start") for r in range(2)])
spec, codes, results = prochelp.run_procs(
    prob, 2, alg, os.path.join(root, "run"), plan=plan)
assert codes == [FAULT_EXIT] * 2, f"crash phase: {codes}"
assert not results, "no rank may publish a result from the crashed run"
codes, results = prochelp.resume_procs(spec)
assert codes == [0, 0], f"resume phase: {codes}"
for r in (0, 1):
    prochelp.assert_result_equal(results[r], base)
    assert int(results[r]["recoveries"]) == 0
print("crash-restart smoke: resumed run is bit-identical")
EOF
    then
        echo "CI FAIL: crash-restart smoke — resumed job not" \
             "bit-identical (or resume failed)" >&2
        exit 1
    fi
    suite_timer_end "crash-restart smoke (REPRO_FAULT_FULL)"
fi

echo "CI OK: no regressions vs baseline ($(wc -l < "$CURRENT") known failures)"
