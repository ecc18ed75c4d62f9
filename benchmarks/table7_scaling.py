"""Paper Table 7: scalability over P = 1, 2, 4, 8 partitions.

One CPU core cannot show wall-clock speedup, so we report the quantity that
*produces* the paper's speedup: the maximum per-partition work (edges
touched + messages handled + vertex I/O), which the α-balanced range
partitioning drives down near-linearly with P.  Wall time is reported for
reference; the shard_map executor in tests/test_distributed_engine.py proves
the same program runs on a real multi-device mesh.

The dist_ooc section scales the *measured* quantities: for W = 1, 2, 4
workers over the same 8-partition graph, each worker owns its own chunk
shard and vertex spill, and we report the maximum per-worker disk bytes,
network bytes, and edges touched actually served — the distributed
fully-out-of-core claim made by the storage and exchange tiers themselves.

Each W row also reports the wall clock of the same run executed twice:
``seq_s`` with the workers' send/receive loops run one after another (the
sequential reference) and ``par_s`` with ``parallel_workers=True`` (per-
phase thread pools + the long-lived lazy-schedule prefetcher, DESIGN.md
§8).  The two runs are bit-identical in every counter, so the seq/par
pair is the measured wall-clock analogue of the max-per-worker metric:
workers overlapping each other's disk, decode, and compute is exactly
what the paper's Table 7 speedup rests on.  How much of the overlap a
given host can realize depends on its core count (on a 1–2 core CI box
the GIL pins the ratio near 1.0); ``max_worker_busy_s`` vs
``sum_worker_busy_s`` reports the core-count-independent critical path
next to it.  See benchmarks/README.md for the full column map.
"""
from __future__ import annotations

import tempfile

import numpy as np

from benchmarks.engines_common import (
    bench_graph, bench_record, csv_row, merge_bench_json,
    PROBE_DEVICES, shardmap_payload_probe, timed, write_bench_json,
)
from repro.core import (
    ChunkStore, Engine, EngineConfig, build_dist_graph, build_formats,
    make_spec,
)
from repro.core import algorithms as alg


def per_partition_work(g, spec):
    """alpha*|Vi| + |Ei_in| + |Ei_out| per partition (paper §4.5 model)."""
    bounds = np.asarray(spec.boundaries)
    out_deg = g.out_degrees()
    in_deg = g.in_degrees()
    work = []
    for p in range(spec.num_partitions):
        lo, hi = bounds[p], bounds[p + 1]
        work.append(spec.alpha * (hi - lo) + out_deg[lo:hi].sum()
                    + in_deg[lo:hi].sum())
    return np.asarray(work, np.float64)


def main(scale=10) -> list[str]:
    g = bench_graph(scale)
    rows = []
    records = []

    def rec(config, metric, value, units):
        records.append(bench_record("table7_scaling", config, metric,
                                    value, units))

    work1 = None
    for p in (1, 2, 4, 8):
        spec = make_spec(g, num_partitions=p, batch_size=64)
        dg = build_dist_graph(g, spec)
        eng = Engine(dg, build_formats(dg))
        (pr, st), t = timed(lambda: alg.pagerank(eng, 3))
        work = per_partition_work(g, spec)
        if work1 is None:
            work1 = work.max()
        speedup_model = work1 / work.max()
        imbalance = work.max() / work.mean()
        rows.append(csv_row(
            f"t7/scaling/p{p}", t,
            f"max_work={work.max():.0f};modeled_speedup={speedup_model:.2f};"
            f"imbalance={imbalance:.3f};"
            f"msgs={st.counters['msgs_sent']:.0f}"))
        rec(f"p{p}", "wall_time", t, "s")
        rec(f"p{p}", "modeled_speedup", speedup_model, "x")
        rec(f"p{p}", "max_partition_work", work.max(), "work_units")

    # dist_ooc: measured max per-worker traffic for W = 1, 2, 4 workers
    # (8 partitions; every byte below was physically served by a worker's
    # own shard/spill or serialized across the exchange wire).  Each W runs
    # in both modes — sequential worker loops and parallel_workers=True —
    # over the same shards; the runs are bit-identical in every counter,
    # so the seq/par wall-clock pair isolates the pipeline-overlap win.
    # Both modes are warmed once and timed as best-of-N (min filters
    # scheduler noise; overlap scales with cores — on a 1–2 core CI box
    # the GIL bounds the ratio near 1.0, see benchmarks/README.md).
    # max_worker_busy_s vs sum_worker_busy_s is the core-count-independent
    # twin: the critical path a parallel run has to pay vs the serial sum.
    spec = make_spec(g, num_partitions=8, batch_size=64)
    dg = build_dist_graph(g, spec)
    fm = build_formats(dg)
    reps = 5
    for w in (1, 2, 4):
        with tempfile.TemporaryDirectory() as root:
            store = ChunkStore.build_sharded(dg, fm, root, w)
            eng = Engine(dg, fm,
                         EngineConfig(executor="dist_ooc", num_workers=w),
                         store=store)
            par = Engine(dg, fm,
                         EngineConfig(executor="dist_ooc", num_workers=w,
                                      parallel_workers=True),
                         store=store)
            # Warm both engines (page cache, jax op caches, thread pool),
            # then interleave the timed reps so neither mode benefits from
            # running second on a warmer machine; min-of-reps per mode.
            for e in (eng, par):
                alg.pagerank(e, 1)
                e.reset_worker_totals()
            outs_seq, outs_par = [], []
            for _ in range(reps):
                outs_seq.append(timed(lambda: alg.pagerank(eng, 3)))
                outs_par.append(timed(lambda: alg.pagerank(par, 3)))
            (pr, st), t_seq = outs_seq[0][0], min(t for _, t in outs_seq)
            (pr_p, st_p), t_par = outs_par[0][0], min(t for _, t in outs_par)
            assert np.array_equal(np.asarray(pr), np.asarray(pr_p))
            assert st.counters == st_p.counters
            # worker_totals / worker_times accumulated over all `reps`
            # identical runs — divide back to per-run quantities (traffic
            # reps are bit-identical, so this is exact; busy is the mean).
            # Busy comes from the SEQUENTIAL engine: uncontended, its
            # per-worker elapsed is true work time, so sum = the serial
            # cost and max = the critical-path floor any parallel run
            # could reach (the parallel engine's elapsed includes
            # compute-token waits and would overstate both).
            disk = max(wt["disk_bytes"] for wt in eng.worker_totals) / reps
            net = max(wt["net_bytes"] for wt in eng.worker_totals) / reps
            edges = max(wt["edges_touched"]
                        for wt in eng.worker_totals) / reps
            busy = [sum(wt.values()) / reps for wt in eng.worker_times]
            rows.append(csv_row(
                f"t7/dist_ooc/w{w}", t_par,
                f"max_worker_disk_bytes={disk:.0f};"
                f"max_worker_net_bytes={net:.0f};"
                f"max_worker_edges={edges:.0f};"
                f"net_modeled={st.counters['net_bytes']:.0f};"
                f"net_measured={st.counters['measured_net_bytes']:.0f};"
                f"seq_s={t_seq:.3f};par_s={t_par:.3f};"
                f"overlap_speedup={t_seq / max(t_par, 1e-9):.2f};"
                f"max_worker_busy_s={max(busy):.3f};"
                f"sum_worker_busy_s={sum(busy):.3f}"))
            rec(f"dist_ooc_w{w}", "seq_wall_time", t_seq, "s")
            rec(f"dist_ooc_w{w}", "par_wall_time", t_par, "s")
            rec(f"dist_ooc_w{w}", "overlap_speedup",
                t_seq / max(t_par, 1e-9), "x")
            rec(f"dist_ooc_w{w}", "max_worker_disk_bytes", disk, "bytes")
            rec(f"dist_ooc_w{w}", "max_worker_net_bytes", net, "bytes")
            rec(f"dist_ooc_w{w}", "device_decoded_chunks",
                st.counters.get("measured_chunks_device_decoded", 0.0),
                "chunks")

    # shard_map physical exchange: dense-vs-compacted payload elements as
    # the mesh widens (BFS — selective frontiers are where compaction
    # pays; run on p forced host devices in a child so this process keeps
    # seeing one device).  Compacted must never exceed the dense slab and
    # must be strictly below it on at least one selective iteration.
    sm_records = []
    for p in (2, 4, 8):
        c = shardmap_payload_probe(scale, p, algos=("bfs",))["bfs"]
        dense, comp = c["net_payload_elems_dense"], c["net_payload_elems"]
        assert comp <= dense, (p, comp, dense)
        assert comp < dense, (
            f"shard_map compaction never beat dense at p={p}")
        assert abs(c["measured_net_payload_elems"] - comp) <= 0.5, (p, c)
        rows.append(csv_row(
            f"t7/shardmap/p{p}", 0.0,
            f"payload_elems={comp:.0f};payload_elems_dense={dense:.0f};"
            f"compacted_iters={c['exchange_compacted_iters']:.0f};"
            f"dense_iters={c['exchange_dense_iters']:.0f}"))
        for metric, val in (("payload_elems", comp),
                            ("payload_elems_dense", dense),
                            ("compacted_iters",
                             c["exchange_compacted_iters"])):
            sm_records.append(bench_record(
                "table7_shardmap", f"bfs/p{p}/{PROBE_DEVICES}", metric, val,
                "elems" if "elems" in metric else "iters"))
    sm_path = merge_bench_json("BENCH_shardmap.json", sm_records)
    rows.append(csv_row("t7/shardmap/bench_json", 0.0, f"path={sm_path}"))

    path = write_bench_json("BENCH_scaling.json", records)
    rows.append(csv_row("t7/bench_json", 0.0, f"path={path}"))
    return rows


if __name__ == "__main__":
    print("\n".join(main()))
