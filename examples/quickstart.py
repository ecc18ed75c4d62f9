"""Quickstart: DFOGraph engine on an R-MAT graph — the paper's PageRank +
SSSP with the signal/slot API, filtering counters, and a checkpoint/restart.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.ckpt import BlockStore  # noqa: E402
from repro.core import Engine, build_dist_graph, build_formats, make_spec  # noqa: E402
from repro.core import algorithms as alg  # noqa: E402
from repro.data.graphs import rmat_graph  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    print("== build graph (R-MAT scale 10, edge factor 16) ==")
    g = rmat_graph(10, 16, seed=42, weighted=True)
    print(f"|V|={g.num_vertices}  |E|={g.num_edges}")

    print("== two-level column-oriented partition (P=4, batch=64) ==")
    spec = make_spec(g, num_partitions=4, batch_size=64)
    dg = build_dist_graph(g, spec)
    fm = build_formats(dg)
    print(f"boundaries={spec.boundaries}  batches/partition={spec.num_batches}")
    engine = Engine(dg, fm)

    print("== PageRank (5 iterations) ==")
    pr, stats = alg.pagerank(engine, num_iters=5)
    ref = alg.ref_pagerank(g.num_vertices, g.src, g.dst, 5)
    print(f"max |err| vs oracle: {np.abs(pr - ref).max():.2e}")
    c = stats.counters
    print(f"messages sent: {c['msgs_sent']:.0f} "
          f"(unfiltered would be {c['msgs_sent_nofilter']:.0f} — "
          f"filtering saved "
          f"{100 * (1 - c['msgs_sent'] / c['msgs_sent_nofilter']):.1f}%)")
    print(f"net bytes: {c['net_bytes']:.0f}  edge bytes read: "
          f"{c['edge_read_bytes']:.0f}")

    print("== SSSP with checkpoint/restart (paper §3.2) ==")
    source = int(np.argmax(g.out_degrees()))
    with tempfile.TemporaryDirectory() as d:
        store = BlockStore(d, keep=2)
        # run 3 iterations, checkpoint, 'crash', restore, finish
        state = engine.init_state(
            dist=np.where(np.asarray(engine.global_id) == source,
                          0.0, np.float32(np.finfo(np.float32).max / 4)))
        import jax.numpy as jnp
        active = (engine.global_id == source) & engine.graph.vertex_valid
        for i in range(3):
            state, active, upd, _ = engine.process_edges(
                state,
                signal_fn=lambda s, gid: s["dist"],
                slot_fn=lambda m, d_: m + d_,
                monoid=alg.MIN,
                apply_fn=lambda s, agg, has, gid: (
                    {"dist": jnp.minimum(s["dist"], agg)},
                    has & (agg < s["dist"]),
                    (agg < s["dist"]).astype(jnp.float32)),
                active=active)
        store.save({"dist": np.asarray(state["dist"]),
                    "active": np.asarray(active)}, step=3)
        print("checkpointed at iteration 3; simulating crash + restore...")
        step, restored = store.restore_latest()
        state = engine.init_state(dist=restored["dist"])
        active = jnp.asarray(restored["active"])
        it = step
        while True:
            state, active, upd, _ = engine.process_edges(
                state,
                signal_fn=lambda s, gid: s["dist"],
                slot_fn=lambda m, d_: m + d_,
                monoid=alg.MIN,
                apply_fn=lambda s, agg, has, gid: (
                    {"dist": jnp.minimum(s["dist"], agg)},
                    has & (agg < s["dist"]),
                    (agg < s["dist"]).astype(jnp.float32)),
                active=active)
            it += 1
            if float(upd) == 0:
                break
        from repro.core.partition import gather_vertex_values
        dist = gather_vertex_values(spec, np.asarray(state["dist"]))
        ref_d = alg.ref_sssp(g.num_vertices, g.src, g.dst, g.data, source)
        print(f"resumed at iter 3, converged at iter {it}; "
              f"max |err| vs oracle: {np.abs(dist - ref_d).max():.2e}")
    print("quickstart OK")


if __name__ == "__main__":
    main()
