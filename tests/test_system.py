"""End-to-end behaviour tests for the DFOGraph engine (paper core)."""
import numpy as np
import pytest

from repro.core import (
    Engine, EngineConfig, build_dist_graph, build_formats, make_spec,
    storage_summary,
)
from repro.core import algorithms as alg
from repro.data.graphs import chain_graph, rmat_graph, star_graph, uniform_graph


@pytest.fixture(scope="module")
def small_engine():
    g = rmat_graph(8, 8, seed=1, weighted=True)
    spec = make_spec(g, num_partitions=4, batch_size=16)
    dg = build_dist_graph(g, spec)
    fm = build_formats(dg)
    return g, Engine(dg, fm)


def test_pagerank_matches_oracle(small_engine):
    g, eng = small_engine
    pr, _ = alg.pagerank(eng, num_iters=5)
    ref = alg.ref_pagerank(g.num_vertices, g.src, g.dst, 5)
    np.testing.assert_allclose(pr, ref, rtol=1e-4, atol=1e-7)


def test_bfs_matches_oracle(small_engine):
    g, eng = small_engine
    source = int(np.argmax(g.out_degrees()))
    lv, stats = alg.bfs(eng, source)
    ref = alg.ref_bfs(g.num_vertices, g.src, g.dst, source)
    np.testing.assert_allclose(np.where(lv < 1e37, lv, -1),
                               np.where(ref < 1e37, ref, -1))
    assert stats.iterations >= 2


def test_sssp_matches_oracle(small_engine):
    g, eng = small_engine
    source = int(np.argmax(g.out_degrees()))
    ds, _ = alg.sssp(eng, source)
    ref = alg.ref_sssp(g.num_vertices, g.src, g.dst, g.data, source)
    np.testing.assert_allclose(ds, ref, rtol=1e-5, atol=1e-5)


def test_wcc_matches_oracle(small_engine):
    import collections
    g, eng = small_engine
    spec = eng.graph.spec
    dg_rev = build_dist_graph(g.reversed(), spec)
    eng_rev = Engine(dg_rev, build_formats(dg_rev))
    lb, _ = alg.wcc(eng, eng_rev)
    ref = alg.ref_wcc(g.num_vertices, g.src, g.dst)
    norm = lambda l: sorted(collections.Counter(l).values())
    assert norm(lb.tolist()) == norm(ref.tolist())


def test_chain_graph_long_diameter():
    """uk-2014-style: many iterations, tiny active set per iteration."""
    g = chain_graph(64, weighted=True)
    spec = make_spec(g, num_partitions=4, batch_size=4)
    dg = build_dist_graph(g, spec)
    eng = Engine(dg, build_formats(dg))
    lv, stats = alg.bfs(eng, 0)
    assert stats.iterations == 64  # 63 hops + terminating empty round
    np.testing.assert_allclose(lv, np.arange(64))
    # selective push: total messages = one per activated vertex (incl. the
    # terminal vertex's no-outedge signal), not O(V * iters)
    assert stats.counters["msgs_generated"] == 64


def test_filtering_reduces_traffic(small_engine):
    g, eng = small_engine
    _, st = alg.pagerank(eng, num_iters=3)
    assert st.counters["msgs_sent"] < st.counters["msgs_sent_nofilter"]
    assert st.counters["net_bytes"] < st.counters["net_bytes_nofilter"]


def test_filtering_disabled_matches_results():
    g = rmat_graph(7, 8, seed=3, weighted=True)
    spec = make_spec(g, num_partitions=4, batch_size=8)
    dg = build_dist_graph(g, spec)
    fm = build_formats(dg)
    e1 = Engine(dg, fm, EngineConfig(enable_filtering=True))
    e2 = Engine(dg, fm, EngineConfig(enable_filtering=False))
    p1, _ = alg.pagerank(e1, 3)
    p2, _ = alg.pagerank(e2, 3)
    np.testing.assert_allclose(p1, p2, rtol=1e-6)


def test_star_graph_hub_push():
    """Hub pushes to everyone in one iteration."""
    g = star_graph(32)
    spec = make_spec(g, num_partitions=4, batch_size=4)
    dg = build_dist_graph(g, spec)
    eng = Engine(dg, build_formats(dg))
    lv, stats = alg.bfs(eng, 0)
    assert stats.iterations == 2
    np.testing.assert_allclose(lv[1:], 1.0)


def test_storage_summary_adaptive_smaller_than_raw(small_engine):
    g, eng = small_engine
    s = storage_summary(eng.fmts, eng.graph)
    # adaptive representation reads fewer bytes than raw (src,dst) pairs
    assert s["adaptive_best_read_bytes"] < 2 * s["raw_pair_bytes"]
    assert 0.0 <= s["csr_chunk_fraction"] <= 1.0


def test_uniform_graph_pagerank():
    g = uniform_graph(200, 2000, seed=5)
    spec = make_spec(g, num_partitions=8, batch_size=8)
    dg = build_dist_graph(g, spec)
    eng = Engine(dg, build_formats(dg))
    pr, _ = alg.pagerank(eng, num_iters=4)
    ref = alg.ref_pagerank(g.num_vertices, g.src, g.dst, 4)
    np.testing.assert_allclose(pr, ref, rtol=1e-4, atol=1e-7)


def _loop_bfs(n, src, dst, source):
    """Vertex-at-a-time BFS: the plain form ``alg.ref_bfs`` vectorizes."""
    level = np.full(n, np.finfo(np.float32).max, np.float32)
    level[source] = 0
    adj = [[] for _ in range(n)]
    for s, d in zip(src.tolist(), dst.tolist()):
        adj[s].append(d)
    frontier, d = [source], 0
    while frontier:
        d += 1
        nxt = set()
        for v in frontier:
            for u in adj[v]:
                if level[u] > d:
                    level[u] = d
                    nxt.add(u)
        frontier = sorted(nxt)
    return level


@pytest.mark.parametrize("graph", [
    rmat_graph(9, 8, seed=4), chain_graph(50), star_graph(40),
    uniform_graph(300, 900, seed=2)], ids=["rmat", "chain", "star",
                                           "uniform"])
def test_reference_oracles_match_plain_loops(graph):
    n, src, dst = graph.num_vertices, graph.src, graph.dst
    for root in (0, n // 2, n - 1):
        np.testing.assert_array_equal(alg.ref_bfs(n, src, dst, root),
                                      _loop_bfs(n, src, dst, root))
    rank = np.full(n, 1.0 / n)
    outdeg = np.maximum(np.bincount(src, minlength=n), 1)
    for _ in range(5):
        acc = np.zeros(n)
        np.add.at(acc, dst, rank[src] / outdeg[src])
        rank = (1 - 0.85) / n + 0.85 * acc
    np.testing.assert_array_equal(alg.ref_pagerank(n, src, dst, 5), rank)
