"""The engine's own host spans (``repro.core.tracing``) and the phase scopes
of the jitted LOCAL step, read back from a profiler trace on the CPU.

An OOC BFS and PageRank run under ``jax.profiler``: every span of the
out-of-core path appears, the prefetch thread's spans sit on another host
line than the executor's, and the counts the spans carry agree with the
``verify_io`` audit the engine runs on every call."""
import collections
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import (ChunkStore, Engine, EngineConfig, algorithms,
                        build_dist_graph, build_formats, make_spec)
from repro.core import executor
from repro.core.engine import ADD
from repro.core.tracing import PREFIX, span
from repro.data.graphs import rmat_graph

MAIN_SPANS = ("ooc.generate", "ooc.filter", "ooc.dispatch",
              "ooc.stream_wait", "ooc.combine", "ooc.apply")
PREFETCH_SPANS = ("chunk.read", "chunk.decode", "chunk.put_wait")
SPILL_SPANS = ("spill.read", "spill.write")
SCOPES = ("generate", "filter", "dispatch", "combine", "apply")


@pytest.fixture(scope="module")
def graph():
    g = rmat_graph(10, 8, seed=3, weighted=False)
    dg = build_dist_graph(g, make_spec(g, num_partitions=4))
    return dg, build_formats(dg)


def _host_spans(root):
    """The ``dfo.*`` spans of the trace under ``root``: ``(name, args)``
    per host line."""
    path, = glob.glob(os.path.join(root, "trace", "**", "*.xplane.pb"),
                      recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = [(ev.name[len(PREFIX):], dict(ev.stats))
                     for ev in line.events if ev.name.startswith(PREFIX)]
            if found:
                lines.append(found)
    return lines


@pytest.fixture(scope="module")
def traced(graph, tmp_path_factory):
    """``(spans by line, run counters)`` of one traced BFS and PageRank on
    the OOC engine; spans are ``(name, args)`` per host line."""
    dg, fm = graph
    root = tmp_path_factory.mktemp("tracing")
    store = ChunkStore.build(dg, fm, str(root / "store"))
    eng = Engine(dg, fm, EngineConfig(executor="ooc", verify_io=True),
                 store=store)
    algorithms.bfs(eng, 1)                   # compile outside the trace
    jax.profiler.start_trace(str(root / "trace"))
    try:
        _, bfs = algorithms.bfs(eng, 5)
        _, pr = algorithms.pagerank(eng, 2)
    finally:
        jax.profiler.stop_trace()
    lines = _host_spans(root)
    counters = collections.Counter()
    for stats in (bfs, pr):
        counters.update({k: float(v) for k, v in stats.counters.items()})
    return lines, counters, bfs.iterations + pr.iterations, eng


def test_every_span_of_the_ooc_path_appears(traced):
    lines, _, pe_calls, _ = traced
    names = collections.Counter(n for line in lines for n, _ in line)
    for name in MAIN_SPANS + PREFETCH_SPANS + SPILL_SPANS:
        assert names[name] > 0, name
    for name in ("ooc.generate", "ooc.filter", "ooc.dispatch", "ooc.apply"):
        assert names[name] == pe_calls, name
    # one wait per streamed batch, plus the wait that ends the stream
    assert names["ooc.stream_wait"] == names["ooc.combine"] + pe_calls
    assert names["chunk.read"] == names["chunk.decode"] == \
        names["ooc.combine"]


def test_prefetch_spans_sit_on_another_thread(traced):
    lines, _, _, _ = traced
    main = {i for i, line in enumerate(lines)
            if any(n in MAIN_SPANS for n, _ in line)}
    prefetch = {i for i, line in enumerate(lines)
                if any(n in PREFETCH_SPANS for n, _ in line)}
    assert main and prefetch
    assert not main & prefetch


def test_span_counts_agree_with_the_io_audit(traced):
    lines, counters, _, eng = traced
    total = collections.Counter()
    for line in lines:
        for name, args in line:
            for k, v in args.items():
                total[name, k] += v
    assert total["chunk.read", "bytes"] == \
        counters["measured_edge_read_bytes"] > 0
    assert total["chunk.read", "chunks"] == \
        counters["measured_chunks_read"]
    assert total["chunk.decode", "chunks"] == \
        counters["measured_chunks_read"]
    assert total["ooc.dispatch", "chunks"] == counters["chunks_read"]
    assert total["chunk.decode", "edges"] == total["ooc.combine", "edges"]
    assert total["chunk.decode", "calls"] == \
        counters["measured_device_decode_calls"]
    # spill spans carry the spill's bytes, ProcessVertices' included; the
    # first call of each of the two jobs loads the job's initial state,
    # which writes the active bitmap outside the audit
    assert total["spill.read", "bytes"] == \
        counters["measured_vertex_read_bytes"]
    assert total["spill.write", "bytes"] == \
        counters["measured_vertex_write_bytes"] + 2 * eng.spill.bitmap_nbytes()


def test_device_decode_spans_count_the_calls(tmp_path):
    # one decode call per streamed batch, each marked on its chunk.decode
    g = rmat_graph(8, 8, seed=3, weighted=False)
    dg = build_dist_graph(g, make_spec(g, num_partitions=4))
    fm = build_formats(dg)
    eng = Engine(dg, fm, EngineConfig(executor="ooc", device_decode=True),
                 store=ChunkStore.build(dg, fm, str(tmp_path / "store")))
    algorithms.pagerank(eng, 1)              # compile outside the trace
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        _, stats = algorithms.pagerank(eng, 2)
    finally:
        jax.profiler.stop_trace()
    decodes = [args for line in _host_spans(tmp_path)
               for name, args in line if name == "chunk.decode"]
    assert decodes and all(a["calls"] == 1 == a["device"] for a in decodes)
    assert stats.counters["measured_device_decode_calls"] == len(decodes)
    assert stats.counters["measured_chunks_device_decoded"] == \
        sum(a["chunks"] for a in decodes)


def test_local_step_carries_the_phase_scopes(graph):
    dg, fm = graph
    eng = Engine(dg, fm, EngineConfig())
    step = executor.make_local_pe(
        eng, signal_fn=lambda s, gid: s["x"],
        slot_fn=lambda msg, data: msg * data, monoid=ADD,
        apply_fn=lambda s, agg, has, gid: ({"x": agg}, has, agg),
        backend="segment", mode_meta=None)
    state = {"x": jnp.ones(dg.vertex_valid.shape, jnp.float32)}
    text = step.lower(state, None, eng.graph, eng.fmts, eng.global_id,
                      None, None).as_text(debug_info=True)
    scopes = set(re.findall(r'loc\("jit\(step\)/([^"]*)"', text))
    found = {s for s in SCOPES
             if any(re.search(rf"(^|[/(]){s}([/)]|$)", p) for p in scopes)}
    assert found == set(SCOPES)


def test_pv_step_span_marks_cache_hits(graph, tmp_path):
    # LOCAL ProcessVertices builds its program on the first call of an
    # algorithm and takes it from the engine's cache on every later one
    dg, fm = graph
    eng = Engine(dg, fm, EngineConfig())
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        algorithms.pagerank(eng, 2)
        algorithms.pagerank(eng, 2)
    finally:
        jax.profiler.stop_trace()
    cached = [args["cached"] for line in _host_spans(tmp_path)
              for name, args in line if name == "pv.step"]
    assert cached == [0, 1, 1, 1]


def test_a_span_passes_exceptions_through():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with pytest.raises(KeyError, match="inside"):
        with span("ooc.combine", q=1, k=2) as sp:
            sp.set_metadata(edges=3)
            raise KeyError("inside")
