"""Out-of-core storage tier (DESIGN.md §6): chunk-store round trips, vertex
spill accounting, and OOC executor parity — values, analytic counters, and
measured-vs-modeled I/O — for all four paper algorithms."""
import os

import numpy as np
import pytest

from repro.core import (
    ChunkStore, ChunkStoreError, Engine, EngineConfig, VertexSpill,
    build_dist_graph, build_formats, make_spec,
)
from repro.core import algorithms as alg
from repro.core.chunkstore import (
    MANIFEST_NAME, MANIFEST_VERSION, REP_CSR, REP_DCSR, REP_DCSR_DELTA,
)
from repro.core.engine import MEASURED_PAIRS
from repro.data.graphs import rmat_graph


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    g = rmat_graph(7, 8, seed=3, weighted=True)
    spec = make_spec(g, num_partitions=4, batch_size=16)
    dg = build_dist_graph(g, spec)
    fm = build_formats(dg)
    root = str(tmp_path_factory.mktemp("chunkstore"))
    store = ChunkStore.build(dg, fm, root)
    return g, dg, fm, store


# ---------------------------------------------------------------------------
# ChunkStore round trip
# ---------------------------------------------------------------------------

def test_roundtrip_bit_identical(built):
    """Every nonempty chunk decodes — via raw DCSR, delta-varint DCSR, *and*
    pruned CSR where stored — to exactly the (src, dst, data) triples of
    the in-HBM edge arrays."""
    _, dg, fm, store = built
    spec = dg.spec
    chunk_ptr = np.asarray(dg.chunk_ptr)
    esl = np.asarray(dg.edge_src_local)
    edl = np.asarray(dg.edge_dst_local)
    edata = np.asarray(dg.edge_data)
    has_csr = np.asarray(fm.has_csr)
    n_nonempty = 0
    for q in range(spec.num_partitions):
        for p in range(spec.num_partitions):
            for k in range(spec.num_batches):
                s, e = int(chunk_ptr[q, p, k]), int(chunk_ptr[q, p, k + 1])
                if e <= s:
                    continue
                n_nonempty += 1
                reps = [REP_DCSR, REP_DCSR_DELTA] + (
                    [REP_CSR] if has_csr[q, p, k] else [])
                for rep in reps:
                    src, dst, data, _ = store.read_chunk(q, p, k, rep)
                    np.testing.assert_array_equal(src, esl[q, s:e])
                    np.testing.assert_array_equal(dst, edl[q, s:e])
                    np.testing.assert_array_equal(data, edata[q, s:e])
    assert n_nonempty > 0


def test_stored_sizes_match_byte_model(built):
    """On-disk read sizes equal the analytic csr_bytes / dcsr_bytes /
    dcsr_delta_bytes model — the precondition for measured == modeled edge
    I/O (compressed layout)."""
    _, dg, fm, store = built
    spec = dg.spec
    csr_bytes = np.asarray(fm.csr_bytes)
    dcsr_bytes = np.asarray(fm.dcsr_bytes)
    delta_bytes = np.asarray(fm.dcsr_delta_bytes)
    for q in range(spec.num_partitions):
        for p in range(spec.num_partitions):
            for k in range(spec.num_batches):
                d_nb, c_nb, dd_nb = store.chunk_stored_nbytes(q, p, k)
                assert d_nb == dcsr_bytes[q, p, k]
                assert c_nb == csr_bytes[q, p, k]
                assert dd_nb == delta_bytes[q, p, k]


def test_uncompressed_store_sizes_match_raw_model(built, tmp_path):
    """A compression=False store keeps the legacy layout whose read sizes
    equal the *_raw model twins."""
    _, dg, fm, _ = built
    store = ChunkStore.build(dg, fm, str(tmp_path / "rawstore"),
                             compression=False)
    spec = dg.spec
    csr_raw = np.asarray(fm.csr_raw_bytes)
    dcsr_raw = np.asarray(fm.dcsr_raw_bytes)
    for q in range(spec.num_partitions):
        for p in range(spec.num_partitions):
            for k in range(spec.num_batches):
                d_nb, c_nb, dd_nb = store.chunk_stored_nbytes(q, p, k)
                assert d_nb == dcsr_raw[q, p, k]
                assert c_nb == csr_raw[q, p, k]
                assert dd_nb == 0
    nz = np.argwhere(np.asarray(dg.chunk_ptr)[:, :, 1:]
                     > np.asarray(dg.chunk_ptr)[:, :, :-1])[0]
    with pytest.raises(ValueError, match="without compression"):
        store.read_chunk(*nz, REP_DCSR_DELTA)


def test_read_counts_match_chosen_representation(built):
    _, dg, fm, store = built
    chunk_ptr = np.asarray(dg.chunk_ptr)
    q, p, k = np.argwhere(
        np.asarray(fm.has_csr) &
        (chunk_ptr[:, :, 1:] > chunk_ptr[:, :, :-1]))[0]
    store.reset_io_counters()
    *_, nb_d = store.read_chunk(q, p, k, REP_DCSR)
    *_, nb_c = store.read_chunk(q, p, k, REP_CSR)
    *_, nb_dd = store.read_chunk(q, p, k, REP_DCSR_DELTA)
    assert nb_d == np.asarray(fm.dcsr_bytes)[q, p, k]
    assert nb_c == np.asarray(fm.csr_bytes)[q, p, k]
    assert nb_dd == np.asarray(fm.dcsr_delta_bytes)[q, p, k]
    assert store.chunks_read == 3
    assert store.bytes_read == nb_d + nb_c + nb_dd


def test_open_missing_manifest_raises(tmp_path):
    root = tmp_path / "empty"
    root.mkdir()
    with pytest.raises(ChunkStoreError, match="manifest"):
        ChunkStore.open(str(root))


def test_open_truncated_manifest_raises(tmp_path):
    """A manifest cut off mid-write must surface as a ChunkStoreError
    naming the file, not a raw JSONDecodeError."""
    root = tmp_path / "trunc"
    root.mkdir()
    path = root / MANIFEST_NAME
    path.write_text('{"version": 1, "num_partitions": 2, "chu')
    with pytest.raises(ChunkStoreError, match="truncated or corrupt") as ei:
        ChunkStore.open(str(root))
    assert str(path) in str(ei.value)


def test_open_missing_edge_file_raises(built, tmp_path):
    """A manifest whose edge file vanished must raise a ChunkStoreError
    naming the missing path, not an OSError at first read."""
    import shutil
    _, _, _, store = built
    root = tmp_path / "copy"
    shutil.copytree(store.root, root)
    victim = root / "edges_q0.bin"
    victim.unlink()
    with pytest.raises(ChunkStoreError, match="missing edge file") as ei:
        ChunkStore.open(str(root))
    assert str(victim) in str(ei.value)


def test_manifest_reopen(built):
    _, dg, fm, store = built
    reopened = ChunkStore.open(store.root)
    chunk_ptr = np.asarray(dg.chunk_ptr)
    nz = np.argwhere(chunk_ptr[:, :, 1:] > chunk_ptr[:, :, :-1])[0]
    a = store.read_chunk(*nz, REP_DCSR)
    b = reopened.read_chunk(*nz, REP_DCSR)
    for x, y in zip(a[:3], b[:3]):
        np.testing.assert_array_equal(x, y)
    assert os.path.exists(os.path.join(store.root, MANIFEST_NAME))


def test_open_old_manifest_version_raises(built, tmp_path):
    """Opening a store written with a previous layout version must raise a
    ChunkStoreError naming both the found and the expected version."""
    import json
    import shutil
    _, _, _, store = built
    root = tmp_path / "vold"
    shutil.copytree(store.root, root)
    manifest = json.loads((root / MANIFEST_NAME).read_text())
    manifest["version"] = MANIFEST_VERSION - 1
    (root / MANIFEST_NAME).write_text(json.dumps(manifest))
    with pytest.raises(ChunkStoreError) as ei:
        ChunkStore.open(str(root))
    msg = str(ei.value)
    assert f"found version {MANIFEST_VERSION - 1}" in msg
    assert f"expected {MANIFEST_VERSION}" in msg


# ---------------------------------------------------------------------------
# VertexSpill
# ---------------------------------------------------------------------------

def test_vertex_spill_batch_io(tmp_path):
    p_cnt, b_cnt, bs, v_max = 2, 3, 4, 10   # deliberately ragged tail batch
    spill = VertexSpill(str(tmp_path), p_cnt, b_cnt, bs, v_max)
    rng = np.random.default_rng(0)
    state = {"x": rng.random((p_cnt, v_max)).astype(np.float32),
             "y": rng.integers(0, 9, (p_cnt, v_max)).astype(np.int32)}
    spill.load(state)
    assert spill.bytes_read == 0 and spill.bytes_written == 0  # load unmeasured

    mask = np.zeros((p_cnt, b_cnt), bool)
    mask[0, 1] = mask[1, 2] = True
    got = spill.read(mask)
    assert spill.bytes_read == 2 * bs * (4 + 4)
    np.testing.assert_array_equal(got["x"][0, bs:2 * bs],
                                  state["x"][0, bs:2 * bs])
    assert (got["x"][0, :bs] == 0).all()    # unread batches stay zero

    got["x"][0, bs:2 * bs] = 7.0
    spill.write(got, mask)
    assert spill.bytes_written == 2 * bs * (4 + 4)
    views = spill.state_views()
    assert (views["x"][0, bs:2 * bs] == 7.0).all()
    np.testing.assert_array_equal(views["x"][1, :bs], state["x"][1, :bs])

    spill.write_bitmap(np.ones((p_cnt, v_max), bool))
    assert spill.bytes_written == 2 * bs * 8 + p_cnt * ((v_max + 7) // 8)
    bm = spill.read_bitmap()
    assert bm.shape == (p_cnt, v_max) and bm.all()


def test_vertex_spill_merge_write_leaves_the_read_arrays(tmp_path):
    """The merge goes to new arrays: on the CPU a jnp.asarray of what
    read() returned may alias it, and a computation still pending on that
    alias must see the values that were read."""
    p_cnt, b_cnt, bs, v_max = 2, 3, 4, 12   # v_pad == v_max: views alias
    spill = VertexSpill(str(tmp_path), p_cnt, b_cnt, bs, v_max)
    spill.load({"x": np.arange(p_cnt * v_max, dtype=np.float32)
                .reshape(p_cnt, v_max)})
    mask = np.ones((p_cnt, b_cnt), bool)
    pad = spill.read(mask)
    before = pad["x"].copy()
    vm = np.zeros((p_cnt, v_max), bool)
    vm[1, 3:9] = True
    spill.merge_write(pad, {"x": np.full((p_cnt, v_max), -1.0, np.float32)},
                      vm, mask)
    np.testing.assert_array_equal(pad["x"], before)
    np.testing.assert_array_equal(spill.state_views()["x"],
                                  np.where(vm, -1.0, before[:, :v_max]))


def test_vertex_spill_num_queries_validation(tmp_path):
    """A spill root records its Q; reopening with a different panel width
    must fail with a clear ChunkStoreError, not oblique key errors."""
    with pytest.raises(ChunkStoreError, match="num_queries"):
        VertexSpill(str(tmp_path / "bad"), 2, 3, 4, 10, num_queries=0)
    root = str(tmp_path / "q2")
    VertexSpill(root, 2, 3, 4, 10, num_queries=2)
    with pytest.raises(ChunkStoreError, match="num_queries=2") as ei:
        VertexSpill(root, 2, 3, 4, 10, num_queries=3)
    assert "fresh spill root" in str(ei.value)
    VertexSpill(root, 2, 3, 4, 10, num_queries=2)   # matching reopen OK


def test_vertex_spill_per_query_io_accounting(tmp_path):
    """Multi-query layout: ``keys=`` restricts reads (and bytes) to one
    query's ``{key}@q{j}`` columns, ``name=`` gives each query its own
    measured bitmap file — query j pays exactly a solo run's bytes."""
    p_cnt, b_cnt, bs, v_max = 2, 3, 4, 10
    spill = VertexSpill(str(tmp_path), p_cnt, b_cnt, bs, v_max,
                        num_queries=2)
    rng = np.random.default_rng(1)
    state = {f"x@q{j}": rng.random((p_cnt, v_max)).astype(np.float32)
             for j in range(2)}
    spill.load(state)
    assert spill.arrays_bytes(["x@q0"]) == 4
    assert spill.arrays_bytes() == 8

    mask = np.zeros((p_cnt, b_cnt), bool)
    mask[0, 1] = True
    got = spill.read(mask, keys=["x@q1"])
    assert set(got) == {"x@q1"}
    assert spill.bytes_read == bs * 4                # one column array only
    np.testing.assert_array_equal(got["x@q1"][0, bs:2 * bs],
                                  state["x@q1"][0, bs:2 * bs])

    spill.reset_io_counters()
    row = (v_max + 7) // 8
    spill.write_bitmap(np.ones((p_cnt, v_max), bool), name="active_q1")
    assert spill.bytes_written == p_cnt * row
    assert spill.read_bitmap(name="active_q1").all()
    assert spill.read_bitmap(name="active_q0") is None  # fresh file
    assert spill.bytes_read == 2 * p_cnt * row       # both reads measured

    # per-query merge_write touches only the requested columns' bytes
    spill.reset_io_counters()
    pad = spill.read(mask, keys=["x@q0"])
    upd = {"x@q0": np.full((p_cnt, v_max), 7.0, np.float32)}
    vm = np.zeros((p_cnt, v_max), bool)
    vm[0, bs:2 * bs] = True
    spill.merge_write(pad, upd, vm, mask)
    assert spill.bytes_written == bs * 4
    assert (spill.state_views()["x@q0"][0, bs:2 * bs] == 7.0).all()
    np.testing.assert_array_equal(spill.state_views()["x@q1"],
                                  state["x@q1"])


# ---------------------------------------------------------------------------
# OOC executor parity: all four algorithms, values + counters + measured I/O
# ---------------------------------------------------------------------------

def _parity(out_ref, out_ooc):
    (v1, s1), (v2, s2) = out_ref, out_ooc
    np.testing.assert_allclose(v1, v2, rtol=1e-5, atol=1e-5)
    assert s1.iterations == s2.iterations
    for k in s1.counters:               # all modeled counters identical
        assert abs(s1.counters[k] - s2.counters[k]) < 1e-3, (
            k, s1.counters[k], s2.counters[k])
    for mk, ak in MEASURED_PAIRS:       # measured == modeled, accumulated
        assert abs(s2.counters[mk] - s2.counters[ak]) < 1e-3, (
            mk, s2.counters[mk], s2.counters[ak])


@pytest.fixture(scope="module")
def engines(built):
    g, dg, fm, store = built
    local = Engine(dg, fm)
    ooc = Engine(dg, fm, EngineConfig(executor="ooc"), store=store)
    return g, dg, fm, store, local, ooc


def test_ooc_pagerank_parity(engines):
    *_, local, ooc = engines
    _parity(alg.pagerank(local, 4), alg.pagerank(ooc, 4))


def test_ooc_bfs_parity_selective(engines):
    """BFS frontiers make iterations *partially active*: assert the OOC run
    actually skipped chunks (selective schedule) while measured == modeled."""
    g, dg, *_, local, ooc = engines
    src = int(np.argmax(g.out_degrees()))
    out_l, out_o = alg.bfs(local, src), alg.bfs(ooc, src)
    _parity(out_l, out_o)
    spec = dg.spec
    total_chunks = int((np.asarray(dg.chunk_edges) > 0).sum())
    iters = out_o[1].iterations
    # at least one iteration read fewer chunks than exist (first frontier
    # is a single vertex — its sources can't touch every chunk)
    assert out_o[1].counters["chunks_read"] < total_chunks * iters
    assert out_o[1].counters["measured_chunks_read"] == \
        out_o[1].counters["chunks_read"]


def test_ooc_sssp_parity(engines):
    g, *_, local, ooc = engines
    src = int(np.argmax(g.out_degrees()))
    _parity(alg.sssp(local, src), alg.sssp(ooc, src))


def test_ooc_wcc_parity(engines, tmp_path):
    g, dg, fm, store, local, ooc = engines
    dg_r = build_dist_graph(g.reversed(), dg.spec)
    fm_r = build_formats(dg_r)
    local_r = Engine(dg_r, fm_r)
    store_r = ChunkStore.build(dg_r, fm_r, str(tmp_path / "rev"))
    ooc_r = Engine(dg_r, fm_r, EngineConfig(executor="ooc"), store=store_r)
    _parity(alg.wcc(local, local_r), alg.wcc(ooc, ooc_r))


def test_ooc_block_csr_backend_parity(engines):
    """OOC's streamed Pallas block-CSR combine == LOCAL segment reference."""
    g, dg, fm, store, local, _ = engines
    oocb = Engine(dg, fm,
                  EngineConfig(executor="ooc", compute_backend="block_csr"),
                  store=store)
    src = int(np.argmax(g.out_degrees()))
    _parity(alg.pagerank(local, 3), alg.pagerank(oocb, 3))
    _parity(alg.sssp(local, src), alg.sssp(oocb, src))


def test_ooc_oracle(engines):
    g, *_, ooc = engines
    pr, _ = alg.pagerank(ooc, 5)
    ref = alg.ref_pagerank(g.num_vertices, g.src, g.dst, 5)
    np.testing.assert_allclose(pr, ref, rtol=1e-4, atol=1e-7)


def test_ooc_config_validation(built):
    _, dg, fm, store = built
    with pytest.raises(ValueError, match="ChunkStore"):
        Engine(dg, fm, EngineConfig(executor="ooc"))
    with pytest.raises(ValueError, match="adaptive"):
        Engine(dg, fm, EngineConfig(executor="ooc",
                                    enable_adaptive_formats=False),
               store=store)
    with pytest.raises(ValueError, match="executor"):
        Engine(dg, fm, EngineConfig(executor="bogus"))


def test_modeled_read_bytes_stay_exact_past_float32_integers():
    """Per-chunk prices are float32; their per-destination sum must not be,
    or a store past 2**24 bytes per call fails its measured == model
    audit by rounding alone."""
    import types
    from repro.core.executor import _dispatch_schedule_one_dest
    b_cnt, per = 3, 2**23 + 1            # exact in float32, the sum is not
    price = np.full((1, 1, b_cnt), per, np.int64)
    source = types.SimpleNamespace(
        dcsr_part=np.zeros((1, b_cnt), np.int32),
        dcsr_batch=np.arange(b_cnt, dtype=np.int32)[None],
        dcsr_src=np.zeros((1, b_cnt), np.int32),
        dcsr_valid=np.ones((1, b_cnt), bool),
        dcsr_ptr=np.arange(b_cnt + 1, dtype=np.int32)[None, None],
        has_csr=np.zeros((1, 1, b_cnt), bool),
        csr_bytes=price, dcsr_bytes=price, dcsr_delta_bytes=price + 1,
        csr_raw_bytes=price, dcsr_raw_bytes=price)
    cd, _, _ = _dispatch_schedule_one_dest(
        source, 0, np.ones((1, 4), bool), np.array([4.0], np.float32),
        1024.0, True)
    assert cd["edge_read_bytes"] == b_cnt * per
