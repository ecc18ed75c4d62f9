"""Pallas varint/delta kernel parity (DESIGN.md §10): every device decode
primitive bit-identical to the numpy codec on the int32 domain, the fused
store decode identical to the host decode chunk by chunk, and the
``EngineConfig.device_decode`` knob bit-identical on/off across all four
executors (including ``parallel_workers``) with ``verify_io`` holding.

Kernels run in interpret mode off-TPU; on a TPU backend the compiled
parity case runs too.

Run standalone by ``scripts/ci.sh`` as the device-decode parity gate.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (
    ChunkStore, Engine, EngineConfig, build_dist_graph, build_formats,
    codec, make_spec,
)
from repro.core import algorithms as alg
from repro.core import chunkstore
from repro.core.chunkstore import (
    REP_CSR, REP_DCSR, REP_DCSR_DELTA, ChunkPrefetcher,
)
from repro.data.graphs import GraphData, rmat_graph
from repro.kernels import varint as vk
from repro.kernels.csr_spmv import default_interpret

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

INT32_MAX = 2**31 - 1
# the counters that report which decode path ran, not what it computed
DEVICE_DECODE_KEYS = ("measured_chunks_device_decoded",
                      "measured_device_decode_calls")


def _kernel_decode(vals, *, interpret=None):
    """Encode with the numpy codec, decode with the Pallas kernel."""
    vals = np.asarray(vals, np.uint64)
    enc = codec.varint_encode(vals)
    buf = np.frombuffer(enc.tobytes(), np.uint8)
    out = np.asarray(vk.varint_decode(buf, buf.size, count=max(vals.size, 1),
                                      interpret=interpret))
    return out[:vals.size]


# ---------------------------------------------------------------------------
# Varint decode: adversarial explicit cases vs the numpy codec
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    [],                                     # empty chunk
    [0],                                    # single value, zero delta
    [INT32_MAX],                            # max-width: full 5-group varint
    [INT32_MAX] * 7,                        # back-to-back max-width varints
    [0] * 2048,                             # dense: all one-byte residues
    [127, 128, 2**14 - 1, 2**14, 2**21 - 1, 2**21, 2**28 - 1, 2**28,
     INT32_MAX],                            # every int32 group boundary
])
def test_varint_kernel_adversarial(case):
    np.testing.assert_array_equal(
        _kernel_decode(case), np.asarray(case, np.int64).astype(np.int32))


def test_varint_kernel_short_stream_leaves_tail_zero():
    # count is padded to a static per-store maximum; the unfilled tail of
    # the result must stay 0 (the all-inactive remainder of the buffer)
    vals = np.array([5, 300, 7], np.uint64)
    enc = codec.varint_encode(vals)
    buf = np.zeros(64, np.uint8)
    buf[:enc.size] = np.frombuffer(enc.tobytes(), np.uint8)
    out = np.asarray(vk.varint_decode(buf, int(enc.size), count=8))
    np.testing.assert_array_equal(out, [5, 300, 7, 0, 0, 0, 0, 0])


def test_varint_kernel_all_inactive_mask():
    # nbytes == 0: nothing live, every output lane inactive -> zeros
    out = np.asarray(vk.varint_decode(np.zeros(16, np.uint8), 0, count=4))
    np.testing.assert_array_equal(out, np.zeros(4, np.int32))


def test_blocked_scan_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 7, 512, 513, 3000):
        x = rng.integers(0, 1000, n).astype(np.int32)
        np.testing.assert_array_equal(
            np.asarray(vk.blocked_scan(x, mode="add")), np.cumsum(x))
        np.testing.assert_array_equal(
            np.asarray(vk.blocked_scan(x, mode="max")),
            np.maximum.accumulate(x))


# ---------------------------------------------------------------------------
# Hypothesis: kernel == codec on the int32 domain
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:              # pragma: no cover - explicit cases above
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.integers(0, INT32_MAX), max_size=100))
    def test_varint_kernel_roundtrip_property(vals):
        np.testing.assert_array_equal(
            _kernel_decode(vals),
            np.asarray(vals, np.int64).astype(np.int32))

    VPAD = 2**24                 # slot stride: a power of two above srcs

    def _pow2(n):
        return 1 << (max(int(n), 1) - 1).bit_length()

    @st.composite
    def batches(draw):
        """1-3 adversarial sorted chunks of one dst batch: edges grouped
        into runs by src, dst non-decreasing within a run, all >= the
        shared batch base.  Each chunk's run heads reach the expand either
        as delta-varint pairs (REP_DCSR_DELTA) or read directly."""
        base = draw(st.integers(0, 2**20)) * 16
        out = []
        for _ in range(draw(st.integers(1, 3))):
            n_runs = draw(st.integers(0, 12))
            srcs = draw(st.lists(st.integers(0, VPAD - 1), min_size=n_runs,
                                 max_size=n_runs, unique=True))
            runs, dst = [], [np.zeros(0, np.int64)]
            for _ in range(n_runs):
                r = draw(st.integers(1, 9))
                runs.append(r)
                d = draw(st.lists(st.integers(0, 2**20), min_size=r,
                                  max_size=r))
                dst.append(base + np.sort(np.asarray(d, np.int64)))
            out.append((np.sort(np.asarray(srcs, np.int64)),
                        np.asarray(runs, np.int64), np.concatenate(dst),
                        draw(st.booleans())))
        return base, out

    @settings(max_examples=25, deadline=None)
    @given(batches())
    def test_chunk_restore_kernels_match_codec(batch):
        # stages a batch as DeviceChunkDecoder.decode_batch does, and checks
        # every stage against the codec's restores concatenated
        base, chunks = batch
        n_es = np.array([d.size for _, _, d, _ in chunks])
        eoff = np.cumsum(n_es) - n_es
        n_e = int(n_es.sum())
        out_len = _pow2(n_e)
        width = _pow2(sum(s.size for s, _, _, _ in chunks))
        direct_src, direct_pos, pair_vals, seg, residues = [], [], [], [], []
        delta_src, delta_pos = [], []
        runs_seen = 0
        for c, (srcs, runs, dst, as_pairs) in enumerate(chunks):
            starts = np.cumsum(runs) - runs
            residues.append(codec.dst_delta_values(dst, starts, base))
            if as_pairs:
                seg.append((runs_seen, c * VPAD, eoff[c]))
                runs_seen += srcs.size
                pair_vals.append(codec.pair_delta_values(srcs, starts))
                delta_src.append(srcs + c * VPAD)
                delta_pos.append(starts + eoff[c])
            else:
                direct_src.append(srcs + c * VPAD)
                direct_pos.append(starts + eoff[c])

        def staged(parts):
            buf = np.zeros(width, np.int32)
            x = np.concatenate([np.zeros(0, np.int64)] + parts)
            buf[:x.size] = x
            return buf, x.size
        none = np.zeros(width, np.int32)
        delta = (none, none, 0)
        if seg:
            # pair stream: one kernel decode, chunk-segmented cumsums
            dec = _kernel_decode(np.concatenate(pair_vals))
            pad = np.zeros(2 * width, np.int32)
            pad[:dec.size] = dec
            meta = np.full((3, 3), 2**31 - 1, np.int32)
            meta[:, :len(seg)] = np.array(seg, np.int32).T
            s2, i2 = vk.pair_delta_restore(pad, *meta)
            np.testing.assert_array_equal(np.asarray(s2)[:runs_seen],
                                          np.concatenate(delta_src))
            np.testing.assert_array_equal(np.asarray(i2)[:runs_seen],
                                          np.concatenate(delta_pos))
            delta = (s2, i2, runs_seen)
        hs, nh = staged(direct_src)
        hp, _ = staged(direct_pos)
        # run expansion + dst residues vs the codec's repeat-based restore
        esrc, smask = vk.expand_dcsr_index(
            (hs, delta[0]), (hp, delta[1]), (nh, delta[2]), n_e, VPAD,
            out_len=out_len)
        want_src = np.concatenate([np.zeros(0, np.int64)] + [
            np.repeat(s, r) for s, r, _, _ in chunks])
        np.testing.assert_array_equal(np.asarray(esrc)[:n_e], want_src)
        rdec = _kernel_decode(np.concatenate(residues))
        rpad = np.zeros(out_len, np.int32)
        rpad[:rdec.size] = rdec
        got = np.asarray(vk.dst_delta_restore(rpad, smask, base, n_e, esrc))
        np.testing.assert_array_equal(got[0, :n_e], want_src)
        np.testing.assert_array_equal(
            got[1, :n_e], np.concatenate([d for _, _, d, _ in chunks]))


def test_dst_restore_survives_int32_wrap_of_the_running_sum():
    # every run restarts at its batch offset, so a batch with many runs of
    # large residues sums past 2**31 while each dst stays small; src
    # restarts every 2000 runs, as each chunk of a batch does
    n_runs, base = 6000, 64
    dst = base + np.repeat(np.arange(n_runs) % 7 + 2**20, 1)
    starts = np.arange(n_runs)
    res = codec.dst_delta_values(dst, starts, base)
    assert res.astype(np.int64).sum() > 2**31
    smask = np.ones(n_runs, np.int32)
    src = (np.arange(n_runs) % 2000).astype(np.int32)
    got = vk.dst_delta_restore(res.astype(np.int32), smask, base, n_runs,
                               src)
    np.testing.assert_array_equal(np.asarray(got), [src, dst])


def test_expand_csr_index_matches_repeat():
    # CSR chunks enter the batched expand as their rows of nonzero degree
    # (rows of degree 0 at the start, inside and after the last nonzero
    # row are left out): two chunks back to back, heads carrying
    # slot * vpad + row, in two groups as wide as the output
    rng = np.random.default_rng(1)
    v_src, vpad = 37, 64
    degs = [rng.integers(0, 4, v_src) for _ in range(2)]
    for deg in degs:
        deg[[0, 5, -3, -2, -1]] = 0
        deg[1] = 2
    n_es = [int(d.sum()) for d in degs]
    n_e = sum(n_es)
    out_len = n_e + 5
    groups = []
    for c, (d, off) in enumerate(zip(degs, [0, n_es[0]])):
        rows = np.flatnonzero(d)
        heads = np.zeros((2, out_len), np.int32)
        heads[0, :rows.size] = c * vpad + rows
        heads[1, :rows.size] = (np.cumsum(d) - d)[rows] + off
        groups.append((heads[0], heads[1], rows.size))
    srcs, starts, live = zip(*groups)
    esrc, smask = vk.expand_dcsr_index(srcs, starts, live, n_e, vpad,
                                       out_len=out_len)
    want = np.concatenate([np.repeat(np.arange(v_src), d) for d in degs])
    np.testing.assert_array_equal(np.asarray(esrc)[:n_e], want)
    exp_mask = np.zeros(out_len, np.int32)
    for d, off in zip(degs, [0, n_es[0]]):
        exp_mask[(np.cumsum(d) - d)[d > 0] + off] = 1
    np.testing.assert_array_equal(np.asarray(smask), exp_mask)


def test_varint_kernel_compiled_parity():
    if default_interpret():
        pytest.skip("compiled-kernel parity needs a TPU backend")
    rng = np.random.default_rng(2)
    vals = rng.integers(0, INT32_MAX, 4096).astype(np.uint64)
    np.testing.assert_array_equal(
        _kernel_decode(vals, interpret=False), vals.astype(np.int32))
    x = rng.integers(0, 1000, 3000).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(vk.blocked_scan(x, mode="add", interpret=False)),
        np.cumsum(x))


# ---------------------------------------------------------------------------
# Store-level: device decode == host decode for every chunk and rep
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=[True, False],
                ids=["weighted", "unweighted"])
def built(request, tmp_path_factory):
    g = rmat_graph(7, 12, seed=9, weighted=request.param)
    spec = make_spec(g, num_partitions=4, batch_size=16)
    dg = build_dist_graph(g, spec)
    fm = build_formats(dg)
    root = tmp_path_factory.mktemp(
        "vk_store_" + ("w" if request.param else "u"))
    return g, dg, fm, root


def test_device_decode_matches_host_per_chunk(built):
    g, dg, fm, root = built
    store = ChunkStore.build(dg, fm, str(root / "parity"))
    assert store.values_elided == fm.values_elided
    spec = dg.spec
    has_csr = np.asarray(fm.has_csr)
    chunk_ptr = np.asarray(dg.chunk_ptr)
    checked = 0
    for q in range(spec.num_partitions):
        for p in range(spec.num_partitions):
            for k in range(spec.num_batches):
                if chunk_ptr[q, p, k + 1] <= chunk_ptr[q, p, k]:
                    continue
                reps = [REP_DCSR, REP_DCSR_DELTA] + (
                    [REP_CSR] if has_csr[q, p, k] else [])
                for rep in reps:
                    index, payload, _ = store.read_chunk_bytes(q, p, k, rep)
                    hs, hd, hw = store.decode_chunk(q, p, k, rep, index,
                                                    payload)
                    ds, dd, dw = store.decode_chunk_device(q, p, k, rep,
                                                           index, payload)
                    np.testing.assert_array_equal(hs, ds)
                    np.testing.assert_array_equal(hd, dd)
                    np.testing.assert_array_equal(hw, dw)
                    checked += 1
    assert checked > 0


# ---------------------------------------------------------------------------
# Batch decode: one dst batch's chunks in one device call == the host
# decode of each chunk, concatenated
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def batch_stores(tmp_path_factory):
    root = tmp_path_factory.mktemp("vk_batch")
    # sparse RMAT, 4 partitions x 6 batches: one-edge chunks, CSR chunks
    g = rmat_graph(8, 2, seed=0, weighted=True)
    dg = build_dist_graph(g, make_spec(g, num_partitions=4, batch_size=16))
    rmat = ChunkStore.build(dg, build_formats(dg), str(root / "rmat"))
    # every third vertex points near the top of both partitions, whose
    # batch is the whole partition: residues of about 2**16 at each of
    # 2 x 21,846 run starts, so dst batch (0, 0) sums past 2**31 and each
    # of its two chunks stays under
    n = 2**17
    s = np.arange(0, n, 3)
    g = GraphData(n, np.concatenate([s, s]),
                  np.concatenate([65535 - s % 1024, n - 1 - s % 1024]), None)
    dg = build_dist_graph(g, make_spec(g, num_partitions=2,
                                       batch_size=65536))
    wrap = ChunkStore.build(dg, build_formats(dg), str(root / "wrap"))
    return rmat, wrap


def _chunks(store, q, k):
    lay = store._layout_of(q)
    return [p for p in range(store.num_partitions) if lay.edges[p, k] > 0]


def _items(store):
    for q in store.partitions:
        for k in range(store.num_batches):
            ps = _chunks(store, q, k)
            if ps:
                yield q, k, ps


def _host_decode(store, q, k, chunks):
    return [store.decode_chunk(q, p, k, rep,
                               *store.read_chunk_bytes(q, p, k, rep)[:2])
            for p, rep in chunks]


def _case_mixed_reps(stores):
    store = stores[0]
    for q, k, ps in _items(store):
        csr = [p for p in ps if store._layout_of(q).has_csr[p, k]]
        if len(ps) >= 3 and csr:
            reps = [REP_DCSR_DELTA, REP_DCSR]
            return store, q, k, [
                (p, REP_CSR if p == csr[0] else reps[i % 2])
                for i, p in enumerate(ps)]
    pytest.fail("no batch of three chunks with a CSR one")


def _case_one_chunk(stores):
    store = stores[0]
    q, k, ps = next(_items(store))
    return store, q, k, [(ps[0], REP_DCSR_DELTA)]


def _case_one_edge_chunk(stores):
    store = stores[0]
    for q, k, ps in _items(store):
        lay = store._layout_of(q)
        if len(ps) > 1 and any(lay.edges[p, k] == 1 for p in ps):
            return store, q, k, [(p, REP_DCSR_DELTA) for p in ps]
    pytest.fail("no batch with a one-edge chunk")


def _case_residue_sum_past_2_31(stores):
    store = stores[1]
    chunks = [(0, REP_DCSR_DELTA), (1, REP_DCSR)]
    sums = []
    for p, rep in chunks:
        _, payload, _ = store.read_chunk_bytes(0, p, 0, rep)
        vnb = int(store._layout_of(0).dstv_nb[p, 0])
        n_e = int(store._layout_of(0).edges[p, 0])
        sums.append(int(codec.varint_decode(payload[:vnb], n_e).sum()))
    assert max(sums) < 2**31 < sum(sums)
    return store, 0, 0, chunks


def _case_src_restarts_lower(stores):
    store = stores[0]
    for q, k, ps in _items(store):
        chunks = [(p, REP_DCSR_DELTA) for p in ps]
        if len(ps) >= 2:
            (s0, _, _), (s1, _, _) = _host_decode(store, q, k, chunks[:2])
            if s1[0] < s0[-1]:
                return store, q, k, chunks
    pytest.fail("no batch whose second chunk starts at a lower src")


@pytest.mark.parametrize("case", [
    _case_mixed_reps, _case_one_chunk, _case_one_edge_chunk,
    _case_residue_sum_past_2_31, _case_src_restarts_lower,
], ids=lambda f: f.__name__[len("_case_"):])
def test_decode_batch_matches_host_chunks(batch_stores, case):
    store, q, k, chunks = case(batch_stores)
    raw = [(p, rep, store.read_chunk_bytes(q, p, k, rep))
           for p, rep in chunks]
    src, part, dst, data = store.decode_batch_device(q, k, raw)
    host = _host_decode(store, q, k, chunks)
    np.testing.assert_array_equal(src, np.concatenate([h[0] for h in host]))
    np.testing.assert_array_equal(
        part, np.concatenate([np.full(h[0].size, p, np.int32)
                              for (p, _), h in zip(chunks, host)]))
    np.testing.assert_array_equal(dst, np.concatenate([h[1] for h in host]))
    np.testing.assert_array_equal(data,
                                  np.concatenate([h[2] for h in host]))


def test_device_decode_rejects_uncompressed_store(built):
    g, dg, fm, root = built
    store = ChunkStore.build(dg, fm, str(root / "uncomp"), compression=False)
    q, p, k = np.argwhere(
        np.asarray(dg.chunk_ptr)[:, :, 1:]
        > np.asarray(dg.chunk_ptr)[:, :, :-1])[0]
    index, payload, _ = store.read_chunk_bytes(q, p, k, REP_DCSR)
    with pytest.raises(ValueError, match="compress"):
        store.decode_chunk_device(q, p, k, REP_DCSR, index, payload)


def test_values_elided_mismatch_rejected(built):
    g, dg, fm, root = built
    store = ChunkStore.build(dg, fm, str(root / "mm"))
    store.manifest["values_elided"] = not store.manifest.get(
        "values_elided", False)
    with pytest.raises(ValueError, match="values_elided"):
        Engine(dg, fm, EngineConfig(executor="ooc"), store=store)


def test_device_decode_requires_compression(built):
    g, dg, fm, _ = built
    with pytest.raises(ValueError, match="compression"):
        Engine(dg, fm, EngineConfig(device_decode=True, compression=False))


@pytest.mark.parametrize("sizes,fits", [
    ([2**27] * 8, True), ([2**27 + 1] * 8, False), ([2**28] * 8, False),
    ([2**30], True), ([2**30 + 1], False), ([5, 2**27 + 1, 3, 7], True),
])
def test_device_decode_fits_the_int32_slot_domain(sizes, fits):
    # a batch's run heads reach num_partitions x the largest partition's
    # size rounded up to a power of two
    assert chunkstore.device_decode_fits(np.array(sizes)) is fits


def test_store_past_the_int32_domain_decodes_on_the_host(built,
                                                          monkeypatch):
    import repro.core.engine as engine_mod
    import repro.kernels.csr_spmv as csr_spmv
    g, dg, fm, root = built
    ooc = (EngineConfig(executor="ooc"),
           ChunkStore.build(dg, fm, str(root / "fits_ooc")))
    dist = (EngineConfig(executor="dist_ooc", num_workers=2),
            ChunkStore.build_sharded(dg, fm, str(root / "fits_dist"), 2))
    monkeypatch.setattr(csr_spmv, "default_interpret", lambda: False)
    for cfg, store in (ooc, dist):
        assert Engine(dg, fm, cfg, store=store).device_decode
    monkeypatch.setattr(engine_mod, "device_decode_fits", lambda sizes: False)
    # auto falls back to the host decode; asking for the device fails
    # when the engine is built, not on the prefetch thread's first decode
    for cfg, store in (ooc, dist):
        assert not Engine(dg, fm, cfg, store=store).device_decode
        with pytest.raises(ValueError, match="2\\*\\*31"):
            Engine(dg, fm, dataclasses.replace(cfg, device_decode=True),
                   store=store)
    # LOCAL reads no chunk store: the flag stays as given
    assert Engine(dg, fm, EngineConfig(device_decode=True)).device_decode


def test_unweighted_store_elides_value_column(built):
    g, dg, fm, root = built
    store = ChunkStore.build(dg, fm, str(root / "elide"))
    if not fm.values_elided:
        pytest.skip("weighted graph: nothing elided")
    # the compressed byte model prices no f32 data column ...
    assert np.asarray(fm.dcsr_bytes).sum() < np.asarray(
        fm.dcsr_raw_bytes).sum()
    # ... and decoded weights are the implicit ones
    q, p, k = np.argwhere(
        np.asarray(dg.chunk_ptr)[:, :, 1:]
        > np.asarray(dg.chunk_ptr)[:, :, :-1])[0]
    index, payload, _ = store.read_chunk_bytes(q, p, k, REP_DCSR)
    _, _, w = store.decode_chunk(q, p, k, REP_DCSR, index, payload)
    np.testing.assert_array_equal(w, np.ones_like(w))


# ---------------------------------------------------------------------------
# Engine-level: device_decode on/off bit-identity, all four executors
# ---------------------------------------------------------------------------

def _run_all_lazily(engine, g):
    src = int(np.argmax(g.out_degrees()))
    yield alg.pagerank(engine, 3)
    yield alg.bfs(engine, src)
    yield alg.sssp(engine, src)


def _run_all(engine, g):
    return list(_run_all_lazily(engine, g))


def _assert_bit_identical(outs_a, outs_b):
    for (va, sa), (vb, sb) in zip(outs_a, outs_b):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
        assert sa.per_iter_return == sb.per_iter_return
        for k in sa.counters:
            if k not in DEVICE_DECODE_KEYS:
                assert sa.counters[k] == sb.counters[k], k


def test_local_device_decode_on_off_bit_identical(built):
    g, dg, fm, _ = built
    on = Engine(dg, fm, EngineConfig(device_decode=True))
    off = Engine(dg, fm, EngineConfig(device_decode=False))
    _assert_bit_identical(_run_all(on, g), _run_all(off, g))


def test_ooc_device_decode_on_off_bit_identical(built, monkeypatch):
    g, dg, fm, root = built
    on = Engine(dg, fm, EngineConfig(executor="ooc", device_decode=True),
                store=ChunkStore.build(dg, fm, str(root / "ooc_on")))
    off = Engine(dg, fm, EngineConfig(executor="ooc", device_decode=False),
                 store=ChunkStore.build(dg, fm, str(root / "ooc_off")))
    # count the nonempty work items the prefetchers hand the executor
    items = []
    stream = ChunkPrefetcher.__iter__

    def counted(self):
        for w in stream(self):
            items.append(w.n_chunks > 0)
            yield w
    monkeypatch.setattr(ChunkPrefetcher, "__iter__", counted)

    def run_all(engine):
        outs = []
        for out in _run_all_lazily(engine, g):
            outs.append((*out, sum(items)))
            items.clear()
        return outs
    # verify_io is on by default: every call cross-checks measured==model
    outs_on, outs_off = run_all(on), run_all(off)
    _assert_bit_identical([o[:2] for o in outs_on],
                          [o[:2] for o in outs_off])
    for _, s, n_items in outs_on:
        assert s.counters["measured_chunks_device_decoded"] == \
            s.counters["measured_chunks_read"]
        assert n_items > 0
        assert s.counters["measured_device_decode_calls"] == n_items
    for _, s, _ in outs_off:
        assert s.counters["measured_chunks_device_decoded"] == 0
        assert s.counters["measured_device_decode_calls"] == 0


@pytest.mark.parametrize("parallel", [False, True])
def test_dist_device_decode_on_off_bit_identical(built, parallel):
    g, dg, fm, root = built
    tag = "par" if parallel else "seq"
    on = Engine(dg, fm,
                EngineConfig(executor="dist_ooc", num_workers=2,
                             parallel_workers=parallel, device_decode=True),
                store=ChunkStore.build_sharded(
                    dg, fm, str(root / f"dv_on_{tag}"), 2))
    off = Engine(dg, fm,
                 EngineConfig(executor="dist_ooc", num_workers=2,
                              parallel_workers=parallel,
                              device_decode=False),
                 store=ChunkStore.build_sharded(
                     dg, fm, str(root / f"dv_off_{tag}"), 2))
    outs_on, outs_off = _run_all(on, g), _run_all(off, g)
    _assert_bit_identical(outs_on, outs_off)
    # the wire audit holds on both decode paths
    for _, s in outs_on + outs_off:
        assert abs(s.counters["measured_net_bytes"]
                   - s.counters["net_bytes"]) < 1e-3
    for _, s in outs_on:
        assert s.counters["measured_chunks_device_decoded"] > 0
        assert 0 < s.counters["measured_device_decode_calls"] <= \
            s.counters["measured_chunks_device_decoded"]


SHARD_MAP_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.core import (Engine, EngineConfig, build_dist_graph,
                        build_formats, make_spec)
from repro.core import algorithms as alg
from repro.data.graphs import rmat_graph

g = rmat_graph(8, 8, seed=11, weighted=True)
spec = make_spec(g, num_partitions=8, batch_size=8)
dg = build_dist_graph(g, spec)
fm = build_formats(dg)
mesh = jax.make_mesh((8,), ("part",))
on = Engine(dg, fm, EngineConfig(device_decode=True), mesh=mesh,
            axis="part")
off = Engine(dg, fm, EngineConfig(device_decode=False), mesh=mesh,
             axis="part")
pr_a, st_a = alg.pagerank(on, 3)
pr_b, st_b = alg.pagerank(off, 3)
np.testing.assert_array_equal(np.asarray(pr_a), np.asarray(pr_b))
for k in st_a.counters:
    assert st_a.counters[k] == st_b.counters[k], k
print("SHARD_MAP_DEVICE_DECODE_OK")
"""


def test_shard_map_device_decode_on_off_bit_identical():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", SHARD_MAP_CODE], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARD_MAP_DEVICE_DECODE_OK" in out.stdout
