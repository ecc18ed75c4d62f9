"""Pallas varint/delta kernel parity (DESIGN.md §10): every device decode
primitive bit-identical to the numpy codec on the int32 domain, the fused
store decode identical to the host decode chunk by chunk, and the
``EngineConfig.device_decode`` knob bit-identical on/off across all four
executors (including ``parallel_workers``) with ``verify_io`` holding.

Kernels run in interpret mode off-TPU; on a TPU backend the compiled
parity case runs too.

Run standalone by ``scripts/ci.sh`` as the device-decode parity gate.
"""
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
from repro.core.chunkstore import REP_CSR, REP_DCSR, REP_DCSR_DELTA
from repro.data.graphs import rmat_graph
from repro.kernels import varint as vk
from repro.kernels.csr_spmv import default_interpret

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

INT32_MAX = 2**31 - 1


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

    @st.composite
    def chunks(draw):
        """An adversarial sorted chunk: edges grouped into runs by src,
        dst non-decreasing within a run, all >= the batch base."""
        base = draw(st.integers(0, 2**20)) * 16
        n_runs = draw(st.integers(0, 12))
        srcs = draw(st.lists(st.integers(0, 2**24), min_size=n_runs,
                             max_size=n_runs, unique=True))
        srcs = np.sort(np.asarray(srcs, np.int64))
        runs, dst = [], []
        for _ in range(n_runs):
            r = draw(st.integers(1, 9))
            runs.append(r)
            d = draw(st.lists(st.integers(0, 2**20), min_size=r, max_size=r))
            dst.extend(base + np.sort(np.asarray(d, np.int64)))
        return base, srcs, np.asarray(runs, np.int64), \
            np.asarray(dst, np.int64)

    @settings(max_examples=25, deadline=None)
    @given(chunks())
    def test_chunk_restore_kernels_match_codec(chunk):
        base, srcs, runs, dst = chunk
        nnz, n_e = srcs.size, dst.size
        starts = (np.cumsum(runs) - runs).astype(np.int64)
        out_len = max(n_e, 1)
        # pair stream: kernel decode + kernel cumsum restore
        pv = codec.pair_delta_values(srcs, starts)
        dec = _kernel_decode(pv)
        pad = np.zeros(2 * max(nnz, 1), np.int32)
        pad[:dec.size] = dec
        s2, i2 = vk.pair_delta_restore(pad)
        np.testing.assert_array_equal(np.asarray(s2)[:nnz], srcs)
        np.testing.assert_array_equal(np.asarray(i2)[:nnz], starts)
        # run expansion + dst residues vs the codec's repeat-based restore
        sp = np.zeros(max(nnz, 1), np.int32)
        sp[:nnz] = srcs
        ip = np.zeros(max(nnz, 1), np.int32)
        ip[:nnz] = starts
        esrc, smask = vk.expand_dcsr_index(sp, ip, nnz, n_e,
                                           out_len=out_len)
        np.testing.assert_array_equal(
            np.asarray(esrc)[:n_e], np.repeat(srcs, runs))
        res = codec.dst_delta_values(dst, starts, base)
        rdec = _kernel_decode(res)
        rpad = np.zeros(out_len, np.int32)
        rpad[:rdec.size] = rdec
        d2 = vk.dst_delta_restore(rpad, smask, base, n_e)
        np.testing.assert_array_equal(np.asarray(d2)[:n_e], dst)


def test_dst_restore_survives_int32_wrap_of_the_running_sum():
    # every run restarts at its batch offset, so a chunk with many runs of
    # large residues sums past 2**31 while each dst stays small
    n_runs, base = 6000, 64
    dst = base + np.repeat(np.arange(n_runs) % 7 + 2**20, 1)
    starts = np.arange(n_runs)
    res = codec.dst_delta_values(dst, starts, base)
    assert res.astype(np.int64).sum() > 2**31
    smask = np.ones(n_runs, np.int32)
    got = vk.dst_delta_restore(res.astype(np.int32), smask, base, n_runs)
    np.testing.assert_array_equal(np.asarray(got), dst)


def test_expand_csr_index_matches_repeat():
    rng = np.random.default_rng(1)
    v_src, vpad = 37, 48
    deg = rng.integers(0, 4, v_src)
    idx = np.zeros(vpad + 1, np.int32)
    idx[1:v_src + 1] = np.cumsum(deg)
    idx[v_src + 1:] = idx[v_src]
    n_e = int(deg.sum())
    esrc, smask = vk.expand_csr_index(idx, v_src, n_e, out_len=n_e + 5)
    np.testing.assert_array_equal(
        np.asarray(esrc)[:n_e], np.repeat(np.arange(v_src), deg))
    starts = (np.cumsum(deg) - deg)[deg > 0]
    exp_mask = np.zeros(n_e + 5, np.int32)
    exp_mask[starts] = 1
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

def _run_all(engine, g):
    src = int(np.argmax(g.out_degrees()))
    return [alg.pagerank(engine, 3), alg.bfs(engine, src),
            alg.sssp(engine, src)]


def _assert_bit_identical(outs_a, outs_b):
    for (va, sa), (vb, sb) in zip(outs_a, outs_b):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
        assert sa.per_iter_return == sb.per_iter_return
        for k in sa.counters:
            if k != "measured_chunks_device_decoded":
                assert sa.counters[k] == sb.counters[k], k


def test_local_device_decode_on_off_bit_identical(built):
    g, dg, fm, _ = built
    on = Engine(dg, fm, EngineConfig(device_decode=True))
    off = Engine(dg, fm, EngineConfig(device_decode=False))
    _assert_bit_identical(_run_all(on, g), _run_all(off, g))


def test_ooc_device_decode_on_off_bit_identical(built):
    g, dg, fm, root = built
    on = Engine(dg, fm, EngineConfig(executor="ooc", device_decode=True),
                store=ChunkStore.build(dg, fm, str(root / "ooc_on")))
    off = Engine(dg, fm, EngineConfig(executor="ooc", device_decode=False),
                 store=ChunkStore.build(dg, fm, str(root / "ooc_off")))
    # verify_io is on by default: every call cross-checks measured==model
    outs_on, outs_off = _run_all(on, g), _run_all(off, g)
    _assert_bit_identical(outs_on, outs_off)
    for _, s in outs_on:
        assert s.counters["measured_chunks_device_decoded"] == \
            s.counters["measured_chunks_read"]
    for _, s in outs_off:
        assert s.counters["measured_chunks_device_decoded"] == 0


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
