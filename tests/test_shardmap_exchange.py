"""Physical sparse exchange for SHARD_MAP (DESIGN.md §12).

Two subprocess suites on 8 forced host devices:

* collective level — padding contract (``recv_src_index == -1`` + zero
  payload), overflow-flag semantics at/one-below the per-peer maximum,
  and compacted+scatter-back == ``filtered_all_to_all`` bit-for-bit for
  the solo and multi-query panel wires;
* engine level — the ``physical_sparse_exchange`` knob is bit-identical
  to the dense exchange for all four algorithms plus multi-BFS, the
  ``measured_net_payload_elems == net_payload_elems`` audit holds, and
  compacted wins strictly on selective iterations while PageRank's
  all-active frontier arbitrates dense.

Deterministic twins of the hypothesis properties in
``test_sparse_collectives.py`` — these must run even where hypothesis
is not installed.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COLLECTIVE_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import sparse_collectives as sc

mesh = jax.make_mesh((8,), ("part",))
PCNT, V = 8, 96
rng = np.random.default_rng(7)


def _s1(out):
    # overflow is a pmax'd scalar; shard_map out_specs need an axis
    return out[:-1] + (out[-1][None],)


def shmap(fn, *args):
    wrapped = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(P("part") for _ in args),
        out_specs=P("part"), check_vma=False))
    return wrapped(*args)


# --- compacted_all_to_all: padding contract + -1-inactive handling ----------
dest = rng.integers(-1, PCNT, size=(PCNT, V)).astype(np.int32)
payload = rng.normal(size=(PCNT, V, 3)).astype(np.float32)
payload[0, 0] = 0.0                                   # live entry w/ value 0
dest[0, 0] = 3
cap = int(max(sc.capacity_bucket(int((dest == p).sum(axis=1).max()))
              for p in range(PCNT)))

recv, ridx, ovf = shmap(
    lambda x, d: _s1(sc.compacted_all_to_all(x[0], d[0], cap, "part")),
    payload, dest)
recv = np.asarray(recv).reshape(PCNT, PCNT, cap, 3)   # [dst, src, slot, D]
ridx = np.asarray(ridx).reshape(PCNT, PCNT, cap)
assert not bool(np.asarray(ovf).any()), "bucketed capacity must not overflow"
pad = ridx < 0
assert np.all(recv[pad] == 0), "padding slots must carry zero payload"
# every live (src, dst) entry arrives exactly once, with its payload
for dst in range(PCNT):
    for src in range(PCNT):
        want = np.flatnonzero(dest[src] == dst)
        got = ridx[dst, src]
        got = got[got >= 0]
        assert sorted(got.tolist()) == sorted(want.tolist()), (dst, src)
        for v in want:
            slot = np.flatnonzero(ridx[dst, src] == v)[0]
            np.testing.assert_array_equal(recv[dst, src, slot],
                                          payload[src, v])
# dest == -1 entries never ship
inactive = {(s, v) for s in range(PCNT) for v in np.flatnonzero(dest[s] < 0)}
for dst in range(PCNT):
    for src in range(PCNT):
        for v in ridx[dst, src][ridx[dst, src] >= 0]:
            assert (src, int(v)) not in inactive
print("PAD_OK")

# --- overflow flag: trips one-below the true max, not at it -----------------
maxc = int(max((dest[s] == p).sum() for s in range(PCNT) for p in range(PCNT)))
_, _, ovf_at = shmap(
    lambda x, d: _s1(sc.compacted_all_to_all(x[0], d[0], maxc, "part")),
    payload, dest)
_, _, ovf_low = shmap(
    lambda x, d: _s1(sc.compacted_all_to_all(x[0], d[0], maxc - 1, "part")),
    payload, dest)
assert not bool(np.asarray(ovf_at).any())
assert bool(np.asarray(ovf_low).all()), "pmax'd flag must trip on all shards"
print("OVF_OK")

# --- masked solo wire: compaction + scatter-back == filtered_all_to_all ----
for density, tag in ((0.15, "sparse"), (0.0, "allinactive"), (0.9, "dense")):
    sm = (rng.random((PCNT, PCNT, V)) < density)
    vals = rng.normal(size=(PCNT, V)).astype(np.float32)
    capm = sc.capacity_bucket(int(sm.sum(axis=2).max()))

    def both(x, m):
        rd, md = sc.filtered_all_to_all(x[0], m[0], "part")
        rc, ri, ov = sc.masked_compacted_all_to_all(x[0], m[0], capm, "part")
        rs, ms = sc.compacted_scatter_back(rc, ri, V)
        return rd, md, rs, ms, ov[None]

    rd, md, rs, ms, ov = shmap(both, vals, sm)
    assert not bool(np.asarray(ov).any()), tag
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(rs), err_msg=tag)
    np.testing.assert_array_equal(np.asarray(md), np.asarray(ms), err_msg=tag)
print("SOLO_RT_OK")

# --- multi-query panel wire: union-compacted == dense panel -----------------
NQ = 3
smq = (rng.random((PCNT, PCNT, V, NQ)) < 0.2)
valq = rng.normal(size=(PCNT, V, NQ)).astype(np.float32)
capq = sc.capacity_bucket(int(np.any(smq, axis=3).sum(axis=2).max()))


def both_mq(x, m):
    sv = jnp.where(m[0], x[0][None], 0)
    rd = jax.lax.all_to_all(sv, "part", 0, 0, tiled=True)
    md = jax.lax.all_to_all(m[0].astype(jnp.int8), "part", 0, 0,
                            tiled=True) > 0
    rv, rm, ri, ov = sc.masked_compacted_all_to_all_mq(x[0], m[0], capq,
                                                       "part")
    rs, ms = sc.compacted_scatter_back_mq(rv, rm, ri, V)
    return rd, md, rs, ms, ov[None]


rd, md, rs, ms, ov = shmap(both_mq, valq, smq)
assert not bool(np.asarray(ov).any())
np.testing.assert_array_equal(np.asarray(rd), np.asarray(rs))
np.testing.assert_array_equal(np.asarray(md), np.asarray(ms))
print("MQ_RT_OK")
print("SHARDMAP_COLLECTIVES_OK")
"""

ENGINE_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.core import (make_spec, build_dist_graph, build_formats, Engine,
                        EngineConfig)
from repro.core import algorithms as alg
from repro.data.graphs import rmat_graph

g = rmat_graph(8, 8, seed=11, weighted=True)
spec = make_spec(g, num_partitions=8, batch_size=8)
dg = build_dist_graph(g, spec)
fm = build_formats(dg)
mesh = jax.make_mesh((8,), ("part",))
src0 = int(np.argmax(g.out_degrees()))


def run(physical, algo):
    nq = 4 if algo == "multi_bfs" else 1
    cfg = EngineConfig(physical_sparse_exchange=physical, num_queries=nq)
    eng = Engine(dg, fm, cfg, mesh=mesh, axis="part")
    if algo == "pagerank":
        return alg.pagerank(eng, 3)
    if algo == "bfs":
        return alg.bfs(eng, src0)
    if algo == "sssp":
        return alg.sssp(eng, src0)
    if algo == "wcc":
        return alg.wcc(eng)
    return alg.multi_bfs(eng, [0, 3, src0, 17])


for algo in ("pagerank", "bfs", "sssp", "wcc", "multi_bfs"):
    out_off, st_off = run(False, algo)
    out_on, st_on = run(True, algo)
    np.testing.assert_array_equal(np.asarray(out_off), np.asarray(out_on),
                                  err_msg=algo)
    c_on, c_off = st_on.counters, st_off.counters
    # physical path never touches the priced wire model
    for k in ("net_bytes", "net_bytes_raw", "msgs_sent", "msgs_generated"):
        assert abs(c_on[k] - c_off[k]) < 1e-3, (algo, k)
    # measured == model audit (verify_io re-checks this inside the engine)
    assert abs(c_on["measured_net_payload_elems"]
               - c_on["net_payload_elems"]) <= 0.5, algo
    assert c_on["net_payload_elems"] <= c_on["net_payload_elems_dense"], algo
    iters = c_on["exchange_compacted_iters"] + c_on["exchange_dense_iters"]
    assert iters >= 1, algo
    if algo == "pagerank":
        # all-active frontier: arbitration must keep the dense slab
        assert c_on["exchange_compacted_iters"] == 0, c_on
        assert c_on["net_payload_elems"] == c_on["net_payload_elems_dense"]
    else:
        # selective frontiers: compacted fires and strictly beats dense
        assert c_on["exchange_compacted_iters"] >= 1, (algo, c_on)
        assert (c_on["net_payload_elems"]
                < c_on["net_payload_elems_dense"]), algo
    print(algo, "PARITY_OK",
          int(c_on["exchange_compacted_iters"]),
          int(c_on["exchange_dense_iters"]))

# off-mesh engines must reject the knob
try:
    Engine(dg, fm, EngineConfig(physical_sparse_exchange=True))
    raise SystemExit("expected ValueError for local engine")
except ValueError:
    pass
print("SHARDMAP_ENGINE_OK")
"""


def _run(code):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, cwd=REPO, timeout=1200)


def test_compacted_collectives_contract():
    r = _run(COLLECTIVE_CODE)
    assert "SHARDMAP_COLLECTIVES_OK" in r.stdout, (r.stdout[-1000:],
                                                   r.stderr[-3000:])


def test_physical_exchange_engine_parity():
    r = _run(ENGINE_CODE)
    assert "SHARDMAP_ENGINE_OK" in r.stdout, (r.stdout[-1000:],
                                              r.stderr[-3000:])
