"""DFO sparse-collective invariants (routing, dispatch, combine) +
hypothesis properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.sparse_collectives import (
    dense_combine, dense_dispatch, topk_routing,
)


@settings(max_examples=20, deadline=None)
@given(st.integers(4, 64), st.integers(2, 8), st.integers(1, 3),
       st.integers(0, 2**16))
def test_topk_routing_positions_unique(t, e, k, seed):
    k = min(k, e)
    logits = jax.random.normal(jax.random.PRNGKey(seed), (t, e))
    cap = max(1, t)  # no drops
    dispatch, idx, pos, w, _ = topk_routing(logits, k, cap)
    # every kept (expert, position) pair is unique -> no scatter collision
    kept = np.asarray(dispatch).reshape(-1)
    flat = (np.asarray(idx) * cap + np.asarray(pos)).reshape(-1)[kept]
    assert len(set(flat.tolist())) == kept.sum()
    # weights of kept slots are normalized per token when all kept
    wsum = np.asarray(jnp.sum(jnp.where(dispatch, w, 0.0), -1))
    assert (wsum <= 1.0 + 1e-5).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(4, 32), st.integers(2, 6), st.integers(0, 2**16))
def test_capacity_drops_exactly_overflow(t, e, seed):
    logits = jax.random.normal(jax.random.PRNGKey(seed), (t, e))
    cap = 2
    dispatch, idx, pos, w, _ = topk_routing(logits, 1, cap)
    # per expert, at most cap tokens survive, and survivors are the first
    counts = np.zeros(e, int)
    disp = np.asarray(dispatch)[:, 0]
    for i in range(t):
        ei = int(np.asarray(idx)[i, 0])
        if counts[ei] < cap:
            assert disp[i]
            counts[ei] += 1
        else:
            assert not disp[i]


def test_dispatch_combine_roundtrip():
    """dispatch -> identity experts -> combine == weighted copy of tokens."""
    t, d, e, k, cap = 16, 8, 4, 2, 16
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (t, d))
    logits = jax.random.normal(jax.random.PRNGKey(1), (t, e))
    dispatch, idx, pos, w, _ = topk_routing(logits, k, cap)
    buf = dense_dispatch(x, dispatch, idx, pos, e, cap)
    out = dense_combine(buf, dispatch, idx, pos, w, t)
    expected = x * np.asarray(jnp.sum(jnp.where(dispatch, w, 0.0),
                                      -1))[:, None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_dispatch_buffer_contains_each_token_once():
    t, d, e, k, cap = 12, 4, 3, 1, 12
    x = jnp.arange(t * d, dtype=jnp.float32).reshape(t, d) + 1.0
    logits = jax.random.normal(jax.random.PRNGKey(2), (t, e))
    dispatch, idx, pos, w, _ = topk_routing(logits, k, cap)
    buf = np.asarray(dense_dispatch(x, dispatch, idx, pos, e, cap))
    # non-zero rows of the buffer are exactly the dispatched tokens
    nz = (np.abs(buf).sum(-1) > 0).sum()
    assert nz == int(np.asarray(dispatch).sum())


def test_filtered_all_to_all_in_subprocess():
    """shard_map filtered exchange: run in a child with 4 host devices."""
    import subprocess, sys, os
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.sparse_collectives import filtered_all_to_all

mesh = jax.make_mesh((4,), ("part",))
V = 8
payload = jnp.arange(4 * V, dtype=jnp.float32).reshape(4, V)
mask = jnp.asarray(np.random.default_rng(0).random((4, 4, V)) > 0.5)

def f(payload, mask):
    recv, rmask = filtered_all_to_all(payload[0], mask[0], "part")
    return recv[None], rmask[None]

fn = jax.jit(jax.shard_map(f, mesh=mesh,
    in_specs=(P("part"), P("part")), out_specs=(P("part"), P("part"))))
recv, rmask = fn(payload, mask)
recv, rmask = np.asarray(recv), np.asarray(rmask)
mask_np = np.asarray(mask)
pay = np.asarray(payload)
for q in range(4):
    for p in range(4):
        for v in range(V):
            if mask_np[p, q, v]:
                assert rmask[q, p, v], (q, p, v)
                assert recv[q, p, v] == pay[p, v]
            else:
                assert not rmask[q, p, v]
print("FILTERED_A2A_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert "FILTERED_A2A_OK" in r.stdout, r.stderr[-2000:]


COMPACTED_PROPERTY_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from hypothesis import given, settings, strategies as st
from repro.core import sparse_collectives as sc

mesh = jax.make_mesh((8,), ("part",))
PCNT = 8
SETTINGS = settings(max_examples=8, deadline=None)


def shmap(fn, *args):
    wrapped = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(P("part") for _ in args),
        out_specs=P("part"), check_vma=False))
    return wrapped(*args)


@SETTINGS
@given(st.integers(4, 48), st.sampled_from([0.0, 0.1, 0.5, 1.0]),
       st.integers(0, 2**16))
def prop_masked_roundtrip(v, density, seed):
    # random [P, P, V] masks incl. the all-inactive frontier; capacity
    # at the true per-peer max -> overflow never trips and
    # compaction + scatter-back equals filtered_all_to_all bit-for-bit.
    rng = np.random.default_rng(seed)
    sm = rng.random((PCNT, PCNT, v)) < density
    vals = rng.normal(size=(PCNT, v)).astype(np.float32)
    cap = max(int(sm.sum(axis=2).max()), 1)

    def both(x, m):
        rd, md = sc.filtered_all_to_all(x[0], m[0], "part")
        rc, ri, ov = sc.masked_compacted_all_to_all(x[0], m[0], cap, "part")
        rs, ms = sc.compacted_scatter_back(rc, ri, v)
        return rd, md, rs, ms, ov[None]

    rd, md, rs, ms, ov = shmap(both, vals, sm)
    assert not bool(np.asarray(ov).any())
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(md), np.asarray(ms))


@SETTINGS
@given(st.integers(4, 48), st.booleans(), st.integers(0, 2**16))
def prop_dest_map_delivery(v, all_inactive, seed):
    # random dest maps (incl. all-inactive): with capacity AT the exact
    # per-peer max every live entry is delivered exactly once to its
    # destination with its payload and overflow stays False; one below
    # the max the pmax'd overflow flag trips on every shard.
    rng = np.random.default_rng(seed)
    dest = (np.full((PCNT, v), -1, np.int32) if all_inactive
            else rng.integers(-1, PCNT, size=(PCNT, v)).astype(np.int32))
    payload = rng.normal(size=(PCNT, v, 2)).astype(np.float32)
    maxc = max(int(max((dest[s] == p).sum() for s in range(PCNT)
                       for p in range(PCNT))), 1)

    recv, ridx, ovf = shmap(
        lambda x, d: (lambda o: o[:-1] + (o[-1][None],))(
            sc.compacted_all_to_all(x[0], d[0], maxc, "part")),
        payload, dest)
    assert not bool(np.asarray(ovf).any())
    recv = np.asarray(recv).reshape(PCNT, PCNT, maxc, 2)
    ridx = np.asarray(ridx).reshape(PCNT, PCNT, maxc)
    assert np.all(recv[ridx < 0] == 0)            # padding contract
    for dst in range(PCNT):
        for src in range(PCNT):
            want = np.flatnonzero(dest[src] == dst)
            got = ridx[dst, src][ridx[dst, src] >= 0]
            assert sorted(got.tolist()) == sorted(want.tolist())
            for vi in want:
                slot = np.flatnonzero(ridx[dst, src] == vi)[0]
                np.testing.assert_array_equal(recv[dst, src, slot],
                                              payload[src, vi])
    if maxc > 1:
        _, _, ovf_low = shmap(
            lambda x, d: (lambda o: o[:-1] + (o[-1][None],))(
                sc.compacted_all_to_all(x[0], d[0], maxc - 1, "part")),
            payload, dest)
        if any((dest[s] == p).sum() == maxc for s in range(PCNT)
               for p in range(PCNT)):
            assert bool(np.asarray(ovf_low).all())


prop_masked_roundtrip()
prop_dest_map_delivery()
print("COMPACTED_PROPERTIES_OK")
"""


def test_compacted_roundtrip_properties_in_subprocess():
    """Hypothesis round-trip equivalence for the compacted collectives,
    run on 8 forced host devices in a child process (DESIGN.md §12)."""
    import subprocess, sys, os
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", COMPACTED_PROPERTY_CODE],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=1800)
    assert "COMPACTED_PROPERTIES_OK" in r.stdout, (r.stdout[-1000:],
                                                   r.stderr[-3000:])
