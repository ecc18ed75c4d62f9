"""ProcessVertices compiles once per algorithm on LOCAL and SHARD_MAP.

The engine caches the jitted ProcessVertices program per callback code
key (``executor.fn_code_key``), so an algorithm's fresh lambda each
iteration reuses the program of the first.  A second PageRank job on the
same engine then traces and compiles nothing, and its ranks are the
bit-identical ranks of a fresh engine.  A callback without a key (a
closure over an array) is jitted afresh every call, and two callbacks
that differ only in a global name (``jnp.abs`` against ``jnp.exp``), or
in the type of an equal value (``2`` against ``2.0``), get keys of their
own."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Engine, build_dist_graph, build_formats, make_spec
from repro.core import executor
from repro.data.graphs import rmat_graph

_SRC_DIR = os.path.join(os.path.dirname(__file__), "..", "src")

# One run per executor and surface, in a child: the forced host devices
# must be set before JAX starts.  Prints the second job's traces and
# compiles as a JSON line.
_CODE = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import Engine, EngineConfig, build_dist_graph, \
    build_formats, make_spec
from repro.core import algorithms as alg
from repro.data.graphs import rmat_graph

where, surface = sys.argv[1], sys.argv[2]
g = rmat_graph(8, 8, seed=5, weighted=False)
dg = build_dist_graph(g, make_spec(g, num_partitions=4, batch_size=16))
fm = build_formats(dg)
nq = 3 if surface == "multi" else 1
sources = [int(x) for x in np.argsort(-g.out_degrees())[:nq]]


def engine():
    mesh = (Mesh(np.array(jax.devices()[:4]), ("part",))
            if where == "shard_map" else None)
    return Engine(dg, fm, EngineConfig(num_queries=nq), mesh=mesh,
                  axis="part")


def job(eng):
    if surface == "multi":
        return np.asarray(alg.personalized_pagerank(eng, sources, 5)[0])
    return np.asarray(alg.pagerank(eng, 5)[0])


counts = {"traces": 0, "compiles": 0}


def listen(event, duration, **_):
    if event == "/jax/core/compile/jaxpr_trace_duration":
        counts["traces"] += 1
    elif event == "/jax/core/compile/backend_compile_duration":
        counts["compiles"] += 1


eng = engine()
first = job(eng)
jax.monitoring.register_event_duration_secs_listener(listen)
second = job(eng)
jax.monitoring.unregister_event_duration_listener(listen)
fresh = job(engine())
np.testing.assert_array_equal(second, first)
np.testing.assert_array_equal(second, fresh)
print(json.dumps(counts))
"""


@pytest.mark.parametrize("where,surface", [
    ("local", "solo"), ("shard_map", "solo"),
    ("local", "multi"), ("shard_map", "multi")])
def test_second_pagerank_job_compiles_nothing(where, surface):
    env = dict(os.environ, PYTHONPATH=_SRC_DIR + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _CODE, where, surface],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    counts = json.loads(out.stdout.strip().splitlines()[-1])
    assert counts == {"traces": 0, "compiles": 0}


@pytest.fixture(scope="module")
def local():
    g = rmat_graph(7, 8, seed=3, weighted=False)
    dg = build_dist_graph(g, make_spec(g, num_partitions=4, batch_size=16))
    eng = Engine(dg, build_formats(dg))
    valid = np.asarray(dg.vertex_valid)
    x = np.linspace(-2.0, 2.0, valid.size, dtype=np.float32).reshape(
        valid.shape)
    return eng, valid, x


def test_callback_without_a_key_is_jitted_afresh(local):
    eng, valid, x = local
    for factor in (2.0, 3.0):
        scale = jnp.asarray([factor], jnp.float32)   # a device array
        work = lambda s, gid: ({"x": s["x"] * scale[0]}, s["x"])
        assert executor.fn_code_key(work) is None
        new, _, _ = eng.process_vertices(eng.init_state(x=x), work)
        np.testing.assert_array_equal(np.asarray(new["x"]),
                                      np.where(valid, x * factor, x))


# Pairs of callbacks whose bytecode is equal: each pair must get two keys,
# two programs and two results from one engine.
TWINS = {
    # the two differ only in the global name they load
    "global_name": (
        np.float32,
        (lambda s, gid: ({"x": jnp.abs(s["x"])}, s["x"]), np.abs),
        (lambda s, gid: ({"x": jnp.exp(s["x"])}, s["x"]), np.exp)),
    # 2 == 2.0, yet int32 * 2 stays int32 and int32 * 2.0 is float32
    "value_type": (
        np.int32,
        (lambda s, gid: ({"x": s["x"] * 2}, s["x"]), lambda x: x * 2),
        (lambda s, gid: ({"x": s["x"] * 2.0}, s["x"]),
         lambda x: (x * 2).astype(np.float32))),
}


@pytest.mark.parametrize("twins", sorted(TWINS))
def test_equal_bytecode_gets_a_program_of_its_own(local, twins):
    eng, valid, x = local
    dtype, (f_a, ref_a), (f_b, ref_b) = TWINS[twins]
    x = (x * 8).astype(dtype)
    assert f_a.__code__.co_code == f_b.__code__.co_code
    assert executor.fn_code_key(f_a) != executor.fn_code_key(f_b)
    for work, ref in ((f_a, ref_a), (f_b, ref_b), (f_a, ref_a)):
        new, _, _ = eng.process_vertices(eng.init_state(x=x), work)
        want = np.where(valid, ref(x), x).astype(ref(x).dtype)
        assert np.asarray(new["x"]).dtype == want.dtype
        np.testing.assert_allclose(np.asarray(new["x"]), want, rtol=1e-6)


def test_closure_and_default_values_carry_their_type():
    closures = [(lambda c: lambda x: x * c)(c) for c in (1, 1.0, True)]
    defaults = [lambda x, c=c: x * c for c in (1, 1.0, True)]
    for fns in (closures, defaults):
        assert len({executor.fn_code_key(f) for f in fns}) == 3
    assert (executor.fn_code_key(closures[1])
            == executor.fn_code_key((lambda c: lambda x: x * c)(1.0)))
