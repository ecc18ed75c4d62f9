"""Shared helpers for the paper-validation benchmarks."""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import Engine, EngineConfig, build_dist_graph, build_formats, make_spec  # noqa: E402
from repro.core import algorithms as alg  # noqa: E402
from repro.data.graphs import rmat_graph  # noqa: E402


def build_engine(g, p, batch_size=None, config=EngineConfig(),
                 backend=None):
    """backend overrides ``config.compute_backend`` ("segment" |
    "block_csr") so benchmark drivers can sweep both compute paths."""
    import dataclasses
    if backend is not None:
        config = dataclasses.replace(config, compute_backend=backend)
    spec = make_spec(g, num_partitions=p, batch_size=batch_size)
    dg = build_dist_graph(g, spec)
    return Engine(dg, build_formats(dg), config)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(jax.tree_util.tree_leaves(out)[0]
                          if jax.tree_util.tree_leaves(out) else out)
    return out, time.perf_counter() - t0


def bench_graph(scale=10, edge_factor=16, seed=7):
    return rmat_graph(scale, edge_factor, seed=seed, weighted=True)


def run_algorithms(engine, g, source=None):
    """Returns {algo: (seconds, RunStats)} for PR/BFS/SSSP (WCC is slow on
    1 CPU core; covered by tests)."""
    if source is None:
        source = int(np.argmax(g.out_degrees()))
    out = {}
    (pr, st), t = timed(lambda: alg.pagerank(engine, 5))
    out["pagerank"] = (t, st)
    (lv, st2), t2 = timed(lambda: alg.bfs(engine, source))
    out["bfs"] = (t2, st2)
    (ds, st3), t3 = timed(lambda: alg.sssp(engine, source))
    out["sssp"] = (t3, st3)
    return out


def csv_row(name: str, seconds: float, derived: str) -> str:
    return f"{name},{seconds * 1e6:.1f},{derived}"


def bench_record(benchmark: str, config: str, metric: str, value,
                 units: str) -> dict:
    """One perf-trajectory record (the BENCH_*.json schema): which
    benchmark, which configuration row, which metric, its value, and the
    value's units — flat so re-anchor tooling can diff curves across
    commits without knowing any suite's layout."""
    return dict(benchmark=benchmark, config=config, metric=metric,
                value=float(value), units=units)


_SHARDMAP_PROBE_CODE = """
import json, os, sys
cfg = json.loads(sys.argv[1])
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%d" % cfg["p"])
import jax
import numpy as np
from repro.core import (make_spec, build_dist_graph, build_formats, Engine,
                        EngineConfig)
from repro.core import algorithms as alg
from repro.data.graphs import rmat_graph

g = rmat_graph(cfg["scale"], cfg["edge_factor"], seed=cfg["seed"],
               weighted=True)
spec = make_spec(g, num_partitions=cfg["p"], batch_size=cfg["batch_size"])
dg = build_dist_graph(g, spec)
fm = build_formats(dg)
mesh = jax.make_mesh((cfg["p"],), ("part",))
src = int(np.argmax(g.out_degrees()))
out = {}
for algo in cfg["algos"]:
    eng = Engine(dg, fm, mesh=mesh, axis="part")
    if algo == "pagerank":
        _, st = alg.pagerank(eng, 5)
    elif algo == "bfs":
        _, st = alg.bfs(eng, src)
    elif algo == "sssp":
        _, st = alg.sssp(eng, src)
    else:
        raise ValueError(algo)
    out[algo] = {k: float(v) for k, v in st.counters.items()}
print("PROBE_JSON:" + json.dumps(out))
"""


# Tag for rows derived from the probe below: element counts taken on
# forced host CPU devices, never a time.
PROBE_DEVICES = "cpu-host-devices"


def shardmap_payload_probe(scale: int, p: int, algos=("pagerank", "bfs"),
                           edge_factor=16, seed=7, batch_size=64,
                           timeout=1800) -> dict:
    """Run SHARD_MAP algorithms on ``p`` forced host CPU devices in a
    child process (the main process keeps seeing one device) and return
    ``{algo: counters}``.  The child is pinned to ``JAX_PLATFORMS=cpu`` so
    it never claims an accelerator the parent holds; it measures payload
    element counts, not time.  The engine is built with defaults, so the
    physical sparse exchange arbitrates per iteration (DESIGN.md §12) and
    the counters carry the dense-vs-compacted payload-element pair."""
    import json
    import subprocess
    cfg = dict(scale=scale, p=p, algos=list(algos), edge_factor=edge_factor,
               seed=seed, batch_size=batch_size)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", _SHARDMAP_PROBE_CODE, json.dumps(cfg)],
        capture_output=True, text=True, env=env, cwd=repo, timeout=timeout)
    for line in r.stdout.splitlines():
        if line.startswith("PROBE_JSON:"):
            return json.loads(line[len("PROBE_JSON:"):])
    raise RuntimeError(
        f"shardmap probe failed (p={p}, scale={scale}):\n"
        f"{r.stdout[-1000:]}\n{r.stderr[-3000:]}")


def write_bench_json(filename: str, records: list) -> str:
    """Write a perf-trajectory file (list of :func:`bench_record` dicts).

    Files land in ``REPRO_BENCH_DIR`` (default: current directory) under
    the given name, e.g. ``BENCH_kernels.json``; written atomically so a
    killed benchmark run never leaves a truncated trajectory."""
    from repro.utils import atomic_write_json
    out_dir = os.environ.get("REPRO_BENCH_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, filename)
    atomic_write_json(path, records)
    return path


def merge_bench_json(filename: str, records: list) -> str:
    """Like :func:`write_bench_json`, but benchmarks that share a
    trajectory file (table7 + fig5 both contribute to
    ``BENCH_shardmap.json``) replace only their own ``benchmark`` rows and
    keep everyone else's."""
    import json
    out_dir = os.environ.get("REPRO_BENCH_DIR", ".")
    path = os.path.join(out_dir, filename)
    mine = {r["benchmark"] for r in records}
    kept = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                kept = [r for r in json.load(f)
                        if r.get("benchmark") not in mine]
        except (json.JSONDecodeError, OSError):
            kept = []
    return write_bench_json(filename, kept + records)
