"""Multi-process dist_ooc worker entrypoint + parent-side launcher
(DESIGN.md §13).

Each rank is a full SPMD engine replica: it rebuilds the graph, the
two-level spec, and the chunk formats deterministically from the run spec,
opens the shared :class:`~repro.core.chunkstore.ShardedChunkStore`
read-only, constructs an Engine carrying a
:class:`~repro.core.transport.ProcContext`, and runs the *same* algorithm
driver as a single-process run — the engine executes only the logical
workers its rank owns, the transport carries the rest.  Every rank writes
a ``result_r{rank}.npz`` with the assembled global values, per-iteration
returns, counters, per-worker totals and the transport's fault/recovery
statistics; live ranks' results are identical, which the fault-injection
tests assert bit-for-bit against a failure-free run.

Run one rank:  ``python -m repro.runtime.procworker <spec.json> <rank>``
Run a fleet:   :func:`launch` (used by tests/test_fault_injection.py).

The run spec is a JSON object::

    {"run_id": str, "world": int, "num_workers": int,
     "rendezvous": dir, "result_dir": dir,
     "graph": {"scale": 7, "edge_factor": 16, "seed": 5, "weighted": true}
              or {"edge_file": path, "crc32": int}  (serialized edge list),
     "spec": {"num_partitions": 4, "batch_size": 16},
     "store_root": sharded-store dir,
     "store_root_rev": optional reversed-graph store dir (wcc),
     "engine": {optional EngineConfig overrides},
     "algorithm": {"name": "pagerank" | "bfs" | "sssp" | "wcc",
                   "args": {...}},
     "fault_plan": FaultPlan.to_json() string or null,
     "io_timeout": seconds, "stall_timeout": seconds,
     "resume": bool  (set by launch(resume=True): restart the whole job
                      from the durable run log + per-op checkpoints)}
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

FAULT_EXIT = 42     # mirrored from repro.runtime.faults (importable cheaply)

# Ranks are host-only by design: W rank processes on one host would race
# for its accelerator, which belongs to one process at a time.  Every rank
# pins JAX to the CPU, so device decode stays off there (DESIGN.md §10).
RANK_PLATFORM = "cpu"


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------


def _build_problem(spec: dict):
    """Deterministic per-rank reconstruction of the graph and formats —
    every rank derives bit-identical preprocessing, so the replicas agree
    on specs, need lists, and byte models without shipping arrays."""
    from repro.core import build_dist_graph, build_formats, make_spec
    gsp = spec["graph"]
    if gsp.get("edge_file"):
        # Arbitrary graphs: the parent serialized (and checksummed) the
        # edge list once; every rank loads the identical bytes instead of
        # regenerating from RMAT parameters.
        from repro.data.graphs import load_edge_list
        g = load_edge_list(gsp["edge_file"],
                           expect_crc=gsp.get("crc32"))
    else:
        from repro.data.graphs import rmat_graph
        g = rmat_graph(int(gsp["scale"]), int(gsp.get("edge_factor", 16)),
                       seed=int(gsp.get("seed", 0)),
                       weighted=bool(gsp.get("weighted", False)))
    two = make_spec(g, num_partitions=int(spec["spec"]["num_partitions"]),
                    batch_size=int(spec["spec"]["batch_size"]))
    dg = build_dist_graph(g, two)
    fm = build_formats(dg)
    return g, two, dg, fm


def _run_algorithm(spec: dict, engine, engine_rev):
    from repro.core import algorithms as alg
    name = spec["algorithm"]["name"]
    args = spec["algorithm"].get("args", {})
    if name == "pagerank":
        return alg.pagerank(engine, int(args.get("num_iters", 3)))
    if name == "bfs":
        return alg.bfs(engine, int(args["source"]))
    if name == "sssp":
        return alg.sssp(engine, int(args["source"]))
    if name == "wcc":
        if engine_rev is None:
            raise ValueError("wcc needs store_root_rev in the run spec")
        return alg.wcc(engine, engine_rev)
    raise ValueError(f"unknown algorithm {name!r}")


def _assemble_values(ctx, two, worker_of, values) -> np.ndarray:
    """Each rank's gathered values are authoritative only on its owned
    partitions (process-mode states are padded with zeros elsewhere);
    overlay per partition from its owner's vector."""
    mine = np.asarray(values)
    vecs = ctx.allgather(mine)
    bounds = np.asarray(two.boundaries)
    full = np.zeros_like(mine)
    for p in range(two.num_partitions):
        r = ctx.assign[int(worker_of[p])]
        full[bounds[p]:bounds[p + 1]] = vecs[r][bounds[p]:bounds[p + 1]]
    return full


def worker_main(spec_path: str, rank: int) -> None:
    import jax
    from repro.utils import enable_compile_cache
    jax.config.update("jax_platforms", RANK_PLATFORM)
    enable_compile_cache()
    with open(spec_path) as f:
        spec = json.load(f)
    from repro.core import Engine, EngineConfig
    from repro.core.chunkstore import ShardedChunkStore
    from repro.core.transport import ProcContext
    from repro.runtime.faults import FaultInjector, FaultPlan

    g, two, dg, fm = _build_problem(spec)
    store = ShardedChunkStore.open(spec["store_root"])

    injector = None
    if spec.get("fault_plan"):
        injector = FaultInjector(FaultPlan.from_json(spec["fault_plan"]),
                                 rank)
    ctx = ProcContext(rank, int(spec["world"]), int(spec["num_workers"]),
                      spec["rendezvous"], run_id=spec.get("run_id", "run"),
                      injector=injector,
                      io_timeout=float(spec.get("io_timeout", 120.0)),
                      stall_timeout=float(spec.get("stall_timeout", 30.0)),
                      log_dir=spec["result_dir"],
                      resume=bool(spec.get("resume", False)))
    cfg = EngineConfig(executor="dist_ooc",
                       num_workers=int(spec["num_workers"]),
                       **spec.get("engine", {}))
    engine = Engine(dg, fm, cfg, store=store, proc_ctx=ctx)
    engine_rev = None
    if spec.get("store_root_rev"):
        from repro.core import build_dist_graph, build_formats
        dg_r = build_dist_graph(g.reversed(), two)
        fm_r = build_formats(dg_r)
        store_r = ShardedChunkStore.open(spec["store_root_rev"])
        engine_rev = Engine(dg_r, fm_r, cfg, store=store_r, proc_ctx=ctx)
    # Whole-job restart: with every engine registered, compute the resume
    # point from the durable run logs and restore the spills to it; the
    # driver below then fast-forwards through the committed ops.
    ctx.prepare_resume()

    values, stats = _run_algorithm(spec, engine, engine_rev)
    full = _assemble_values(ctx, two, store.worker_of, values)

    names = sorted(stats.counters)
    wt = engine.worker_totals
    out = dict(
        values=full,
        iterations=np.int64(stats.iterations),
        rets=np.asarray(stats.per_iter_return, np.float64),
        counter_names=np.asarray(names),
        counter_vals=np.asarray([stats.counters[k] for k in names],
                                np.float64),
        wt_disk=np.asarray([t["disk_bytes"] for t in wt], np.float64),
        wt_net=np.asarray([t["net_bytes"] for t in wt], np.float64),
        wt_edges=np.asarray([t["edges_touched"] for t in wt], np.float64),
        assign=np.asarray(ctx.assign, np.int64),
        epoch=np.int64(ctx.epoch),
        recoveries=np.int64(ctx.stats["recoveries"]),
        wire_frames=ctx.stats["wire_frames"],
        dropped=ctx.stats["dropped"],
        redelivered=ctx.stats["redelivered"],
        held=ctx.stats["held"],
        late_delivered=ctx.stats["late_delivered"],
        corrupted=ctx.stats["corrupted"],
        corrupt_frames=ctx.stats["corrupt_frames"],
    )
    os.makedirs(spec["result_dir"], exist_ok=True)
    tmp = os.path.join(spec["result_dir"], f".result_r{rank}.npz.tmp")
    with open(tmp, "wb") as f:
        np.savez(f, **out)
    os.replace(tmp, os.path.join(spec["result_dir"],
                                 f"result_r{rank}.npz"))
    ctx.finalize()


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------


def launch(spec: dict, timeout: float = 300.0,
           resume: bool = False) -> list:
    """Spawn one OS process per rank, wait, return the exit codes.

    Writes ``spec.json`` (and per-rank ``log_r{rank}.txt``) under the
    spec's ``result_dir``.  On a hang past ``timeout`` every straggler is
    killed and a RuntimeError names it — a fault-injection run must
    terminate via recovery, never via the parent's watchdog.

    ``resume=True`` restarts a crashed job from its durable run logs +
    per-op checkpoints (same spec, same dirs): the fault plan is stripped
    — the op the crash interrupted was never committed, so a replayed
    plan would re-fire the same kill forever — and the ranks fast-forward
    through every committed op, producing results bit-identical to a
    failure-free run."""
    rdir = spec["result_dir"]
    os.makedirs(rdir, exist_ok=True)
    os.makedirs(spec["rendezvous"], exist_ok=True)
    if resume:
        spec = dict(spec)
        spec["resume"] = True
        spec["fault_plan"] = None
    # Stale port files from a previous (crashed) incarnation would race
    # the fresh rendezvous: a rank could dial a long-gone port.
    for r in range(int(spec["world"])):
        stale = os.path.join(spec["rendezvous"], f"rank{r}.port")
        if os.path.exists(stale):
            os.remove(stale)
    spec_path = os.path.join(rdir, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    import repro
    # repro may be a namespace package (__file__ is None): locate src/
    # through __path__ so workers can import it regardless
    src_dir = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ)
    env["PYTHONPATH"] = (src_dir + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else src_dir)
    procs, logs = [], []
    for r in range(int(spec["world"])):
        log = open(os.path.join(rdir, f"log_r{r}.txt"), "wb")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.runtime.procworker", spec_path,
             str(r)],
            stdout=log, stderr=subprocess.STDOUT, env=env))
    codes = []
    try:
        for r, p in enumerate(procs):
            try:
                codes.append(p.wait(timeout=timeout))
            except subprocess.TimeoutExpired:
                for q in procs:
                    if q.poll() is None:
                        q.kill()
                raise RuntimeError(
                    f"rank {r} did not finish within {timeout}s "
                    f"(logs under {rdir})")
    finally:
        for log in logs:
            log.close()
    return codes


def load_result(result_dir: str, rank: int) -> dict:
    path = os.path.join(result_dir, f"result_r{rank}.npz")
    with np.load(path, allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


def main(argv) -> int:
    if len(argv) != 3:
        print("usage: python -m repro.runtime.procworker <spec.json> "
              "<rank>", file=sys.stderr)
        return 2
    worker_main(argv[1], int(argv[2]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
