#!/usr/bin/env python3
"""Drive the graph engine's main path once on a TPU and check its answers.

    python3 chip_smoke.py              # one chip: phases 1-4 below
    python3 chip_smoke.py --chips 4    # SHARD_MAP on a 4-chip mesh only

The graph is Graph500's Kronecker (R-MAT) generator, edge factor 16,
(A, B, C) = (0.57, 0.19, 0.19), made from ``--seed``: scale 22 (4,194,304
vertices, 67,108,864 edges) on the 4-chip mesh, and scale 21 on one chip,
where phases 2-3 below make four host-bound passes over the graph and
scale 22 would not finish inside a 1200 s run.  Every phase runs through
the public ``Engine`` / ``algorithms`` entry points: PageRank for a fixed
5 iterations and BFS from 4 roots of non-zero out-degree.

1. LOCAL, segment backend, P=8, against the numpy references
   (``algorithms.ref_pagerank`` / ``ref_bfs``): BFS levels exactly equal,
   PageRank max|delta| / max|ref| <= 1e-4 (``PR_RTOL``).
2. OOC on a compressed chunk store with the default ``EngineConfig``, so
   chunk decode runs in the Pallas kernels; again with
   ``device_decode=False``, which must be bit-identical in values and every
   counter but ``measured_chunks_device_decoded`` and
   ``measured_device_decode_calls``.
3. dist_ooc, W=4 in-process workers with ``parallel_workers=True`` on a
   sharded store: the same checks as phase 2.
4. ``compute_backend="block_csr"`` on LOCAL and OOC at a smaller scale
   (``--block-scale``), against the segment backend at that scale.

Phases 2-4 match their baseline in values (as above) and in every modeled
counter, to float32 rounding (LOCAL accumulates counters in float32).
With ``--chips 4`` the script runs only SHARD_MAP on a ("part",) mesh of
the 4 chips at P=4 against LOCAL at P=4 on the first chip (PageRank and
BFS from 2 of the roots), and checks that the mesh state is spread over
all 4 chips.  ``--scale`` overrides either
default.

Each phase prints its checks, its wall clock split into compilation and
run, the number of compilations and the device's peak bytes in use.  The
script fails (nonzero exit, no result line) when JAX finds no TPU, when
the Pallas kernels would run interpreted, when the block-CSR backend falls
back to the segment backend, or when any check fails.  Its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Stores and spills live in a temporary directory that is removed at exit.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import warnings

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GRAPH500 = dict(edge_factor=16, a=0.57, b=0.19, c=0.19)
SCALE = {1: 21, 4: 22}  # RMAT scale by --chips (see the module docstring)
PR_ITERS = 5
NUM_ROOTS = 4
MESH_ROOTS = 2          # a 4-chip call is charged per chip
# max|delta| / max|ref| for PageRank.  The engine sums each vertex's
# contributions in float32 against float64 references, and the hub's sum
# runs over its whole in-degree (69,217 edges at scale 20, where XLA's CPU
# backend already lands at 1.06e-5), so the bound sits one decade above.
PR_RTOL = 1e-4
COUNTER_RTOL = 1e-5     # float32 counter accumulation on LOCAL
# The block-CSR backend's warning when a slot cannot be lowered to tiles.
SLOT_FALLBACK = "compute_backend='block_csr' requires slot"
DEVICE_DECODED = "measured_chunks_device_decoded"
DEVICE_DECODE_CALLS = "measured_device_decode_calls"


class CheckFailed(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


# ---------------------------------------------------------------------------
# Problem, references, runs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Problem:
    scale: int
    graph: object           # GraphData
    spec: object            # TwoLevelSpec
    dist: object            # DistGraph
    fmts: object            # ChunkFormats
    roots: list


def build_problem(scale: int, seed: int, num_partitions: int) -> Problem:
    """Graph500-parameter R-MAT graph, two-level partition and chunk
    formats, plus BFS roots drawn from ``seed`` among vertices of
    non-zero out-degree."""
    from repro.core import build_dist_graph, build_formats, make_spec
    from repro.data.graphs import rmat_graph
    g = rmat_graph(scale, GRAPH500["edge_factor"], a=GRAPH500["a"],
                   b=GRAPH500["b"], c=GRAPH500["c"], seed=seed)
    spec = make_spec(g, num_partitions=num_partitions)
    dist = build_dist_graph(g, spec)
    fmts = build_formats(dist)
    rng = np.random.default_rng(seed)
    cands = np.flatnonzero(g.out_degrees() > 0)
    roots = sorted(int(r) for r in rng.choice(cands, NUM_ROOTS,
                                                replace=False))
    return Problem(scale, g, spec, dist, fmts, roots)


@dataclasses.dataclass
class Result:
    pagerank: np.ndarray
    bfs: list               # levels per root
    counters: list          # PageRank's, then each BFS's
    iterations: list        # BFS ProcessEdges calls per root
    seconds: float = 0.0    # wall clock of the runs


def reference(prob: Problem) -> Result:
    from repro.core import algorithms as alg
    g = prob.graph
    n = g.num_vertices
    pr = alg.ref_pagerank(n, g.src, g.dst, PR_ITERS)
    bfs = [alg.ref_bfs(n, g.src, g.dst, r) for r in prob.roots]
    return Result(pr, bfs, [], [])


def run_algorithms(engine, roots) -> Result:
    from repro.core import algorithms as alg
    t0 = time.perf_counter()
    pr, st = alg.pagerank(engine, PR_ITERS)
    bfs, counters, iters = [], [st.counters], []
    for r in roots:
        lv, sb = alg.bfs(engine, r)
        bfs.append(np.asarray(lv))
        counters.append(sb.counters)
        iters.append(sb.iterations)
    return Result(np.asarray(pr), bfs, counters, iters,
                  time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Comparisons
# ---------------------------------------------------------------------------

def pr_rel_err(got, ref) -> float:
    ref = np.asarray(ref, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - ref).max()
                 / np.abs(ref).max())


def check_values(name: str, got: Result, want: Result) -> str:
    """PageRank within PR_RTOL of ``want``, BFS levels exactly equal."""
    err = pr_rel_err(got.pagerank, want.pagerank)
    check(err <= PR_RTOL, f"{name}: PageRank max|delta|/max|ref| = {err:.3e}"
                          f" > {PR_RTOL:g}")
    for r, lg, lw in zip(range(len(want.bfs)), got.bfs, want.bfs):
        bad = int(np.count_nonzero(lg != lw))
        check(bad == 0, f"{name}: BFS root #{r} differs at {bad} vertices")
    return f"pagerank rel err {err:.3e}, {len(want.bfs)} BFS exact"


def check_counters(name: str, got: Result, want: Result, keys) -> str:
    """Modeled counters of two executors agree to float32 rounding."""
    worst = 0.0
    for cg, cw in zip(got.counters, want.counters):
        for k in keys:
            a, b = float(cg[k]), float(cw[k])
            tol = max(1e-3, COUNTER_RTOL * abs(b))
            check(abs(a - b) <= tol, f"{name}: counter {k} = {a!r}, "
                                     f"baseline {b!r}")
            if b:
                worst = max(worst, abs(a - b) / abs(b))
    return f"{len(keys)} modeled counters agree (worst rel {worst:.1e})"


def check_identical(name: str, got: Result, want: Result,
                    except_keys=(DEVICE_DECODED, DEVICE_DECODE_CALLS)
                    ) -> str:
    """Bit-identical values and every counter but ``except_keys``."""
    check(np.array_equal(got.pagerank.view(np.uint32),
                         want.pagerank.view(np.uint32)),
          f"{name}: PageRank not bit-identical")
    for lg, lw in zip(got.bfs, want.bfs):
        check(np.array_equal(lg.view(np.uint32), lw.view(np.uint32)),
              f"{name}: BFS levels not bit-identical")
    for cg, cw in zip(got.counters, want.counters):
        check(set(cg) == set(cw), f"{name}: counter sets differ")
        for k in cg:
            if k not in except_keys:
                check(cg[k] == cw[k], f"{name}: counter {k} = {cg[k]!r} "
                                      f"vs {cw[k]!r}")
    return "bit-identical values and counters"


def device_decoded(res: Result) -> float:
    return float(sum(c[DEVICE_DECODED] for c in res.counters))


# ---------------------------------------------------------------------------
# Timing: wall clock split into compile and run
# ---------------------------------------------------------------------------

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileMeter:
    """Counts compilations and their seconds through jax.monitoring."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            with self._lock:
                self.seconds += duration
                if event == _COMPILE_EVENTS[-1]:
                    self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.compiles, self.cache_hits, self.seconds


class Phase:
    """``with Phase(meter, name, log):`` prints the phase's wall clock,
    compile share, compile count and the device's peak bytes in use."""

    def __init__(self, meter, name, log):
        self.meter, self.name, self.log = meter, name, log

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.meter.snapshot()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        import jax
        wall = time.perf_counter() - self.t0
        n, hits, secs = (b - a for a, b in zip(self.c0,
                                               self.meter.snapshot()))
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        rec = dict(phase=self.name, wall_s=wall, compile_s=secs,
                   run_s=wall - secs, compiles=n, cache_hits=hits,
                   peak_bytes_in_use=peak)
        self.log.append(rec)
        print(f"[{self.name}] wall {wall:.1f} s; compile {secs:.1f} s "
              f"summed over threads ({n} compilations, {hits} cache hits);"
              f" peak_bytes_in_use {peak}", flush=True)
        return False


def say(name: str, msg: str) -> None:
    print(f"[{name}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_local(prob: Problem, ref: Result, backend="segment") -> Result:
    """LOCAL executor against the numpy references."""
    from repro.core import Engine, EngineConfig
    eng = Engine(prob.dist, prob.fmts, EngineConfig(compute_backend=backend))
    res = run_algorithms(eng, prob.roots)
    say(f"local/{backend}", check_values("local", res, ref)
        + f"; BFS iterations {res.iterations}; {res.seconds:.1f} s")
    if eng._block is not None:
        bt = eng._block
        say(f"local/{backend}", f"tiles: {bt.s_max} slots per partition, "
            f"{bt.n_rows} row blocks x {bt.max_tiles_per_row} tiles per "
            f"row in the kernel grid")
    return res


def _ooc_pair(prob, base, make_store, config, name, device_decode,
              keys):
    """Run ``config`` with device decode at its setting (``None`` = the
    engine's own choice) and again with it off; check both against
    ``base`` (modeled counters ``keys``) and against each other."""
    from repro.core import Engine
    store = make_store()
    cfg_on = (config if device_decode is None
              else dataclasses.replace(config, device_decode=device_decode))
    eng = Engine(prob.dist, prob.fmts, cfg_on, store=store)
    check(eng.device_decode, f"{name}: device decode is off")
    on = run_algorithms(eng, prob.roots)
    n_dev = device_decoded(on)
    check(n_dev > 0, f"{name}: no chunk was decoded on the device")
    say(name, check_values(name, on, base) + "; "
        + check_counters(name, on, base, keys)
        + f"; verify_io held; {n_dev:.0f} chunks decoded on device; "
        f"{on.seconds:.1f} s")
    widths = {}
    for st in getattr(store, "shards", [store]):
        for k, v in st._device_decoder.max_widths.items():
            widths[k] = max(widths.get(k, 0), v)
    say(name, f"largest padded decode widths: {widths}")
    eng_off = Engine(prob.dist, prob.fmts,
                     dataclasses.replace(config, device_decode=False),
                     store=store)
    off = run_algorithms(eng_off, prob.roots)
    check(device_decoded(off) == 0, f"{name}: device decode ran while off")
    say(name, "device_decode=False: " + check_identical(name, off, on)
        + f"; {off.seconds:.1f} s")
    return on


def phase_ooc(prob: Problem, base: Result, workdir: str,
              device_decode=None) -> Result:
    """OOC on a compressed chunk store, default config."""
    from repro.core import ChunkStore, EngineConfig
    from repro.core.engine import COUNTER_KEYS
    return _ooc_pair(
        prob, base,
        lambda: ChunkStore.build(prob.dist, prob.fmts,
                                 os.path.join(workdir, "ooc")),
        EngineConfig(executor="ooc"), "ooc", device_decode, COUNTER_KEYS)


def phase_dist_ooc(prob: Problem, base: Result, workdir: str, workers=4,
                   device_decode=None) -> Result:
    """In-thread dist_ooc, parallel workers, on a sharded store.  Its
    network model prices crossings between the W workers, not between
    the P partitions, so ``net_bytes`` / ``net_bytes_raw`` are not
    compared with LOCAL's."""
    from repro.core import ChunkStore, EngineConfig
    from repro.core.engine import COUNTER_KEYS
    keys = [k for k in COUNTER_KEYS if k not in ("net_bytes",
                                                  "net_bytes_raw")]
    return _ooc_pair(
        prob, base,
        lambda: ChunkStore.build_sharded(prob.dist, prob.fmts,
                                         os.path.join(workdir, "dist"),
                                         workers),
        EngineConfig(executor="dist_ooc", num_workers=workers,
                     parallel_workers=True), "dist_ooc", device_decode, keys)


def phase_block(prob: Problem, workdir: str, device_decode=None) -> None:
    """block_csr on LOCAL and OOC against LOCAL segment at the same
    scale (which is itself checked against the references)."""
    from repro.core import ChunkStore, Engine, EngineConfig
    from repro.core.engine import COUNTER_KEYS
    seg = phase_local(prob, reference(prob))
    blk = phase_local(prob, seg, backend="block_csr")
    say("local/block_csr", check_counters("local/block_csr", blk, seg,
                                          COUNTER_KEYS))
    store = ChunkStore.build(prob.dist, prob.fmts,
                             os.path.join(workdir, "block"))
    cfg = EngineConfig(executor="ooc", compute_backend="block_csr")
    if device_decode is not None:
        cfg = dataclasses.replace(cfg, device_decode=device_decode)
    ooc = run_algorithms(Engine(prob.dist, prob.fmts, cfg, store=store),
                         prob.roots)
    say("ooc/block_csr", check_values("ooc/block_csr", ooc, seg) + "; "
        + check_counters("ooc/block_csr", ooc, seg, COUNTER_KEYS)
        + f"; {ooc.seconds:.1f} s")


def phase_mesh(prob: Problem, devices) -> None:
    """SHARD_MAP over a ("part",) mesh of ``devices`` against LOCAL on the
    first of them: values, modeled counters, the physical exchange's
    payload audit, and state placed on every device of the mesh."""
    import jax
    from repro.core import Engine
    from repro.core.engine import COUNTER_KEYS
    p = len(devices)
    check(prob.spec.num_partitions == p,
          f"mesh of {p} devices needs P={p}")
    roots = prob.roots[:MESH_ROOTS]
    with jax.default_device(devices[0]):
        local = run_algorithms(Engine(prob.dist, prob.fmts), roots)
    say("local", f"P={p} on one chip: {local.seconds:.1f} s")
    mesh = jax.sharding.Mesh(np.asarray(devices), ("part",))
    eng = Engine(prob.dist, prob.fmts, mesh=mesh, axis="part")
    sharded = run_algorithms(eng, roots)
    state = eng.init_state(rank=prob.dist.out_degree)
    for name, arr in list(eng._garrs.items()) + list(state.items()):
        on = {s.device for s in arr.addressable_shards}
        check(on == set(devices) and all(
            s.data.shape[0] == 1 for s in arr.addressable_shards),
            f"mesh array {name} is not spread one partition per device")
    for c in sharded.counters:
        check(c["measured_net_payload_elems"] == c["net_payload_elems"],
              "measured_net_payload_elems != net_payload_elems")
    # the physical-wire counters exist only where a collective runs
    keys = [k for k in COUNTER_KEYS if k not in (
        "net_payload_elems", "net_payload_elems_dense",
        "measured_net_payload_elems", "exchange_compacted_iters",
        "exchange_dense_iters")]
    iters = {k: sum(c[f"exchange_{k}_iters"] for c in sharded.counters)
             for k in ("compacted", "dense")}
    say("shard_map", check_values("shard_map", sharded, local) + "; "
        + check_counters("shard_map", sharded, local, keys)
        + f"; payload audit held; graph and vertex state one partition "
        f"per device on {p} devices; exchange iterations {iters}")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int,
                    help="RMAT scale (default: 21 on one chip, 22 on four)")
    ap.add_argument("--block-scale", type=int, default=14,
                    help="RMAT scale of the block_csr phase")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--record", help="also write per-phase timings as JSON")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.scale is None:
        args.scale = SCALE[args.chips]
    import jax
    from repro.kernels.csr_spmv import default_interpret
    from repro.utils import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if default_interpret():
        print("chip_smoke: Pallas kernels would run interpreted",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    enable_compile_cache()
    warnings.filterwarnings("error", message=SLOT_FALLBACK)
    print(f"devices: {len(devices)} x {dev.device_kind}; seed {args.seed}",
          flush=True)
    meter = CompileMeter()
    log: list = []
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            with Phase(meter, f"build scale {args.scale} P=4", log):
                prob = build_problem(args.scale, args.seed, 4)
            with Phase(meter, "shard_map x4 vs local P=4", log):
                phase_mesh(prob, devices[:4])
        else:
            run_one_chip(args, meter, log, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if args.record:
        os.makedirs(os.path.dirname(os.path.abspath(args.record)),
                    exist_ok=True)
        with open(args.record, "w") as f:
            json.dump(dict(scale=args.scale, block_scale=args.block_scale,
                           seed=args.seed, device_kind=dev.device_kind,
                           phases=log), f, indent=1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


def run_one_chip(args, meter, log, workdir) -> None:
    if args.scale == SCALE[1]:
        say("build", f"RMAT scale {args.scale}, not Graph500's 22: phases "
            "2-3 run OOC and dist_ooc with device decode on and off, four "
            "host-bound passes that put scale 22 past a 1200 s run")
    with Phase(meter, f"build scale {args.scale} P=8", log):
        prob = build_problem(args.scale, args.seed, 8)
        g = prob.graph
        say("build", f"{g.num_vertices} vertices, {g.num_edges} edges, "
            f"v_max {prob.spec.v_max}, e_max {prob.dist.e_max}, "
            f"batch {prob.spec.batch_size} x {prob.spec.num_batches}, "
            f"roots {prob.roots}")
    with Phase(meter, "numpy references", log):
        ref = reference(prob)
    with Phase(meter, "1 local/segment", log):
        base = phase_local(prob, ref)
    with Phase(meter, "2 ooc", log):
        phase_ooc(prob, base, workdir)
    with Phase(meter, "3 dist_ooc W=4 parallel", log):
        phase_dist_ooc(prob, base, workdir)
    del prob, ref, base
    gc.collect()
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir, exist_ok=True)
    with Phase(meter, f"4 block_csr at scale {args.block_scale}", log):
        phase_block(build_problem(args.block_scale, args.seed, 8), workdir)


if __name__ == "__main__":
    sys.exit(main())
