"""Graph500 kernel 2: BFS from search keys drawn from the seed, run back to
back through ``repro.core.algorithms.bfs``.

The reference is the benchmark's own copy of the program's
``algorithms.ref_bfs`` as it stood when the benchmark was written:
level-synchronous BFS with float32 levels and ``float32.max`` for
unreached vertices.  The control searches the edge list as generated, one
direction only, which breaks kernel 1's undirected graph.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import bench.graph500 as g500

CHECK = "bfs_level_mismatches"
UNREACHED = np.float32(np.finfo(np.float32).max)


@dataclasses.dataclass(frozen=True)
class Job:
    root: int


def jobs(traffic: dict, graph, seed: int):
    """The window walks ``search_keys`` keys in the order drawn; the
    warm-up searches from further keys outside that list."""
    n_keys, n_warm = int(traffic["search_keys"]), int(traffic["warmup_jobs"])
    roots = g500.search_keys(*graph, n_keys + n_warm, seed)
    return ([Job(r) for r in roots[n_keys:]],
            [Job(r) for r in roots[:n_keys]])


def run(engine, job: Job):
    from repro.core import algorithms
    return algorithms.bfs(engine, job.root)


class Reference:
    """Level-synchronous BFS over a CSR of the edge list, built once and
    searched from many roots: each round expands the whole frontier."""

    def __init__(self, graph):
        n, src, dst = graph
        self.n = n
        self.d_sorted = dst[np.argsort(src, kind="stable")]
        self.starts = np.concatenate(
            [[0], np.cumsum(np.bincount(src, minlength=n))])

    def answer(self, job: Job) -> np.ndarray:
        return self.levels(job.root)

    def levels(self, root: int) -> np.ndarray:
        level = np.full(self.n, UNREACHED, np.float32)
        level[root] = 0
        frontier = np.array([root])
        d = 0
        while frontier.size:
            d += 1
            lo = self.starts[frontier]
            deg = self.starts[frontier + 1] - lo
            first = np.cumsum(deg) - deg
            nbrs = self.d_sorted[np.repeat(lo - first, deg)
                                 + np.arange(deg.sum())]
            frontier = np.unique(nbrs[level[nbrs] > d])
            level[frontier] = d
        return level


def gap(got, want) -> int:
    """Vertices whose BFS level differs from the reference's."""
    return int(np.count_nonzero(np.asarray(got, np.float32)
                                != np.asarray(want, np.float32)))


def control(config: dict, graph, jobs: list) -> list:
    """The reference over the generated edges in one direction only."""
    n, src, dst = graph
    half = src.size // 2 if config["symmetrize"] else src.size
    directed = Reference(g500.Graph(n, src[:half], dst[:half]))
    return [directed.answer(j) for j in jobs]
