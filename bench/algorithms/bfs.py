"""Graph500 kernel 2: BFS from search keys drawn from the seed, run back to
back through ``repro.core.algorithms.bfs``.

The reference is level-synchronous BFS with float32 levels and
``float32.max`` for unreached vertices, as the program's
``algorithms.ref_bfs`` computes them (a test holds the two equal), but
faster: a level whose frontier holds a quarter of the edges or more scans
the edge list once instead of gathering each frontier vertex's row, and a
level's new vertices are marked in a mask instead of sorted.  The control
searches the edge list as generated, one direction only, which breaks
kernel 1's undirected graph.
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
    """Level-synchronous BFS over the edge list and a CSR of it, built once
    and searched from many roots: each round expands the whole frontier."""

    def __init__(self, graph):
        n, src, dst = graph
        index = np.int32 if n <= np.iinfo(np.int32).max else np.int64
        self.n = n
        self.src, self.dst = src.astype(index), dst.astype(index)
        self.d_sorted = self.dst[np.argsort(self.src)]
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
            total = int(deg.sum())
            if 4 * total < self.src.size:     # the frontier's rows
                first = np.cumsum(deg) - deg
                nbrs = self.d_sorted[np.repeat(lo - first, deg)
                                     + np.arange(total)]
            else:                             # one scan of every edge
                front = np.zeros(self.n, bool)
                front[frontier] = True
                nbrs = self.dst[front[self.src]]
            reached = np.zeros(self.n, bool)
            reached[nbrs] = True
            frontier = np.flatnonzero(reached & (level > d))
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
