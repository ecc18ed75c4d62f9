"""LDBC Graphalytics PageRank at a fixed iteration count, as
``repro.core.algorithms.pagerank`` runs it.

The reference is the benchmark's own copy of the program's
``algorithms.ref_pagerank`` as it stood when the benchmark was written:
power iteration in float64 over the raw edge list.  The control computes
it in bfloat16, the precision below the engine's float32.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

CHECK = "pagerank_rel_err"


@dataclasses.dataclass(frozen=True)
class Job:
    iterations: int
    damping: float


def jobs(traffic: dict, graph, seed: int):
    job = Job(int(traffic["iterations"]), float(traffic["damping"]))
    # every iteration runs the same programs over every chunk, so a
    # shorter warm-up job compiles all the window uses
    warm = dataclasses.replace(job,
                               iterations=int(traffic["warmup_iterations"]))
    return [warm] * int(traffic["warmup_jobs"]), [job]


def run(engine, job: Job):
    from repro.core import algorithms
    return algorithms.pagerank(engine, job.iterations, job.damping)


def pagerank(n: int, src, dst, num_iters: int = 5, damping: float = 0.85,
             dtype=np.float64):
    """Power iteration: rank <- (1 - d) / n + d * A^T (rank / outdeg),
    from rank = 1 / n; vertices without out-edges divide by 1.  Every
    intermediate array is rounded to ``dtype`` (float64 is the reference;
    bfloat16 is the control)."""
    cast = lambda a: np.asarray(a, np.float64).astype(dtype)
    rank = cast(np.full(n, 1.0 / n))
    outdeg = np.maximum(np.bincount(src, minlength=n), 1)
    for _ in range(num_iters):
        contrib = cast(rank.astype(np.float64)[src] / outdeg[src])
        acc = cast(np.bincount(dst, weights=contrib.astype(np.float64),
                               minlength=n))
        rank = cast((1 - damping) / n + damping * acc.astype(np.float64))
    return rank.astype(np.float64)


class Reference:
    """The float64 answer, computed once for each distinct job."""

    def __init__(self, graph):
        self.graph = graph
        self._answers: dict = {}

    def answer(self, job: Job):
        if job not in self._answers:
            self._answers[job] = pagerank(*self.graph, job.iterations,
                                          job.damping)
        return self._answers[job]


def gap(got, want) -> float:
    """max |got - want| / max |want|."""
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / np.abs(want).max())


def control(config: dict, graph, jobs: list) -> list:
    """The reference with every array held in bfloat16."""
    return [pagerank(*graph, j.iterations, j.damping, ml_dtypes.bfloat16)
            for j in jobs]
