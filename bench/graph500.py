"""Graph500 Kronecker generator (Graph500 specification, section 3,
"Graph Generation"), the benchmark's own copy.

Each of the ``edge_factor * 2**scale`` edges picks, for each of ``scale``
bits, one quadrant of the initiator: A (neither bit), B (column bit), C
(row bit) or D (both), with probabilities (A, B, C, D).  The
specification's reference ``kronecker_generator`` draws the row bit and
then the column bit given it; one uniform draw per bit that picks the
quadrant has the same distribution at half the draws.  Then the vertex
labels are permuted and the edge list shuffled.  Self-loops and duplicate
edges stay, as the generator emits them.  Kernel 1 builds an undirected
graph, so :func:`graph500_graph` stores every generated edge in both
directions.

Every random draw comes from ``numpy.random.default_rng`` seeded with
``[seed, stream]``, so the graph depends only on the seed and the
parameters, and drawing search keys never moves the graph.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

GRAPH_STREAM = 0
ROOTS_STREAM = 1


class Graph(NamedTuple):
    """A generated graph: its directed edge list as int64 arrays."""
    num_vertices: int
    src: np.ndarray
    dst: np.ndarray


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """The generator of one independent random stream of a run."""
    return np.random.default_rng([int(seed), int(stream)])


def kronecker_edges(scale: int, edge_factor: int, initiator, seed: int):
    """The specification's edge list: ``edge_factor * 2**scale`` directed
    pairs ``(start, end)`` as int64 arrays, labels permuted and the list
    shuffled."""
    a, b, c, d = (float(x) for x in initiator)
    if abs(a + b + c + d - 1.0) > 1e-9:
        raise ValueError(f"initiator {initiator} does not sum to 1")
    rng = rng_for(seed, GRAPH_STREAM)
    n = 1 << scale
    m = edge_factor * n
    lab = np.int32 if scale < 31 else np.int64
    a_, ab, abc = (np.float32(x) for x in (a, a + b, a + b + c))
    start = np.zeros(m, lab)
    end = np.zeros(m, lab)
    for _ in range(scale):
        u = rng.random(m, dtype=np.float32)
        row = u >= ab
        start <<= 1
        start |= row
        end <<= 1
        end |= ((u >= a_) & ~row) | (u >= abc)
    perm = rng.permutation(n).astype(lab)
    start, end = perm[start], perm[end]
    order = rng.permutation(m)
    return start[order].astype(np.int64), end[order].astype(np.int64)


def graph500_graph(graph: dict, seed: int) -> Graph:
    """The :class:`Graph` of a configuration's graph keys
    (``generator``, ``scale``, ``edge_factor``, ``initiator``,
    ``symmetrize``): the Kronecker edge list, in both directions when
    ``symmetrize`` is set (Graph500 kernel 1)."""
    if graph["generator"] != "graph500_kronecker":
        raise ValueError(f"unknown generator {graph['generator']!r}")
    scale = int(graph["scale"])
    start, end = kronecker_edges(scale, int(graph["edge_factor"]),
                                 graph["initiator"], seed)
    if graph["symmetrize"]:
        return Graph(1 << scale, np.concatenate([start, end]),
                     np.concatenate([end, start]))
    return Graph(1 << scale, start, end)


def search_keys(num_vertices: int, src, dst, count: int, seed: int):
    """``count`` distinct BFS roots drawn from the seed among vertices with
    an edge to some other vertex (Graph500: degree not counting
    self-loops at least one), in the order drawn."""
    loop = src == dst
    deg = np.bincount(src[~loop], minlength=num_vertices)
    cands = np.flatnonzero(deg > 0)
    if cands.size < count:
        raise ValueError(f"only {cands.size} vertices have an edge; "
                         f"{count} search keys asked for")
    rng = rng_for(seed, ROOTS_STREAM)
    return [int(r) for r in rng.choice(cands, count, replace=False)]
