#!/usr/bin/env python3
"""The controls of the comparison that decides ``correct``: the reference
put in the program's place with one guarantee of the configuration broken
(``control`` of the traffic's algorithm module), its answers judged by the
comparison a run makes (``harness.compare``).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--jobs 8]

* PageRank: the reference computed in bfloat16, the precision below the
  float32 the configuration states.
* BFS: the reference over the edge list as generated, one direction only,
  which breaks Graph500 kernel 1's undirected graph.

Each seed prints one JSON line with ``correct`` and each number compared
beside its limit, over the first ``--jobs`` jobs of the window's list.  A
sound control comes out not correct on every seed; the exit code is 1
where one does not.  It needs no accelerator.
"""
import argparse
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT]

import bench.catalog as catalog  # noqa: E402
import bench.graph500 as g500  # noqa: E402
import bench.harness as harness  # noqa: E402


def control_result(cell, seed: int, jobs: int):
    """``(correct, checks)`` of the control in the program's place on the
    first ``jobs`` jobs of ``seed``'s window."""
    graph = g500.graph500_graph(cell.config, seed)
    alg = catalog.algorithm(cell.traffic["algorithm"])
    _, window = alg.jobs(cell.traffic, graph, seed)
    window = window[:jobs]
    answers = alg.control(cell.config, graph, window)
    records = [harness.JobRecord(job, 0.0, values)
               for job, values in zip(window, answers)]
    failed, checks = harness.compare(records, cell.traffic, graph)
    return harness.is_correct(records, failed), checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--jobs", type=int, default=8,
                    help="window jobs compared per seed")
    args = ap.parse_args(argv)
    cell = catalog.find_cell(args.workload)
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        correct, checks = control_result(cell, seed, args.jobs)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": correct, "checks": checks}), flush=True)
        rc |= correct
    return int(rc)


if __name__ == "__main__":
    sys.exit(main())
