"""CPU tests of the benchmark harness that need no chip: discovery by name,
the Graph500 generator, the copied references, the per-layer readers'
arithmetic, and the entry point's refusal to run without a TPU."""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench.catalog as catalog
import bench.graph500 as g500
import bench.harness as harness
from bench.algorithms import bfs, pagerank
from bench.spans import Spans
from bench.trace_reduce import TraceSummary

BENCH = catalog.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
ALGORITHMS = sorted({json.load(open(catalog.traffic_file(w["traffic"])))
                     ["algorithm"] for w in BENCH["workloads"]})
SCALE = 9


def small_config(name="g500-s19-ooc", scale=SCALE):
    files = {c["name"]: c["file"] for c in BENCH["configs"]}
    with open(os.path.join(catalog.CHECKOUT, files[name])) as f:
        return dict(json.load(f), scale=scale)


# -- discovery by name --------------------------------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_cell_parts_are_found_by_name(name):
    cell = catalog.find_cell(name)
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert cell.config["name"] == w["config"]
    assert catalog.algorithm(cell.traffic["algorithm"]).CHECK in \
        cell.traffic["limits"]
    assert [m.name for m in cell.end_to_end] == ["evps", "setup_s"]
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(catalog.layer_reader(m.name))


def test_benchmark_files_are_each_others():
    cfg_files = [c["file"] for c in BENCH["configs"]]
    assert len(set(cfg_files)) == len(cfg_files)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        with open(os.path.join(catalog.CHECKOUT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) <= set(cfg), "reduced keys are the file's"
        assert cfg["engine"]["verify_io"] is True


def test_a_new_cell_is_an_entry_and_files():
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "made-up-local-bfs", "config":
                               "g500-s19-local", "traffic": "bfs",
                               "chips": 1, "why": "test"})
    cell = catalog.find_cell("made-up-local-bfs", bench)
    assert cell.traffic["algorithm"] == "bfs"
    assert cell.config["engine"].get("executor", "auto") == "auto"
    # metrics that list their cells leave an unlisted cell out
    assert cell.per_layer == []


def test_a_cell_across_chips_needs_a_partition_per_chip_and_the_chips():
    cell = catalog.find_cell("local-bfs")               # num_partitions 8
    cell.config = dict(cell.config, scale=SCALE)
    with pytest.raises(catalog.CatalogError, match="num_partitions 4"):
        harness.run_cell(dataclasses.replace(cell, chips=4), 1, 0.0, False,
                         require_chip=False)
    import jax
    n = len(jax.devices()) + 1
    cell = dataclasses.replace(cell, chips=n, config=dict(
        cell.config, num_partitions=n))
    with pytest.raises(harness.NoChip):
        harness.run_cell(cell, 1, 0.0, False, require_chip=False)


def test_unknown_names_are_errors():
    with pytest.raises(catalog.CatalogError):
        catalog.find_cell("no-such-cell")
    with pytest.raises(catalog.CatalogError):
        catalog.layer_reader("no_such_metric")
    for name in ("no_such_algorithm", "../harness", "bfs.py"):
        with pytest.raises(catalog.CatalogError):
            catalog.algorithm(name)
    with pytest.raises(catalog.CatalogError):
        catalog.peaks("TPU v0 imaginary")
    assert catalog.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# -- the Graph500 generator -----------------------------------------------------

def test_generator_is_deterministic_per_seed():
    cfg = small_config()
    a = g500.graph500_graph(cfg, 2**31 + 11)
    b = g500.graph500_graph(cfg, 2**31 + 11)
    c = g500.graph500_graph(cfg, 2**31 + 12)
    assert a[0] == b[0] == 1 << SCALE
    assert np.array_equal(a[1], b[1]) and np.array_equal(a[2], b[2])
    assert not np.array_equal(a[1], c[1])
    keys = lambda g: g500.search_keys(g[0], g[1], g[2], 65, 7)
    assert keys(a) == keys(b)


def test_generator_symmetrizes_and_keeps_its_shapes():
    cfg = small_config(scale=10)
    n, src, dst = g500.graph500_graph(cfg, 5)
    m = cfg["edge_factor"] * n
    assert src.size == dst.size == 2 * m
    assert src.min() >= 0 and src.max() < n
    # both directions present: the multiset of (u, v) equals that of (v, u)
    fwd = np.sort(src * n + dst)
    rev = np.sort(dst * n + src)
    assert np.array_equal(fwd, rev)
    out_deg = np.bincount(src, minlength=n)
    in_deg = np.bincount(dst, minlength=n)
    assert np.array_equal(out_deg, in_deg)
    assert out_deg.sum() == 2 * m
    # self-loops and duplicates are kept as generated
    assert np.count_nonzero(src == dst) > 0
    assert np.unique(fwd).size < fwd.size


def test_generator_permutes_labels_and_shuffles_edges():
    cfg = small_config(scale=12)
    n, src, dst = g500.graph500_graph(cfg, 3)
    deg = np.bincount(src, minlength=n)
    # unpermuted, a Kronecker vertex's degree falls with the 1-bits of its
    # label and vertex 0 is the hub; permuted, the two are unrelated
    ones = np.array([bin(v).count("1") for v in range(n)])
    assert abs(np.corrcoef(deg, ones)[0, 1]) < 0.1
    assert int(np.argmax(deg)) != 0
    # the generated list is shuffled: its starts are not sorted
    half = src[: src.size // 2]
    assert np.count_nonzero(np.diff(half) < 0) > half.size // 3


def test_search_keys_have_edges_to_other_vertices():
    cfg = small_config()
    n, src, dst = g500.graph500_graph(cfg, 9)
    keys = g500.search_keys(n, src, dst, 65, 9)
    assert len(set(keys)) == 65
    loopless = src != dst
    assert np.isin(keys, src[loopless]).all()


def test_traffic_draws_warmup_outside_the_window():
    graph = g500.graph500_graph(small_config(), 4)
    traffic = json.load(open(catalog.traffic_file("bfs")))
    warm, window = bfs.jobs(traffic, graph, 4)
    assert len(window) == traffic["search_keys"]
    assert {j.root for j in warm}.isdisjoint({j.root for j in window})
    traffic = json.load(open(catalog.traffic_file("pagerank")))
    warm, window = pagerank.jobs(traffic, graph, 4)
    assert window[0].iterations == 5 and window[0].damping == 0.85
    assert warm[0].iterations == traffic["warmup_iterations"] < 5


@pytest.mark.parametrize("name", ALGORITHMS)
def test_algorithm_modules_have_the_interface(name):
    alg = catalog.algorithm(name)
    graph = g500.graph500_graph(small_config(), 8)
    traffic = next(t for t in map(lambda w: json.load(open(
        catalog.traffic_file(w["traffic"]))), BENCH["workloads"])
        if t["algorithm"] == name)
    warm, window = alg.jobs(traffic, graph, 8)
    assert warm and window
    ref = alg.Reference(graph)
    want = ref.answer(window[0])
    assert alg.gap(want, want) == 0
    (control,) = alg.control(small_config(), graph, window[:1])
    assert alg.gap(control, want) > traffic["limits"][alg.CHECK]


# -- the copied references -------------------------------------------------------

def test_references_equal_the_programs():
    from repro.core import algorithms
    graph = g500.graph500_graph(small_config(), 21)
    job = pagerank.Job(5, 0.85)
    assert np.array_equal(pagerank.Reference(graph).answer(job),
                          algorithms.ref_pagerank(*graph, 5, 0.85))
    ref = bfs.Reference(graph)
    for r in g500.search_keys(*graph, 6, 21):
        assert np.array_equal(ref.answer(bfs.Job(r)),
                              algorithms.ref_bfs(*graph, r))


def test_comparison_numbers():
    ref = np.array([0.5, 0.25, 0.25])
    assert pagerank.gap(ref * (1 + 1e-3), ref) == pytest.approx(1e-3)
    lv = np.array([0, 1, 2, bfs.UNREACHED], np.float32)
    bad = lv.copy()
    bad[3] = 3
    assert bfs.gap(lv, lv) == 0
    assert bfs.gap(bad, lv) == 1


# -- per-layer readers ---------------------------------------------------------

def fake_window(cell_name):
    cell = catalog.find_cell(cell_name)
    spans = Spans(block=False)
    spans.records = [("job", 0.0, 10.0), ("process_edges", 1.0, 3.0),
                     ("process_vertices", 3.0, 4.0),
                     ("process_edges", 5.0, 9.0)]
    counters = {"measured_edge_read_bytes": 600.0,
                "measured_vertex_read_bytes": 300.0,
                "measured_vertex_write_bytes": 100.0,
                "measured_chunks_device_decoded": 30.0,
                "measured_chunks_read": 40.0}
    job = harness.JobRecord(None, 10.0, None, 2, counters)
    trace = TraceSummary(window_s=10.0, busy_s=2.5, devices=1,
                         op_s={"jit_step/fusion": 2.0},
                         module_s={"jit_varint_decode": 0.5,
                                   "jit_step": 1.5},
                         busy_by_label={"process_edges": 2.0, "job": 0.5},
                         idle_by_label={"process_edges": 4.0, "job": 3.5})
    return harness.Window(cell, [job], 10.0,
                          {"compiles": 0, "traces": 5, "cache_hits": 5,
                           "compile_s": 0.1}, spans, trace,
                          memory_peak_bytes=1234)


def test_layer_readers_arithmetic():
    w = fake_window("ooc-bfs")
    read = lambda name: catalog.layer_reader(name)(w)
    assert read("pe_ms") == pytest.approx(3000.0)        # (2 + 4) s / 2
    assert read("driver_self_ms") == pytest.approx(1500.0)  # (10-6-1) / 2
    assert read("window_compiles") == 0
    assert read("window_traces") == 5
    assert read("disk_bytes_per_pe") == pytest.approx(500.0)
    assert read("device_decode_share") == pytest.approx(75.0)
    assert read("decode_kernel_ms") == pytest.approx(250.0)
    assert read("step_device_ms") == pytest.approx(1000.0)
    assert read("device_idle_share") == pytest.approx(75.0)
    assert read("hbm_peak_bytes") == 1234


def test_layer_readers_find_nothing_to_read():
    w = fake_window("local-pagerank")
    w.jobs[0].counters = {}
    w.trace = None
    w.spans = None
    for name in ("pe_ms", "driver_self_ms", "disk_bytes_per_pe",
                 "device_decode_share", "decode_kernel_ms",
                 "step_device_ms", "device_idle_share"):
        assert catalog.layer_reader(name)(w) is None, name
    assert harness.layer_metrics(w.cell, w).keys() == {
        "window_compiles", "window_traces", "hbm_peak_bytes"}


def test_evps_counts_vertices_plus_undirected_edges():
    cell = catalog.find_cell("local-pagerank")
    w = fake_window("local-pagerank")
    out = harness.end_to_end_metrics(cell, w, 50.0)
    per_job = 2**19 + 16 * 2**19
    assert out["evps"] == {"value": per_job / 10.0, "unit": "EVPS"}
    assert out["setup_s"] == {"value": 50.0, "unit": "s"}


# -- the entry point -----------------------------------------------------------

@pytest.mark.parametrize("argv", [
    ["--workload", "ooc-bfs", "--seed", "3000000019", "--seconds", "1",
     "--trace", "0"],
    ["--workload", "local-pagerank", "--seed", "1", "--seconds", "1",
     "--trace", "1"]])
def test_run_exits_nonzero_without_a_tpu(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(catalog.BENCH_DIR, "run.py"), *argv],
        cwd=catalog.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
