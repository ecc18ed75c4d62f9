"""``decode_chunks_per_call``: the program's device-decode counters read
as chunks per decode call, and nothing where the program lacks the
call counter."""
import pytest

from bench import catalog
from bench.tests.test_bench_harness import fake_window


def test_decode_chunks_per_call_reads_the_counters():
    w = fake_window("ooc-pagerank")
    read = catalog.layer_reader("decode_chunks_per_call")
    # a program without the call counter (the parent of the batched decode)
    assert read(w) is None
    w.jobs[0].counters["measured_device_decode_calls"] = 4.0
    assert read(w) == pytest.approx(7.5)          # 30 chunks / 4 calls
    # the host decode: no call, no ratio
    w.jobs[0].counters["measured_device_decode_calls"] = 0.0
    assert read(w) is None


def test_decode_chunks_per_call_is_reported_in_the_ooc_cells():
    for name in ("ooc-bfs", "ooc-pagerank"):
        cell = catalog.find_cell(name)
        assert "decode_chunks_per_call" in [m.name for m in cell.per_layer]
    cell = catalog.find_cell("local-pagerank")
    assert "decode_chunks_per_call" not in [m.name for m in cell.per_layer]
