"""The reduction of the program's own spans and scopes (``program_trace``):
exactly on a hand-made trace with a prefetch thread, nested ``dfo.*``
spans and JAX's compile events, and on the small trace recorded on one v5e
chip against ``trace_reduce``, whose figures it must split without
changing; then the seven readers built on it."""
import os
import tempfile

import pytest
from jax.profiler import ProfileData

import bench.catalog as catalog
import bench.program_trace as pt
import bench.trace_reduce as tr
from bench.tests.test_bench_harness import fake_window
from bench.tests.test_bench_trace_reduce import RECORDED, _events

READERS = ("stream_wait_ms", "chunk_read_ms", "spill_io_ms",
           "decode_host_ms", "combine_host_ms", "control_host_ms",
           "combine_gather_device_ms")


def _meta(names):
    """Event metadata for ``(name, tf_op)`` pairs; stat 9 is ``tf_op``."""
    return "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" '
        + (f'stats {{ metadata_id: 9 str_value: "{op}" }} ' if op else "")
        + "} }\n" for i, (name, op) in enumerate(names, 1))


# times in ns.  The benchmark's spans and device ops of the hand-made trace
# in test_bench_trace_reduce.py (window [0, 100], job [10, 90], ProcessEdges
# [20, 50], ProcessVertices [60, 70]; busy [25, 45], [62, 66], [95, 100]),
# and the program's spans: on the main thread generate [20, 28] holding a
# spill read [21, 24], the stream wait [28, 40], the combine [40, 47]; in
# ProcessVertices a spill read [61, 63], JAX's lowering [63, 64] and
# compilation [64, 68]; in the job a spill write [80, 85] holding another
# [81, 83].  On a second host line the prefetch thread reads [22, 30],
# decodes [30, 38] and waits to put [38, 41].
HAND_MADE = f"""
planes {{
  name: "/device:TPU:0"
  lines {{ name: "XLA Modules" timestamp_ns: 0
{_events([(1, 22, 48), (2, 61, 67), (3, 94, 106)])} }}
  lines {{ name: "XLA Ops" timestamp_ns: 0
{_events([(4, 25, 35), (5, 30, 45), (6, 62, 66), (7, 95, 105)])} }}
{_meta([("jit_step(11)", ""), ("jit_varint_decode(12)", ""),
        ("jit_x(13)", ""),
        ("%fusion.1 = f32[8] fusion(f32[8] %a)",
         "jit(step)/vmap(combine)/gather:"),
        ("%fusion.2 = f32[8] fusion(f32[8] %b)", "jit(step)/generate/mul:"),
        ("%varint_decode.1 = s32[512] custom-call(s32[512] %c)",
         "jit(varint_decode)/pallas_call:"),
        ("%copy.1 = f32[8] copy(f32[8] %d)", "")])}
  stat_metadata {{ key: 9 value {{ id: 9 name: "tf_op" }} }}
}}
planes {{
  name: "/host:CPU"
  lines {{ name: "python3" timestamp_ns: 0
{_events([(1, 0, 100), (2, 10, 90), (3, 20, 50), (4, 60, 70), (5, 71, 72),
          (6, 20, 28), (7, 21, 24), (8, 28, 40), (9, 40, 47), (7, 61, 63),
          (10, 63, 64), (11, 64, 68), (12, 80, 85), (12, 81, 83)])} }}
  lines {{ name: "python3" timestamp_ns: 0
{_events([(13, 22, 30), (14, 30, 38), (15, 38, 41)])} }}
{_meta([(n, "") for n in (
    "bench.window", "bench.job", "bench.process_edges",
    "bench.process_vertices", "PjitFunction(f)", "dfo.ooc.generate",
    "dfo.spill.read", "dfo.ooc.stream_wait", "dfo.ooc.combine",
    "lower_sharding_computation", "backend_compile_and_load",
    "dfo.spill.write", "dfo.chunk.read", "dfo.chunk.decode",
    "dfo.chunk.put_wait")])}
}}
"""


def _ns(table):
    return {k: pytest.approx(v * 1e-9) for k, v in table.items()}


@pytest.fixture(scope="module")
def hand_made():
    xspace = ProfileData.text_proto_to_serialized_xspace(HAND_MADE)
    return xspace, ProfileData.from_serialized_xspace(xspace)


def test_hand_made_trace_exactly(hand_made):
    xspace, _ = hand_made
    s = pt.reduce_xspace(xspace)
    assert s.idle_by_span == _ns({
        "window": 15, "job": 35, "job/spill.write": 5,
        "process_edges": 3, "process_edges/ooc.generate": 2,
        "process_edges/spill.read": 3, "process_edges/ooc.stream_wait": 0,
        "process_edges/ooc.combine": 2, "process_vertices": 3,
        "process_vertices/spill.read": 1, "process_vertices/jax.lower": 0,
        "process_vertices/jax.compile": 2})
    assert s.busy_by_span == _ns({
        "window": 5, "job": 0, "job/spill.write": 0, "process_edges": 0,
        "process_edges/ooc.generate": 3, "process_edges/spill.read": 0,
        "process_edges/ooc.stream_wait": 12, "process_edges/ooc.combine": 5,
        "process_vertices": 0, "process_vertices/spill.read": 1,
        "process_vertices/jax.lower": 1, "process_vertices/jax.compile": 2})
    # a spill write inside a spill write counts once
    assert s.span_s == _ns({
        "ooc.generate": 8, "spill.read": 5, "ooc.stream_wait": 12,
        "ooc.combine": 7, "spill.write": 5, "chunk.read": 8,
        "chunk.decode": 8, "chunk.put_wait": 3})
    assert s.span_s_by_label == _ns({
        "process_edges/ooc.generate": 8, "process_edges/spill.read": 3,
        "process_edges/ooc.stream_wait": 12, "process_edges/ooc.combine": 7,
        "process_vertices/spill.read": 2, "job/spill.write": 5,
        "process_edges/chunk.read": 8, "process_edges/chunk.decode": 8,
        "process_edges/chunk.put_wait": 3})
    assert s.self_s_by_label == _ns({
        "process_edges/ooc.generate": 5, "process_edges/spill.read": 3,
        "process_edges/ooc.stream_wait": 12, "process_edges/ooc.combine": 7,
        "process_vertices/spill.read": 2, "job/spill.write": 5,
        "process_edges/chunk.read": 8, "process_edges/chunk.decode": 8,
        "process_edges/chunk.put_wait": 3})


def test_hand_made_split_sums_to_the_bench_labels(hand_made):
    xspace, profile = hand_made
    old = tr.reduce_profile(profile)
    new = pt.reduce_xspace(xspace)
    for table, by_label in ((new.idle_by_span, old.idle_by_label),
                            (new.busy_by_span, old.busy_by_label)):
        sums = {}
        for key, v in table.items():
            label = key.split("/", 1)[0]
            sums[label] = sums.get(label, 0.0) + v
        assert sums == {k: pytest.approx(v) for k, v in by_label.items()}


def test_hand_made_device_scopes(hand_made):
    xspace, _ = hand_made
    s = pt.reduce_xspace(xspace)
    (starts, ends, paths), = s.ops
    assert paths == ["combine", "generate", "", None]
    assert ends[-1] == 100                      # clipped to the window
    assert s.scope_s == _ns({"combine": 10, "generate": 15, "": 4})
    assert s.scope_busy_s("combine") == pytest.approx(10e-9)
    assert s.scope_busy_s("apply") == 0.0


@pytest.mark.parametrize("op_name, path", [
    ("jit(step)/vmap(combine)/gather:", "combine"),
    ("jit(step)/vmap()/mul:", ""),
    ("jit(step)/vmap(dispatch)/while/body/add:", "dispatch/while/body"),
    ("jit(step)/combine/pallas_call:", "combine"),
    ("jit(step)/transpose(jvp(apply))/mul:", "apply"),
    ("jit(step)/jit(_where)/select_n:", "jit(_where)"),
    ("jit(maximum)/max:", "")])
def test_scope_path(op_name, path):
    assert pt.scope_path(op_name) == path


def test_sweep_labels_equal_the_quadratic_scan_on_the_recorded_trace():
    profile = ProfileData.from_file(RECORDED)
    spans = [(ev.name[len(tr.SPAN_PREFIX):], ev.start_ns,
              ev.start_ns + ev.duration_ns)
             for plane in profile.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(tr.SPAN_PREFIX)]
    (w0, w1), = [(a, b) for n, a, b in spans if n == tr.WINDOW]
    inner = [s for s in spans if s[0] != tr.WINDOW]
    old_a, old_b, old_labels = tr._label_segments(inner, w0, w1)
    cuts = pt._cuts(inner, w0, w1)
    new_labels = pt._sweep(inner, cuts, tr.WINDOW)
    assert cuts[:-1] == list(old_a) and cuts[1:] == list(old_b)
    assert new_labels == old_labels
    assert len(set(old_labels)) > 2


def test_recorded_trace_split_keeps_every_bench_figure():
    old = tr.reduce_trace(RECORDED)
    new = pt.reduce_trace(RECORDED)
    assert (new.window[1] - new.window[0]) * 1e-9 == old.window_s
    for table, by_label in ((new.idle_by_span, old.idle_by_label),
                            (new.busy_by_span, old.busy_by_label)):
        sums = {}
        for key, v in table.items():
            label = key.split("/", 1)[0]
            sums[label] = sums.get(label, 0.0) + v
        assert sums.keys() == by_label.keys()
        for label, v in by_label.items():
            assert sums[label] == pytest.approx(v, rel=1e-9, abs=1e-12)
    # recorded before the program had spans: JAX's lowering is the only
    # program label, in ProcessVertices' re-jit
    assert {k.split("/", 1)[1] for k in new.idle_by_span if "/" in k} == \
        {"jax.lower"}
    assert new.span_s == {}


def test_recorded_trace_carries_each_ops_name_stack():
    """On a v5e trace an XLA op carries its name stack in the ``tf_op``
    stat of its event metadata; ops the compiler made with no source op
    (copies, layout fusions) carry none.  Their times are the device
    clock and whole nanoseconds the ``ProfileData`` events give."""
    new = pt.reduce_trace(RECORDED)
    (starts, ends, paths), = new.ops
    named = sum(b - a for a, b, p in zip(starts, ends, paths)
                if p is not None)
    assert named > 0.5 * sum(b - a for a, b in zip(starts, ends))
    assert "jit(_take)" in paths and None in paths
    profile = ProfileData.from_file(RECORDED)
    w0, w1 = new.window
    plain = sorted((max(ev.start_ns, w0), min(ev.end_ns, w1))
                   for plane in profile.planes
                   if plane.name == "/device:TPU:0"
                   for line in plane.lines if line.name == tr.OPS_LINE
                   for ev in line.events
                   if ev.end_ns > w0 and ev.start_ns < w1)
    assert plain == sorted(zip(starts, ends))


def test_a_window_finds_its_own_trace(tmp_path, monkeypatch, hand_made):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    paths = []
    for run, data in (("bench-x", open(RECORDED, "rb").read()),
                      ("bench-y", hand_made[0])):
        d = tmp_path / run / "trace" / "plugins" / "profile" / "r1"
        d.mkdir(parents=True)
        (d / "h.xplane.pb").write_bytes(data)
        paths.append(str(d / "h.xplane.pb"))
    os.utime(paths[0], (1e9, 1e9))      # another run's trace is newer
    w = fake_window("local-pagerank")
    w.trace = tr.reduce_trace(RECORDED)
    found = pt.for_window(w)
    assert found is not None
    assert found.window == pt.reduce_trace(paths[0]).window
    assert pt.for_window(w) is found            # reduced once
    other = fake_window("local-pagerank")
    other.trace.window_s = w.trace.window_s + 1.0
    assert pt.for_window(other) is None
    other.trace = None
    assert pt.for_window(other) is None


def _program_window(cell):
    """The harness tests' fake window (2 ProcessEdges spans) with a
    program trace of round numbers."""
    w = fake_window(cell)
    w.program_trace = pt.ProgramTrace(
        window=(0.0, 10e9), devices=1,
        idle_by_span={}, busy_by_span={},
        span_s={},
        span_s_by_label={
            "process_edges/ooc.stream_wait": 0.4,
            "process_edges/chunk.read": 0.2,
            "process_edges/spill.read": 0.06,
            "process_edges/spill.write": 0.04,
            "process_vertices/spill.read": 5.0,
            "process_edges/chunk.decode": 1.0,
            "process_edges/ooc.combine": 0.8},
        self_s_by_label={
            "process_edges/ooc.generate": 0.1,
            "process_edges/ooc.filter": 0.2,
            "process_edges/ooc.dispatch": 0.3,
            "process_edges/ooc.apply": 0.4,
            "process_edges/ooc.combine": 9.0},
        ops=[([0.0, 1e8, 5e8], [2e8, 3e8, 6e8],
              ["combine", "combine/while", "apply"])])
    return w


def test_program_readers_arithmetic():
    w = _program_window("ooc-bfs")
    read = lambda name: catalog.layer_reader(name)(w)   # noqa: E731
    assert read("stream_wait_ms") == pytest.approx(200.0)   # 0.4 s / 2
    assert read("chunk_read_ms") == pytest.approx(100.0)
    assert read("spill_io_ms") == pytest.approx(50.0)      # PE's only
    assert read("decode_host_ms") == pytest.approx(500.0)
    assert read("combine_host_ms") == pytest.approx(400.0)
    assert read("control_host_ms") == pytest.approx(500.0)
    # [0, 0.2] and [0.1, 0.3] s under combine: 0.3 s, over 2 calls
    assert read("combine_gather_device_ms") == pytest.approx(150.0)


def test_program_readers_find_nothing_to_read():
    # the parent's traced run: a trace with no program spans or scopes
    w = fake_window("ooc-bfs")
    w.program_trace = pt.reduce_trace(RECORDED)
    for name in READERS:
        assert catalog.layer_reader(name)(w) is None, name
    # an untraced window, and one whose trace is nowhere to be found
    w.trace = None
    del w.program_trace
    for name in READERS:
        assert catalog.layer_reader(name)(w) is None, name
    w = fake_window("ooc-bfs")
    w.trace.window_s = -1.0
    for name in READERS:
        assert catalog.layer_reader(name)(w) is None, name


def test_new_metrics_are_entries_with_readers():
    bench = catalog.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = entries[name]
        assert m["moves"] == "evps" and m["better"] == "lower"
        assert os.path.exists(os.path.join(
            catalog.BENCH_DIR, "layer_metrics", f"{name}.py"))
    assert entries["combine_gather_device_ms"]["workloads"] == [
        "local-pagerank", "local-bfs"]
