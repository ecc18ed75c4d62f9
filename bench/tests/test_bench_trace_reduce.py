"""The reduction from a profiler trace to busy time, op time and labelled
idle gaps: exactly on a hand-made trace, and on a small trace recorded on
one v5e chip against a plain recomputation from the same events."""
import os

import pytest
from jax.profiler import ProfileData

import bench.trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small_trace.xplane.pb")


def _events(spec):
    return "".join(f"events {{ metadata_id: {m} offset_ps: {a * 1000} "
                   f"duration_ps: {(b - a) * 1000} }}\n" for m, a, b in spec)


def _meta(names):
    return "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{n}" }} }}\n' for i, n in enumerate(names, 1))


# times in ns: window [0, 100]; job [10, 90] holding process_edges
# [20, 50] and process_vertices [60, 70]; device ops [25, 35] and [30, 45]
# overlapping in jit_step, [62, 66] in the decode program, and [95, 105]
# running past the window's end.
HAND_MADE = f"""
planes {{
  name: "/device:TPU:0"
  lines {{ name: "XLA Modules" timestamp_ns: 0
{_events([(1, 22, 48), (2, 61, 67), (3, 94, 106)])} }}
  lines {{ name: "XLA Ops" timestamp_ns: 0
{_events([(4, 25, 35), (5, 30, 45), (6, 62, 66), (7, 95, 105)])} }}
{_meta(["jit_step(11)", "jit_varint_decode(12)", "jit_x(13)",
        "%fusion.1 = f32[8] fusion(f32[8] %a)",
        "%fusion.2 = f32[8] fusion(f32[8] %b)",
        "%varint_decode.1 = s32[512] custom-call(s32[512] %c)",
        "%copy.1 = f32[8] copy(f32[8] %d)"])}
}}
planes {{
  name: "/host:CPU"
  lines {{ name: "python3" timestamp_ns: 0
{_events([(1, 0, 100), (2, 10, 90), (3, 20, 50), (4, 60, 70), (5, 71, 72)])} }}
{_meta(["bench.window", "bench.job", "bench.process_edges",
        "bench.process_vertices", "PjitFunction(f)"])}
}}
"""


def test_hand_made_trace_exactly():
    s = tr.reduce_profile(ProfileData.from_text_proto(HAND_MADE))
    ns = pytest.approx
    assert s.devices == 1
    assert s.window_s == ns(100e-9)
    assert s.busy_s == ns(29e-9)              # [25, 45] + [62, 66] + [95, 100]
    assert s.idle_share == ns(0.71)
    assert s.idle_by_label == {"window": ns(15e-9), "job": ns(40e-9),
                               "process_edges": ns(10e-9),
                               "process_vertices": ns(6e-9)}
    assert s.busy_by_label == {"window": ns(5e-9), "job": ns(0.0),
                               "process_edges": ns(20e-9),
                               "process_vertices": ns(4e-9)}
    assert s.op_s == {"jit_step/fusion.1": ns(10e-9),
                      "jit_step/fusion.2": ns(15e-9),
                      "jit_varint_decode/varint_decode.1": ns(4e-9),
                      "jit_x/copy.1": ns(10e-9)}
    assert s.module_s == {"jit_step": ns(26e-9),
                          "jit_varint_decode": ns(6e-9), "jit_x": ns(12e-9)}
    assert tr.top(s.op_s, 2) == [["jit_step/fusion.2", ns(15e-9)],
                                 ["jit_step/fusion.1", ns(10e-9)]]


def test_a_trace_without_a_window_or_device_is_refused():
    no_window = HAND_MADE.replace('"bench.window"', '"bench.other"')
    with pytest.raises(ValueError, match="window"):
        tr.reduce_profile(ProfileData.from_text_proto(no_window))
    no_device = HAND_MADE.replace('"/device:TPU:0"', '"/device:CUSTOM:0"')
    with pytest.raises(ValueError, match="device"):
        tr.reduce_profile(ProfileData.from_text_proto(no_device))


def _plain(path):
    """Busy and idle per label from the recorded events, by plain loops."""
    prof = ProfileData.from_file(path)
    spans, ops = [], []
    for plane in prof.planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if plane.name.startswith("/host:") and \
                        ev.name.startswith("bench."):
                    spans.append((ev.name[6:],) + iv)
                elif plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append(iv)
    (w0, w1), = [(a, b) for n, a, b in spans if n == "window"]
    merged = []
    for a, b in sorted((max(a, w0), min(b, w1)) for a, b in ops
                       if b > w0 and a < w1):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    gaps, t = [], w0
    for a, b in merged:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    inner = [s for s in spans if s[0] != "window"]
    idle = {}
    for g0, g1 in gaps:
        cuts = sorted({g0, g1} | {x for _, a, b in inner for x in (a, b)
                                  if g0 < x < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            open_ = [s for s in inner if s[1] <= mid < s[2]]
            label = max(open_, key=lambda s: s[1])[0] if open_ else "window"
            idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9
    busy = sum(b - a for a, b in merged) * 1e-9
    return (w1 - w0) * 1e-9, busy, idle


def test_recorded_trace_matches_a_plain_recomputation():
    s = tr.reduce_trace(RECORDED)
    window, busy, idle = _plain(RECORDED)
    assert s.window_s == pytest.approx(window, rel=1e-12)
    assert s.busy_s == pytest.approx(busy, rel=1e-9)
    assert set(s.idle_by_label) <= {"window", "job", "process_edges",
                                    "process_vertices"}
    for label, secs in idle.items():
        assert s.idle_by_label[label] == pytest.approx(secs, rel=1e-6,
                                                       abs=1e-9)
    assert sum(s.idle_by_label.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    assert sum(s.busy_by_label.values()) == pytest.approx(s.busy_s,
                                                          rel=1e-9)
    # the LOCAL ProcessEdges step is the busiest program of the trace
    (prog, _), = tr.top(s.module_s, 1)
    assert prog == "jit_step"
    assert s.busy_by_label["process_edges"] > 0.5 * s.busy_s


def test_a_profiler_log_directory_is_searched(tmp_path):
    run = tmp_path / "plugins" / "profile" / "2026_10_17_00_00_00"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(open(RECORDED, "rb").read())
    assert tr.find_xspace(str(tmp_path)) == str(run / "host.xplane.pb")
    assert tr.reduce_trace(str(tmp_path)).window_s == \
        tr.reduce_trace(RECORDED).window_s
    with pytest.raises(FileNotFoundError):
        tr.find_xspace(str(run / "empty"))
