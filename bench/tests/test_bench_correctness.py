"""The comparison that decides ``correct`` can fail: the controls read
beyond their limits, and a run driven on the CPU with its timed path
broken underneath comes out not correct, for each fault a cell can have.

A one-chip cell runs in this process, under three faults: a step that
returns its state unchanged, half of the vertices left out, an answer
altered where it is produced.  A cell that spans chips runs in a child
process that sees as many CPU devices as the cell has chips
(``--xla_force_host_platform_device_count``), so the harness builds the
SHARD_MAP executor over a mesh of them.  The child plants each fault
itself, one run after another, and has one fault more: the exchange
between chips delivers each shard only its own messages
(``remote_messages_dropped``).  Two made-up four-chip cells,
``g500-s19-local`` with four partitions under ``bfs`` and ``pagerank``,
take that path whether or not BENCHMARK.json has such a cell."""
import collections
import dataclasses
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import bench.catalog as catalog
import bench.control as control
import bench.harness as harness

SCALE = 9
SEED = 2**31 + 77
CELLS = [w["name"] for w in catalog.load_benchmark()["workloads"]]
# made-up cells across four chips: name -> (the one-chip cell of the same
# configuration and traffic, relative tolerance between their answers)
MESH_CELLS = {"mesh4-bfs": ("local-bfs", 0.0),
              "mesh4-pagerank": ("local-pagerank", 1e-5)}


def small_cell(name, scale=SCALE):
    base = MESH_CELLS[name][0] if name in MESH_CELLS else name
    cell = catalog.find_cell(base)
    cell.config = dict(cell.config, scale=scale)
    if name in MESH_CELLS:
        cell = dataclasses.replace(
            cell, name=name, chips=4,
            config=dict(cell.config, num_partitions=4))
    return cell


def run(cell, monkeypatch):
    """One run of ``cell`` on the CPU: ``(result line, seen)``, where
    ``seen`` is what the run's engine and window showed: the devices of
    the engine's mesh (0: no mesh), each answer, and the exchange's
    payload as measured and as modeled, summed over the window."""
    seen = {}
    build, compare = harness.build_system, harness.compare

    def build_system(*args):
        system = build(*args)
        mesh = system.engine.mesh
        seen["mesh_devices"] = 0 if mesh is None else int(mesh.devices.size)
        return system

    def compare_answers(records, traffic, graph):
        done = [r for r in records if r.error is None]
        seen["answers"] = [np.asarray(r.values).tolist() for r in done]
        seen["payload"] = [sum(float(r.counters.get(k, 0.0)) for r in done)
                           for k in ("measured_net_payload_elems",
                                     "net_payload_elems")]
        return compare(records, traffic, graph)

    monkeypatch.setattr(harness, "build_system", build_system)
    monkeypatch.setattr(harness, "compare", compare_answers)
    res = harness.run_cell(cell, SEED, 0.0, False, require_chip=False)
    return res, seen


def _state_unchanged(monkeypatch):
    from repro.core.engine import Engine
    orig = Engine.process_edges

    def step(self, state, *args, **kwargs):
        _, active, total, counters = orig(self, state, *args, **kwargs)
        return state, active, total, counters
    monkeypatch.setattr(Engine, "process_edges", step)


def _half_batch_left_out(monkeypatch):
    from repro.core.engine import Engine
    orig = Engine.process_edges

    def step(self, state, signal_fn, slot_fn, monoid, apply_fn,
             active=None):
        keep = (self.graph.vertex_valid if active is None
                else jnp.asarray(active)) & (self.global_id % 2 == 0)
        return orig(self, state, signal_fn, slot_fn, monoid, apply_fn,
                    keep)
    monkeypatch.setattr(Engine, "process_edges", step)


def _answer_altered(monkeypatch):
    from repro.core import algorithms
    orig = algorithms._finish

    def finish(engine, values):
        out = np.array(orig(engine, values))
        i = int(np.argmax(np.where(out < 1e30, out, -1)))
        out[i] = out[i] * 1.01 + 1
        return out
    monkeypatch.setattr(algorithms, "_finish", finish)


def _remote_messages_dropped(monkeypatch):
    import jax
    from repro.core import executor

    def own_rows_only(exchange):
        # received row q came from shard q; keep this shard's own row
        def deliver(msg_row, sendmask, *args):
            recv_msg, recv_mask, measured = exchange(msg_row, sendmask,
                                                     *args)
            own = (jnp.arange(recv_mask.shape[0])
                   == jax.lax.axis_index(args[-1]))[:, None]
            return jnp.where(own, recv_msg, 0), recv_mask & own, measured
        return deliver
    for name in ("_dense_exchange", "_compacted_exchange"):
        monkeypatch.setattr(executor, name,
                            own_rows_only(getattr(executor, name)))


FAULTS = {"state_unchanged": _state_unchanged,
          "half_batch_left_out": _half_batch_left_out,
          "answer_altered": _answer_altered,
          "remote_messages_dropped": _remote_messages_dropped}
EXCHANGE_FAULTS = {"remote_messages_dropped"}


def faults_of(name):
    spans = small_cell(name).chips > 1
    return [f for f in sorted(FAULTS) if spans or f not in EXCHANGE_FAULTS]


SPANNING = [n for n in CELLS + sorted(MESH_CELLS) if small_cell(n).chips > 1]

CHILD = """
import json, sys
import pytest
from bench.tests import test_bench_correctness as t
out = {}
for name, fault in json.loads(sys.argv[1]):
    with pytest.MonkeyPatch.context() as mp:
        if fault:
            t.FAULTS[fault](mp)
        out[f"{name}-{fault}"] = t.run(t.small_cell(name), mp)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def spanning_runs():
    """Every run of a cell that spans chips, sound and under each of its
    faults, made in one child process per number of chips."""
    cases = collections.defaultdict(list)
    for name in SPANNING:
        cases[small_cell(name).chips] += [(name, f)
                                          for f in (None, *faults_of(name))]
    out = {}
    for chips, todo in cases.items():
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " --xla_"
                              f"force_host_platform_device_count={chips}"),
                   PYTHONPATH=os.pathsep.join(
                       [catalog.CHECKOUT,
                        os.path.join(catalog.CHECKOUT, "src")]))
        proc = subprocess.run([sys.executable, "-c", CHILD,
                               json.dumps(todo)], cwd=catalog.CHECKOUT,
                              env=env, capture_output=True, text=True,
                              timeout=600)
        assert proc.returncode == 0, proc.stderr[-4000:]
        out.update(json.loads(proc.stdout.splitlines()[-1]))
    return out


def outcome(name, fault, monkeypatch, request):
    """``run`` of ``name`` with ``fault`` planted (None: sound), in this
    process for a one-chip cell and from the child for one across chips."""
    cell = small_cell(name)
    if cell.chips > 1:
        return request.getfixturevalue("spanning_runs")[f"{name}-{fault}"]
    if fault:
        FAULTS[fault](monkeypatch)
    return run(cell, monkeypatch)


@pytest.mark.parametrize("name", ["ooc-bfs", "local-pagerank"])
def test_controls_fail_their_limits(name):
    cell = small_cell(name, scale=10)
    for seed in (1, 2**31 + 3, 3_000_000_007):
        correct, checks = control.control_result(cell, seed, 4)
        assert correct is False, (seed, checks)
        (check, c), = [(k, v) for k, v in checks.items()
                       if k != "jobs_failed"]
        assert c["value"] > c["limit"], (seed, check, c)
        assert checks["jobs_failed"]["value"] >= 1


@pytest.mark.parametrize("name", CELLS + sorted(MESH_CELLS))
def test_a_sound_run_is_correct(name, monkeypatch, request):
    res, seen = outcome(name, None, monkeypatch, request)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"evps", "setup_s"}
    chips = small_cell(name).chips
    assert seen["mesh_devices"] == (chips if chips > 1 else 0)


@pytest.mark.parametrize("name", sorted(MESH_CELLS))
def test_a_cell_across_four_chips_runs_shard_map(name, monkeypatch,
                                                 request):
    res, seen = outcome(name, None, monkeypatch, request)
    assert res["correct"] and res["device"]["count"] == 4
    assert seen["mesh_devices"] == 4
    measured, modeled = seen["payload"]
    assert measured == modeled > 0
    base, rtol = MESH_CELLS[name]
    _, one_chip = run(small_cell(base), monkeypatch)
    assert one_chip["mesh_devices"] == 0
    assert len(seen["answers"]) == len(one_chip["answers"]) >= 1
    for got, want in zip(seen["answers"], one_chip["answers"]):
        np.testing.assert_allclose(got, want, rtol=rtol, atol=0)


@pytest.mark.parametrize("name,fault", [
    (n, f) for n in CELLS + sorted(MESH_CELLS) for f in faults_of(n)])
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch,
                                            request):
    res, _ = outcome(name, fault, monkeypatch, request)
    assert res["correct"] is False
    assert res["failed"] >= 1
    (check, c), = [(k, v) for k, v in res["checks"].items()
                   if k != "jobs_failed"]
    assert c["value"] > c["limit"], (check, c)
