"""The comparison that decides ``correct`` can fail: the controls read
beyond their limits, and a run driven on the CPU with its timed path
broken underneath comes out not correct, for each fault a one-chip graph
cell can have.  (No cell spans chips, so there is no exchange between
chips to leave out.)"""
import jax.numpy as jnp
import numpy as np
import pytest

import bench.catalog as catalog
import bench.control as control
import bench.harness as harness

SCALE = 9
CELLS = [w["name"] for w in catalog.load_benchmark()["workloads"]]


def small_cell(name, scale=SCALE):
    cell = catalog.find_cell(name)
    cell.config = dict(cell.config, scale=scale)
    return cell


def run(cell):
    return harness.run_cell(cell, 2**31 + 77, 0.0, False, require_chip=False)


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


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    res = run(small_cell(name))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"evps", "setup_s"}


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


FAULTS = {"state_unchanged": _state_unchanged,
          "half_batch_left_out": _half_batch_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run(small_cell(name))
    assert res["correct"] is False
    assert res["failed"] >= 1
    (check, c), = [(k, v) for k, v in res["checks"].items()
                   if k != "jobs_failed"]
    assert c["value"] > c["limit"], (check, c)
