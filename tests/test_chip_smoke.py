"""chip_smoke.py's phases at RMAT scale 9 on the CPU (Pallas kernels
interpreted), with the same comparisons the script makes on the chip, plus
the compile-cache helper every entry point calls."""
import json
import os
import subprocess
import sys
import warnings

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

SCALE = 9


@pytest.fixture(scope="module")
def prob():
    return cs.build_problem(SCALE, 3, 8)


@pytest.fixture(scope="module")
def base(prob):
    return cs.phase_local(prob, cs.reference(prob))


def test_roots_have_out_edges(prob):
    assert len(prob.roots) == cs.NUM_ROOTS
    assert (prob.graph.out_degrees()[prob.roots] > 0).all()


def test_local_matches_reference(prob, base):
    ref = cs.reference(prob)
    assert cs.pr_rel_err(base.pagerank, ref.pagerank) <= cs.PR_RTOL
    assert all((a == b).all() for a, b in zip(base.bfs, ref.bfs))
    assert len(base.counters) == 1 + cs.NUM_ROOTS


def test_ooc_phase(prob, base, tmp_path):
    on = cs.phase_ooc(prob, base, str(tmp_path), device_decode=True)
    assert cs.device_decoded(on) > 0


def test_dist_ooc_phase(prob, base, tmp_path):
    on = cs.phase_dist_ooc(prob, base, str(tmp_path), device_decode=True)
    assert cs.device_decoded(on) > 0


def test_block_phase_without_fallback(prob, tmp_path):
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=cs.SLOT_FALLBACK)
        cs.phase_block(prob, str(tmp_path), device_decode=True)


def test_checks_catch_a_wrong_answer(prob, base):
    bad = cs.Result(base.pagerank * (1 + 10 * cs.PR_RTOL), base.bfs,
                    base.counters, base.iterations)
    with pytest.raises(cs.CheckFailed, match="PageRank"):
        cs.check_values("probe", bad, base)
    lv = base.bfs[0].copy()
    lv[prob.roots[0]] = 1.0
    bad = cs.Result(base.pagerank, [lv] + base.bfs[1:], base.counters,
                    base.iterations)
    with pytest.raises(cs.CheckFailed, match="BFS"):
        cs.check_values("probe", bad, base)


def test_mesh_phase_on_forced_host_devices():
    code = (
        "import os, sys\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import jax, chip_smoke as cs\n"
        f"cs.phase_mesh(cs.build_problem({SCALE}, 3, 4), jax.devices()[:4])\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-3000:]
    assert ("payload audit held; graph and vertex state one partition per "
            "device on 4 devices") in r.stdout


def test_main_refuses_a_host_without_tpu(capsys):
    assert cs.main([]) == 1
    out = capsys.readouterr()
    assert "no TPU" in out.err and '"ok"' not in out.out


_CACHE_CODE = (
    "import sys; sys.path.insert(0, sys.argv[1])\n"
    "import json, jax\n"
    "from repro.utils import enable_compile_cache\n"
    "got = enable_compile_cache()\n"
    "print(json.dumps([got, jax.config.jax_compilation_cache_dir]))\n")


def _cache_dir_in_child(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_CODE, os.path.join(REPO, "src")],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_leaves_a_set_env_var_alone(tmp_path):
    want = str(tmp_path / "elsewhere")
    assert _cache_dir_in_child(want) == [want, want]


def test_compile_cache_default_is_one_path_in_the_checkout():
    first = _cache_dir_in_child(None)
    second = _cache_dir_in_child(None)
    assert first == second
    assert first[0] == first[1] == os.path.join(REPO, ".jax_cache")
