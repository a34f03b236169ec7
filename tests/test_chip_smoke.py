"""``chip_smoke.py``: the CPU rehearsal of the chip run, and the exits
the chip contract fixes.

The driver runs ``python3 chip_smoke.py`` on the machine with the chip;
what can be held on a CPU host is that the same phases pass at toy size
under ``--rehearsal`` (kernels interpreted, result says ``cpu``), that
the script refuses to run without an accelerator — the sandbox exports
``JAX_PLATFORMS=cpu``, so that variable cannot be what turns the
rehearsal on — and that it fails alone, without the package.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run(args, cwd=REPO, script=SMOKE, env_extra=None, timeout=900):
    env = {**os.environ, **(env_extra or {})}
    return subprocess.run([sys.executable, str(script), *args],
                          cwd=str(cwd), env=env, capture_output=True,
                          text=True, timeout=timeout)


def test_rehearsal_passes_every_phase_at_toy_size(tmp_path):
    cache = tmp_path / "cache"
    proc = _run(["--rehearsal"],
                env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    last = proc.stdout.strip().splitlines()[-1]
    # the result line holds exactly the contract's keys, in its order:
    # the driver refuses anything more (it refused "mesh" and
    # "compile_s" here once)
    assert last == json.dumps(
        {"ok": True,
         "device": {"platform": "cpu", "kind": "cpu", "count": 8}})
    # the suite's eight virtual CPU devices: two client columns, the
    # two stages chained on each (heavy stages never pipeline on CPU)
    assert "summary: mesh [2, 1]" in proc.stdout
    for phase in ("device", "round", "kernels", "cache"):
        assert f"== phase {phase}: ok" in proc.stdout
    assert "'interpret': True" in proc.stdout
    assert "'compilations_round1': 0" in proc.stdout
    assert "'fedavg_all_reduce': True" in proc.stdout
    # the cache went where the environment said, and nowhere else
    assert f"compile cache: {cache}" in proc.stdout
    assert any(cache.iterdir())


def test_without_an_accelerator_it_fails_and_prints_no_result():
    """Plain ``python3 chip_smoke.py`` on a host whose jax backend is
    the CPU (this one, where JAX_PLATFORMS=cpu is exported) exits
    non-zero before any phase and prints no result line."""
    proc = _run([])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 'tpu'" in proc.stderr


def test_alone_without_the_package_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearsal"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "split_learning_tpu" in proc.stderr


def test_a_failed_phase_names_itself_and_the_rest_still_run(tmp_path):
    """A phase that raises makes the exit code non-zero and is named;
    the later phases still run, and no result line is printed."""
    driver = tmp_path / "drive.py"
    driver.write_text(
        "import sys\n"
        f"sys.path.insert(0, {str(REPO)!r})\n"
        "import chip_smoke\n"
        "def boom(*a):\n"
        "    raise AssertionError('round 1 not ok')\n"
        "chip_smoke.phase_round = boom\n"
        "chip_smoke.phase_kernels = lambda sizes: {'skipped': 'stub'}\n"
        "sys.exit(chip_smoke.main(['--rehearsal']))\n")
    proc = _run([], script=driver,
                env_extra={"JAX_COMPILATION_CACHE_DIR":
                           str(tmp_path / "cache")})
    assert proc.returncode == 1
    assert "== phase round: FAILED" in proc.stdout
    assert "== phase kernels: ok" in proc.stdout
    # nothing was compiled, so the cache phase fails too — and says so
    assert "FAILED in phase(s): round, cache" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_the_smoke_and_the_lowering_gate_agree_on_shapes():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    from split_learning_tpu.analysis import pallas_check
    assert chip_smoke.FULL.flash_shapes == pallas_check.FLASH_SHAPES
    assert chip_smoke.CUT == 7
    assert pallas_check.CUT7_BOUNDARY == (32, 16, 16, 64)
    assert chip_smoke.expected_mesh("tpu", 1) == (1, 1)
    assert chip_smoke.expected_mesh("tpu", 4) == (2, 2)
    assert chip_smoke.expected_mesh("cpu", 8) == (2, 1)
