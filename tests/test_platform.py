"""Process set-up (``split_learning_tpu/platform.py``): one resolver for
the compile cache, a platform check that raises, and entry points that
report a failed round or a missing backend through their exit code.

Whatever touches jax's process-wide config runs in a child process.
"""

import os
import pathlib
import re
import subprocess
import sys

import pytest

from split_learning_tpu import platform as slt_platform

REPO = pathlib.Path(__file__).resolve().parent.parent


def _child(code: str, env_extra=None, drop=(), timeout=300):
    env = os.environ.copy()
    env.update(env_extra or {})
    for k in drop:
        env.pop(k, None)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=str(REPO), capture_output=True, text=True,
                          timeout=timeout)


# --------------------------------------------------------------------------
# the compile-cache resolver
# --------------------------------------------------------------------------

def test_cache_dir_is_the_environments_or_the_checkouts(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert slt_platform.compile_cache_dir() == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert slt_platform.compile_cache_dir() == str(REPO / ".jax_cache")
    assert slt_platform.DEFAULT_CACHE_DIR == REPO / ".jax_cache"


_RECORD_UPDATES = """
import jax
calls = []
real = jax.config.update
jax.config.update = lambda k, v: (calls.append(k), real(k, v))[1]
from split_learning_tpu.platform import apply_compile_cache
print("DIR", apply_compile_cache())
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("SET_IN_CODE", "jax_compilation_cache_dir" in calls)
"""


def test_variable_set_means_no_directory_is_set_in_code(tmp_path):
    proc = _child(_RECORD_UPDATES,
                  {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"DIR {tmp_path}" in proc.stdout
    assert f"CONFIG {tmp_path}" in proc.stdout
    assert "SET_IN_CODE False" in proc.stdout


def test_variable_unset_means_the_checkouts_cache():
    proc = _child(_RECORD_UPDATES, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = REPO / ".jax_cache"
    assert f"DIR {want}" in proc.stdout
    assert f"CONFIG {want}" in proc.stdout
    assert "SET_IN_CODE True" in proc.stdout


def _sources():
    roots = [REPO / "split_learning_tpu", REPO / "tools",
             REPO / "examples", REPO / "configs"]
    files = [p for r in roots for p in r.rglob("*") if p.is_file()
             and p.suffix in (".py", ".sh", ".yaml", ".yml")]
    return files + [REPO / "bench.py", REPO / "chip_smoke.py",
                    REPO / "config.yaml"]


def test_no_entry_point_sets_another_cache_directory():
    """Only ``platform.py`` may choose the directory; nothing else
    updates the jax knob, assigns the variable, or names a cache path
    with a temporary name, a pid, a time or a host hash in it."""
    setters = re.compile(
        r"""update\(\s*["']jax_compilation_cache_dir"""
        r"""|environ\[["']JAX_COMPILATION_CACHE_DIR["']\]\s*="""
        r"""|setdefault\(\s*["']JAX_COMPILATION_CACHE_DIR"""
        r"""|export\s+JAX_COMPILATION_CACHE_DIR"""
        r"""|compile[-_]cache[-_]dir\s*[:=]""")
    offenders = []
    for path in _sources():
        if path == REPO / "split_learning_tpu" / "platform.py":
            continue
        for n, line in enumerate(path.read_text().splitlines(), 1):
            if setters.search(line):
                offenders.append(f"{path.relative_to(REPO)}:{n}: {line}")
    assert not offenders, "\n".join(offenders)


@pytest.mark.parametrize("rel", [
    "split_learning_tpu/run.py", "split_learning_tpu/runtime/server.py",
    "split_learning_tpu/runtime/client.py",
    "split_learning_tpu/runtime/stagehost.py",
    "split_learning_tpu/profiler.py", "bench.py", "chip_smoke.py",
    "tools/flagship.py"])
def test_every_entry_point_uses_the_resolver(rel):
    text = (REPO / rel).read_text()
    assert "apply_compile_cache()" in text, rel
    assert "apply_platform_env()" in text, rel


def test_an_entry_point_writes_its_cache_where_the_variable_says(tmp_path):
    """The profiler CLI (the lightest entry point that compiles) with
    the variable set: entries land there."""
    cache = tmp_path / "cache"
    out = tmp_path / "profile.json"
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(cache)}
    # the suite's own threshold would keep these sub-second programs
    # out of any cache; without it the resolver caches everything
    env.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")
    proc = subprocess.run(
        [sys.executable, "-m", "split_learning_tpu.profiler", "--config",
         "examples/quickstart.yaml", "--method", "flops", "--output",
         str(out)],
        env=env, cwd=str(REPO), capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert out.exists()
    assert any(cache.iterdir()), "no cache entry where the variable says"


# --------------------------------------------------------------------------
# the platform check
# --------------------------------------------------------------------------

def test_platform_env_holds_or_raises(monkeypatch):
    import jax
    assert jax.default_backend() == "cpu"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    slt_platform.apply_platform_env()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    slt_platform.apply_platform_env()
    monkeypatch.delenv("JAX_PLATFORMS")
    slt_platform.apply_platform_env()
    # asked for one backend, got another: an error, not a warning
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(RuntimeError, match="effective backend is 'cpu'"):
        slt_platform.apply_platform_env()


def test_run_cli_fails_when_the_requested_platform_is_absent():
    proc = subprocess.run(
        [sys.executable, "-m", "split_learning_tpu.run", "--config",
         "examples/quickstart.yaml"],
        env={**os.environ, "JAX_PLATFORMS": "tpu"}, cwd=str(REPO),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "round 0" not in proc.stdout


# --------------------------------------------------------------------------
# run.main's exit code
# --------------------------------------------------------------------------

_STUB_RUN = """
import sys
import split_learning_tpu.run as run
from split_learning_tpu.runtime.loop import RoundRecord, TrainResult
second_ok = sys.argv[1] == "ok"
run.run_local = lambda cfg: TrainResult(None, None, [
    RoundRecord(0, True, 8, 0.1, val_loss=2.0, val_accuracy=0.1),
    RoundRecord(1, second_ok, 8 if second_ok else 0, 0.1)])
sys.exit(run.main(["--config", "examples/quickstart.yaml"]))
"""


@pytest.mark.parametrize("second,rc", [("ok", 0), ("failed", 1)])
def test_run_main_exit_code_says_whether_every_round_was_ok(
        second, rc, tmp_path):
    """The loop records a diverged round and carries on; the process
    must still end non-zero (it used to print ``ok=False`` and exit
    0)."""
    proc = subprocess.run(
        [sys.executable, "-c", _STUB_RUN, second], cwd=str(REPO),
        env={**os.environ, "PYTHONPATH": str(REPO)},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == rc, proc.stderr[-2000:]
    assert f"round 1: ok={second == 'ok'}" in proc.stdout
