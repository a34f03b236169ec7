"""Native runtime components (C++), built on demand with the system
toolchain.

``NativeBroker`` wraps ``src/broker.cpp`` (shipped as package data so
installed distributions can build it too) — the framework's native
message broker (the role RabbitMQ plays for the reference,
``/root/reference/README.md:43-69``): compile (cached by a hash of
the source and the build command), spawn as a subprocess, parse the
bound port, and manage lifetime.  The
Python ``TcpTransport`` speaks to it unchanged; ``python -m
split_learning_tpu.broker`` prefers it and falls back to the threaded
Python broker when no compiler is available.

Built artifacts go next to the sources when that directory is writable
(source checkout), else to ``~/.cache/split_learning_tpu/bin``
(site-packages installs are often read-only).
"""

from __future__ import annotations

import hashlib
import os
import pathlib
import shutil
import subprocess

_SRC_DIR = pathlib.Path(__file__).resolve().parent / "src"
_SRC = _SRC_DIR / "broker.cpp"
_MFCC_SRC = _SRC_DIR / "mfcc.cpp"


def _bin_dir() -> pathlib.Path:
    override = os.environ.get("SLT_NATIVE_BIN")
    if override:
        return pathlib.Path(override)
    local = _SRC_DIR.parent / "bin"
    try:
        local.mkdir(parents=True, exist_ok=True)
        probe = local / ".writable"
        probe.touch()
        probe.unlink()
        return local
    except OSError:
        return pathlib.Path.home() / ".cache" / "split_learning_tpu" / "bin"


_BIN_DIR = _bin_dir()
_BIN = _BIN_DIR / "slt_broker"
_MFCC_LIB = _BIN_DIR / "libslt_mfcc.so"


class NativeBuildError(RuntimeError):
    pass


def _compiler() -> str:
    gxx = shutil.which("g++") or shutil.which("clang++")
    if gxx is None:
        raise NativeBuildError("no C++ compiler on PATH")
    return gxx


def _build(src: pathlib.Path, dest: pathlib.Path,
           extra: list | None = None, force: bool = False) -> pathlib.Path:
    """Compile ``src`` -> ``dest`` unless ``dest`` was built from this
    very source with these flags.

    The artifact directory is not under version control, and a copied
    or checked-out tree does not keep mtimes, so freshness is keyed on
    a hash (source bytes + flags) kept in a sidecar next to the
    artifact: a binary of unknown origin is rebuilt, never run."""
    if not src.exists():
        raise NativeBuildError(f"missing source {src}")
    flags = ["-O2", "-std=c++17", *(extra or [])]
    digest = hashlib.sha256(
        src.read_bytes() + "\0".join(flags).encode()).hexdigest()
    stamp = dest.with_name(dest.name + ".srchash")
    if not force and dest.exists() and stamp.exists() \
            and stamp.read_text().strip() == digest:
        return dest
    try:
        _BIN_DIR.mkdir(parents=True, exist_ok=True)
    except OSError as e:
        raise NativeBuildError(f"cannot create bin dir {_BIN_DIR}: {e}")
    # build beside the target and rename into place: a concurrent
    # builder (several test processes share the directory) never
    # executes a half-written binary
    tmp = dest.with_name(f"{dest.name}.{os.getpid()}.tmp")
    cmd = [_compiler(), *flags, "-o", str(tmp), str(src)]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        tmp.unlink(missing_ok=True)
        raise NativeBuildError(
            f"build of {src.name} failed:\n{proc.stderr[-2000:]}")
    os.replace(tmp, dest)
    stamp.write_text(digest + "\n")
    return dest


def build_broker(force: bool = False) -> pathlib.Path:
    return _build(_SRC, _BIN, force=force)


def build_mfcc(force: bool = False) -> pathlib.Path:
    return _build(_MFCC_SRC, _MFCC_LIB,
                  extra=["-O3", "-shared", "-fPIC"], force=force)


_mfcc_lib = None


def mfcc_batch_native(signals, sample_rate: int = 16000, n_mfcc: int = 40,
                      frame_ms: float = 25.0, hop_ms: float = 10.0,
                      n_fft: int = 512, n_mels: int = 64,
                      pre_emphasis: float = 0.97):
    """(B, n_mfcc, n_frames) MFCCs via the C++ extractor.

    Raises :class:`NativeBuildError` when no compiler is available —
    callers fall back to the numpy pipeline (``data/mfcc.py``).
    """
    import ctypes

    import numpy as np

    global _mfcc_lib
    if _mfcc_lib is None:
        lib = ctypes.CDLL(str(build_mfcc()))
        lib.slt_mfcc_batch.restype = ctypes.c_int
        lib.slt_mfcc_batch.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_double, ctypes.c_double,
            ctypes.c_int, ctypes.c_int, ctypes.c_double,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int),
        ]
        _mfcc_lib = lib

    sig = np.ascontiguousarray(signals, dtype=np.float32)
    if sig.ndim == 1:
        sig = sig[None]
    batch, n_samples = sig.shape
    frame_len = int(round(sample_rate * frame_ms / 1000.0))
    hop = int(round(sample_rate * hop_ms / 1000.0))
    n_frames = max(1, 1 + (n_samples - frame_len) // hop)
    out = np.empty((batch, n_mfcc, n_frames), dtype=np.float32)
    got_frames = ctypes.c_int(0)
    rc = _mfcc_lib.slt_mfcc_batch(
        sig.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        batch, n_samples, sample_rate, n_mfcc, frame_ms, hop_ms,
        n_fft, n_mels, pre_emphasis,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.byref(got_frames))
    if rc != 0 or got_frames.value != n_frames:
        raise NativeBuildError(f"slt_mfcc_batch failed rc={rc}")
    return out


class NativeBroker:
    """A running native broker subprocess."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        if host not in ("127.0.0.1", "localhost"):
            raise NativeBuildError("native broker binds loopback only")
        binary = build_broker()
        self._proc = subprocess.Popen(
            [str(binary), str(port)], stdout=subprocess.PIPE, text=True)
        line = self._proc.stdout.readline().strip()
        if not line.startswith("LISTENING "):
            self._proc.kill()
            raise NativeBuildError(f"unexpected broker banner {line!r}")
        self.host = host
        self.port = int(line.split()[1])

    def close(self):
        if self._proc.poll() is None:
            self._proc.terminate()
            try:
                self._proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self._proc.kill()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
