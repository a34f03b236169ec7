"""Native C++ broker: frame protocol conformance, blocking/timeout GET
semantics, purge, concurrent producers/consumers, and a full protocol
training round — all through the unchanged Python TcpTransport."""

import shutil
import threading

import pytest

from split_learning_tpu.runtime.bus import TcpTransport

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None and shutil.which("clang++") is None,
    reason="no C++ compiler")


@pytest.fixture(scope="module")
def broker():
    from split_learning_tpu.native import NativeBroker
    b = NativeBroker("127.0.0.1", 0)
    yield b
    b.close()


def test_publish_get_roundtrip(broker):
    t = TcpTransport(broker.host, broker.port)
    t.publish("q1", b"hello")
    t.publish("q1", b"world")
    assert t.get("q1", timeout=5) == b"hello"   # FIFO
    assert t.get("q1", timeout=5) == b"world"
    t.close()


def test_get_timeout_and_blocking_wakeup(broker):
    t1 = TcpTransport(broker.host, broker.port)
    assert t1.get("empty_q", timeout=0.2) is None    # timeout reply

    got = {}

    def consumer():
        t2 = TcpTransport(broker.host, broker.port)
        got["msg"] = t2.get("wake_q", timeout=10)
        t2.close()

    th = threading.Thread(target=consumer)
    th.start()
    import time
    time.sleep(0.3)            # let the GET park on the broker
    t1.publish("wake_q", b"delivered")
    th.join(timeout=5)
    assert got["msg"] == b"delivered"
    t1.close()


def test_purge(broker):
    t = TcpTransport(broker.host, broker.port)
    t.publish("pa", b"1")
    t.publish("pb", b"2")
    t.purge(["pa"])
    assert t.get("pa", timeout=0.1) is None
    assert t.get("pb", timeout=5) == b"2"
    t.publish("pc", b"3")
    t.purge()                   # purge all
    assert t.get("pc", timeout=0.1) is None
    t.close()


def test_large_payload(broker):
    t = TcpTransport(broker.host, broker.port)
    big = bytes(range(256)) * (4 * 1024 * 16)   # 16 MB
    t.publish("big_q", big)
    assert t.get("big_q", timeout=30) == big
    t.close()


def test_many_concurrent_clients(broker):
    n = 8
    results = [None] * n

    def worker(i):
        t = TcpTransport(broker.host, broker.port)
        t.publish(f"cq_{i % 2}", f"m{i}".encode())
        results[i] = t.get(f"cq_{i % 2}", timeout=10)
        t.close()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=15)
    assert all(r is not None for r in results)


def test_full_training_round_over_native_broker(broker, tmp_path):
    """The complete split-learning protocol (server + 2 clients) with
    the C++ broker as the only transport."""
    from tests.test_protocol_runtime import proto_cfg, run_deployment

    cfg = proto_cfg(
        tmp_path, clients=[1, 1],
        transport={"kind": "tcp", "host": broker.host,
                   "port": broker.port})
    result = run_deployment(
        cfg, lambda: TcpTransport(broker.host, broker.port),
        TcpTransport(broker.host, broker.port))
    assert result.history[0].ok
    assert result.history[0].num_samples > 0


def test_native_build_is_keyed_on_the_source_not_on_mtimes(
        tmp_path, monkeypatch):
    """The artifact directory is git-ignored and a copied tree keeps no
    mtimes: reuse is keyed on a hash of the source (and flags) kept in
    a sidecar.  A binary with no sidecar, or one built from other
    source, is rebuilt however new its mtime is."""
    import os

    from split_learning_tpu import native
    monkeypatch.setattr(native, "_BIN_DIR", tmp_path)
    src = tmp_path / "hello.cpp"
    dest = tmp_path / "hello"
    stamp = tmp_path / "hello.srchash"
    src.write_text('#include <cstdio>\nint main(){puts("one");}\n')

    def out():
        import subprocess
        return subprocess.run([str(native._build(src, dest))],
                              capture_output=True, text=True).stdout

    assert out() == "one\n" and stamp.exists()
    built = dest.stat().st_mtime_ns
    assert out() == "one\n"
    assert dest.stat().st_mtime_ns == built          # reused as is

    # new source, OLDER than the artifact by mtime: the old rule reused
    src.write_text('#include <cstdio>\nint main(){puts("two");}\n')
    os.utime(src, ns=(built - 10**12, built - 10**12))
    assert out() == "two\n"

    # an artifact of unknown origin (no sidecar) is never run
    stamp.unlink()
    dest.write_text("#!/bin/sh\necho stale\n")
    dest.chmod(0o755)
    assert out() == "two\n" and stamp.exists()
