"""Zero-copy TENSOR framing + chunking + async transport (PR 3).

Fast tier-1 surface: encode/decode roundtrip parity across every wire
dtype (fp32/fp16/bf16/int/bool and QuantLeaf), bit-exactness of the new
framing vs the legacy pickled frames, corrupt/truncated-frame rejection
BEFORE ``np.frombuffer``, chunk reassembly, the AsyncTransport
sender/prefetch behavior, wire counters, and the persistent-compile-
cache smoke.  The ``slow`` round-level checks pin bf16-vs-fp32 loss
parity over a real protocol round.
"""

import os
import subprocess
import sys
import threading
import time

import ml_dtypes
import numpy as np
import pytest

from split_learning_tpu.runtime import protocol as P
from split_learning_tpu.runtime.bus import (
    AsyncTransport, InProcTransport, QueueClosed,
)
from split_learning_tpu.runtime.trace import WireCounters


def _tree_bit_identical(a, b):
    import jax
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype, (x.dtype, y.dtype)
        np.testing.assert_array_equal(x, y)


class TestTensorFrameRoundtrip:
    DTYPES = [np.float32, np.float64, np.float16, ml_dtypes.bfloat16,
              np.int8, np.int16, np.int32, np.int64, np.uint8, np.bool_]

    @pytest.mark.parametrize("dtype", DTYPES,
                             ids=[np.dtype(d).name for d in DTYPES])
    def test_every_wire_dtype_roundtrips_bit_exact(self, dtype):
        rng = np.random.default_rng(0)
        a = (rng.normal(size=(3, 5)) * 10).astype(dtype)
        act = P.Activation(data_id="d", data=a,
                           labels=np.arange(3, dtype=np.int32),
                           trace=["c1"], cluster=0, round_idx=7)
        raw = P.encode(act)
        assert raw[:4] == P.TENSOR_MAGIC
        out = P.decode(raw)
        assert out.data_id == "d" and out.round_idx == 7
        _tree_bit_identical(out.data, a)
        _tree_bit_identical(out.labels, act.labels)

    def test_mixed_pytree_with_quantleaf_scalars_and_empty(self):
        payload = {
            "h": np.arange(12, dtype=np.float32).reshape(3, 4),
            "mask": np.array([[True, False, True]]),
            "bf": np.ones((2, 2), ml_dtypes.bfloat16),
            "q": P.QuantLeaf(q=np.arange(6, dtype=np.int8), scale=0.25),
            "scalar": np.float32(3.5),       # np scalar: stays pickled
            "zero_d": np.array(2.0, np.float32),
            "empty": np.zeros((0, 4), np.float32),
            "nested": [np.int64(1), (np.full(3, 9, np.uint8), "str")],
        }
        g = P.Gradient(data_id="g", data=payload, trace=["a", "b"])
        out = P.decode(P.encode(g))
        assert isinstance(out.data["q"], P.QuantLeaf)
        assert out.data["q"].scale == 0.25
        _tree_bit_identical(out.data["q"].q, payload["q"].q)
        for key in ("h", "mask", "bf", "zero_d", "empty"):
            _tree_bit_identical(out.data[key], payload[key])
        assert out.data["scalar"] == np.float32(3.5)
        assert out.data["nested"][1][1] == "str"
        assert out.trace == ["a", "b"]

    def test_noncontiguous_input_roundtrips(self):
        a = np.arange(24, dtype=np.float32).reshape(4, 6)[:, ::2]
        assert not a.flags["C_CONTIGUOUS"]
        out = P.decode(P.encode(P.Gradient(data_id="g", data=a,
                                           trace=[])))
        _tree_bit_identical(out.data, a)

    def test_fp32_wire_bit_identical_to_legacy_framing(self):
        """Acceptance: fp32 wire mode decodes to exactly what the legacy
        pickled frames delivered — same values, same dtypes, bit for
        bit — for every tensor-framed message type."""
        rng = np.random.default_rng(1)
        tree = {"layer1": {"kernel": rng.normal(
            size=(4, 3)).astype(np.float32),
            "bias": rng.normal(size=(3,)).astype(np.float32)}}
        msgs = [
            P.Activation(data_id="a", data=tree,
                         labels=np.arange(4, dtype=np.int32),
                         trace=["c"], cluster=1, round_idx=2),
            P.Gradient(data_id="g", data=tree, trace=["c"], round_idx=2),
            P.Update(client_id="c", stage=1, cluster=0, params=tree,
                     num_samples=8, batch_stats={"bn": {"mean": np.zeros(
                         3, np.float32)}}, round_idx=2),
        ]
        for msg in msgs:
            new = P.decode(P.encode(msg))
            legacy = P.decode(P.encode_pickled(msg))
            for f in ("data", "params", "batch_stats", "labels"):
                if hasattr(msg, f):
                    _tree_bit_identical(getattr(new, f),
                                        getattr(legacy, f))

    def test_update_weight_less_and_none_fields(self):
        out = P.decode(P.encode(P.Update(
            client_id="c", stage=2, cluster=0, params=None,
            num_samples=5, ok=False)))
        assert out.params is None and out.num_samples == 5 and not out.ok

    def test_bf16_wire_halves_fp32_frame_bytes(self):
        a32 = np.ones((64, 64), np.float32)
        a16 = a32.astype(ml_dtypes.bfloat16)
        n32 = len(P.encode(P.Gradient(data_id="g", data=a32, trace=[])))
        n16 = len(P.encode(P.Gradient(data_id="g", data=a16, trace=[])))
        assert n16 < 0.55 * n32, (n16, n32)


class TestTensorFrameRejection:
    def _frame(self):
        rng = np.random.default_rng(2)
        return P.encode(P.Activation(
            data_id="d", data=rng.normal(size=(16, 16)).astype(
                np.float32),
            labels=np.arange(16, dtype=np.int32), trace=["c"],
            cluster=0))

    def test_any_flipped_byte_rejected_before_frombuffer(self):
        raw = self._frame()
        # header, skeleton, AND deep inside the raw blob region: the
        # per-tensor crc must catch bulk corruption the meta crc
        # doesn't cover
        for i in (0, 4, 9, 40, len(raw) // 2, len(raw) - 100,
                  len(raw) - 1):
            bad = raw[:i] + bytes([raw[i] ^ 0xFF]) + raw[i + 1:]
            with pytest.raises(P.CorruptFrame):
                P.decode(bad)

    def test_truncation_rejected(self):
        raw = self._frame()
        for n in (0, 3, 7, 12, 60, len(raw) - 4, len(raw) - 1):
            with pytest.raises(P.CorruptFrame):
                P.decode(raw[:n])

    def test_smuggled_control_message_rejected_in_tensor_frame(self):
        import pickle
        import struct
        import zlib
        # a well-formed SLT2 frame whose skeleton pickles a CONTROL
        # message must still be rejected (tensor framing is data-plane
        # only, so a Start can't dodge its schema checks there)
        skel = pickle.dumps(P.Syn(round_idx=1))
        meta = (struct.pack(">H", 0) + struct.pack(">I", 0)
                + struct.pack(">I", len(skel)) + skel)
        raw = (P.TENSOR_MAGIC + struct.pack(">I", zlib.crc32(meta))
               + meta)
        with pytest.raises(pickle.UnpicklingError,
                           match="not a tensor-frame"):
            P.decode(raw)

    def test_chunk_frame_outside_assembler_rejected(self):
        parts = P.encode_parts(P.Gradient(
            data_id="g", data=np.zeros(256, np.float32), trace=[]),
            max_bytes=128)
        assert len(parts) > 1
        with pytest.raises(P.CorruptFrame, match="FrameAssembler"):
            P.decode(parts[0])


class TestChunking:
    def _msg(self, n=4096):
        return P.Gradient(data_id="g",
                          data=np.arange(n, dtype=np.float32),
                          trace=["c"], round_idx=3)

    def test_below_cap_single_frame(self):
        parts = P.encode_parts(self._msg(8), max_bytes=1 << 20)
        assert len(parts) == 1
        assert P.FrameAssembler().feed(parts[0]).round_idx == 3

    def test_reassembly_in_and_out_of_order(self):
        msg = self._msg()
        parts = P.encode_parts(msg, max_bytes=1024)
        assert len(parts) > 3
        asm = P.FrameAssembler()
        results = [asm.feed(p) for p in parts]
        assert all(r is None for r in results[:-1])
        _tree_bit_identical(results[-1].data, msg.data)
        # out-of-order arrival (chaos reorder below the reliable layer)
        import random
        random.seed(0)
        shuffled = list(parts)
        random.shuffle(shuffled)
        asm2 = P.FrameAssembler()
        got = [m for m in (asm2.feed(p) for p in shuffled)
               if m is not None]
        assert len(got) == 1
        _tree_bit_identical(got[0].data, msg.data)

    def test_corrupt_chunk_rejected(self):
        parts = P.encode_parts(self._msg(), max_bytes=1024)
        bad = parts[1][:50] + bytes([parts[1][50] ^ 0xFF]) + parts[1][51:]
        asm = P.FrameAssembler()
        with pytest.raises(P.CorruptFrame):
            asm.feed(bad)
        # the rest of the stream still assembles (redelivery model)
        got = [m for m in (asm.feed(p) for p in parts) if m is not None]
        assert len(got) == 1

    def test_stale_partial_evicted_bounded(self):
        asm = P.FrameAssembler(max_pending=2)
        # three partial messages: the stalest is evicted, memory bounded
        for _ in range(3):
            parts = P.encode_parts(self._msg(), max_bytes=1024)
            assert asm.feed(parts[0]) is None
        assert len(asm._pending) == 2


class TestAsyncTransport:
    def test_fifo_order_and_deferred_thunks(self):
        bus = InProcTransport()
        tx = AsyncTransport(bus, send_depth=4, wire=WireCounters())
        try:
            tx.publish("q", b"a")
            tx.publish("q", lambda: b"b")                 # deferred
            tx.publish("q", lambda: [b"c1", b"c2"])       # frame parts
            assert tx.flush(timeout=5.0)
            assert [bus.get("q", 1) for _ in range(4)] == \
                [b"a", b"b", b"c1", b"c2"]
        finally:
            tx.stop(close_inner=False)

    def test_wire_counters_track_bytes_and_hwm(self):
        bus = InProcTransport()
        wire = WireCounters()
        tx = AsyncTransport(bus, send_depth=16, wire=wire)
        try:
            for _ in range(8):
                tx.publish("intermediate_queue_0_0", lambda: b"x" * 10)
            assert tx.flush(timeout=5.0)
            snap = wire.snapshot()
            assert snap["bytes_out_total"] == 80
            assert snap["data_bytes_out"] == 80
            assert snap["msgs_out"] == 8
            assert snap["encode_n"] == 8       # thunk builds timed
            assert 1 <= snap["send_queue_hwm"] <= 16
        finally:
            tx.stop(close_inner=False)

    def test_prefetch_delivers_in_order_and_counts_in(self):
        bus = InProcTransport()
        wire = WireCounters()
        tx = AsyncTransport(bus, wire=wire)
        try:
            q = "gradient_queue_1_c0"
            for i in range(6):
                bus.publish(q, b"m%d" % i)
            got = [tx.get(q, timeout=5.0) for i in range(6)]
            assert got == [b"m%d" % i for i in range(6)]
            assert tx.get(q, timeout=0.05) is None
            assert wire.snapshot()["bytes_in_total"] == 12
        finally:
            tx.stop(close_inner=False)

    def test_sender_error_surfaces_on_training_thread(self):
        bus = InProcTransport()
        tx = AsyncTransport(bus, wire=WireCounters())

        class Boom(RuntimeError):
            pass

        def explode():
            raise Boom("wire died")

        tx.publish("q", explode)
        with pytest.raises(Boom):
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                tx.publish("q", b"next")
                time.sleep(0.01)
        with pytest.raises(Boom):
            tx.get("gradient_queue_1_c0", timeout=0.01)
        tx.stop(close_inner=False)

    def test_bounded_sender_queue_blocks_not_grows(self):
        bus = InProcTransport()
        tx = AsyncTransport(bus, send_depth=2, wire=WireCounters())
        try:
            release = threading.Event()

            def slow():
                release.wait(5.0)
                return b"s"

            tx.publish("q", slow)       # occupies the sender thread
            tx.publish("q", b"1")
            tx.publish("q", b"2")       # queue now full (depth 2)
            blocked = []

            def overflow():
                tx.publish("q", b"3")
                blocked.append(True)

            t = threading.Thread(target=overflow, daemon=True)
            t.start()
            time.sleep(0.1)
            assert not blocked, "publish should block at depth"
            release.set()
            t.join(timeout=5.0)
            assert blocked
            assert tx.flush(timeout=5.0)
        finally:
            tx.stop(close_inner=False)

    def test_close_propagates_queue_closed(self):
        bus = InProcTransport()
        tx = AsyncTransport(bus, wire=WireCounters())
        q = "intermediate_queue_0_0"
        bus.publish(q, b"x")
        assert tx.get(q, timeout=2.0) == b"x"
        tx.stop(close_inner=True)
        with pytest.raises(QueueClosed):
            tx.publish("q", b"y")


class TestWireCounters:
    def test_monotonic_snapshot_contract(self):
        w = WireCounters()
        w.count_out("intermediate_queue_0_0", 100)
        w.count_out("rpc_queue", 40)
        w.count_in("gradient_queue_1_c", 60)
        w.add_encode(0.25)
        w.add_decode(0.5)
        w.note_send_depth(3)
        w.note_send_depth(1)   # hwm keeps the max
        s = w.snapshot()
        assert s["bytes_out_total"] == 140
        assert s["data_bytes_out"] == 100
        assert s["bytes_in_total"] == 60
        assert s["data_bytes_in"] == 60
        assert s["encode_s"] == 0.25 and s["decode_s"] == 0.5
        assert s["send_queue_hwm"] == 3
        per_q = w.per_queue()
        assert per_q["bytes_out"]["rpc_queue"] == 40


_CACHE_SCRIPT = """
from split_learning_tpu.platform import apply_platform_env, \
    apply_compile_cache
apply_platform_env()
apply_compile_cache()
import jax
import jax.numpy as jnp
import numpy as np
out = jax.jit(lambda x: (x * 2.0 + 1.0).sum())(jnp.arange(64.0))
print(float(np.asarray(out)))
"""


def test_compile_cache_populates_and_reuses(tmp_path):
    """Compile-cache smoke (the directory comes from
    JAX_COMPILATION_CACHE_DIR, platform.apply_compile_cache): a first
    run populates the persistent XLA cache; a second run of the same
    program adds NO new entries (it loaded the compiled executable
    instead of recompiling)."""
    cache = tmp_path / "xla_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=os.pathsep.join(
                   [str(os.path.dirname(os.path.dirname(__file__)))]
                   + [p for p in (os.environ.get("PYTHONPATH"),) if p]))
    # cache everything: this program compiles in milliseconds
    env.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)

    def run():
        r = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT],
                           env=env, capture_output=True,
                           text=True, timeout=240)
        assert r.returncode == 0, r.stderr[-2000:]
        return r

    run()
    entries = sorted(f.name for f in cache.rglob("*") if f.is_file())
    assert entries, "first run left the compile cache empty"
    run()
    entries2 = sorted(f.name for f in cache.rglob("*") if f.is_file())
    assert entries2 == entries, "second run recompiled (new cache entries)"


# --------------------------------------------------------------------------
# round-level parity (slow: compiles real split programs)
# --------------------------------------------------------------------------

TINY_KWT = {"embed_dim": 16, "num_heads": 2, "mlp_dim": 32}


def _proto_cfg(tmp_path, wire_dtype):
    from split_learning_tpu.config import from_dict
    return from_dict(dict(
        model="KWT", dataset="SPEECHCOMMANDS", clients=[2, 1],
        global_rounds=1, synthetic_size=48, val_max_batches=1,
        val_batch_size=16, compute_dtype="float32",
        model_kwargs=TINY_KWT, log_path=str(tmp_path / wire_dtype),
        learning={"batch_size": 4, "control_count": 1,
                  "optimizer": "adamw", "learning_rate": 1e-3},
        distribution={"num_samples": 8},
        topology={"cut_layers": [2]},
        aggregation={"strategy": "sda", "sda_size": 2,
                     "sda_strict": True, "local_rounds": 1},
        checkpoint={"directory": str(tmp_path / "ckpt"), "save": False},
        transport={"wire_dtype": wire_dtype},
    ))


def _run_round(cfg):
    from split_learning_tpu.runtime.client import ProtocolClient
    from split_learning_tpu.runtime.server import ProtocolServer

    bus = InProcTransport()
    server = ProtocolServer(cfg, transport=bus, client_timeout=300.0)
    threads = []
    for stage, count in enumerate(cfg.clients, start=1):
        for i in range(count):
            cid = f"client_{stage}_{i}"
            client = ProtocolClient(cfg, cid, stage, transport=bus)
            t = threading.Thread(target=client.run, daemon=True)
            t.start()
            threads.append(t)
    result = server.serve()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    return result


@pytest.mark.slow
def test_bf16_wire_loss_parity_with_fp32(tmp_path):
    """The bf16 wire default must train the same model the fp32 wire
    does, within bf16 rounding: one short protocol round, same data,
    same seeds — validation loss within tolerance and parameters
    allclose (NOT bit-identical: that is fp32's bar)."""
    r32 = _run_round(_proto_cfg(tmp_path, "fp32"))
    r16 = _run_round(_proto_cfg(tmp_path, "bf16"))
    assert r32.history[0].ok and r16.history[0].ok
    assert r32.history[0].num_samples == r16.history[0].num_samples
    assert r32.history[0].val_loss is not None
    assert abs(r32.history[0].val_loss - r16.history[0].val_loss) < 0.05, \
        (r32.history[0].val_loss, r16.history[0].val_loss)
    import jax
    la = jax.tree_util.tree_leaves(r32.params)
    lb = jax.tree_util.tree_leaves(r16.params)
    for a, b in zip(la, lb):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-2)
