"""Delta-encoded Update frames — the rpc wire codec
(``transport.codec: {rpc: "delta:int8"}``).

A round's UPDATE is the biggest frame a client publishes (the full
trained shard, fp32).  But the server already SENT this client a shard
in START — after one round of SGD the trained params sit a small step
away from that base, so the client ships ``trained - base`` instead,
quantized (bf16 or tiled int8) with a client-side error-feedback
residual, tagged with the base's **version** (the server's per-
invocation generation).

Both endpoints keep the base: the client remembers the params exactly
as received in START, the server keeps a **versioned shadow copy per
client** (:class:`DeltaShadow`, recorded at START fan-out) and folds
``base + dequant(delta)`` back into a full tree before aggregation
(``runtime/strategies.py`` only ever sees reconstructed updates).

The version chain self-heals: the server advertises the shadow version
it holds in every START (``extra["delta_base_version"]``), and a
client sends a delta ONLY when its local base matches that
advertisement — a restarted client (no base), a hold-weights round
whose base drifted, or a server that lost its shadow all degrade to a
full fp32 frame automatically (counted ``delta_full_frames``).  A
delta that still arrives against a version the shadow lacks
(redelivery gap, shadow loss after fan-out) is rejected and counted
``delta_resyncs``; the server marks the client for a full re-seed so
the next round repairs the chain.

This path runs once per round on host-side trees, so the quantizer is
the numpy twin in :mod:`~split_learning_tpu.runtime.codec.quant` — the
device-side discipline the slcheck codec analyzer enforces applies to
the per-microbatch data plane, not here.
"""

from __future__ import annotations

import threading
from typing import Any

import ml_dtypes
import numpy as np

from split_learning_tpu.runtime.codec.quant import (
    dequantize_leaf_np, quantize_np,
)
from split_learning_tpu.runtime.codec.specs import CodecSpec
from split_learning_tpu.runtime.protocol import QuantLeaf

_BF16 = np.dtype(ml_dtypes.bfloat16)


def _tree_map_np(fn, *trees):
    import jax
    return jax.tree_util.tree_map(fn, *trees)


class DeltaCodec:
    """Client half: encode ``trained - base`` (+ EF residual)."""

    name = "delta"
    COUNTERS = ("delta_folds", "delta_full_frames", "delta_resyncs",
                "quant_nonfinite")

    def __init__(self, spec: CodecSpec, faults=None):
        self.delta_dtype = spec.delta_dtype
        self.tile = spec.tile
        if faults is None:
            from split_learning_tpu.runtime.trace import (
                default_fault_counters,
            )
            faults = default_fault_counters
        self.faults = faults
        # leaf-index -> residual (what quantization dropped last round)
        self._res: dict[int, np.ndarray] = {}

    def encode_update(self, params: Any, base: Any) -> Any:
        """Full trained tree + base tree (both host np, matching
        structure) -> quantized delta tree."""
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(params)
        base_leaves = jax.tree_util.tree_leaves(base)
        if len(leaves) != len(base_leaves):
            raise ValueError("delta base/params structure mismatch")
        out = []
        for i, (p, b) in enumerate(zip(leaves, base_leaves)):
            p = np.asarray(p)
            if not np.issubdtype(p.dtype, np.floating):
                out.append(p)
                continue
            res = self._res.get(i)
            if res is not None and np.shape(res) != p.shape:
                # an elastic re-plan moved this client's layer range:
                # leaf i is a different tensor now — the old residual
                # is another shard's unsent mass, drop it
                res = None
            d = (p.astype(np.float32) - np.asarray(b, np.float32)
                 + (res if res is not None else np.float32(0.0)))
            if self.delta_dtype == "int8":
                leaf = quantize_np(d, self.tile, bits=8)
                if not np.isfinite(np.asarray(leaf.scale)).all():
                    self.faults.inc("quant_nonfinite")
                sent = dequantize_leaf_np(leaf)
            else:
                leaf = d.astype(_BF16)
                sent = np.asarray(leaf, np.float32)
            self._res[i] = d - sent
            out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- checkpointable residual state ---------------------------------

    def state_dict(self) -> dict[str, np.ndarray]:
        return {f"delta|{i}": np.asarray(r)
                for i, r in sorted(self._res.items())}

    def load_state_dict(self, state: dict) -> None:
        self._res = {}
        for name, arr in state.items():
            _, _, i = name.rpartition("|")
            self._res[int(i)] = np.asarray(arr, np.float32)


def decode_delta_tree(delta: Any) -> Any:
    """Quantized delta tree -> float32 np delta tree (server side).
    Non-float leaves passed through the encoder unchanged stay as-is
    (they carry the full trained value, not a delta)."""
    def conv(leaf):
        if isinstance(leaf, QuantLeaf):
            return dequantize_leaf_np(leaf)
        a = np.asarray(leaf)
        if np.issubdtype(a.dtype, np.floating):
            return a.astype(np.float32)
        return a
    return _tree_map_np(conv, delta)


class DeltaShadow:
    """Server half: versioned per-client shadow copies + the fold.

    ``note_sent`` records the exact tree a START carried (keyed by the
    invocation generation); ``fold`` reconstructs a delta UPDATE
    against it.  One version per client is enough — the client can
    only ever hold the latest base (a delta against an older one means
    the chain broke, which is exactly what fold refuses)."""

    def __init__(self, faults=None):
        if faults is None:
            from split_learning_tpu.runtime.trace import (
                default_fault_counters,
            )
            faults = default_fault_counters
        self.faults = faults
        self._shadow: dict[str, tuple[int, Any]] = {}
        # running byte total, maintained incrementally: nbytes() sits
        # on the server's round path (gauge refresh per START fan-out
        # and per lost-client prune), so an O(clients x leaves) rescan
        # there would scale with exactly the fleet width the streaming
        # aggregation plane exists to remove
        self._nbytes_total = 0
        self._nbytes_by_client: dict[str, int] = {}
        # the lost-client prune runs on whatever thread advances the
        # FleetMonitor — including the exporter's HTTP handler — while
        # note_sent/fold run on the round/pump thread; the compound
        # ledger updates (total += new - old) need the lock or a
        # clear/note_sent interleave drifts the gauge and can pin a
        # pruned client's tree uncounted
        self._lock = threading.Lock()

    @staticmethod
    def _tree_nbytes(tree: Any) -> int:
        import jax
        return sum(int(np.asarray(leaf).nbytes)
                   for leaf in jax.tree_util.tree_leaves(tree))

    def note_sent(self, client_id: str, version: int, tree: Any) -> None:
        n = self._tree_nbytes(tree)
        with self._lock:
            self._nbytes_total += (n
                                   - self._nbytes_by_client.get(
                                       client_id, 0))
            self._nbytes_by_client[client_id] = n
            self._shadow[client_id] = (version, tree)

    def version_for(self, client_id: str) -> int | None:
        with self._lock:
            ent = self._shadow.get(client_id)
        return ent[0] if ent is not None else None

    def clear(self, client_id: str | None = None) -> None:
        with self._lock:
            if client_id is None:
                self._shadow.clear()
                self._nbytes_total = 0
                self._nbytes_by_client.clear()
            else:
                self._shadow.pop(client_id, None)
                self._nbytes_total -= self._nbytes_by_client.pop(
                    client_id, 0)

    def nbytes(self) -> int:
        """Host bytes pinned across every client's shadow tree — the
        ``sl_agg_shadow_bytes`` gauge (memory audit: without the
        lost-client and elastic prunes this grows without bound under
        membership churn).  O(1): maintained incrementally by
        note_sent/clear."""
        with self._lock:
            return self._nbytes_total

    def fold(self, client_id: str, base_version: int,
             delta: Any) -> Any | None:
        """base + dequant(delta) as a full float tree, or None when the
        shadow does not hold ``base_version`` for this client (version
        gap -> the caller must trigger a full-frame resync)."""
        with self._lock:
            ent = self._shadow.get(client_id)
        if ent is None or ent[0] != base_version:
            self.faults.inc("delta_resyncs")
            return None
        _, base = ent
        self.faults.inc("delta_folds")
        d32 = decode_delta_tree(delta)

        def comb(b, d):
            b = np.asarray(b)
            if np.issubdtype(b.dtype, np.floating):
                # float leaves fold base + delta, back in the master
                # dtype (fp32 — the master path stays full precision)
                return (b.astype(np.float32)
                        + np.asarray(d, np.float32)).astype(b.dtype)
            return np.asarray(d)   # non-float leaves ship whole
        return _tree_map_np(comb, base, d32)
