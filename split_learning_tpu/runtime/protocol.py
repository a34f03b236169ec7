"""Typed control-plane protocol.

The reference's wire vocabulary is untyped dicts with an ``action`` key
pushed through RabbitMQ (client→server REGISTER ``client.py:57``, NOTIFY
``src/train/VGG16.py:121-126``, UPDATE ``src/RpcClient.py:128-132``;
server→client START ``src/Server.py:262-272``, SYN ``:293-296``, PAUSE
``:140-153``, STOP ``:276-287``).  Here every message is a dataclass; a
READY ack is added so the server's 25-second settle sleep
(``src/Server.py:289`` — a time-based barrier papering over a race,
SURVEY.md §5.2) becomes an explicit barrier, and a HEARTBEAT frame
(no reference equivalent — its failure model is "hang forever",
SURVEY.md §5.3) carries each client's live telemetry snapshot to the
server's fleet monitor (``runtime/telemetry.py``).

Queue naming keeps the reference topology so the protocol surface maps
1:1 (SURVEY.md §1 L0 table):

* ``rpc_queue``                              any client → server
* ``reply_{client_id}``                      server → one client
* ``intermediate_queue_{stage}_{cluster}``   stage k → k+1 activations
  (shared per cluster — natural load balance across same-stage clients)
* ``gradient_queue_{stage}_{client_id}``     stage k+1 → one stage-k client
"""

from __future__ import annotations

import collections
import dataclasses
import io
import math
import os
import pickle
import struct
import uuid
import zlib
from typing import Any

import ml_dtypes
import numpy as np

_BF16 = np.dtype(ml_dtypes.bfloat16)   # bf16 wire payloads

RPC_QUEUE = "rpc_queue"


def reply_queue(client_id: str) -> str:
    return f"reply_{client_id}"


def intermediate_queue(stage: int, cluster: int,
                       pair: int | None = None) -> str:
    """Forward-activation queue.  ``pair`` selects 2LS's fixed 1:1
    edge<->head pairing (``intermediate_queue_{layer}_{idx}``,
    ``other/2LS/src/train/VGG16.py:23``) instead of the shared
    per-cluster queue's natural load balancing."""
    base = f"intermediate_queue_{stage}_{cluster}"
    return base if pair is None else f"{base}_p{pair}"


def gradient_queue(stage: int, client_id: str) -> str:
    return f"gradient_queue_{stage}_{client_id}"


def aggregate_queue(cluster: int, group: int) -> str:
    """Aggregator-tree upload queue (``aggregation.fan-in``): the
    clients of L1 group ``group`` publish their round UPDATE here
    instead of ``rpc_queue``; the group's
    :class:`~split_learning_tpu.runtime.aggregate.L1Aggregator` folds
    them into one :class:`PartialAggregate` for the root."""
    return f"aggregate_queue_{cluster}_{group}"


def digest_queue(node_id: str) -> str:
    """Heartbeat roll-up queue (``observability.digest-interval``):
    clients assigned to aggregator node ``node_id`` publish their
    HEARTBEAT frames here instead of ``rpc_queue``; the node's digest
    worker folds them into one :class:`FleetDigest` per interval, so
    the server's rpc ingest is O(nodes), not O(clients)."""
    return f"digest_queue_{node_id}"


# --------------------------------------------------------------------------
# control messages
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Register:
    """client → server: join the round (with the offline profile)."""
    client_id: str
    stage: int                      # 1-based stage index ("layer_id")
    cluster: int | None = None      # manual cluster assignment, or None
    profile: dict | None = None     # {exe_time, size_data, speed, network}


@dataclasses.dataclass
class Ready:
    """client → server: shard built, data loaded — replaces sleep(25).

    ``round_idx`` carries the START's generation: a late READY from an
    invocation the server already gave up on must not count toward a
    newer invocation's READY barrier (the server would then SYN a client
    that is still unwinding the old round)."""
    client_id: str
    round_idx: int = 0


@dataclasses.dataclass
class Notify:
    """stage-1 client → server: local data exhausted this round.

    ``round_idx`` fences the barrier: a straggler's NOTIFY from a round
    the server already dropped must not satisfy a later round's barrier."""
    client_id: str
    cluster: int
    round_idx: int = 0


@dataclasses.dataclass
class Update:
    """client → server: round's trained shard parameters.

    ``round_idx`` fences aggregation: without it, a straggler dropped in
    round N that wakes during round N+1 would have its stale round-N
    weights counted as N+1's contribution."""
    client_id: str
    stage: int
    cluster: int
    params: Any                     # pytree of np arrays (host-side)
    num_samples: int                # FedAvg weight (data_count semantics)
    ok: bool = True                 # False -> NaN seen, skip aggregation
    batch_stats: Any | None = None  # shard's running stats (BN models)
    round_idx: int = 0
    # delta-encoded Update (transport.codec rpc family): params holds
    # ``trained - base`` against the server's versioned shadow copy of
    # what it sent in START.  None = full frame (the resync fallback
    # whenever the version chain broke: client restart, shadow loss).
    delta_base: int | None = None
    # async mode (learning.mode: async): the server generation this
    # client's params were SEEDED from — rides the existing delta-base
    # advertisement chain (START extra carries the gen, the client
    # stamps it back).  The server's bounded-staleness admission window
    # folds ``server_version - version <= learning.max-staleness`` with
    # staleness-scaled weight and rejects-and-counts the rest.  None =
    # sync client (round_idx carries the same fence).
    version: int | None = None
    # piggybacked TelemetrySnapshot dict (runtime/telemetry.py): every
    # sync round delivers one fleet sample for free, heartbeat thread
    # or not.  A plain dict, NOT the dataclass — the restricted
    # unpickler's vocabulary stays closed.
    telemetry: dict | None = None


@dataclasses.dataclass
class Start:
    """server → client: round config + shard weights."""
    start_layer: int
    end_layer: int                  # -1 = to the end
    cluster: int
    params: Any                     # shard pytree (np arrays)
    batch_stats: Any | None = None
    learning: dict | None = None    # lr/momentum/... overrides
    label_counts: Any | None = None  # stage-1: per-label sample counts
    round_idx: int = 0
    extra: dict | None = None       # strategy-specific knobs (sda_size, ...)


@dataclasses.dataclass
class Syn:
    """server → client: begin training.

    ``sda_fence_quorum`` / ``sda_feeders``, when set, override the
    static values sent in START: the server recomputes them from the
    RESPONSIVE client set after the READY barrier, so a previous-stage
    client dropped mid-round (whose fence copies will never arrive)
    can't leave the strict-SDA drain waiting on a quorum that can no
    longer be met (ADVICE round 5)."""
    round_idx: int = 0
    sda_fence_quorum: int | None = None
    sda_feeders: list | None = None


@dataclasses.dataclass
class Pause:
    """server → client: stop the hot loop, upload weights.

    ``send_weights=False`` is FLEX's non-aggregation-round PAUSE
    (``other/FLEX/src/Server.py:140-143``)."""
    send_weights: bool = True


@dataclasses.dataclass
class Stop:
    """server → client: terminate."""
    reason: str = ""


@dataclasses.dataclass
class PartialAggregate:
    """Aggregator → its parent (rpc queue at the root, the parent
    group's aggregate queue below it): one aggregator-tree group's
    folded contribution (``aggregation.fan-in`` /
    ``aggregation.levels``, ``runtime/aggregate.py``).  Carries the
    group's per-path weighted **sums** (f32, NOT averaged — every
    interior level continues the running fold and the root divides
    once, so tree depth never changes how many divides touch the
    data).  ``members`` is the per-client metadata the root needs for
    barrier bookkeeping and fleet telemetry (client_id, stage,
    num_samples, ok, telemetry) — the clients behind an aggregator
    still count individually everywhere except the fold itself; an L2
    node concatenates its children's member lists.  ``round_idx``
    carries the server's invocation generation, same fence as Update.

    ``codec``/``codec_base`` describe a compressed payload
    (``transport.codec: {partial: ...}``, ``runtime/codec/partial.py``):
    ``sums`` then holds tiled-int8 :class:`QuantLeaf` codes of the
    group **mean** (optionally delta'd against the generation
    ``codec_base`` START shard both endpoints hold), and the receiver
    reconstructs f32 sums before folding.  None = raw f32 sums — the
    bit-parity leg."""
    aggregator_id: str
    cluster: int
    group: int                      # group index (canonical position)
    stage: int                      # the one stage this group covers
    round_idx: int = 0
    sums: Any = None                # pytree of f32 weighted sums
    weight: float = 0.0             # total fold weight behind the sums
    dtypes: Any = None              # pytree of original dtype strings
    stat_sums: Any = None           # batch-stats sums (BN models)
    stat_weight: float = 0.0
    stat_dtypes: Any = None
    n_samples: int = 0              # stage-1 samples folded (0 otherwise)
    members: list | None = None     # per-client {client_id, stage, ...}
    level: int = 1                  # tree level that produced this
    codec: str | None = None        # partial codec spec, None = raw f32
    codec_base: int | None = None   # delta base generation, None = plain
    # packed members (codec path only): at 10k clients the per-client
    # member dicts dominate a root partial's bytes — zlib'd pickle
    # (pack_members/unpack_members, ~10x on the repetitive id/key
    # text) keeps the root ingress flat.  Exclusive with ``members``;
    # decode_partial_msg restores the plain list.
    members_z: bytes | None = None


def pack_members(members: list | None) -> bytes | None:
    """crc32-prefixed zlib'd pickle of a PartialAggregate member list
    (the codec'd wire form — see ``PartialAggregate.members_z``)."""
    if not members:
        return None
    body = zlib.compress(
        pickle.dumps(members, protocol=pickle.HIGHEST_PROTOCOL), 6)
    return struct.pack(">I", zlib.crc32(body)) + body


def unpack_members(blob: bytes) -> list:
    """Inverse of :func:`pack_members`: own crc checked BEFORE any
    decompression/unpickling (the outer frame crc already covered
    these bytes, but the blob also crosses aggregator levels — same
    integrity-first discipline as every frame family), then the
    restricted unpickler (member dicts are plain builtins; anything
    else in the blob is rejected like any hostile frame payload)."""
    if len(blob) < 4:
        raise CorruptFrame("packed member list truncated")
    (want,) = struct.unpack_from(">I", blob, 0)
    body = blob[4:]
    if zlib.crc32(body) != want:
        raise CorruptFrame("packed member list checksum mismatch")
    out = _SafeUnpickler(io.BytesIO(zlib.decompress(body))).load()
    if not isinstance(out, list):
        raise CorruptFrame(
            f"packed member list decoded to {type(out).__name__}")
    return out


@dataclasses.dataclass
class AggHello:
    """aggregator node → server (rpc queue): a standalone aggregator
    process announcing itself for adoption (``aggregation.remote``).
    Re-sent on reconnect; liveness afterwards rides the node's
    HEARTBEAT frames like any client's."""
    node_id: str
    capacity: int = 0               # informational (groups it can take)


@dataclasses.dataclass
class AggAssign:
    """server → one aggregator node (its reply queue): the node's
    group assignment for one train_cluster invocation.  ``groups`` is
    a list of plain dicts ``{idx, stage, level, members, parent}``
    (members are client ids at level 1, child group keys above;
    ``parent`` is the parent group's index, None = publish to the
    root's rpc queue).  ``bases`` carries the per-stage START shard
    trees when the partial codec is delta-encoded — both endpoints
    must hold the same base."""
    node_id: str
    cluster: int
    gen: int                        # invocation generation fence
    round_idx: int = 0
    groups: list | None = None
    deadline_s: float = 600.0       # forced-flush deadline from receipt
    codec: str | None = None        # partial codec spec for publishes
    bases: Any = None               # {stage: tree} delta bases
    chunk_bytes: int | None = None  # partial chunking cap


@dataclasses.dataclass
class AggFlush:
    """server → one aggregator node: flush every still-incomplete
    group of generation ``gen`` now (the server gave up waiting on the
    group's stragglers)."""
    node_id: str = ""
    gen: int = 0


@dataclasses.dataclass
class StageHello:
    """stage host → server (rpc queue): a standalone pipeline stage
    host announcing itself for adoption (``pipeline.remote``,
    ``runtime/stagehost.py``).  Re-sent until adopted (an assignment
    arrives); liveness afterwards rides the host's HEARTBEAT frames
    like any client's.  ``capacity`` is informational — how many
    later-stage client slots the host is willing to run."""
    host_id: str
    capacity: int = 0


@dataclasses.dataclass
class StageAssign:
    """server → one stage host (its reply queue): the later-stage
    client slots the host must run.  ``slots`` is a list of plain
    dicts ``{client_id, stage, cluster}`` — the host spins one inner
    protocol client per slot, which REGISTERs under the assigned
    ``client_id`` and then speaks the ordinary choreography (so the
    whole transport/chaos/codec stack composes unchanged).  ``gen``
    carries the server's invocation generation on MID-ROUND
    re-assignment (stage-host death fallback): a re-assigned slot
    reuses the dead host's ``client_id``, so the ShardRunner seed —
    and therefore the fold — is bit-identical to the fault-free
    round."""
    host_id: str
    gen: int = 0
    round_idx: int = 0
    slots: list | None = None


@dataclasses.dataclass
class FleetDigest:
    """aggregator node → server (rpc queue), every
    ``observability.digest-interval`` seconds: one merged health
    summary of the clients whose heartbeats the node consumes from its
    :func:`digest_queue` — exact per-state counts and counter sums,
    log-bucket rate/compute-rate quantile sketches, per-stage step
    stats, the top-K worst stragglers with their last snapshots, and
    the state transitions since the previous digest
    (``runtime/sketch.py``).  ``digest['t']``/``digest['seq']`` are
    the server's staleness guard, same contract as a Heartbeat's: a
    duplicated or reordered digest is rejected-and-counted
    (``stale_digests``), never double-folded.  A plain dict — the
    restricted unpickler's vocabulary stays closed."""
    node_id: str
    round_idx: int = 0
    digest: dict | None = None


@dataclasses.dataclass
class DigestRoute:
    """server → one client (its reply queue): re-point the client's
    heartbeat publishes.  ``queue`` names a :func:`digest_queue`
    (roll up through that aggregator node) or is None (beat directly
    on the rpc queue — the fallback when the client's digest node
    died).  The initial route rides START ``extra['digest']`` so the
    common path costs no extra frame; this message exists for the
    MID-ROUND fallback, where waiting for the next START would leave
    the client beating into a dead node's queue."""
    client_id: str
    queue: str | None = None


@dataclasses.dataclass
class BlackboxDump:
    """server → one participant (its reply queue): flush your flight
    recorder NOW (``runtime/blackbox.py``).  Fanned out to every live
    client / aggregator node / stage host when the FleetMonitor marks
    any participant ``lost`` or a child process exits, so one death
    snapshots the whole fleet's last N seconds of ring events — the
    inputs ``tools/sl_postmortem.py`` assembles into a causal
    root-cause report.  Lifecycle-orthogonal (like Heartbeat): legal
    in every protocol state, consumed by the participants' control
    pumps without touching the round FSM.  ``reason`` names the
    trigger (e.g. ``lost:client_2_1``); ``t_req`` is the server's send
    clock, recorded into each dump so the assembler can align the
    snapshot edge across processes."""
    participant: str
    reason: str = ""
    t_req: float = 0.0


@dataclasses.dataclass
class Heartbeat:
    """client → server, on the rpc queue, from a background thread at
    ``observability.heartbeat-interval``: liveness + a full
    :class:`~split_learning_tpu.runtime.telemetry.TelemetrySnapshot`
    as a plain dict (counters, gauges, histogram digests, current
    round, EWMA samples/s).  The snapshot's monotonic ``seq`` and
    sender clock ``t`` are the server's staleness guard: a duplicated
    or reordered heartbeat must never flap a ``lost`` client back to
    life.  Deliberately small and pickled (SLT1) — it shares the rpc
    queue with UPDATE uploads and must cost ~nothing."""
    client_id: str
    round_idx: int = 0
    telemetry: dict | None = None


# --------------------------------------------------------------------------
# data-plane messages
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Activation:
    """stage k → stage k+1. ``trace`` is the routing stack of client_ids,
    appended per forward hop, popped per backward hop
    (``src/train/VGG16.py:24-31``, ``:41-43``).  ``round_idx`` fences
    rounds: a consumer drops messages stamped with a different round, so
    activations published into a round the server already dropped (elastic
    mid-round PAUSE) can't leak into the next round's batches — the
    reference has no such fence because its queues only ever carry one
    round at a time (it hangs instead of dropping rounds, SURVEY.md §5.3)."""
    data_id: str
    data: Any          # ndarray, or a pytree of ndarrays for models whose
    labels: np.ndarray  # stage boundaries carry extras (e.g. BERT's mask)
    trace: list
    cluster: int
    round_idx: int = 0


@dataclasses.dataclass
class Gradient:
    """stage k+1 → the originating stage-k client."""
    data_id: str
    data: Any   # cotangent, same pytree structure as the Activation.data
    trace: list
    round_idx: int = 0


@dataclasses.dataclass
class EpochEnd:
    """stage k → stage k+1 (strict-SDA only): the feeder has dispatched
    its last batch of this epoch.  DCSL's hard ``sda_size`` window
    drains its leftovers only at epoch end
    (``other/DCSL/src/Scheduler.py:152-191`` processes full windows,
    then the epoch boundary clears the queues); this marker is how the
    head learns the boundary without the server round-trip.  Rides the
    data-plane queues so per-queue FIFO ordering guarantees it arrives
    AFTER every activation it fences.

    In >2-stage plans middle stages PROPAGATE the marker to every
    downstream queue, but only once the full previous-stage quorum of
    copies has arrived (``sda_fence_quorum``): a receiver hears one
    copy per previous-stage device, and only the LAST copy proves —
    via per-queue FIFO — that every activation the fence covers has
    arrived, whichever previous-stage device relayed it."""
    client_id: str
    round_idx: int = 0
    epoch: int = 0


@dataclasses.dataclass
class QuantLeaf:
    """One absmax-quantized float tensor on the data-plane wire:
    ``x ≈ q * scale``.  Deliberately NOT a registered pytree so
    tree_maps over a wire payload treat it as a leaf.

    Two generations share this class:

    * legacy per-tensor form (``transport.wire-dtype: int8``,
      ``src/train/VGG16.py:27`` fp32-pickle contrast): ``q`` int8 with
      the tensor's own shape, ``scale`` a python float
      (``max|x| / 127``), defaults for the rest;
    * tiled codec form (``transport.codec`` quantizers,
      ``runtime/codec/quant.py``): ``q`` is the FLAT padded code array
      — int8 codes, or uint8 with two 4-bit codes per byte when
      ``bits == 4`` — ``scale`` a float32 array with one entry per
      ``tile`` elements, and ``shape`` the original tensor shape.  A
      non-finite payload tile ships a NaN scale so the receiver's NaN
      sentinel still fires after dequantization.
    """
    q: np.ndarray            # codes (see above)
    scale: Any               # float, or float32 ndarray of tile scales
    bits: int = 8            # 8 = one code per byte, 4 = packed pairs
    tile: int = 0            # elements per scale; 0 = per-tensor scalar
    shape: tuple | None = None   # original shape (tiled form only)


@dataclasses.dataclass
class SparseLeaf:
    """One top-k sparsified float tensor on the data-plane wire
    (``transport.codec`` ``topk:<frac>``, ``runtime/codec/sparse.py``):
    flat ``idx`` into the dense tensor, the kept ``val``ues, and the
    dense ``shape`` to scatter back into (zeros elsewhere).  The
    sender's error-feedback residual holds what was not sent.  Like
    QuantLeaf, deliberately NOT a registered pytree."""
    idx: np.ndarray          # int32 flat indices, sorted ascending
    val: np.ndarray          # float32 values at idx
    shape: tuple = ()        # dense shape


@dataclasses.dataclass
class _TensorRef:
    """Placeholder left in a TENSOR frame's pickled skeleton where an
    ndarray leaf was lifted out into the raw out-of-band blob table
    (index into it).  Wire-internal only — never a top-level message."""
    idx: int


CONTROL_TYPES = (Register, Ready, Notify, Update, Start, Syn, Pause,
                 Stop, Heartbeat, PartialAggregate, AggHello, AggAssign,
                 AggFlush, FleetDigest, DigestRoute, StageHello,
                 StageAssign, BlackboxDump)
DATA_TYPES = (Activation, Gradient, EpochEnd)
#: messages whose ndarray payloads ride the zero-copy TENSOR framing
#: (the high-volume data plane + the round's weight uploads — Update
#: and the aggregator tree's PartialAggregate); control messages keep
#: the pickled frame — their payloads are small and their schema
#: churns more
TENSOR_TYPES = (Activation, Gradient, Update, PartialAggregate)
_TYPE_BY_NAME = {t.__name__: t for t in CONTROL_TYPES + DATA_TYPES}
#: nested wire-format helpers (never valid as a top-level message)
_WIRE_HELPERS = {"QuantLeaf": QuantLeaf, "SparseLeaf": SparseLeaf,
                 "_TensorRef": _TensorRef}


# --------------------------------------------------------------------------
# serialization
# --------------------------------------------------------------------------
# Three frame families, dispatched on a 4-byte magic:
#
# * ``SLT1`` — pickled frame: ``MAGIC | crc32(body) | pickle(body)``.
#   Control messages only; a restricted unpickler admits protocol
#   dataclasses + builtins, unlike the reference's bare pickle.loads of
#   broker bytes (SURVEY.md §1 L0).
# * ``SLT2`` — zero-copy TENSOR frame for the data plane
#   (Activation/Gradient/Update): every ndarray leaf is lifted out of
#   the message into a raw out-of-band blob with a fixed binary header
#   (dtype code, flags, shape, crc32, byte length) and decoded with
#   ``np.frombuffer`` straight off the received buffer — no pickle
#   byte-shuffling on the hot path, and the (tiny) pickled skeleton
#   holds only ``_TensorRef`` placeholders.  The meta region opens with
#   an OPTIONAL length-prefixed trace context (``runtime/spans.py``:
#   trace id, sender span id, send timestamp — 32 bytes when tracing,
#   0 otherwise) that links the sender's publish span to the
#   receiver's consume span; it is covered by the outer crc and
#   surfaced on the decoded message as ``msg._ctx`` (opaque bytes).
# * ``SLTC`` — chunk frame: a frame larger than the chunk cap is split
#   into crc'd parts (``encode_parts``) that a :class:`FrameAssembler`
#   reassembles, so one huge UPDATE can't trip the broker's frame cap.
#
# Every family is checksummed end to end: a corrupt or truncated frame
# raises :class:`CorruptFrame` BEFORE any unpickling or np.frombuffer —
# bit-rot on the wire (or an injected chaos fault) must never reach the
# unpickler, whose failure modes on garbage are arbitrary exceptions deep
# inside numpy reconstruction.  In the TENSOR frame the outer crc covers
# the headers + skeleton and each blob carries its OWN crc, so every
# byte is covered exactly once (no double hashing of bulk data).

FRAME_MAGIC = b"SLT1"
TENSOR_MAGIC = b"SLT2"
CHUNK_MAGIC = b"SLTC"
_HDR_LEN = len(FRAME_MAGIC) + 4


class CorruptFrame(pickle.UnpicklingError):
    """Frame failed the integrity check (bad magic / length / checksum).

    Subclasses UnpicklingError so callers guarding decode() with the
    pre-checksum except clause keep working."""


class _SafeUnpickler(pickle.Unpickler):
    _ALLOWED = {
        ("builtins", "dict"), ("builtins", "list"), ("builtins", "tuple"),
        ("builtins", "set"), ("builtins", "frozenset"),
        ("builtins", "complex"), ("builtins", "bytearray"),
        ("numpy", "dtype"), ("numpy", "ndarray"),
        ("ml_dtypes", "bfloat16"),  # compressed wire payloads
        ("numpy._core.multiarray", "_reconstruct"),
        ("numpy.core.multiarray", "_reconstruct"),
        ("numpy._core.multiarray", "scalar"),
        ("numpy.core.multiarray", "scalar"),
        ("numpy._core.numeric", "_frombuffer"),
        ("numpy.core.numeric", "_frombuffer"),
    }

    def find_class(self, module, name):
        if module == "split_learning_tpu.runtime.protocol":
            if name in _TYPE_BY_NAME:
                return _TYPE_BY_NAME[name]
            if name in _WIRE_HELPERS:
                return _WIRE_HELPERS[name]
        if (module, name) in self._ALLOWED:
            return super().find_class(module, name)
        raise pickle.UnpicklingError(
            f"disallowed class in protocol message: {module}.{name}")


def encode_pickled(msg) -> bytes:
    """Legacy pickled frame (``SLT1``) — still what control messages
    use, and kept callable on data messages so the fp32 wire-parity
    test can diff the two framings."""
    if type(msg).__name__ not in _TYPE_BY_NAME:
        raise TypeError(f"not a protocol message: {type(msg)!r}")
    body = pickle.dumps(msg, protocol=pickle.HIGHEST_PROTOCOL)
    return FRAME_MAGIC + struct.pack(">I", zlib.crc32(body)) + body


def _decode_pickled(raw: bytes):
    (want,) = struct.unpack_from(">I", raw, len(FRAME_MAGIC))
    body = raw[_HDR_LEN:]
    if zlib.crc32(body) != want:
        raise CorruptFrame("protocol frame checksum mismatch "
                           f"({len(raw)} bytes)")
    msg = _SafeUnpickler(io.BytesIO(body)).load()
    # wire helpers (QuantLeaf/_TensorRef) are only valid NESTED in a
    # payload — a bare one must fail here, not as an AttributeError in
    # a hot loop
    if not isinstance(msg, CONTROL_TYPES + DATA_TYPES):
        raise pickle.UnpicklingError(
            f"not a protocol message: {type(msg).__name__}")
    return msg


# -- TENSOR frames ----------------------------------------------------------

#: dtype code table — the fixed vocabulary of raw-blob payloads.  bf16
#: is a first-class code (the wire default for activations/gradients);
#: anything outside the table (object arrays, exotic dtypes) stays in
#: the pickled skeleton, which the restricted unpickler still guards.
_DTYPE_BY_CODE: dict[int, np.dtype] = {
    1: np.dtype(np.float32), 2: np.dtype(np.float64),
    3: np.dtype(np.float16), 4: _BF16, 5: np.dtype(np.int8),
    6: np.dtype(np.int16), 7: np.dtype(np.int32),
    8: np.dtype(np.int64), 9: np.dtype(np.uint8),
    10: np.dtype(np.uint16), 11: np.dtype(np.uint32),
    12: np.dtype(np.uint64), 13: np.dtype(np.bool_),
}
_CODE_BY_DTYPE = {dt: c for c, dt in _DTYPE_BY_CODE.items()}

#: per-tensor fixed header: dtype code, flags, ndim, crc32(raw bytes),
#: raw byte length — shape dims (u64 each) follow
_THDR = struct.Struct(">BBHIQ")
#: header ``flags`` bits, set on a QuantLeaf's code blob and
#: cross-checked against the pickled skeleton at decode time — a
#: skeleton/blob disagreement (bit rot the crc math happened to
#: forgive, or a crafted skeleton) is rejected as corrupt instead of
#: being mis-dequantized:
TENSOR_FLAG_PACKED4 = 0x01   # two 4-bit codes per byte (bits == 4)
TENSOR_FLAG_TILED = 0x02     # per-tile scales (tile > 0)
_MAX_NDIM = 32
_MAX_TENSORS = 1 << 20


def _blob(a: np.ndarray):
    """Contiguous little-endian buffer view of one array (no copy when
    the array already is one)."""
    a = np.ascontiguousarray(a)
    if a.dtype.byteorder == ">":
        a = a.astype(a.dtype.newbyteorder("<"))
    try:
        return a, memoryview(a).cast("B")
    except (TypeError, ValueError):   # dtype without buffer support
        return a, a.tobytes()


#: trace-context sanity cap: today's context is 32 bytes; the u8 cap
#: bounds what a corrupt length field can make the decoder slice
_MAX_CTX_BYTES = 255


def _encode_tensor(msg, ctx: bytes = b"") -> bytes:
    if len(ctx) > _MAX_CTX_BYTES:
        raise ValueError(f"trace context of {len(ctx)} bytes exceeds "
                         f"the {_MAX_CTX_BYTES}-byte cap")
    tensors: list = []
    tflags: list[int] = []

    def strip(o, flags: int = 0):
        if isinstance(o, np.ndarray) and o.dtype in _CODE_BY_DTYPE:
            tensors.append(o)
            tflags.append(flags)
            return _TensorRef(len(tensors) - 1)
        if isinstance(o, QuantLeaf):
            qf = ((TENSOR_FLAG_PACKED4 if o.bits == 4 else 0)
                  | (TENSOR_FLAG_TILED if o.tile else 0))
            return QuantLeaf(q=strip(o.q, qf), scale=strip(o.scale),
                             bits=o.bits, tile=o.tile, shape=o.shape)
        if isinstance(o, SparseLeaf):
            return SparseLeaf(idx=strip(o.idx), val=strip(o.val),
                              shape=o.shape)
        if isinstance(o, dict):
            return {k: strip(v) for k, v in o.items()}
        if isinstance(o, list):
            return [strip(v) for v in o]
        if isinstance(o, tuple):
            return tuple(strip(v) for v in o)
        return o

    skel = type(msg)(**{f.name: strip(getattr(msg, f.name))
                        for f in dataclasses.fields(msg)})
    skel_bytes = pickle.dumps(skel, protocol=pickle.HIGHEST_PROTOCOL)

    headers: list[bytes] = []
    blobs: list = []
    for a, fl in zip(tensors, tflags):
        a, buf = _blob(a)
        headers.append(
            _THDR.pack(_CODE_BY_DTYPE[a.dtype], fl, a.ndim,
                       zlib.crc32(buf), a.nbytes)
            + struct.pack(f">{a.ndim}Q", *a.shape))
        blobs.append(buf)
    meta = (struct.pack(">H", len(ctx)) + ctx
            + struct.pack(">I", len(tensors)) + b"".join(headers)
            + struct.pack(">I", len(skel_bytes)) + skel_bytes)
    return b"".join([TENSOR_MAGIC, struct.pack(">I", zlib.crc32(meta)),
                     meta, *blobs])


def _decode_tensor(raw: bytes):
    view = memoryview(raw)
    try:
        (want,) = struct.unpack_from(">I", raw, 4)
        off = 8
        (ctx_len,) = struct.unpack_from(">H", raw, off)
        off += 2
        if ctx_len > _MAX_CTX_BYTES or off + ctx_len > len(raw):
            raise CorruptFrame(f"tensor frame claims {ctx_len}-byte "
                               "trace context")
        ctx = raw[off:off + ctx_len]
        off += ctx_len
        (n_tensors,) = struct.unpack_from(">I", raw, off)
        off += 4
        if n_tensors > _MAX_TENSORS:
            raise CorruptFrame(f"tensor frame claims {n_tensors} tensors")
        hdrs = []
        for _ in range(n_tensors):
            code, flags, ndim, bcrc, nbytes = _THDR.unpack_from(raw, off)
            off += _THDR.size
            if ndim > _MAX_NDIM:
                raise CorruptFrame(f"tensor frame claims ndim={ndim}")
            shape = struct.unpack_from(f">{ndim}Q", raw, off)
            off += 8 * ndim
            hdrs.append((code, flags, shape, bcrc, nbytes))
        (skel_len,) = struct.unpack_from(">I", raw, off)
        off += 4
        if off + skel_len > len(raw):
            raise CorruptFrame("tensor frame skeleton truncated")
        skel = raw[off:off + skel_len]
        off += skel_len
    except struct.error as e:
        raise CorruptFrame(f"tensor frame header truncated: {e}") from None
    # integrity BEFORE np.frombuffer / unpickling: meta (headers +
    # skeleton) under the outer crc, each raw blob under its own
    if zlib.crc32(view[8:off]) != want:
        raise CorruptFrame("tensor frame meta checksum mismatch "
                           f"({len(raw)} bytes)")
    if len(raw) - off != sum(h[4] for h in hdrs):
        raise CorruptFrame("tensor frame blob region length mismatch")
    arrays = []
    flags_of: list[int] = []
    for code, flags, shape, bcrc, nbytes in hdrs:
        dt = _DTYPE_BY_CODE.get(code)
        if dt is None:
            raise CorruptFrame(f"unknown tensor dtype code {code}")
        count, rem = divmod(nbytes, dt.itemsize)
        if rem or math.prod(shape) != count:
            raise CorruptFrame("tensor header shape/length mismatch")
        if zlib.crc32(view[off:off + nbytes]) != bcrc:
            raise CorruptFrame("tensor blob checksum mismatch")
        arrays.append(np.frombuffer(raw, dtype=dt, count=count,
                                    offset=off).reshape(shape))
        flags_of.append(flags)
        off += nbytes
    msg = _SafeUnpickler(io.BytesIO(skel)).load()
    if not isinstance(msg, TENSOR_TYPES):
        raise pickle.UnpicklingError(
            f"not a tensor-frame message: {type(msg).__name__}")

    def fill(o):
        if isinstance(o, _TensorRef):
            if not 0 <= o.idx < len(arrays):
                raise CorruptFrame(f"tensor ref {o.idx} out of range")
            return arrays[o.idx]
        if isinstance(o, QuantLeaf):
            # the skeleton's quantizer parameters must agree with the
            # flags stamped on the code blob's header (both are under
            # the outer crc, but a crafted frame can lie in one place)
            if isinstance(o.q, _TensorRef) \
                    and 0 <= o.q.idx < len(flags_of):
                want = ((TENSOR_FLAG_PACKED4 if o.bits == 4 else 0)
                        | (TENSOR_FLAG_TILED if o.tile else 0))
                if flags_of[o.q.idx] != want:
                    raise CorruptFrame(
                        "quantized blob flags disagree with skeleton "
                        f"(header {flags_of[o.q.idx]:#x}, skeleton "
                        f"bits={o.bits} tile={o.tile})")
            return QuantLeaf(q=fill(o.q), scale=fill(o.scale),
                             bits=o.bits, tile=o.tile, shape=o.shape)
        if isinstance(o, SparseLeaf):
            idx, val = fill(o.idx), fill(o.val)
            # bounds-check HERE, where decode errors are caught and
            # counted (client._decode) — not at densify time on the
            # training thread, where an uncaught CorruptFrame would
            # kill the process a crafted frame should only cost one
            # message
            n = int(math.prod(o.shape)) if o.shape else 1
            if isinstance(idx, np.ndarray):
                if np.shape(idx) != np.shape(val):
                    raise CorruptFrame("sparse leaf idx/val length "
                                       "mismatch")
                if idx.size and (int(idx.min()) < 0
                                 or int(idx.max()) >= n):
                    raise CorruptFrame(
                        f"sparse leaf index out of range for shape "
                        f"{o.shape}")
            return SparseLeaf(idx=idx, val=val, shape=o.shape)
        if isinstance(o, dict):
            return {k: fill(v) for k, v in o.items()}
        if isinstance(o, list):
            return [fill(v) for v in o]
        if isinstance(o, tuple):
            return tuple(fill(v) for v in o)
        return o

    out = type(msg)(**{f.name: fill(getattr(msg, f.name))
                       for f in dataclasses.fields(msg)})
    if ctx_len:
        # opaque tracing sidecar, NOT a message field: consumers read it
        # via getattr so control frames (no attribute) degrade to None
        out._ctx = bytes(ctx)
    return out


def encode(msg, ctx: bytes = b"") -> bytes:
    """One complete frame: TENSOR framing for the data-plane payload
    types, the pickled frame for everything else.  ``ctx`` (an opaque
    trace context, ``runtime/spans.py``) rides the TENSOR meta header;
    the legacy pickled framing ignores it — SLT1 bytes stay bit-stable
    for the fp32 parity contract."""
    if type(msg).__name__ not in _TYPE_BY_NAME:
        raise TypeError(f"not a protocol message: {type(msg)!r}")
    if isinstance(msg, TENSOR_TYPES):
        return _encode_tensor(msg, ctx)
    return encode_pickled(msg)


def decode(raw: bytes):
    """Decode one COMPLETE frame (either framing).  Chunk frames only
    make sense inside a :class:`FrameAssembler`."""
    if len(raw) < _HDR_LEN:
        raise CorruptFrame(
            f"protocol frame missing magic/header ({len(raw)} bytes)")
    magic = raw[:4]
    if magic == TENSOR_MAGIC:
        return _decode_tensor(raw)
    if magic == CHUNK_MAGIC:
        raise CorruptFrame("chunk frame outside a FrameAssembler")
    if magic != FRAME_MAGIC:
        raise CorruptFrame(
            f"protocol frame missing magic/header ({len(raw)} bytes)")
    return _decode_pickled(raw)


# -- chunking ---------------------------------------------------------------

#: one frame's on-the-wire size cap before it is split into SLTC chunks
#: (config: ``transport.chunk-mb``).  Sized well under the broker's
#: 8 GiB frame sanity cap so a giant UPDATE can't kill the connection.
DEFAULT_CHUNK_BYTES = 512 << 20
_CHUNK_HDR = 16 + 8 + 2        # uuid | u32 idx | u32 total | u16 ctx-len
_MAX_CHUNKS = 1 << 16

#: assembled-frame sanity cap, the chunked twin of the broker's
#: per-frame cap (``runtime/bus.py MAX_FRAME_BYTES``): the broker
#: checks each frame's length prefix, but an SLTC-chunked message is
#: many legal frames whose ASSEMBLED size the broker never sees — a
#: corrupt/hostile chunk stream could drive an arbitrarily large
#: reassembly allocation.  Reassembly happens at the ENDPOINTS
#: (server/client/aggregator processes), so the operable knob is the
#: ``SLT_MAX_ASSEMBLED_GB`` env var set on each endpoint process —
#: the broker's ``--max-frame-gb`` cannot reach their
#: FrameAssemblers.  Exceeding the cap is a counted corrupt frame
#: (``oversize_frames``), not a process death.
try:
    MAX_ASSEMBLED_BYTES = int(
        float(os.environ.get("SLT_MAX_ASSEMBLED_GB", "8")) * (1 << 30))
except ValueError:
    MAX_ASSEMBLED_BYTES = 1 << 33


def encode_parts(msg, max_bytes: int | None = None,
                 ctx: bytes = b"") -> list[bytes]:
    """Encode into one or more publishable frames: a single complete
    frame when it fits ``max_bytes``, else crc'd SLTC chunks carrying a
    shared message id.  Per-queue FIFO (which every transport layer
    preserves, reliable included) is what keeps a message's chunks
    together; out-of-order arrival within the id is still handled.

    ``ctx`` (trace context) rides the inner TENSOR frame AND every
    chunk header, so a receiver can attribute chunk arrivals to the
    sender's publish span without waiting for reassembly."""
    if len(ctx) > _MAX_CTX_BYTES:
        raise ValueError(f"trace context of {len(ctx)} bytes exceeds "
                         f"the {_MAX_CTX_BYTES}-byte cap")
    frame = encode(msg, ctx)
    cap = int(max_bytes) if max_bytes else DEFAULT_CHUNK_BYTES
    if len(frame) <= cap:
        return [frame]
    mid = uuid.uuid4().bytes
    total = -(-len(frame) // cap)
    if total > _MAX_CHUNKS:
        raise ValueError(f"frame of {len(frame)} bytes needs {total} "
                         f"chunks (cap {_MAX_CHUNKS})")
    parts = []
    for idx in range(total):
        body = (mid + struct.pack(">II", idx, total)
                + struct.pack(">H", len(ctx)) + ctx
                + frame[idx * cap:(idx + 1) * cap])
        parts.append(CHUNK_MAGIC + struct.pack(">I", zlib.crc32(body))
                     + body)
    return parts


class FrameAssembler:
    """Per-consumer reassembly of SLTC chunk streams.

    ``feed`` returns the decoded message once complete (immediately for
    unchunked frames), or None while a chunked message is still
    partial.  Bounded: at most ``max_pending`` partial messages are
    held — on an at-most-once transport a dropped chunk strands its
    message, and the stalest partial is evicted rather than leaking.
    Not thread-safe: give each consumer thread its own assembler (same
    ownership rule as a transport connection).

    ``last_bytes`` holds the wire byte count of the most recently
    COMPLETED message (all its chunks for an SLTC stream) — how a
    consumer attributes ingress bytes to a decoded message without
    re-measuring the chunk stream."""

    def __init__(self, max_pending: int = 64, faults=None):
        self._max_pending = max_pending
        self._faults = faults
        self.last_bytes = 0
        self._pending: collections.OrderedDict = collections.OrderedDict()
        # mids whose partial was evicted: their LATE chunks must be
        # dropped, not allowed to recreate a can-never-complete partial
        # that would occupy a slot and evict further live messages
        self._evicted: collections.OrderedDict = collections.OrderedDict()

    def _count_oversize(self) -> None:
        if self._faults is None:
            from split_learning_tpu.runtime.trace import (
                default_fault_counters,
            )
            self._faults = default_fault_counters
        self._faults.inc("oversize_frames")

    def feed(self, raw: bytes):
        if raw[:4] != CHUNK_MAGIC:
            if len(raw) > MAX_ASSEMBLED_BYTES:
                self._count_oversize()
                raise CorruptFrame(
                    f"frame of {len(raw)} bytes exceeds the "
                    f"{MAX_ASSEMBLED_BYTES}-byte assembled cap")
            self.last_bytes = len(raw)
            return decode(raw)
        if len(raw) < _HDR_LEN + _CHUNK_HDR:
            raise CorruptFrame(f"chunk frame truncated ({len(raw)} bytes)")
        (want,) = struct.unpack_from(">I", raw, 4)
        body = memoryview(raw)[8:]
        if zlib.crc32(body) != want:
            raise CorruptFrame("chunk frame checksum mismatch")
        mid = bytes(body[:16])
        idx, total = struct.unpack_from(">II", body, 16)
        if not 0 < total <= _MAX_CHUNKS or idx >= total:
            raise CorruptFrame(f"chunk index {idx}/{total} out of range")
        (ctx_len,) = struct.unpack_from(">H", body, 24)
        if ctx_len > _MAX_CTX_BYTES or _CHUNK_HDR + ctx_len > len(body):
            raise CorruptFrame(f"chunk frame claims {ctx_len}-byte "
                               "trace context")
        ctx = bytes(body[_CHUNK_HDR:_CHUNK_HDR + ctx_len])
        if mid in self._evicted:
            return None
        ent = self._pending.get(mid)
        if ent is None:
            ent = self._pending[mid] = {"total": total, "parts": {},
                                        "ctx": ctx, "bytes": 0}
            while len(self._pending) > self._max_pending:
                dead, _ = self._pending.popitem(last=False)
                self._evicted[dead] = True
                while len(self._evicted) > 4 * self._max_pending:
                    self._evicted.popitem(last=False)
        if ent["total"] != total:
            raise CorruptFrame("chunk total mismatch within message")
        if idx not in ent["parts"]:
            ent["parts"][idx] = bytes(body[_CHUNK_HDR + ctx_len:])
            ent["bytes"] += len(raw)
            # the broker's frame cap is per FRAME; a chunked message's
            # ASSEMBLED size must honor the same bound or a legal chunk
            # stream smuggles an arbitrarily large allocation past it
            if ent["bytes"] > MAX_ASSEMBLED_BYTES:
                del self._pending[mid]
                self._evicted[mid] = True
                self._count_oversize()
                raise CorruptFrame(
                    f"chunked message exceeds the "
                    f"{MAX_ASSEMBLED_BYTES}-byte assembled cap "
                    f"({ent['bytes']} bytes across "
                    f"{len(ent['parts'])}/{total} chunks)")
        if len(ent["parts"]) < total:
            return None
        del self._pending[mid]
        self.last_bytes = ent["bytes"]
        msg = decode(b"".join(ent["parts"][i] for i in range(total)))
        if ent["ctx"] and getattr(msg, "_ctx", None) is None:
            # chunked legacy frame: the chunk headers carried the only
            # copy of the context (TENSOR frames restore their own)
            msg._ctx = ent["ctx"]
        return msg
