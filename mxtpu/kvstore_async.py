"""Asynchronous parameter service — the real 'dist_async' mode.

The reference's ``dist_async`` lets the ps-lite server apply each worker's
push the moment it arrives (``src/kvstore/kvstore_dist_server.h:339,462``
``DataHandleDefault``: ``if (sync_mode_) merge-then-update else update``),
with no cross-worker merge barrier. Workers run free: a straggler's pushes
land late (stale) but never block the fleet. That capability has no SPMD
analogue — XLA collectives are barriers by construction — so it gets its
own host-side rendering here:

* :class:`ParameterServer` — a threaded TCP service owning the parameter
  table (ps-lite's ZeroMQ transport rendered with the standard library:
  length-prefixed pickle frames, one daemon thread per connection). The
  optimizer runs server-side the moment a push arrives (the reference's
  server-side updater, ``kvstore_dist_server.h:150-196``), under a per-key
  lock; different keys update concurrently.
* :class:`AsyncDistKVStore` — the worker-side ``create('dist_async')``
  store. ``push`` ships the locally-merged gradient and returns; ``pull``
  fetches whatever the table holds right now. No collective, no barrier,
  no lockstep: workers see each other only through the table.

Staleness is observable, not just implied: every pull carries the key's
update clock, every push carries the clock the worker last based its step
on, and the server records ``staleness = clock_now - clock_base`` per
push (``stats()``/``kv.staleness_stats()``). The nightly straggler test
(tests/nightly/async_worker.py) asserts fast workers outrun a slow one
and that observed staleness > 0 — the behavior sync mode cannot produce.

Key sharding across multiple servers mirrors ps-lite's key→server
assignment: each key lives on ``servers[crc32(key) % n]``; servers are
independent and never talk to each other. A shared
:class:`mxtpu.partition.PartitionRules` spec (``set_partition_rules``)
refines this: keys a rule matches co-locate on their rule group's
shard — the same grouping that drives ShardedTrainer mesh placement
and CheckpointManager layout (ISSUE 10's one-spec-three-layouts).

``push_pull`` fuses apply + read-back into ONE round trip per part
(the reference's ps-lite PushPull, op ``pushpull``): the server
applies the gradient and replies with the post-update value — the
per-batch wire op of the fused Module dist step. Common optimizers
apply on a numpy host mirror (``Optimizer.update_host``) so the
server's per-push cost is arithmetic, not device dispatch. Big arrays additionally split
into row-contiguous parts (the reference's
``MXNET_KVSTORE_BIGARRAY_BOUND`` key splits, ``kvstore_dist.h:500-540``;
bound here via ``MXTPU_KVSTORE_BIGARRAY_BOUND``, default 1e6 elements):
each part is an independent subkey with its own server assignment, lock,
clock, and optimizer-state slot — sound because every built-in optimizer
update is elementwise, so updating row-slices independently computes the
same result as the whole array. Parts move concurrently over a worker
thread pool, so a push/pull of a 100 MB table pipelines across servers
instead of serializing through one socket. ``tools/launch.py -s N``
starts N server processes (DMLC_ROLE=server) and exports
``MXTPU_PS_ADDRS`` to every worker.

Row-sparse fast path (ISSUE 13): giant embedding tables where each
worker touches a few thousand rows per step ride ``sparse_push_pull``
(wire op ``spushpull``; push-only form ``spush``) — frames carry
``(row_ids, rows)`` instead of the full table, the server applies with
the ROW-WISE optimizer mirror (``Optimizer.update_host_rows`` for
sgd/adagrad/adam: only touched rows pay optimizer cost; anything else
densifies the gradient and stays correct), and the reply gathers the
same rows' post-update values in kind — one round trip per row-range
part, wire bytes scaling with rows touched, never with table size. The
part machinery above doubles as the sharding story: a table bigger
than one server's memory splits into row-range parts whose subkeys
spread across shards (``PartitionRules.mark_row_sharded`` distributes
a rule group's parts round-robin instead of co-locating them), sparse
frames fan out to the row-range owners and reassemble with ONE batched
device_put. Seq-deduped replays answer with current row values; sparse
records forward on the replication stream and move through
``("split", dst)`` handoffs exactly-once like any other update. bf16
rows (``MXTPU_AMP``) upcast into the fp32 master table and replies
ride bf16 in kind. ``ci/check_embedding_perf.py`` pins the bytes/step
scaling.

Wire compression: ``set_gradient_compression({'type': '2bit'})`` makes
``push`` ship the 2-bit packed form (16x smaller) with a per-part
worker-side error-feedback residual; the server dequantizes before its
update — the reference's compressed-push pipeline
(``kvstore_dist.h`` PushCompressed) rendered over this transport.

Trust model: the wire format is pickle, so the service must only be
reachable by processes of the same launch — it binds loopback by
default, and ``tools/launch.py`` additionally exports a per-launch
shared secret (``MXTPU_PS_TOKEN``); when set, every connection must
present it in an ``auth`` frame before any other command, and failed
auth closes the socket without unpickling anything further. Do not
expose the port beyond hosts you trust with code execution.

Single-process use (no launcher env) spins up an in-process server
thread, so ``create('dist_async')`` is runnable — and genuinely
asynchronous across threads — everywhere.

Fault tolerance
---------------
The transport assumes connections die mid-conversation and servers crash
mid-epoch (ps-lite only *counted* such deaths via ``NumDeadNodes``; here
each failure has an exercised recovery path — see
``docs/fault_tolerance.md`` and ``tests/test_fault_tolerance.py``):

* **Retry/backoff RPC.** Every request carries a per-call socket timeout
  (``MXTPU_PS_TIMEOUT``) and idempotent commands are retried up to
  ``MXTPU_PS_RETRIES`` times with bounded exponential backoff
  (``MXTPU_PS_BACKOFF`` .. ``MXTPU_PS_BACKOFF_MAX``) plus a
  deterministic per-server jitter. A failed socket is closed, never
  reused (a stale reply must not mispair), and reconnected lazily.
* **At-most-once pushes.** A push acked after the connection died would
  double-apply when replayed, so every push carries an
  ``(origin, seq)`` pair — origin is unique per store instance, seq is
  monotone — and the server skips (but acks) any seq it has already
  applied for that origin+key. The seq table rides in the server
  snapshot, so dedupe survives a server restart.
* **Liveness.** A background heartbeat thread pings each server every
  ``MXTPU_PS_HEARTBEAT`` seconds (0 disables); ``MXTPU_PS_DEAD_AFTER``
  consecutive failures mark it dead. ``kv.health()`` reports per-server
  state + ``num_dead`` (the ps-lite ``NumDeadNodes`` analogue, also via
  ``kv.get_num_dead_node()``); recovery is detected by the same probe
  and re-marks the server ok.
* **Graceful degradation.** A ``pull`` whose shard is dead returns the
  worker's last-pulled value for that part instead of raising; the key
  is staleness-marked in ``kv.degraded_keys()`` / ``health()`` until a
  live pull succeeds. A ``push`` to a dead shard is buffered (bounded
  by ``MXTPU_PS_PENDING_MAX``) and replayed in order — with its
  original seq, so replays stay at-most-once — when the heartbeat sees
  the server again.
* **Auto-resume.** With ``MXTPU_PS_SNAPSHOT_DIR`` set (or
  ``snapshot_dir=``), the server snapshots its table, clocks, dedupe
  seqs and optimizer through :class:`~mxtpu.checkpoint.CheckpointManager`
  every ``MXTPU_PS_SNAPSHOT_EVERY`` pushes, and a restarting server
  restores from the latest snapshot — ``tools/launch.py --ps-respawn``
  wires the respawn so workers reconverge with no operator action.
* **Worker liveness.** The health story runs both ways: every store
  registers with its servers (``hello`` with origin+rank), heartbeat
  probes refresh the lease, and ``close()`` departs cleanly (``bye``).
  Servers keep per-worker push/staleness/step-gap counters — surfaced
  through ``kv.stats()``/``kv.health()`` with a push-count straggler
  verdict (``MXTPU_PS_STRAGGLER_FACTOR``/``_MIN``) — and garbage-
  collect a worker silent past ``MXTPU_PS_WORKER_DEAD_AFTER`` (its
  membership and buffered dedupe seqs; 0 disables). Barriers carry a
  deadline (``MXTPU_PS_BARRIER_TIMEOUT``): a barrier a dead worker can
  never complete force-releases with a logged, counted timeout instead
  of hanging the fleet.
* **Fault injection.** :mod:`mxtpu.fault` (``MXTPU_FAULT_SPEC``) can
  deterministically drop/delay/truncate/sever frames at either side of
  the wire, kill servers on schedule — and, for the worker-side story,
  poison a training step's gradients (``nan_grad``), stall a worker
  (``stall``) or SIGKILL it (``kill_worker``) at exact step numbers;
  the fault-matrix tests drive every path above through it.

Replication & failover
----------------------
Everything above still loses state when a server dies for good: pulls
degrade to stale cached values and ``--ps-respawn`` restores the
*latest snapshot*, discarding every acknowledged push since it was
taken. ``MXTPU_PS_REPLICAS=2`` closes that hole with the OSDI'14
parameter-server replication design (chain replication with a chain of
two): each key shard is a (primary, backup) pair.

* The primary applies each update, then forwards the RAW wire record
  over a dedicated replication stream (``op=repl`` frames with their
  own correlation ids and a monotone per-stream seq the backup dedupes
  on), so the backup replays the exact update — server-side optimizer
  math included — bit for bit.
* ``MXTPU_PS_REPL_MODE=sync`` (default): the worker's ack is withheld
  until the backup acked the forwarded record — a ``kill -9``'d
  primary loses ZERO acknowledged pushes. ``async``: ack immediately,
  forwarding lag bounded by ``MXTPU_PS_REPL_LAG_MAX`` records.
* Clients learn the shard→(primary, backup) map at ``hello`` and, on a
  primary death (failed window or heartbeat probe), promote the backup
  and fail over IN PLACE — no stale-pull window, no buffered-push
  limbo; un-acked pushes replay against the promoted table and its
  transferred dedupe seqs keep them at-most-once.
* A respawned server finds its promoted peer at boot, demotes itself,
  and rejoins as the new backup: the primary streams its full state
  (table + clocks + dedupe seqs, each key snapshotted under its lock)
  as ``xfer`` records followed by ``catchup_done``, after which the
  pair is redundant again. ``kv.health()['replication']`` shows role,
  promotions, forwarding lag and catch-up progress throughout.

Elasticity
----------
The fleet is not fixed at launch: workers join and leave mid-run and a
hot key shard can be split across servers online (the ps-lite promise —
nodes come and go — made operable; see docs/fault_tolerance.md
"Elasticity"):

* **Worker join/leave.** A joining worker simply creates a store: its
  ``hello`` registers membership (counted in ``stats()['elastic']``),
  it pulls current params, and it takes data-shard assignments from the
  server-owned cursor below. A departing worker's ``bye`` (or its
  liveness GC) releases its assignments. With ``MXTPU_PS_ELASTIC=1``
  barriers count against the CURRENT membership, re-evaluated on every
  join/leave — a departed worker releases the survivors by re-count
  (``stats()['barrier_recounts']``) instead of by the
  ``MXTPU_PS_BARRIER_TIMEOUT`` deadline.
* **Server-owned data cursor.** ``kv.shard_cursor(epoch, num_shards)``
  iterates data-shard indices handed out by server 0's epoch-sharded
  cursor: each shard is assigned exactly once per epoch (assignment
  replies are replay-deduped), a finished shard is acknowledged, and a
  dead/departed worker's outstanding shards are re-queued for the
  survivors — ``fit``-style loops stop assuming a static rank/size.
* **Online shard split.** The operator command ``("split", dst_addr)``
  (``tools/launch.py --scale``, ``python -m mxtpu.kvstore_async
  --admin split``) hands half of a hot server's keys — hotness-ordered
  by applied-update clocks — to ``dst_addr``. Each key moves atomically
  under its key lock with its full state (value, clock, push-dedupe
  seqs, accumulated per-key updater state) via an ``adopt_key``
  transfer that reuses the catch-up state-transfer semantics; on a
  replicated destination the ack implies the new shard's OWN backup
  holds the key, so the old primary releases it only once it is
  replicated again. Requests for a moved key are refused with
  ``map_stale`` naming the new home — a routing verdict, not a failure:
  the client records the forwarding override, re-fetches the versioned
  shard map (pushed on hello and heartbeat), and replays there, where
  the transferred dedupe seqs keep the replay at-most-once. A split
  interrupted mid-way leaves a clean prefix moved and the rest owned —
  re-issuing the split resumes it; nothing acknowledged is lost.

Fast path
---------
The data path is built for throughput on top of those fault semantics
(ps-lite's levers — zero-copy scatter-gather, many requests per
connection, message coalescing — rendered here; its counts are pinned
by ``ci/check_comms_perf.py``, docs/perf_analysis.md "Comms fast path"):

* **Zero-copy wire.** Sends are scatter-gather (``socket.sendmsg`` over
  the frame head + each pickle-5 out-of-band buffer), so an N-byte
  gradient leaves the worker without ever being concatenated; receives
  land every buffer of a frame in one preallocated blob (one
  ``recv_into`` stream, buffers are memoryview slices of it), so the
  server applies straight out of the wire buffer.
* **Request pipelining.** Every frame carries a correlation id and each
  socket runs a bounded in-flight window (``MXTPU_PS_WINDOW``, default
  8): sends and receives are decoupled, so the k parts of a big array
  stream back-to-back instead of paying one RTT each. Any failure —
  socket error, injected sever, a waiter's timeout — fails the whole
  unacked window onto the retry layer, whose replays the push seq
  dedupe keeps at-most-once.
* **Small-key coalescing.** Parts at or below ``MXTPU_PS_COALESCE_BYTES``
  (default 16 KiB) within one push/pull call batch into one multi-key
  frame per server (the bigarray bound's dual: tiny embedding/bias keys
  must not pay a full frame + dispatch each); compressed payloads ride
  the same frames.
* **Host-side apply.** The server table is plain numpy: the no-updater
  accumulate is one in-place ``np.add`` per push straight from the wire
  buffer (no device round trip), and pulls of updater-managed keys hand
  out the immutable post-update buffer with zero copies.
* **Same-process shortcut.** A worker whose server lives in THIS
  process (single-process mode, loopback benches) skips socket and
  pickle entirely — ps-lite's local/intra-node path: the request is
  applied by direct dispatch under the same per-key locks, seq dedupe
  and fault-injection points, so a 64 MB push costs one in-place
  ``np.add`` and nothing else. ``MXTPU_PS_LOCAL=0`` forces the wire
  (the fault matrix pins it off so every row exercises real framing;
  note the shortcut also bypasses the ``MXTPU_PS_TOKEN`` preamble —
  a same-process peer already runs our code).
* **Half-width wire (AMP).** With ``MXTPU_AMP=bf16`` the fused Module
  step ships bf16 gradients — the payload array's dtype IS the wire
  tag. ``_wire_decode`` upcasts into the server's fp32 MASTER table
  (accumulate and the host-mirror optimizer always apply full
  precision), ``pushpull`` replies bf16 in kind, and the client's
  ``_assemble_pulled`` restores the pull target's dtype before the
  one batched device_put — both directions halve (~0.50x bytes/step,
  ``ci/check_module_perf.py --amp``). Replays are dtype-stable
  through the seq dedupe; GradientCompression wins the format contest
  when installed (2 bits beat 16 — compressed parts arrive fp32).
* **Counters.** ``kv.stats()`` reports wire bytes/frames, coalescing,
  the in-flight high-water mark and retransmits — ``ci/
  check_comms_perf.py`` pins the overhead without wall-clock timing.
"""
from __future__ import annotations

import io
import itertools
import logging
import os
import pickle
import re
import socket
import socketserver
import struct
import sys
import threading
import time
import zlib

import uuid

import numpy as _np
import jax
import jax.numpy as jnp

from . import fault as _fault
from . import ndarray as nd
from . import obs as _obs
from .devtools import consistency as _consistency
from .kvstore import KVStore, _ctype_key_value, _key_int


class _ModuleUnpickler(pickle.Unpickler):
    """Unpickler that resolves classes through sys.modules before
    falling back to __import__. The server handler threads run while the
    ``mxtpu`` package import may still be in progress (the
    DMLC_ROLE=server hook blocks inside _optional_imports), and a plain
    ``__import__("mxtpu.optimizer")`` from another thread would wait on
    the package's _initializing lock forever; already-loaded modules
    need no import machinery at all."""

    def find_class(self, module, name):
        m = sys.modules.get(module)
        if m is not None:
            return getattr(m, name)
        return super().find_class(module, name)

__all__ = ["ParameterServer", "AsyncDistKVStore", "serve_forever"]

_log = logging.getLogger(__name__)

_LEN = struct.Struct("<Q")

# ps-lite's MXNET_KVSTORE_BIGARRAY_BOUND analogue: arrays above this many
# elements split into row-contiguous parts, each its own subkey
_BIGARRAY_BOUND = int(os.environ.get(
    "MXTPU_KVSTORE_BIGARRAY_BOUND", "1000000"))

_GC_MARK = "gc2bit"  # wire tag for a 2-bit-compressed push payload
_SP_MARK = "sprows"  # pending-buffer tag for a row-sparse push: the
#                      payload slot holds (_SP_MARK, row_ids, rows) and
#                      _flush_pending replays it as an ``spush``

# pipelined-window size: how many requests may ride one socket
# unacknowledged. Correlation ids pair replies to waiters, so the k
# parts of a big push stream back-to-back instead of paying an RTT each
# (ps-lite keeps many requests in flight per connection the same way).
_WINDOW = int(os.environ.get("MXTPU_PS_WINDOW", "8"))

# pushes/pulls whose payload is at most this many bytes coalesce into
# one multi-key frame per server within a push/pull call — the bigarray
# bound's dual: tiny embedding/bias keys must not pay a full frame +
# dispatch each. 0 disables coalescing.
_COALESCE_BYTES = int(os.environ.get("MXTPU_PS_COALESCE_BYTES", "16384"))

_COALESCE_MAX = 512   # sub-commands per multi frame (stays far under
#                       the receiver's 4096 buffer-count guard)

_IOV_MAX = 512        # iovecs per sendmsg call (Linux caps at 1024)

# same-process shortcut (ps-lite's local/intra-node path): a worker
# whose server lives in THIS process — single-process mode, loopback
# benches — skips socket and pickle entirely and applies requests by
# direct dispatch under the same locks, dedupe and fault-injection
# points as a wire request. MXTPU_PS_LOCAL=0 forces the wire (the
# fault-matrix tests pin it off so every row exercises real framing).
_LOCAL_ON = os.environ.get("MXTPU_PS_LOCAL", "1") != "0"
_LOCAL_SERVERS = {}        # "host:port" -> in-process ParameterServer
_LOCAL_GUARD = threading.Lock()

# -- primary/backup shard replication (module docstring, "Replication").
# MXTPU_PS_REPLICAS=2 pairs every key shard with a backup server; the
# primary forwards applied updates over the replication stream and, in
# sync mode (default), acks a push only after the backup acked the
# forwarded copy — a kill -9'd primary then loses zero acknowledged
# updates. async mode acks immediately and bounds the forwarding lag.
_REPLICAS = int(os.environ.get("MXTPU_PS_REPLICAS", "1"))
_REPL_MODE = os.environ.get("MXTPU_PS_REPL_MODE", "sync")
# async mode: max update records in flight to the backup before the
# push path blocks until the stream drains below it (the bounded-lag
# rule)
_REPL_LAG_MAX = int(os.environ.get("MXTPU_PS_REPL_LAG_MAX", "64"))
# sync mode: how long one ack may wait on the backup before the primary
# declares the backup gone and detaches it (redundancy lost — surfaced
# in health — but the fleet keeps training)
_REPL_TIMEOUT = float(os.environ.get("MXTPU_PS_REPL_TIMEOUT", "30"))
# seconds between a backup's peer probes (re-join after a primary
# restart); 0 disables the thread — tests drive _probe_peer() directly
_REPL_PROBE = float(os.environ.get("MXTPU_PS_REPL_PROBE", "2"))


def _racing_copy(d, attempts=100):
    """Reference-copy of a dict other threads keep mutating. Even the
    C-level ``dict.copy()`` / ``list(d.items())`` can observe a resize
    mid-clone (allocation may trigger a GC pass whose destructors are
    a GIL checkpoint), raising "dictionary changed size during
    iteration" — so retry the rare tear. Used by readers whose writers
    hold per-KEY locks (there is no single lock a reader could
    take)."""
    for _ in range(attempts):
        try:
            return d.copy()
        except RuntimeError:
            continue
    # ~impossible: would need `attempts` consecutive mid-copy resizes
    raise RuntimeError("dict copy kept racing a resize after %d tries"
                       % attempts)


def _slice_part(arr, lo, hi):
    """Row slice of a part payload; rank-0 arrays are always one whole
    part (a 0-d numpy array cannot be indexed)."""
    return arr if arr.ndim == 0 else arr[lo:hi]


def _part_bounds(shape, bound=None):
    """Row ranges ``[(start, end), ...]`` splitting an array of ``shape``
    into parts of at most ~``bound`` elements. One part for small or
    rank-0 arrays."""
    bound = _BIGARRAY_BOUND if bound is None else bound
    size = 1
    for d in shape:
        size *= int(d)
    nrows = int(shape[0]) if len(shape) else 1
    if size <= bound or nrows <= 1:
        return [(0, nrows)]
    rows_per = max(1, bound // max(size // nrows, 1))
    return [(r, min(r + rows_per, nrows))
            for r in range(0, nrows, rows_per)]


def _half_float(dtype):
    """Half-width float payload detection — the wire dtype tag of the
    AMP fast path (``MXTPU_AMP=bf16``, docs/perf_analysis.md "Mixed
    precision"): a push/pushpull frame whose payload array is bf16 or
    fp16 carries half the bytes and upcasts into the fp32 master table
    on apply. ml_dtypes registers bfloat16 OUTSIDE numpy's float
    hierarchy (``np.issubdtype`` says False), so compare directly."""
    try:
        dtype = _np.dtype(dtype)
    except TypeError:
        return False
    if dtype == _np.float16:
        return True
    return _bfloat16 is not None and dtype == _bfloat16


try:
    import ml_dtypes as _ml_dtypes
    _bfloat16 = _np.dtype(_ml_dtypes.bfloat16)
except ImportError:      # pragma: no cover - ml_dtypes ships with jax
    _bfloat16 = None


def _wire_decode(grad):
    """Server side of the push payload: dense ndarray passes through;
    a 2-bit-compressed tuple is dequantized (reference PushCompressed →
    server-side dequantize, kvstore_dist_server.h); a half-width (bf16
    AMP) payload upcasts to fp32 so the master table and the server's
    numpy host-mirror optimizer ALWAYS apply in full precision."""
    if isinstance(grad, tuple) and len(grad) == 4 and grad[0] == _GC_MARK:
        from .gradient_compression import dequantize_2bit
        _, threshold, packed, shape = grad
        import jax.numpy as jnp
        return _np.asarray(dequantize_2bit(jnp.asarray(packed),
                                           threshold, shape))
    if isinstance(grad, _np.ndarray) and _half_float(grad.dtype):
        return grad.astype(_np.float32)
    return grad


_NBUF = struct.Struct("<I")


# the kv client comms instruments (ISSUE 14): every _CommStats field is
# a registry series labeled by store/client instance, so the unified
# metrics plane and the per-instance `kv.stats()` dict read the SAME
# counters — the dict is now a view over the registry. Past the
# cardinality bound, labels() hands back detached series: the local
# dict stays exact, the registry stays bounded.
_KVC_COUNTERS = {
    "bytes_sent": _obs.counter(
        "kv.client.bytes_sent", "wire bytes sent", ("inst",)),
    "bytes_recv": _obs.counter(
        "kv.client.bytes_recv", "wire bytes received", ("inst",)),
    "frames_sent": _obs.counter(
        "kv.client.frames_sent", "wire frames sent", ("inst",)),
    "frames_recv": _obs.counter(
        "kv.client.frames_recv", "wire frames received", ("inst",)),
    "coalesced_frames": _obs.counter(
        "kv.client.coalesced_frames", "multi-key frames sent",
        ("inst",)),
    "coalesced_subs": _obs.counter(
        "kv.client.coalesced_subs", "sub-commands coalesced",
        ("inst",)),
    "retransmits": _obs.counter(
        "kv.client.retransmits", "request replays after a failure",
        ("inst",)),
    "local_reqs": _obs.counter(
        "kv.client.local_reqs", "same-process shortcut dispatches",
        ("inst",)),
    "map_reroutes": _obs.counter(
        "kv.client.map_reroutes", "map_stale reroutes followed",
        ("inst",)),
    "sparse_frames": _obs.counter(
        "kv.client.sparse_frames", "row-sparse wire frames",
        ("inst",)),
    "sparse_rows_sent": _obs.counter(
        "kv.client.sparse_rows_sent", "row-sparse rows shipped",
        ("inst",)),
}
_KVC_HWM = _obs.gauge("kv.client.inflight_hwm",
                      "pipelined-window in-flight high-water mark",
                      ("inst",))
_KVC_RPC_MS = _obs.histogram(
    "kv.client.rpc_ms", "client-observed request round-trip latency",
    ("op",))
_KVC_INST = itertools.count(1)

# server-side instruments: the applied-push rate is the fleet's
# steps/s proxy per shard (mxtop's PS rows); everything else on the
# server rides the "kv.server" view registered at start()
_KVS_PUSHES = _obs.counter(
    "kv.server.pushes", "updates applied by this server", ("inst",))
_KVS_INST = itertools.count(1)


class _CommStats:
    """Worker-side comms counters behind ``kv.stats()``. Cheap enough to
    run unconditionally: one lock bump per frame, never per byte —
    each field IS a registry series (label ``inst=<n>``), so the same
    numbers surface in ``obs.REGISTRY.snapshot()`` / the ``metrics``
    wire op without a second bookkeeping path."""

    _FIELDS = ("bytes_sent", "bytes_recv", "frames_sent", "frames_recv",
               "coalesced_frames", "coalesced_subs", "retransmits",
               "inflight_hwm", "local_reqs", "map_reroutes",
               "sparse_frames", "sparse_rows_sent")

    def __init__(self):
        inst = "c%d" % next(_KVC_INST)
        self._c = {f: m.labels(inst) for f, m in _KVC_COUNTERS.items()}
        self._hwm = _KVC_HWM.labels(inst)

    def add(self, field, n=1):
        self._c[field].inc(n)

    def hwm(self, inflight):
        self._hwm.set_max(inflight)

    def snapshot(self):
        out = {f: s.value for f, s in self._c.items()}
        out["inflight_hwm"] = self._hwm.value
        return out

    def release(self):
        """Give the registry series back (store/client close): the
        local dict keeps working, the fleet snapshot forgets this
        instance."""
        for s in self._c.values():
            s.drop()
        self._hwm.drop()


def _sendmsg_all(sock, views):
    """Scatter-gather sendall: one ``sendmsg`` syscall moves the frame
    head and every raw buffer with no intermediate concatenation — the
    zero-copy send half. Sequential ``sendall`` fallback where sendmsg
    is missing (non-POSIX)."""
    views = [v for v in views if v.nbytes]
    if not hasattr(sock, "sendmsg"):
        for v in views:
            sock.sendall(v)
        return
    while views:
        sent = sock.sendmsg(views[:_IOV_MAX])
        while sent:
            if sent >= views[0].nbytes:
                sent -= views[0].nbytes
                views.pop(0)
            else:
                views[0] = views[0][sent:]
                sent = 0


def _send_frame(sock, obj, stats=None):
    """Pickle-5 framing with out-of-band buffers: numpy payloads ride as
    raw frames after the pickle body instead of being copied into it.
    Wire: u64 body_len, body, u32 n_buffers, u64 len x n, then the raw
    buffer bytes back to back. The whole frame leaves in one
    scatter-gather sendmsg — an N-byte gradient is never concatenated,
    and no tiny split segment exists to trip Nagle/delayed-ACK."""
    buffers = []
    body = pickle.dumps(obj, protocol=5, buffer_callback=buffers.append)
    raws = [buf.raw() for buf in buffers]
    head = (_LEN.pack(len(body)) + body + _NBUF.pack(len(raws))
            + b"".join(_LEN.pack(r.nbytes) for r in raws))
    _sendmsg_all(sock, [memoryview(head)] + raws)
    if stats is not None:
        stats.add("bytes_sent", len(head) + sum(r.nbytes for r in raws))
        stats.add("frames_sent")


def _recv_exact(sock, n):
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        try:
            # the ONE audited raw read: server-side it idles unbounded
            # BY DESIGN (workers hold connections open between steps);
            # worker-side every caller runs settimeout() first
            # (_request_once / the receiver thread's poll tick)
            r = sock.recv_into(view[got:], n - got)  # mxlint: allow(blocking-call) — audited frame-read loop
        except socket.timeout:
            if got:
                # mid-frame stall: the stream position is lost and the
                # connection must not be reused (idle timeouts — got==0
                # — are the receiver thread's poll tick and harmless)
                raise ConnectionError(
                    "timed out mid-frame after %d/%d bytes" % (got, n))
            raise
        if not r:
            raise ConnectionError("peer closed")
        got += r
    return buf


_MAX_FRAME = 1 << 34   # 16 GiB: far above any real push, far below the
                       # garbage lengths a protocol mismatch produces


def _read_len(sock):
    (n,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    if n > _MAX_FRAME:
        # e.g. a tokened worker talking to a tokenless server: the raw
        # auth preamble parses as an absurd frame length — fail loudly
        # instead of blocking in _recv_exact forever
        raise ConnectionError(
            "oversized frame length %d — protocol mismatch (is "
            "MXTPU_PS_TOKEN set on one side only?)" % n)
    return n


def _recv_frame(sock, stats=None):
    body = _recv_exact(sock, _read_len(sock))
    (n_buf,) = _NBUF.unpack(_recv_exact(sock, _NBUF.size))
    if n_buf > 4096:
        raise ConnectionError("implausible buffer count %d" % n_buf)
    buffers, total = [], 0
    if n_buf:
        lens_raw = _recv_exact(sock, _LEN.size * n_buf)
        lens = [_LEN.unpack_from(lens_raw, i * _LEN.size)[0]
                for i in range(n_buf)]
        total = sum(lens)
        if any(n > _MAX_FRAME for n in lens) or total > _MAX_FRAME:
            raise ConnectionError(
                "oversized buffer length — protocol mismatch")
        # one blob, one recv_into stream: every out-of-band buffer of
        # the frame is a memoryview slice of it, so the payloads are
        # reconstructed zero-copy straight out of the wire buffer
        blob = memoryview(_recv_exact(sock, total))
        off = 0
        for n in lens:
            buffers.append(blob[off:off + n])
            off += n
    if stats is not None:
        stats.add("bytes_recv", _LEN.size + len(body) + _NBUF.size
                  + _LEN.size * n_buf + total)
        stats.add("frames_recv")
    return pickle.loads(body, buffers=buffers)


_AUTH_MAGIC = b"MXA1"


def _auth_blob(token):
    """Fixed-length raw preamble proving knowledge of the launch secret.
    Deliberately NOT a pickle frame: the point of auth is that no
    attacker-controlled bytes reach pickle.loads, so the check must
    happen on raw bytes before the first frame is read."""
    import hashlib
    return _AUTH_MAGIC + hashlib.sha256(token.encode("utf-8")).digest()


class _Handler(socketserver.BaseRequestHandler):
    def handle(self):
        server = self.server.owner
        with server._active_lock:
            server._active.add(self.request)
        try:
            if self.server.dying:
                # accepted before stop() but registered after it read
                # the list of sockets to sever: a dead server serves no
                # one (stop() sets the flag before it reads the list)
                return
            if server._token:
                # exact-length raw compare before any unpickling; a
                # wrong preamble closes the socket silently
                import hmac
                expected = _auth_blob(server._token)
                got = _recv_exact(self.request, len(expected))
                if not hmac.compare_digest(got, expected):
                    return
            while True:
                # every frame is (correlation id, command[, trace ctx]):
                # requests of one connection pipeline — the worker
                # streams the next frames while this one is being
                # applied — and replies pair back to their waiters by
                # cid. Apply order stays the arrival order (this loop
                # is serial per conn). The optional third element is
                # pure observability metadata (a sampled trace id, see
                # mxtpu/obs/trace.py): it never changes the reply.
                frame = _recv_frame(self.request)
                cid, msg = frame[0], frame[1]
                tctx = frame[2] if len(frame) > 2 else None
                op = msg[0]
                key = msg[1] if len(msg) > 1 and \
                    isinstance(msg[1], (str, int)) else None
                # injection points bracket the dispatch: a server.recv
                # fault loses the request BEFORE it was applied (replay
                # is trivially safe), a server.send fault loses the ack
                # AFTER it was applied (replay must dedupe)
                _fault.fire("server.recv", op=op, key=key,
                            sock=self.request, server=server)
                if tctx is None:
                    reply = server._dispatch(msg)
                else:
                    # continue the caller's trace: the apply span is
                    # what the merged timeline subtracts from the
                    # client rpc span to show wire + queue time
                    with _obs.adopt(tctx), \
                            _obs.span("kv.server.apply", op=op):
                        reply = server._dispatch(msg)
                _fault.fire("server.send", op=op, key=key,
                            sock=self.request, server=server)
                _send_frame(self.request, (cid, reply))
                if op == "stop":
                    break
        except (ConnectionError, EOFError, OSError):
            pass
        finally:
            with server._active_lock:
                server._active.discard(self.request)


class _TCPServer(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True
    # socketserver's default accept backlog is 5: a burst of clients
    # (a redeployed trainer fleet, a serving sweep ramping concurrency)
    # overflows it and the overflow waits out a full ~1s TCP SYN
    # retransmit before connecting — observed as a 1000ms connect wall
    # at >10 simultaneous dials (the kernel clamps this to somaxconn)
    request_queue_size = 1024
    dying = False    # set synchronously by ParameterServer.stop()/kill():
    #                  serve_forever's shutdown poll is ~0.5s, and a dead
    #                  server must refuse new conversations IMMEDIATELY
    #                  or a fast retry slips in during the window

    def verify_request(self, request, client_address):
        return not self.dying

    def process_request(self, request, client_address):
        request.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        super().process_request(request, client_address)


class _ReplStream:
    """The primary→backup replication stream: one strictly-ordered,
    seq-stamped queue of applied-update records drained by a single
    sender thread over a :class:`_ServerConn` to the backup.

    Ordering is the whole design: records are enqueued under the key
    lock that applied them (so per-key stream order == apply order),
    stamped with a monotone ``rseq`` under the queue lock (so global
    stream order is total), and sent by ONE thread (so retries after a
    severed window replay in the same total order). The backup refuses
    any ``rseq`` at or below its high-water mark, which makes every
    replay — window failure, reconnect, duplicate flush — at-most-once
    without per-record bookkeeping, and makes a replayed ``xfer``
    (state-transfer overwrite) unable to clobber a later forwarded
    push.

    Durability contract per mode:

    * ``sync``: :meth:`wait_acked` blocks the push ack until the backup
      acked this record (or the stream died — see below). The worker's
      ack then *implies* backup durability: a SIGKILLed primary loses
      nothing that was acked.
    * ``async``: the push acks immediately; :meth:`forward` blocks only
      when more than ``MXTPU_PS_REPL_LAG_MAX`` records are unacked
      (bounded lag).

    A record whose retries exhaust (backup truly gone, not just a
    severed stream) kills the stream and detaches the backup on the
    owner: redundancy is lost — loudly, in ``health()`` — but the
    primary keeps serving solo rather than wedging the fleet. A
    *transient* sever never reaches that path: the conn's retry layer
    replays and the delayed ack releases the waiters late, not never.
    """

    def __init__(self, owner, conn, mode, lag_max=None):
        self.id = uuid.uuid4().hex       # stream incarnation: the
        #                                  backup resets its rseq
        #                                  watermark on a new id
        self._owner = owner
        self.conn = conn
        self.mode = mode
        self._lag_max = _REPL_LAG_MAX if lag_max is None else int(lag_max)
        self._cv = threading.Condition()
        self._q = []                     # [(rseq, sub_record), ...]
        self._rseq = 0                   # last assigned
        self._acked = 0                  # last backup-acked
        self.dead = False
        self.death_reason = None
        self.pending = []                # unacked window, kept at kill
        self.forwarded = 0               # records acked by the backup
        self.dup_acks = 0                # backup refused as replayed
        self._thread = threading.Thread(
            target=self._drain_loop, daemon=True, name="mxtpu-ps-repl")
        self._thread.start()

    # -- producer side (dispatch handler threads) -------------------------
    def forward(self, sub):
        """Enqueue one update record; returns its rseq (None when the
        stream is already dead). Called under the key lock that applied
        the update, so the stream order matches the apply order per
        key. async mode blocks here — briefly, off the ack path — when
        the unacked backlog is over the lag bound."""
        with self._cv:
            if self.dead:
                return None
            if self.mode == "async":
                deadline = time.monotonic() + _REPL_TIMEOUT
                while self._rseq - self._acked >= self._lag_max \
                        and not self.dead:
                    remain = deadline - time.monotonic()
                    if remain <= 0:
                        break        # drain stalled: the sender's retry
                    self._cv.wait(timeout=min(remain, 0.5))
                if self.dead:
                    return None
            self._rseq += 1
            self._q.append((self._rseq, sub))
            self._cv.notify_all()
            return self._rseq

    def wait_acked(self, rseq, timeout=None):
        """Sync-mode durability point: block until the backup acked
        ``rseq`` (True) or the stream died / the wait timed out (False
        — the caller acks solo and the detach is already surfaced)."""
        timeout = _REPL_TIMEOUT if timeout is None else timeout
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._acked < rseq and not self.dead:
                remain = deadline - time.monotonic()
                if remain <= 0:
                    break
                self._cv.wait(timeout=min(remain, 0.5))
            ok = self._acked >= rseq
        if not ok and not self.dead:
            # the backup is stalling past the sync budget: detach it
            # (redundancy lost, loudly) rather than wedging every push
            self.kill(ConnectionError(
                "backup ack stalled > %.1fs" % timeout))
        return ok

    def wait_drained(self, timeout=None):
        """Block until everything enqueued *so far* is backup-acked —
        the durability point for sync-mode dup-acks (the original
        record may still be in flight when its replay arrives)."""
        with self._cv:
            tail = self._rseq
        return self.wait_acked(tail, timeout=timeout)

    def lag(self):
        with self._cv:
            return self._rseq - self._acked

    def kill(self, reason, unacked=None):
        with self._cv:
            if self.dead:
                return
            self.dead = True
            self.death_reason = "%s: %s" % (type(reason).__name__, reason)
            # the unacked window — records in the dying batch plus
            # everything still queued — survives the teardown WITH its
            # rseq numbering: the owner keeps it for heal-time
            # reconciliation, and the new primary dedupes each record
            # exactly against the stream prefix it already applied
            # (rseq <= its repl watermark for this stream id)
            self.pending = list(unacked or []) + list(self._q)
            self._q = []
            self._cv.notify_all()
        self.conn.close()
        self._owner._on_repl_dead(self, reason)

    # -- the single sender thread -----------------------------------------
    def _drain_loop(self):
        while True:
            with self._cv:
                while not self._q and not self.dead:
                    self._cv.wait(timeout=0.5)
                if self.dead:
                    return
                batch = self._q[:_WINDOW]
                del self._q[:len(batch)]
            try:
                # pipelined fan-out, then per-record in-order retries —
                # all from THIS thread, so the total order the backup
                # sees (and its rseq watermark refuses replays against)
                # is exactly enqueue order. Frames carry the sender's
                # fencing epoch: a deposed primary still draining its
                # stream is refused with ``fenced`` by the promoted
                # peer, which is one of the ways it learns it is
                # deposed.
                epoch = self._owner._epoch
                replies = self.conn.request_all(
                    [("repl", self.id, rseq, sub, epoch)
                     for rseq, sub in batch],
                    timeout=_REPL_TIMEOUT)
            except (ConnectionError, RuntimeError, OSError) as e:
                self.kill(e, unacked=batch)
                return
            with self._cv:
                self._acked = batch[-1][0]
                self.forwarded += len(batch)
                self.dup_acks += sum(1 for r in replies
                                     if len(r) > 1 and r[1] == "dup")
                self._cv.notify_all()


class ParameterServer:
    """Host-side async parameter table (reference KVStoreDistServer with
    ``sync_mode_ == false``, kvstore_dist_server.h:339,462).

    With ``snapshot_dir`` set (or ``MXTPU_PS_SNAPSHOT_DIR``), the table +
    clocks + push-dedupe seqs + optimizer are snapshotted through
    :class:`~mxtpu.checkpoint.CheckpointManager` every ``snapshot_every``
    pushes (``MXTPU_PS_SNAPSHOT_EVERY``, default 100 once a dir is set),
    and a fresh server restores the latest snapshot at construction — the
    auto-resume half of the fault story (the reference's epoch-end
    ``save_checkpoint`` done server-side and continuously)."""

    def __init__(self, port=0, host="127.0.0.1", token=None,
                 snapshot_dir=None, snapshot_every=None, peer_addr=None,
                 role=None, repl_mode=None):
        self._tcp = _TCPServer((host, port), _Handler)
        self._tcp.owner = self
        self._token = token if token is not None \
            else os.environ.get("MXTPU_PS_TOKEN") or None
        # -- replication (module docstring, "Replication & failover") --
        # role is what this server *is right now*: a primary applies
        # client updates and forwards them to its backup; a backup
        # applies only the replication stream until promoted.
        if peer_addr is None:
            peer_addr = os.environ.get("MXTPU_PS_PEER") or None
        if role is None:
            role = os.environ.get("MXTPU_PS_ROLE", "primary")
        if repl_mode is None:
            repl_mode = os.environ.get("MXTPU_PS_REPL_MODE", _REPL_MODE)
        if repl_mode not in ("sync", "async"):
            raise ValueError("MXTPU_PS_REPL_MODE must be sync|async, "
                             "got %r" % (repl_mode,))
        self._role = role
        self._peer_addr = peer_addr
        self._repl_mode = repl_mode
        self._repl = None            # primary side: live _ReplStream
        self._repl_guard = threading.Lock()
        self._backup_addr = None
        self._promotions = 0
        self._catchup = None         # primary side: transfer progress
        # backup side: replication-stream dedupe watermark + catch-up
        self._repl_stream_id = None
        self._repl_applied_rseq = 0
        self._repl_dup = 0
        self._repl_received = 0
        # a fresh backup serves nothing until its catch-up completed; a
        # server born primary is trivially complete
        self._catchup_complete = role != "backup"
        self._peer_conn = None       # lazy _ServerConn for peer probes
        self._probe_stop = threading.Event()
        self._probe_thread = None
        # -- fencing epochs (ISSUE 19): every promotion mints a higher
        # epoch; a primary that learns of a higher one — peer probe,
        # client frame, replication refusal, rejoin handshake — is
        # DEPOSED: it stops acking client state commands with the
        # ``fenced`` routing verdict until it rejoins as a backup.
        # Durable: the epoch rides every snapshot's meta.
        self._epoch = 1
        self._fenced = False
        self._fenced_at = 0          # the higher epoch we learned of
        # heal-time reconciliation: while the repl stream is down this
        # primary keeps the applied-but-unreplicated window (bounded,
        # as (rseq, record) pairs) so a rejoin can replay it at the new
        # primary. The replay CANNOT lean on the (origin, key) push
        # watermarks — those assume FIFO per origin, and the new
        # primary has already applied the client's POST-failover seqs —
        # so the new primary dedupes each record exactly: against the
        # stream prefix it applied (rseq vs its repl watermark) and
        # against the idents it applied for clients since its own
        # promotion (_epoch_applied, recorded promote -> reconcile)
        self._repl_lost = False
        self._unreplicated = []
        self._lost_stream_id = None
        self._epoch_applied = None        # None = not recording
        self._epoch_applied_overflow = False
        self._table = {}           # key -> NDArray (host-side, cpu jax)
        self._locks = {}           # key -> Lock (per-key serialization)
        self._locks_guard = threading.Lock()
        self._clock = {}           # key -> applied-update count
        self._applied = {}         # (origin, key) -> last applied push seq
        # keys that took a row-wise (spush/spushpull) update: their
        # table entries mutate rows IN PLACE, so pulls must copy
        # instead of aliasing (see _ensure_sparse_table). Re-derived
        # lazily after restarts/splits — the flag is set before the
        # first in-place write ever happens on this server.
        self._sparse_keys = set()
        self._sparse_pushes = 0    # row-wise applies (observability)
        self._sparse_rows = 0      # rows touched by them, summed
        self._updater = None
        self._opt_payload = None   # pickled optimizer, kept for snapshots
        # one server-wide lock around updater invocations: the Updater and
        # Optimizer carry cross-key shared state (states dict,
        # num_update's read-modify-write max), which per-key locks alone
        # would race on
        self._updater_lock = threading.Lock()
        # server-wide observability counters are mutated from every
        # per-connection handler thread; the per-key locks serialize
        # same-key pushes only, so cross-key `+=` would lose updates
        # without a dedicated counter lock (leaf lock: nothing is
        # acquired under it)
        self._ctr_lock = threading.Lock()
        self._stale_max = 0
        self._stale_sum = 0
        self._stale_n = 0
        self._dup_n = 0            # deduped push replays (observability)
        # -- worker membership / liveness (ps-lite's NumDeadNodes seen
        # from the server side, but with per-worker evidence): origin ->
        # {rank, pushes, staleness, last_seen, push gaps}. Epoch bumps
        # on every join/leave so workers can observe churn.
        self._workers = {}
        self._workers_lock = threading.Lock()
        self._membership_epoch = 0
        self._joins = 0            # workers that registered (ever)
        self._leaves = 0           # clean byes + liveness GCs
        # -- elasticity: online reshard + server-owned data cursor --
        self._map_version = 0      # bumps per key handed away/adopted
        self._moved = {}           # key -> its new home "host:port"
        self._keys_adopted = 0
        self._keys_moved_out = 0
        self._splits = 0
        self._xfer_conns = {}      # split destination -> _ServerConn
        self._xfer_guard = threading.Lock()
        self._cursors = {}         # epoch -> shard-cursor state
        self._cursor_lock = threading.Lock()
        self._cursor_requeues = 0
        # -- streaming data plane (ISSUE 18): committed consumption
        # cursors per (consumer group, log shard, segment), plus the
        # per-stream-origin commit watermark that keeps a respawned
        # trainer's replayed frames exactly-once. Deliberately NOT in
        # self._applied: worker-death GC must never forget a stream
        # origin — the identity is derived from the log position, not
        # from a worker incarnation, and must outlive every consumer.
        self._stream_lock = threading.Lock()
        self._stream_offsets = {}  # (group, shard, seg) -> [offset, final]
        self._stream_applied = {}  # stream origin -> last commit seq
        self._stream_commits = 0
        self._stream_dup = 0
        self._barrier_recounts = 0
        self._barrier_timeouts = 0
        self._barrier_lock = threading.Lock()
        self._barrier_cv = threading.Condition(self._barrier_lock)
        self._barrier_gen = 0
        self._barrier_arrived = 0
        self._thread = None
        self._active = set()       # live handler sockets, severed on stop
        self._active_lock = threading.Lock()
        # observability (ISSUE 14): the applied-push series + the
        # "kv.server" registry view behind the `metrics` wire op
        self._m_pushes = _KVS_PUSHES.labels("s%d" % next(_KVS_INST))
        self._view_key = None
        # -- snapshot-backed auto-resume --
        if snapshot_dir is None:
            snapshot_dir = os.environ.get("MXTPU_PS_SNAPSHOT_DIR") or None
        self._snapshot_dir = snapshot_dir
        if snapshot_every is None:
            snapshot_every = int(os.environ.get(
                "MXTPU_PS_SNAPSHOT_EVERY", "100"))
        self._snapshot_every = int(snapshot_every)
        self._snap_lock = threading.Lock()
        self._push_count = 0
        self._snap_count = 0
        self._restored_step = None
        self._ckpt = None
        if self._snapshot_dir:
            from .checkpoint import CheckpointManager
            # sync fallback writer: the snapshot already runs off the
            # push path (handler thread, under _snap_lock); orbax's
            # process-wide async machinery buys nothing for a host table
            self._ckpt = CheckpointManager(
                self._snapshot_dir, max_to_keep=2, async_save=False,
                use_orbax=False)
            self._restore_snapshot()
        # -- versioned weight publication (the train→serve stream:
        # trainers drive the ``publish`` op, serving replicas follow
        # via ``weight_sub`` + long-polled ``weights`` — the
        # _ReplStream discipline applied to whole weight versions:
        # totally ordered by version number, the subscriber's
        # have-version watermark dedupes replays, catch-up on
        # reconnect is just asking with the watermark) --
        self._pub_lock = threading.Lock()
        self._pub_cv = threading.Condition(self._pub_lock)
        self._pub_version = 0
        self._published = None      # latest version's host blobs
        self._pub_digest = None
        self._pub_count = 0
        self._weight_subs = {}      # subscriber origin -> watermark
        self._weight_dir = os.environ.get("MXTPU_SERVE_WEIGHT_DIR") \
            or None
        self._weight_ckpt = None    # lazy, first publish

    # -- lifecycle --------------------------------------------------------
    @property
    def address(self):
        h, p = self._tcp.server_address
        return "%s:%d" % (h, p)

    def start(self):
        self._thread = threading.Thread(
            target=self._tcp.serve_forever, daemon=True)
        self._thread.start()
        with _LOCAL_GUARD:
            # same-process workers short-circuit the socket (a restarted
            # server on a reused port re-registers, so the local path
            # resumes after auto-respawn exactly like a reconnect)
            _LOCAL_SERVERS[self.address] = self
        if self._view_key is None:
            self._view_key = _obs.view("kv.server", self.metrics_view)
        return self

    def stop(self):
        """Stop serving AND sever every in-flight connection — a stopped
        server must look like a crashed server to its workers (handler
        threads would otherwise keep serving established sockets after
        the listener closes, hiding the death the fault tests and the
        launcher's respawn path both rely on)."""
        self._tcp.dying = True
        self._probe_stop.set()
        if self._view_key is not None:
            _obs.REGISTRY.unview(self._view_key)
            self._view_key = None
        self._m_pushes.drop()
        with self._repl_guard:
            stream = self._repl
        if stream is not None and not stream.dead:
            stream.kill(ConnectionError("server stopping"))
        conn, self._peer_conn = self._peer_conn, None
        if conn is not None:
            conn.close()
        with self._xfer_guard:
            xfer = list(self._xfer_conns.values())
            self._xfer_conns.clear()
        for c in xfer:
            c.close()
        with _LOCAL_GUARD:
            if _LOCAL_SERVERS.get(self.address) is self:
                del _LOCAL_SERVERS[self.address]
        # sever the established conversations BEFORE the listener's
        # (up to ~0.5s) shutdown poll: a crashed server's sockets die
        # instantly, and failover tests rely on that immediacy — an
        # open channel must not keep serving while the listener winds
        # down
        with self._active_lock:
            active = list(self._active)
        for s in active:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass
        if self._thread is not None:   # shutdown() waits on an event only
            self._tcp.shutdown()       # serve_forever sets — skip for a
        self._tcp.server_close()       # server that never start()ed

    def kill(self):
        """Crash the server as the fault injector sees it: new
        conversations are refused from THIS instant (synchronous flag),
        the full teardown finishes on a side thread. Deterministic for
        tests: no retry can slip into the shutdown poll window."""
        self._tcp.dying = True
        threading.Thread(target=self.stop, daemon=True).start()

    # -- replication: primary side ----------------------------------------
    def _attach_backup(self, addr):
        """Adopt ``addr`` as this primary's backup: build the stream
        (one conn pinned to ONE socket — the backup's serial handler
        loop then preserves total send order, which the rseq watermark
        dedupe is built on) and start the catch-up transfer on a side
        thread. A re-join replaces any previous stream: the fresh
        stream id makes the backup reset its watermark and expect a
        fresh transfer."""
        with self._repl_guard:
            old, self._repl = self._repl, None
        if old is not None and not old.dead:
            old.kill(ConnectionError("backup replaced by %s" % (addr,)))
        conn = _ServerConn(addr, token=self._token, n_socks=1,
                           connect_timeout=_RECONNECT_TIMEOUT)
        with self._repl_guard:
            stream = _ReplStream(self, conn, self._repl_mode)
            self._repl = stream
            self._backup_addr = addr
            # redundancy is back: the catch-up transfer about to run
            # carries the whole table, reconciliation window included
            self._repl_lost = False
            with self._ctr_lock:
                self._unreplicated = []
                self._lost_stream_id = None
        threading.Thread(target=self._run_catchup, args=(stream,),
                         daemon=True, name="mxtpu-ps-catchup").start()
        _log.info("parameter server %s: backup %s attached (%s "
                  "replication); catch-up starting", self.address, addr,
                  self._repl_mode)

    def _run_catchup(self, stream):   # mxlint: allow(shared-state-race) — catch-up runs on its single dedicated thread; _catchup progress is written only here and read as GIL-atomic ints/flags by the stats arm
        """Stream the full service state to a just-joined backup:
        optimizer first (forwarded pushes need the updater installed),
        then every key's value + clock + push-dedupe seqs as overwrite
        records — each snapshotted under its key lock, so a key's
        transfer can never miss an update whose forwarded record
        preceded it on the stream — then the catchup_done marker.
        Pushes keep flowing concurrently; the backup skips forwarded
        pushes for keys it has not received yet (their effect rides in
        the pending xfer)."""
        keys = list(self._table)
        self._catchup = {"total": len(keys), "sent": 0, "done": False}
        if self._opt_payload is not None:
            stream.forward(("set_optimizer", self._opt_payload))
        if self._moved:
            # the forwarding table travels too: a backup promoted later
            # must refuse split-away keys with the right new home, not
            # serve a stale pre-split copy
            stream.forward(("moved_map", dict(self._moved),
                            self._map_version))
        with self._updater_lock:
            if self._updater is not None:
                # the ACCUMULATED updater state — momentum buffers,
                # per-index update counts, the optimizer as it is NOW —
                # not just the pickled initial optimizer. Snapshotted
                # AND enqueued under the updater lock, so it is totally
                # ordered against every updater-path push record: the
                # backup's replayed updates continue the exact
                # trajectory (a zeroed momentum would silently diverge
                # every post-rejoin update).
                stream.forward(
                    ("opt_states",
                     _np.frombuffer(
                         self._updater.get_states(dump_optimizer=True),
                         dtype=_np.uint8)))
        for key in keys:
            if stream.dead:
                return
            with self._lock_for(key):
                if key not in self._table:
                    continue
                applied = [[o, s] for (o, k), s
                           in list(self._applied.items()) if k == key]
                stream.forward(
                    ("xfer", key,
                     _np.array(self._table[key], copy=True),
                     int(self._clock[key]), applied))
            self._catchup["sent"] += 1
        stream.forward(("catchup_done",))
        self._catchup["done"] = True

    def _on_repl_dead(self, stream, reason):
        """Stream-teardown callback: detach the backup if this was
        still the live stream (a replaced stream's death is not a
        detach). Loud — redundancy is gone until a backup rejoins —
        but the primary keeps serving solo rather than wedging the
        fleet. The stream's unacked window moves into the
        reconciliation buffer, and a ``fenced`` refusal from the peer
        means we are the DEPOSED side of a healed partition: fence now
        instead of serving split-brain."""
        with self._repl_guard:
            if self._repl is not stream:
                return
            self._repl = None
            addr, self._backup_addr = self._backup_addr, None
            self._repl_lost = True
            self._lost_stream_id = stream.id
            with self._ctr_lock:
                keep = _RECONCILE_MAX - len(self._unreplicated)
                if keep > 0:
                    self._unreplicated.extend(stream.pending[-keep:])
        _log.warning("parameter server %s: backup %s detached (%s) — "
                     "serving UNREPLICATED until a backup rejoins "
                     "(%d unacked records kept for reconciliation)",
                     self.address, addr, reason,
                     len(stream.pending))
        higher = _fenced_epoch(reason)
        if higher is not None:
            self._fence(higher, "replication refused by promoted peer")

    def _repl_stream(self):   # mxlint: allow(shared-state-race) — GIL-atomic binding read on the apply paths: attach/detach rebinds under _repl_guard, and a stream torn down after this read is handled by _ReplStream.dead / forward() raising onto the retry layer
        """The live replication stream binding, read without
        ``_repl_guard``: the apply paths (under per-key locks) grab the
        binding once and forward through it; taking the guard here
        would nest guard-inside-key-lock on every push for no benefit
        — the race window (stream dies right after the read) already
        has a handler either way."""
        return self._repl

    # -- replication: backup side / role negotiation ----------------------
    def _peer_request(self, *msg, **kw):
        """One request to the configured peer over a lazily-held conn.
        Returns the reply, or None when the peer is unreachable or
        refused — probes are periodic and peer-down is an expected
        state, not an error."""
        if self._peer_addr is None:
            return None
        try:
            if self._peer_conn is None:
                self._peer_conn = _ServerConn(
                    self._peer_addr, token=self._token, n_socks=1,
                    connect_timeout=2.0)
            return self._peer_conn.request(*msg, **kw)
        except (ConnectionError, RuntimeError, OSError) as e:
            conn, self._peer_conn = self._peer_conn, None
            if conn is not None:
                conn.close()
            _log.debug("peer probe of %s failed: %s",
                       self._peer_addr, e)
            return None

    def join_cluster(self, probe_interval=None):
        """Settle this server's role against its configured peer and
        start the background peer monitor (serve_forever calls this;
        tests drive it — and :meth:`_probe_peer` — synchronously).

        * born backup: ask the peer to adopt us; keep asking via the
          monitor until a primary answers and the state transfer
          streams in.
        * born primary but the peer is ALSO primary: we are a respawn
          of a failed-over shard — drop the stale local state and
          rejoin as the new backup; after catch-up the pair is
          redundant again.
        * born primary and the peer is a CAUGHT-UP backup: we are a
          respawn whose clients have not failed over yet (the respawn
          beat them to the port). The peer holds every update we
          acked before dying — it is the authority: promote it, then
          rejoin under it. Serving our empty/stale table as primary
          here would resurface exactly the acknowledged-update loss
          replication exists to close.
        """
        if self._peer_addr is None:
            return
        if self._role == "primary":
            info = self._peer_request("peer_info", retries=0,
                                      timeout=2.0)
            peer = info[1] if info is not None else None
            if peer is not None:
                # the rejoin handshake is one of the fencing triggers:
                # a respawned/healed primary adopts the fleet epoch
                # before it could possibly ack anything stale
                self._epoch = max(self._epoch,   # mxlint: allow(shared-state-race) — monotone max-adopt at boot (join_cluster runs before serving starts); no handler thread exists yet
                                  int(peer.get("fence_epoch", 0)))
            if peer is not None and peer.get("role") == "primary":
                self._become_backup()
            elif peer is not None and peer.get("catchup_complete") \
                    and self._peer_request("promote", retries=0,
                                           timeout=5.0) is not None:
                self._become_backup()
        self._probe_peer()
        interval = _REPL_PROBE if probe_interval is None \
            else probe_interval
        if interval > 0 and self._probe_thread is None:
            self._probe_thread = threading.Thread(
                target=self._probe_loop, args=(float(interval),),
                daemon=True, name="mxtpu-ps-peer-probe")
            self._probe_thread.start()

    def _become_backup(self):   # mxlint: allow(shared-state-race) — demotion path: runs at boot (join_cluster, before serving) or on the single peer-monitor thread with the repl stream already severed; the cleared-table stores publish atomically and catch-up repopulates
        """Demote to backup and drop local state: the surviving
        primary's table is the authority and ours (snapshot-restored,
        pre-crash) silently trails it — catch-up replaces everything,
        acknowledged post-crash updates included."""
        with self._repl_guard:
            stream, self._repl = self._repl, None
            self._backup_addr = None
            self._role = "backup"
            self._catchup_complete = False
            self._repl_stream_id = None
            self._repl_applied_rseq = 0
        if stream is not None and not stream.dead:
            stream.kill(ConnectionError("demoted to backup"))
        for key in list(self._table):
            with self._lock_for(key):
                self._table.pop(key, None)
                self._clock.pop(key, None)
        self._applied = {}
        self._moved = {}   # the authority's catch-up re-teaches the map
        with self._ctr_lock:
            self._repl_lost = False
            self._unreplicated = []
            self._lost_stream_id = None
            self._epoch_applied = None
            self._epoch_applied_overflow = False
        # the wipe mark scopes the consistency checker's node eras:
        # applies before it did NOT survive on this node (they live on
        # only through reconciliation / re-replication elsewhere)
        _consistency.journal("wipe", node=self.address,
                             epoch=self._epoch)
        _log.warning("parameter server %s: demoted to backup of %s "
                     "(the peer was promoted while we were down)",
                     self.address, self._peer_addr)

    def _probe_peer(self):
        """One peer-monitor tick. Backup side: if the peer is a
        primary that does not currently list us as its backup — first
        boot, primary restart, or a detach we never observed — ask to
        (re)join; returns True when attached. Primary side (ISSUE 19):
        the probe is a fencing trigger — a peer that is ALSO primary
        at a higher epoch means WE are the deposed half of a healed
        partition: fence and rejoin under it."""
        if self._tcp.dying:
            return False
        if self._role == "primary":
            if self._fenced:
                return self.rejoin()
            info = self._peer_request("peer_info", retries=0,
                                      timeout=2.0)
            if info is None:
                return False
            peer = info[1]
            if peer.get("role") == "primary" and \
                    int(peer.get("fence_epoch", 0)) > self._epoch:
                self._fence(int(peer.get("fence_epoch", 0)),
                            "peer probe found a higher epoch")
                return self.rejoin()
            return False
        if self._role != "backup":
            return False
        info = self._peer_request("peer_info", retries=0, timeout=2.0)
        if info is None:
            return False
        peer = info[1]
        self._epoch = max(self._epoch,   # mxlint: allow(shared-state-race) — monotone max-adopt on the single peer-monitor thread; concurrent readers see either epoch, both of which this server honored at some instant
                          int(peer.get("fence_epoch", 0)))
        if peer.get("role") != "primary":
            return False   # two backups: a promote must break the tie
        if peer.get("backup") == self.address:
            return True    # already attached
        return self._peer_request("join_backup", self.address,
                                  retries=0, timeout=5.0) is not None

    def _fence(self, higher, why):
        """Learn of a higher fencing epoch: this server is DEPOSED. It
        stops acking every client state command (the ``fenced``
        verdict) immediately — split-brain prevention is exactly this
        line — and waits for :meth:`rejoin` (the peer-monitor drives
        it; drills call it synchronously) to reconcile and demote."""
        with self._repl_guard:
            if higher <= self._epoch or self._fenced:
                if higher > self._fenced_at:
                    self._fenced_at = max(self._fenced_at, higher)
                if higher <= self._epoch:
                    return
            else:
                self._fenced_at = higher
            self._fenced = True
        _consistency.journal("fence", node=self.address,
                             epoch=self._epoch, deposed_by=higher)
        _log.warning(
            "parameter server %s: FENCED at epoch %d — a peer holds "
            "epoch %d (%s); refusing client writes until rejoin",
            self.address, self._epoch, higher, why)

    def rejoin(self, timeout=10.0):
        """Heal-time reconciliation for a fenced ex-primary: replay
        the applied-but-unreplicated window at the new primary — which
        dedupes each record exactly (against the repl-stream prefix it
        applied and the idents it applied for clients since its own
        promotion) — then drop local state and rejoin the pair as its
        backup. Returns True once demoted (catch-up streams in
        asynchronously)."""
        if not self._fenced or self._role != "primary":
            return False
        with self._ctr_lock:
            raw = list(self._unreplicated)
        # unique by (origin, seq, key): the stream-death harvest and
        # the _repl_lost buffering can each capture a record caught in
        # the teardown race, and the replay must carry it once
        seen, entries = set(), []
        for rseq, rec in raw:
            ident = _rec_ident(rec)
            if ident is None or ident in seen:
                continue
            seen.add(ident)
            entries.append((rseq, rec))
        if entries:
            reply = self._peer_request(
                "reconcile", self._epoch, self._lost_stream_id,
                entries, retries=0, timeout=timeout)
            if reply is None:
                return False   # peer unreachable: the monitor retries
            _log.warning(
                "parameter server %s: reconciled %d unacked records "
                "at %s (%s)", self.address, len(entries),
                self._peer_addr, reply[1])
            with self._ctr_lock:
                self._unreplicated = []
        self._become_backup()
        with self._repl_guard:
            self._epoch = max(self._epoch, self._fenced_at)   # mxlint: allow(shared-state-race) — monotone max-adopt under _repl_guard on the peer-monitor thread; the fenced flag (checked first everywhere) kept every client arm refusing throughout
            self._fenced = False
        return self._probe_peer()

    def _probe_loop(self, interval):
        while not self._probe_stop.wait(interval):
            try:
                self._probe_peer()
            except Exception as e:  # a probe bug must not stop serving
                _log.debug("peer probe sweep failed: %s", e)

    def _lock_for(self, key):
        with self._locks_guard:
            return self._locks.setdefault(key, threading.Lock())

    # -- worker membership -------------------------------------------------
    def _worker_rec(self, origin, rank=None):
        """Touch (and lazily create) the liveness record for a worker
        origin. Leaf lock: never taken while holding a key lock's
        sibling — see _gc_workers for the ordering discipline."""
        now = time.monotonic()
        created = False
        with self._workers_lock:
            rec = self._workers.get(origin)
            if rec is None:
                created = True
                self._membership_epoch += 1
                self._joins += 1
                rec = {"rank": rank, "pushes": 0, "stale_sum": 0,
                       "stale_max": 0, "last_seen": now,
                       "last_push": None, "push_gap_max": 0.0,
                       "joined_epoch": self._membership_epoch}
                self._workers[origin] = rec
            if rank is not None:
                rec["rank"] = rank
            rec["last_seen"] = now
        if created:
            # a join can complete a dynamic barrier (its target grew,
            # but so can a waiter's arithmetic change) — wake waiters
            self._notify_membership()
        return rec

    def _notify_membership(self):
        """Wake barrier waiters after a join/leave so a dynamic
        (elastic) barrier re-counts against the new membership. Called
        with NO other lock held — the barrier path nests
        barrier-lock -> workers-lock, never the reverse."""
        with self._barrier_cv:
            self._barrier_cv.notify_all()

    def _drop_worker(self, origin):
        """Forget a worker: membership record AND its buffered dedupe
        seqs (the per-(origin, key) at-most-once table would otherwise
        grow one entry per key per worker incarnation forever). Key
        locks are taken AFTER the membership lock is released — the
        push path nests key-lock → workers-lock, so nesting the other
        way here would deadlock."""
        with self._workers_lock:
            existed = self._workers.pop(origin, None) is not None
            if existed:
                self._membership_epoch += 1
                self._leaves += 1
        if not existed:
            return False
        for key in [k for o, k in list(self._applied) if o == origin]:
            with self._lock_for(key):
                self._applied.pop((origin, key), None)
        # a leaver's unfinished data shards go back on the cursor for
        # the survivors, and its arrival can no longer be awaited — a
        # dynamic barrier re-counts now instead of timing out later
        self._requeue_cursor_shards(origin)
        self._notify_membership()
        return True

    def _gc_workers(self):
        """Reap workers silent past MXTPU_PS_WORKER_DEAD_AFTER (0 =
        disabled). Called lazily from the cheap read paths — no extra
        thread, and fault-matrix schedules stay deterministic."""
        if _WORKER_DEAD_AFTER <= 0:
            return 0
        now = time.monotonic()
        with self._workers_lock:
            dead = [o for o, r in self._workers.items()
                    if now - r["last_seen"] > _WORKER_DEAD_AFTER]
        n = 0
        for o in dead:
            if self._drop_worker(o):
                _log.warning("parameter server: worker %s silent for "
                             ">%gs — membership and dedupe state "
                             "garbage-collected", o, _WORKER_DEAD_AFTER)
                n += 1
        return n

    def _note_worker_push(self, origin, stale):
        if origin is None:
            return
        rec = self._worker_rec(origin)
        now = time.monotonic()
        with self._workers_lock:
            rec["pushes"] += 1
            rec["stale_sum"] += stale
            rec["stale_max"] = max(rec["stale_max"], stale)
            if rec["last_push"] is not None:
                rec["push_gap_max"] = max(rec["push_gap_max"],
                                          now - rec["last_push"])
            rec["last_push"] = now

    # -- elastic data cursor (module docstring, "Elasticity") --------------
    def _cursor_for(self, epoch, num_shards):
        """The (lazily created) cursor record for one epoch; caller
        holds ``_cursor_lock``. History is bounded: int epochs more
        than two behind the newest are dropped. String epochs are the
        streaming plane's segment leases (``st|group|shard|seg``) —
        they neither age out other epochs nor age out themselves here;
        a segment's lease retires with its final stream commit."""
        cur = self._cursors.get(epoch)
        if cur is None:
            cur = {"num_shards": int(num_shards), "next": 0,
                   "requeued": [], "outstanding": {}, "done": set(),
                   "last": {},
                   # shard -> fencing epoch it was last granted under
                   # (ISSUE 19: stale-epoch completions are refused
                   # once the shard was re-granted after a heal)
                   "granted": {}}
            self._cursors[epoch] = cur
            if isinstance(epoch, int):
                for old in [e for e in self._cursors
                            if isinstance(e, int) and e < epoch - 2]:
                    del self._cursors[old]
        return cur

    def _requeue_cursor_shards(self, origin):
        """A departed worker's outstanding shard assignments go back on
        the queue so a surviving worker picks them up (at-least-once:
        the leaver may have processed part of a shard it never
        acknowledged)."""
        with self._cursor_lock:
            for cur in self._cursors.values():
                gone = [s for s, o in cur["outstanding"].items()
                        if o == origin]
                for s in gone:
                    del cur["outstanding"][s]
                    cur["requeued"].append(s)
                    self._cursor_requeues += 1
                cur["last"].pop(origin, None)

    # -- elasticity: online shard split ------------------------------------
    def _stale_reply(self, key, dst):
        # a routing verdict like not_serving, NOT a failure: the command
        # was not executed; the client records the forwarding override,
        # refreshes its map and replays at the key's new home (where the
        # transferred dedupe seqs keep the replay at-most-once)
        return ("err", "map_stale: key %r moved to %s (map_version %d)"
                       % (key, dst, self._map_version))

    def _split_conn(self, addr):
        with self._xfer_guard:
            conn = self._xfer_conns.get(addr)
        if conn is None:
            conn = _ServerConn(addr, token=self._token, n_socks=1,
                               connect_timeout=_RECONNECT_TIMEOUT)
            with self._xfer_guard:
                self._xfer_conns[addr] = conn
        return conn

    def _pick_split_keys(self):
        """Every other key of the hotness-ordered local set: the moving
        half and the staying half carry ~equal applied-update load
        (clocks count applied updates), so splitting a hot shard really
        halves its traffic."""
        local = [k for k in self._table if k not in self._moved]
        local.sort(key=lambda k: (-self._clock.get(k, 0), str(k)))
        return local[0::2]

    def _do_split(self, msg):
        """("split", dst_addr[, keys]) — operator command on a shard
        primary: hand half our keys (or exactly ``keys``) to the server
        at ``dst_addr`` with full state — value, clock, push-dedupe
        seqs, accumulated per-key updater state — then refuse the moved
        keys with ``map_stale`` so clients re-route. Each key's handoff
        is atomic under its key lock; an aborted split leaves a clean
        prefix moved and the rest owned (re-issue the split to resume —
        nothing acknowledged is lost either way)."""
        dst = msg[1]
        want = list(msg[2]) if len(msg) > 2 and msg[2] else None
        if dst == self.address:
            return ("err", "split destination is this server")
        keys = want if want is not None else self._pick_split_keys()
        moved = []
        conn = None
        try:
            conn = self._split_conn(dst)
            if self._opt_payload is not None:
                # dst may be a just-spawned server that never saw the
                # clients' launch-time set_optimizer broadcast
                conn.request("set_optimizer", self._opt_payload)
            for key in keys:
                stream = rseq = None
                # the key lock is held ACROSS the adopt RPC by design:
                # pushes to THIS key wait (bounded by the RPC timeout)
                # while every other key flows freely, and the moment
                # the lock drops the key is either still ours or
                # map_stale — no window where neither server owns it.
                # (pre-v3 this carried an allow(lock-order) pragma:
                # the dst's key locks belong to a DIFFERENT server
                # instance and adopt_key never calls back into this
                # server — the v3 symbol-table precision now proves
                # that nesting acyclic by itself)
                with self._lock_for(key):
                    if key not in self._table or key in self._moved:
                        continue
                    applied = [[o, s] for (o, k), s
                               in list(self._applied.items()) if k == key]
                    state = None
                    with self._updater_lock:
                        if self._updater is not None:
                            state = self._updater.get_state_one(
                                _key_int(key))
                            if state is not None:
                                state = _np.frombuffer(
                                    state, dtype=_np.uint8)
                    conn.request(
                        "adopt_key", key,
                        _np.array(self._table[key], copy=True),
                        int(self._clock[key]), applied, state)
                    # dst's ok means the key — and, on a replicated
                    # destination, its backup copy — is durable there;
                    # only now may ownership be released
                    self._moved[key] = dst
                    # cross-key counters (see the moved-record arm)
                    with self._ctr_lock:
                        self._map_version += 1
                        self._keys_moved_out += 1
                    del self._table[key]
                    self._clock.pop(key, None)
                    for o, s in applied:
                        self._applied.pop((o, key), None)
                    stream = self._repl_stream()
                    if stream is not None and not stream.dead:
                        # our own backup mirrors the release (ordered
                        # against this key's forwarded pushes by the
                        # key lock), so a promotion mid-split still
                        # refuses moved keys with the right forward
                        rseq = stream.forward(("moved", key, dst))
                self._repl_barrier(stream, rseq)
                moved.append(key)
        except (ConnectionError, RuntimeError, OSError) as e:
            with self._xfer_guard:
                self._xfer_conns.pop(dst, None)
            if conn is not None:
                conn.close()
            return ("err", "split to %s aborted after %d of %d key(s) "
                           "moved: %s: %s (re-issue the split to "
                           "resume)" % (dst, len(moved), len(keys),
                                        type(e).__name__, e))
        self._splits += 1
        _log.warning("parameter server %s: split %d key(s) -> %s "
                     "(map_version %d)", self.address, len(moved), dst,
                     self._map_version)
        return ("ok", {"dst": dst, "moved": moved,
                       "map_version": self._map_version})

    @staticmethod
    def _as_table_value(value):
        """Canonicalize an incoming init value to an owned, writable
        numpy array (the table is plain numpy so the accumulate path can
        add in place), with nd.array's float64/int64 narrowing kept."""
        arr = _np.array(value, copy=True)
        if arr.dtype == _np.float64:
            arr = arr.astype(_np.float32)
        elif arr.dtype == _np.int64:
            arr = arr.astype(_np.int32)
        return arr

    def _repl_barrier(self, stream, rseq, dup=False):
        """Block an ack until the configured replication mode's
        durability point (the contract ci/check_robustness.py pins on
        the dispatch source): in sync mode no push — fresh or
        dup-refused — may be acked before the backup holds it. A
        dup-refused push waits for the stream to drain (its original
        record may still be in flight); a fresh one waits for its own
        record. async mode never waits here — its bound is enforced at
        the forward() end."""
        if stream is None or stream.dead or self._repl_mode != "sync":
            return
        if dup:
            stream.wait_drained()
        elif rseq is not None:
            stream.wait_acked(rseq)

    def _do_init(self, msg, _repl=False):
        _, key, value = msg
        stream = rseq = None
        with self._lock_for(key):
            dst = self._moved.get(key)
            if dst is not None:
                return ("ok", "skipped") if _repl \
                    else self._stale_reply(key, dst)
            if key not in self._table:   # first writer wins (rank 0)
                self._table[key] = self._as_table_value(value)
                self._clock[key] = 0
                stream = None if _repl else self._repl_stream()
                if stream is not None:
                    rseq = stream.forward(("init", key, value))
        self._repl_barrier(stream, rseq)
        return ("ok",)

    def _note_applied(self, rec, key, origin, seq, _repl, rseq=None):
        """Post-apply bookkeeping, under the SAME key lock that
        serialized the apply (ISSUE 19): journal the application for
        the consistency checker; while the repl stream is down
        (``_repl_lost``) buffer the record — with the rseq it was
        forwarded under, if any — for heal-time reconciliation; and,
        between this server's own promotion and the deposed peer's
        reconcile, record every client-applied ident so the reconcile
        replay can be deduped exactly (a high-watermark cannot: this
        primary has already applied the client's post-failover seqs,
        which sit ABOVE the divergence window's)."""
        if not _repl and (self._repl_lost   # mxlint: allow(shared-state-race) — GIL-atomic flag reads gating the slow path; the flags flip under _ctr_lock and the lock is retaken before mutating
                          or self._epoch_applied is not None):
            with self._ctr_lock:
                if (self._repl_lost   # mxlint: allow(shared-state-race) — re-checked under _ctr_lock, the lock every _repl_lost/_unreplicated writer holds; the unlocked sites are the gating fast-path reads blessed above
                        and len(self._unreplicated) < _RECONCILE_MAX):
                    self._unreplicated.append((rseq, rec))
                ea = self._epoch_applied
                if ea is not None and origin is not None:
                    if len(ea) < _RECONCILE_MAX * 16:
                        ea.add((origin, seq, key))
                    else:
                        self._epoch_applied_overflow = True
        if origin is not None and _consistency.enabled():
            _consistency.journal(
                "apply", origin=origin, seq=seq, key=str(key),
                epoch=self._epoch, clock=self._clock[key],   # mxlint: allow(shared-state-race) — GIL-atomic journal stamp under the key lock: the epoch an apply records is whichever this server honored at that instant, exactly what the checker wants
                node=self.address, role=self._role,   # mxlint: allow(shared-state-race) — GIL-atomic journal stamp; a role flip mid-apply is scoped by the wipe record the demotion journals
                via="repl" if _repl else "client",
                digest=_consistency.digest(self._table[key]))

    def _do_push(self, msg, _repl=False, _reconcile=False):
        # ("push", key, grad, base_clock[, origin, seq[, epoch]]) — the
        # origin/seq pair makes a retried push at-most-once: a replay
        # whose seq this server already applied for that origin+key
        # is acked but NOT re-applied (the ack, not the update, was
        # what got lost). Legacy 4-tuple pushes skip dedupe. The
        # trailing fencing epoch (ISSUE 19) is the client-frame fencing
        # trigger: a client that witnessed a promotion this server
        # missed deposes it on contact. ``_reconcile`` bypasses the
        # watermark dup check: a heal-time replay carries seqs BELOW
        # the watermark (the client moved on after failover) that were
        # nonetheless never applied here — the reconcile arm has
        # already proven that exactly, per record.
        key, grad, base_clock = msg[1], msg[2], msg[3]
        origin, seq = (msg[4], msg[5]) if len(msg) >= 6 \
            else (None, None)
        if not _repl and len(msg) >= 7 and msg[6] is not None \
                and msg[6] > self._epoch:
            self._fence(msg[6], "client frame carried a newer epoch")
            return ("err", "fenced: shard replica %s was deposed by a "
                           "peer promotion (epoch %d)"
                           % (self.address, self._fenced_at))
        stream = rseq = None
        dup = False
        with self._lock_for(key):
            if key not in self._table:
                dst = self._moved.get(key)
                if dst is not None:
                    # handed away in an online split: route, don't fail
                    # (a repl record for a moved key is a stream replay
                    # the release already ordered after — skip it)
                    return ("ok", "skipped") if _repl \
                        else self._stale_reply(key, dst)
                if _repl and not self._catchup_complete:   # mxlint: allow(shared-state-race) — GIL-atomic flag read under the key lock; the skip-until-transferred protocol tolerates a momentarily stale value
                    # catch-up in progress and this key has not been
                    # transferred yet: skip — the pending xfer record
                    # was snapshotted on the primary AFTER this push
                    # applied there, so it already carries its effect
                    return ("ok", "skipped")
                return ("err", "push to uninitialized key %r" % (key,))
            if not _reconcile and origin is not None and \
                    self._applied.get((origin, key), 0) >= seq:
                with self._ctr_lock:
                    self._dup_n += 1
                dup = True
                stream = None if _repl else self._repl_stream()
            else:
                if origin is not None:
                    # max, not assign: a reconcile replay's seq sits
                    # below the watermark and must not reopen it
                    self._applied[(origin, key)] = max(
                        self._applied.get((origin, key), 0), seq)
                # a restored snapshot may trail the clock a worker based
                # its step on: clamp, staleness is never negative
                stale = max(0, self._clock[key] - base_clock)
                with self._ctr_lock:
                    self._stale_max = max(self._stale_max, stale)
                    self._stale_sum += stale
                    self._stale_n += 1
                self._m_pushes.inc()
                self._note_worker_push(origin, stale)
                g = _wire_decode(grad)
                store = self._table[key]
                stream = None if _repl else self._repl_stream()
                rec = ("push", key, grad, base_clock, origin, seq)
                # records are enqueued UNDER the lock that serialized
                # the apply: per-key stream order matches apply order
                # (a state-transfer snapshot can never be overtaken by
                # a push it already contains), and updater-path records
                # additionally enqueue under the updater lock so the
                # catch-up's optimizer-state snapshot is totally
                # ordered against every state mutation. The raw wire
                # payload is forwarded, so the backup replays the exact
                # update (updater math included) bit-for-bit.
                if self._updater is not None:
                    # async semantics: apply THIS push now, no merge
                    # wait. Common optimizers apply on their numpy host
                    # mirror (Updater.update_host — no per-key device
                    # round-trip, the cost that dominated the dist
                    # Module hot loop); anything without a host mirror
                    # bounces through NDArray and lands the result back
                    # as numpy (np.asarray of a CPU jax buffer is
                    # zero-copy, and that buffer is immutable — pulls
                    # may hand it out without a tear copy; the host
                    # path writes a fresh array for the same reason).
                    with self._updater_lock:
                        new_w = self._updater.update_host(
                            _key_int(key), store, g)
                        if new_w is None:
                            w = nd.array(store)
                            self._updater(_key_int(key), nd.array(g), w)
                            new_w = _np.asarray(w._data)
                        self._table[key] = new_w
                        self._clock[key] += 1
                        if stream is not None:
                            rseq = stream.forward(rec)
                else:
                    # accumulate in place straight from the wire buffer:
                    # no device asarray copy + dispatch per push — the
                    # single biggest CPU cost of the old apply path
                    _np.add(store, g, out=store, casting="unsafe")
                    self._clock[key] += 1
                    if stream is not None:
                        rseq = stream.forward(rec)
                self._note_applied(rec, key, origin, seq, _repl,
                                   rseq=rseq)
        if not dup:
            with self._ctr_lock:
                self._push_count += 1
                pushes = self._push_count
            if self._ckpt is not None and self._snapshot_every > 0 \
                    and pushes % self._snapshot_every == 0:
                self.snapshot()
        self._repl_barrier(stream, rseq, dup=dup)
        return ("ok", "dup") if dup else ("ok",)

    def _ensure_sparse_table(self, key):
        """Mark ``key`` row-wise-mutable and return its table entry.
        The dense updater path replaces entries wholesale so zero-copy
        local pulls may alias them; the row-wise path updates rows IN
        PLACE (the whole point: O(rows touched) per push), so the
        first sparse touch replaces the entry with a private copy and
        flags the key — pulls of flagged keys copy (``pull`` /
        ``pushpull`` arms) instead of aliasing. Caller holds the key
        lock."""
        if key not in self._sparse_keys:
            self._sparse_keys.add(key)
            self._table[key] = _np.array(self._table[key], copy=True)
        return self._table[key]

    def _do_sparse_push(self, msg, _repl=False, _reconcile=False):
        # ("spush", key, row_ids, rows, base_clock[, origin, seq]) —
        # the row-sparse push (reference DataHandleRowSparse,
        # kvstore_dist_server.h:631-792, on the PR-10 wire): only the
        # touched rows travel, the row-wise optimizer
        # (Updater.update_host_rows) charges only those rows, and the
        # same (origin, seq) watermark keeps replays at-most-once.
        # Optimizers without a row-wise mirror densify the gradient
        # and take the dense path — correct for ALL of them, fast for
        # sgd/adagrad/adam.
        key, row_ids, rows, base_clock = msg[1], msg[2], msg[3], msg[4]
        origin, seq = (msg[5], msg[6]) if len(msg) >= 7 else (None, None)
        if not _repl and len(msg) >= 8 and msg[7] is not None \
                and msg[7] > self._epoch:
            self._fence(msg[7], "client frame carried a newer epoch")
            return ("err", "fenced: shard replica %s was deposed by a "
                           "peer promotion (epoch %d)"
                           % (self.address, self._fenced_at))
        stream = rseq = None
        dup = False
        with self._lock_for(key):
            if key not in self._table:
                dst = self._moved.get(key)
                if dst is not None:
                    return ("ok", "skipped") if _repl \
                        else self._stale_reply(key, dst)
                if _repl and not self._catchup_complete:   # mxlint: allow(shared-state-race) — GIL-atomic flag read under the key lock; the skip-until-transferred protocol tolerates a momentarily stale value
                    return ("ok", "skipped")
                return ("err", "push to uninitialized key %r" % (key,))
            if not _reconcile and origin is not None and \
                    self._applied.get((origin, key), 0) >= seq:
                with self._ctr_lock:
                    self._dup_n += 1
                dup = True
                stream = None if _repl else self._repl_stream()
            else:
                ids = _np.asarray(row_ids, dtype=_np.int64)
                store = self._table[key]
                if ids.size and (ids.min() < 0
                                 or ids.max() >= store.shape[0]):
                    return ("err", "sparse push row_ids out of range "
                                   "for %r: [%d, %d] vs %d rows"
                            % (key, ids.min(), ids.max(),
                               store.shape[0]))
                if origin is not None:
                    self._applied[(origin, key)] = max(
                        self._applied.get((origin, key), 0), seq)
                stale = max(0, self._clock[key] - base_clock)
                with self._ctr_lock:
                    self._stale_max = max(self._stale_max, stale)
                    self._stale_sum += stale
                    self._stale_n += 1
                self._m_pushes.inc()
                self._note_worker_push(origin, stale)
                g = _wire_decode(rows)   # bf16 rows upcast; the fp32
                #                          master-table contract holds
                store = self._ensure_sparse_table(key)
                stream = None if _repl else self._repl_stream()
                rec = ("spush", key, row_ids, rows, base_clock, origin,
                       seq)
                if self._updater is not None:
                    with self._updater_lock:
                        new_rows = self._updater.update_host_rows(
                            _key_int(key), store, ids, g)
                        if new_rows is None:
                            # densify fallback: scatter the rows into a
                            # zero gradient and run the dense apply —
                            # any optimizer, O(table) cost
                            dense = _np.zeros_like(store)
                            dense[ids] = _np.asarray(g, store.dtype)
                            new_w = self._updater.update_host(
                                _key_int(key), store, dense)
                            if new_w is None:
                                w = nd.array(store)
                                self._updater(_key_int(key),
                                              nd.array(dense), w)
                                new_w = _np.asarray(w._data)
                            store[...] = new_w
                        else:
                            store[ids] = _np.asarray(new_rows,
                                                     store.dtype)
                        self._clock[key] += 1
                        if stream is not None:
                            rseq = stream.forward(rec)
                else:
                    # accumulate: ids are unique per frame (the worker
                    # dedupes), so a plain scatter-add lands each row
                    _np.add.at(store, ids, _np.asarray(g, store.dtype))
                    self._clock[key] += 1
                    if stream is not None:
                        rseq = stream.forward(rec)
                self._note_applied(rec, key, origin, seq, _repl,
                                   rseq=rseq)
                with self._ctr_lock:
                    self._sparse_pushes += 1
                    self._sparse_rows += int(ids.size)
        if not dup:
            with self._ctr_lock:
                self._push_count += 1
                pushes = self._push_count
            if self._ckpt is not None and self._snapshot_every > 0 \
                    and pushes % self._snapshot_every == 0:
                self.snapshot()
        self._repl_barrier(stream, rseq, dup=dup)
        return ("ok", "dup") if dup else ("ok",)

    def _do_stream_commit(self, commit, origin, seq, _repl=False):
        """Advance one consumer group's committed (segment, offset)
        consumption cursor — the offsets half of a ``stream_push``
        frame (ISSUE 18). The SAME deterministic (origin, seq) identity
        that deduped the frame's gradient parts gates the cursor, so a
        respawned trainer replaying its last frame can neither re-train
        the records (per-key watermark) nor re-advance / rewind the
        cursor (this watermark). Returns True when the commit was a
        refused replay."""
        if commit is None:
            return False
        group, shard, seg, offset, final = commit
        stream = rseq = None
        dup = False
        with self._stream_lock:
            if self._stream_applied.get(origin, -1) >= seq:
                dup = True
                stream = None if _repl else self._repl_stream()
            else:
                self._stream_applied[origin] = int(seq)
                ckey = (group, int(shard), int(seg))
                cur = self._stream_offsets.get(ckey)
                if cur is None:
                    cur = [0, False]
                    self._stream_offsets[ckey] = cur
                cur[0] = max(cur[0], int(offset))
                cur[1] = bool(cur[1] or final)
                with self._ctr_lock:
                    self._stream_commits += 1
                stream = None if _repl else self._repl_stream()
                if stream is not None:
                    # enqueued under the stream lock: the backup's
                    # cursor order matches the primary's apply order
                    rseq = stream.forward(
                        ("stream_commit", tuple(commit), origin,
                         int(seq)))
        if final and not dup:
            # a fully-consumed segment's lease retires with its final
            # commit (the lease epoch string IS the stream origin); a
            # late cursor_next for it re-leases an exhausted segment,
            # which the committed offset renders a no-op re-read
            with self._cursor_lock:
                self._cursors.pop(origin, None)
        self._repl_barrier(stream, rseq, dup=dup)
        return dup

    # state commands a backup refuses until promoted: the replication
    # stream must stay the only writer (and the authoritative reader)
    # of a backup's table, or failover could serve/accept torn state
    _CLIENT_STATE_CMDS = frozenset(
        ("init", "push", "pushpull", "spush", "spushpull", "pull",
         "pull_rows", "multi",
         "set_optimizer", "opt_states", "set_opt_states", "barrier",
         "split", "adopt_key", "cursor_next", "cursor_done",
         "publish", "stream_push", "stream_offsets"))

    def _dispatch(self, msg, _repl=False):
        cmd = msg[0]
        if not _repl and self._role == "backup" \
                and cmd in self._CLIENT_STATE_CMDS:
            # "not_serving" is a routing verdict, not a failure: the
            # client's _ReplicatedConn swaps to the real primary on it
            return ("err", "not_serving: shard replica %s is a backup "
                           "(primary: %s)"
                           % (self.address, self._peer_addr))
        if not _repl and self._fenced and cmd in self._CLIENT_STATE_CMDS:
            # "fenced" is likewise a routing verdict (ISSUE 19): this
            # server was deposed by a promotion it did not witness —
            # acking anything now is split-brain. The message carries
            # the HIGHER epoch so clients adopt it on sight.
            return ("err", "fenced: shard replica %s was deposed by a "
                           "peer promotion (epoch %d)"
                           % (self.address, self._fenced_at))
        if cmd == "init":
            return self._do_init(msg, _repl=_repl)
        if cmd == "push":
            return self._do_push(msg, _repl=_repl)
        if cmd == "pushpull":
            # the reference's fused PushPull (kvstore_dist_server.h
            # DataHandleDefault + response): apply the push, reply with
            # the post-update value and clock in the SAME round trip —
            # the dist Module fast path's per-batch op. Replication
            # forwards the underlying push record, so backups replay it
            # exactly like a plain push; a deduped replay still answers
            # with the current value (at-most-once apply, always-fresh
            # read).
            reply = self._do_push(("push",) + tuple(msg[1:]),
                                  _repl=_repl)
            if reply[0] != "ok":
                return reply
            key = msg[1]
            with self._lock_for(key):
                if key not in self._table:
                    dst = self._moved.get(key)
                    if dst is not None:
                        return self._stale_reply(key, dst)
                    return ("err", "pull of uninitialized key %r" % (key,))
                tbl = self._table[key]
                value = tbl if self._updater is not None and \
                    key not in self._sparse_keys else tbl.copy()
                # half-width wire (AMP): the push payload's dtype IS the
                # tag — reply in kind, so a bf16 pushpull round trip
                # ships half the bytes BOTH ways while the table stays
                # the fp32 master. A deduped replay carries the same
                # payload, so its reply keeps the same dtype (the
                # at-most-once apply / always-fresh read contract is
                # dtype-stable).
                wire_dt = getattr(msg[2], "dtype", None)
                if wire_dt is not None and _half_float(wire_dt) and \
                        isinstance(value, _np.ndarray) and \
                        value.dtype == _np.float32:
                    value = value.astype(wire_dt)
                return ("ok", value, self._clock[key])
        if cmd == "spush":
            return self._do_sparse_push(msg, _repl=_repl)
        if cmd == "spushpull":
            # the row-sparse PushPull (ISSUE 13): apply the touched
            # rows, reply gather-in-kind with the SAME rows' post-
            # update values and the clock in one round trip — the
            # per-batch wire op of the fused sparse-embedding dist
            # step. A seq-deduped replay skips the apply but still
            # answers with the CURRENT row values (at-most-once
            # apply, always-fresh read, exactly like dense pushpull).
            reply = self._do_sparse_push(("spush",) + tuple(msg[1:]),
                                         _repl=_repl)
            if reply[0] != "ok":
                return reply
            key, row_ids = msg[1], msg[2]
            with self._lock_for(key):
                if key not in self._table:
                    dst = self._moved.get(key)
                    if dst is not None:
                        return self._stale_reply(key, dst)
                    return ("err", "pull of uninitialized key %r" % (key,))
                ids = _np.asarray(row_ids, dtype=_np.int64)
                # fancy indexing copies — safe to pickle outside the
                # lock even though sparse entries mutate in place
                rows_out = self._table[key][ids]
                # half-width wire (AMP): the rows payload's dtype IS
                # the tag — reply in kind, fp32 master table unchanged
                wire_dt = getattr(msg[3], "dtype", None)
                if wire_dt is not None and _half_float(wire_dt) and \
                        rows_out.dtype == _np.float32:
                    rows_out = rows_out.astype(wire_dt)
                return ("ok", rows_out, self._clock[key])
        if cmd == "pull":
            _, key = msg
            with self._lock_for(key):
                if key not in self._table:
                    dst = self._moved.get(key)
                    if dst is not None:
                        return self._stale_reply(key, dst)
                    return ("err", "pull of uninitialized key %r" % (key,))
                tbl = self._table[key]
                # the reply is pickled OUTSIDE this lock: hand out a
                # stable copy where in-place writes could tear it (the
                # accumulate path, and any sparse-flagged key — its
                # rows mutate in place). The dense updater path
                # replaces entries wholesale (immutable once visible),
                # so its pulls ship zero-copy.
                value = tbl if self._updater is not None and \
                    key not in self._sparse_keys else tbl.copy()
                return ("ok", value, self._clock[key])
        if cmd == "pull_rows":
            # sparse pull (reference kvstore_dist_server.h:631-792
            # DataHandleRowSparse): only the requested rows travel
            _, key, row_ids = msg
            with self._lock_for(key):
                if key not in self._table:
                    dst = self._moved.get(key)
                    if dst is not None:
                        return self._stale_reply(key, dst)
                    return ("err", "pull of uninitialized key %r" % (key,))
                rows = self._table[key][_np.asarray(row_ids)]
                return ("ok", rows, self._clock[key])
        if cmd == "multi":
            # coalesced frame: one wire frame, many commands, replies in
            # order. Each sub-command fires its own server.recv
            # injection point so op=/key= fault rules still target
            # individual pushes inside a batch; a sever mid-batch leaves
            # a prefix applied, which the client's whole-batch replay +
            # seq dedupe makes at-most-once.
            replies = []
            for sub in msg[1]:
                _fault.fire("server.recv", op=sub[0],
                            key=sub[1] if len(sub) > 1 and
                            isinstance(sub[1], (str, int)) else None,
                            server=self)
                replies.append(self._dispatch(sub))
            return ("ok", replies)
        if cmd == "split":
            return self._do_split(msg)
        if cmd == "adopt_key":
            # ("adopt_key", key, value, clock, applied, updater_state):
            # the receiving half of an online shard split — overwrite-
            # install under the key lock, forward to OUR backup before
            # the ack (sync mode: the new shard is replicated before
            # the old primary releases the key), and refuse replays
            # that would clobber a newer local copy (the clock is the
            # idempotency watermark, exactly like a replayed xfer).
            _, key, value, clock, applied, state = msg
            stream = rseq = None
            dup = False
            with self._lock_for(key):
                if self._clock.get(key, -1) >= int(clock):
                    dup = True
                else:
                    self._table[key] = _np.array(value, copy=True)
                    self._clock[key] = int(clock)
                    for o, s in applied:
                        prev = self._applied.get((o, key), 0)
                        self._applied[(o, key)] = max(prev, int(s))
                    self._moved.pop(key, None)   # a key may move back
                    if state is not None:
                        with self._updater_lock:
                            if self._updater is not None:
                                self._updater.set_state_one(
                                    _key_int(key),
                                    bytes(_np.asarray(
                                        state, dtype=_np.uint8)))
                    self._keys_adopted += 1
                    stream = None if _repl else self._repl_stream()
                    if stream is not None:
                        rseq = stream.forward(
                            ("adopt_key", key, value, clock, applied,
                             state))
            self._repl_barrier(stream, rseq)
            return ("ok", "dup") if dup else ("ok",)
        if cmd == "shard_map":
            # the versioned forwarding table: which keys this server
            # handed away, and where (clients refresh on a version bump
            # advertised in hello/ping replies)
            return ("ok", {"version": self._map_version,
                           "fence_epoch": self._epoch,
                           "moved": dict(self._moved)})
        if cmd == "cursor_next":
            # ("cursor_next", origin, epoch, num_shards, rid): one
            # data-shard assignment off the server-owned epoch cursor.
            # rid makes the reply replay-safe: a retried request (lost
            # ack) gets the SAME shard back instead of a second one.
            _, origin, epoch, num_shards, rid = msg
            self._worker_rec(origin)
            # int epochs are training-data cursors; string epochs are
            # streaming segment leases (exactly-once segment handout)
            if not isinstance(epoch, str):
                epoch = int(epoch)
            with self._cursor_lock:
                cur = self._cursor_for(epoch, num_shards)
                last = cur["last"].get(origin)
                held = [s for s, o in cur["outstanding"].items()
                        if o == origin]
                if last is not None and last[0] == rid:
                    shard = last[1]
                elif held and isinstance(epoch, str):
                    # a segment-lease holder re-asking (fresh rid)
                    # re-gets its own shard — a restarted tail
                    # re-leases its segment instead of deadlocking
                    # behind itself. Training cursors (int epochs)
                    # keep handing out FRESH shards: a worker
                    # legitimately pipelines several at once
                    shard = held[0]
                    cur["last"][origin] = (rid, shard)
                else:
                    if cur["requeued"]:
                        shard = cur["requeued"].pop(0)
                    elif cur["next"] < cur["num_shards"]:
                        shard = cur["next"]
                        cur["next"] += 1
                    else:
                        shard = None
                    if shard is not None:
                        cur["outstanding"][shard] = origin
                    cur["last"][origin] = (rid, shard)
                if shard is not None:
                    # the grant is stamped with the CURRENT fencing
                    # epoch (ISSUE 19): after a partition heals, a
                    # completion presented under an older stamp for a
                    # shard that was re-granted since is refused — a
                    # partitioned StreamingIter tailer cannot double-
                    # consume a segment past the heal
                    cur["granted"][shard] = self._epoch
                pending = cur["num_shards"] - len(cur["done"])
            return ("ok", shard, pending, self._epoch)
        if cmd == "cursor_done":
            # shard finished: it can never be re-queued, and once every
            # shard of the epoch is done the cursor reports pending=0
            # so pollers stop waiting (idempotent: done is a set). The
            # optional trailing element is the fencing epoch the shard
            # was granted under (see cursor_next).
            _, origin, epoch, shard = msg[:4]
            done_epoch = msg[4] if len(msg) > 4 else None
            if not isinstance(epoch, str):
                epoch = int(epoch)
            with self._cursor_lock:
                cur = self._cursors.get(epoch)
                if cur is not None:
                    granted = cur["granted"].get(shard) \
                        if "granted" in cur else None
                    holder = cur["outstanding"].get(shard)
                    if done_epoch is not None and granted is not None \
                            and done_epoch < granted \
                            and holder is not None and holder != origin:
                        return ("err", "fenced: shard %r of cursor %r "
                                       "was re-granted to %s under a "
                                       "newer fleet epoch (epoch %d)"
                                % (shard, epoch, holder, granted))
                    cur["outstanding"].pop(shard, None)
                    cur["done"].add(shard)
            return ("ok",)
        if cmd == "stream_push":
            # ("stream_push", origin, seq, parts, commit) — the
            # exactly-once serve→train frame (ISSUE 18): gradient parts
            # AND the consumption offset they were computed from commit
            # under ONE deterministic identity. ``origin`` names the
            # (consumer group, log shard, segment) and ``seq`` derives
            # from the record end-offset, so a kill -9'd trainer's
            # respawn re-sends bit-identical frames — every replay is
            # refused by the same per-(origin, key) watermarks that
            # dedupe ordinary pushes, and the cursor by its own
            # watermark. Parts are push/spush-shaped: ("d", key, grad,
            # base_clock) or ("s", key, row_ids, rows, base_clock); a
            # parts-less frame is a pure offset commit (segment
            # finalize).
            _, origin, seq, parts, commit = msg
            dups = []
            for p in parts:
                if p[0] == "s":
                    reply = self._do_sparse_push(
                        ("spush", p[1], p[2], p[3], p[4], origin, seq),
                        _repl=_repl)
                else:
                    reply = self._do_push(
                        ("push", p[1], p[2], p[3], origin, seq),
                        _repl=_repl)
                if reply[0] != "ok":
                    return reply
                dups.append(len(reply) > 1 and reply[1] == "dup")
            cdup = self._do_stream_commit(commit, origin, seq,
                                          _repl=_repl)
            if commit is not None:
                dups.append(cdup)
            if dups and all(dups):
                with self._ctr_lock:
                    self._stream_dup += 1
                return ("ok", "dup")
            return ("ok",)
        if cmd == "stream_offsets":
            # ("stream_offsets", group): one consumer group's committed
            # consumption cursors — what a respawned tailer resumes
            # from, and what the GC watermark (fleet-min fully-consumed
            # segment) is computed over
            group = msg[1]
            with self._stream_lock:
                rows = [[sh, sg, int(off), bool(fin)]
                        for (g, sh, sg), (off, fin)
                        in self._stream_offsets.items() if g == group]
            return ("ok", sorted(rows))
        if cmd == "set_optimizer":
            _, payload = msg
            self._install_optimizer(bytes(payload))
            stream = rseq = None
            if not _repl:
                with self._repl_guard:
                    stream = self._repl
                if stream is not None:
                    rseq = stream.forward(
                        ("set_optimizer", self._opt_payload))
            self._repl_barrier(stream, rseq)
            return ("ok",)
        if cmd == "opt_states":
            # this shard's updater states, pickled numpy
            # (Updater.get_states): the client's save_optimizer_states
            # merges the disjoint per-shard slots into one file
            if self._updater is None:
                return ("err", "no optimizer installed on %s"
                        % self.address)
            with self._updater_lock:
                return ("ok", self._updater.get_states())
        if cmd == "set_opt_states":
            # install saved updater states (each shard uses only its
            # own keys' slots); replicated like set_optimizer so a
            # promoted backup carries the restored state too
            _, payload = msg
            if self._updater is None:
                return ("err", "no optimizer installed on %s"
                        % self.address)
            stream = rseq = None
            with self._updater_lock:
                self._updater.set_states(bytes(payload))
                if not _repl:
                    with self._repl_guard:
                        stream = self._repl
                    if stream is not None:
                        rseq = stream.forward(("set_opt_states", payload))
            self._repl_barrier(stream, rseq)
            return ("ok",)
        if cmd == "repl":
            # one replication-stream record from our primary:
            # ("repl", stream_id, rseq, sub). A new stream id is a
            # (re)joined primary incarnation — reset the watermark, a
            # fresh catch-up follows. The monotone rseq watermark
            # refuses every replay (window failure, reconnect,
            # duplicate flush) and keeps a replayed xfer overwrite from
            # clobbering a later forwarded push. Records arrive on ONE
            # pinned socket, so the serial per-connection handler loop
            # preserves the primary's total send order.
            if self._role == "primary":
                # a zombie old primary streaming at a promoted server
                # must be refused, not applied over the live table —
                # and the refusal carries OUR epoch, so the sender
                # fences itself on sight (_on_repl_dead parses it)
                return ("err", "fenced: %s is a promoted primary; "
                               "refusing replication records (epoch %d)"
                        % (self.address, self._epoch))
            _, sid, rseq, sub = msg[:4]
            rec_epoch = msg[4] if len(msg) > 4 else None
            if rec_epoch is not None and rec_epoch != self._epoch:
                if rec_epoch < self._epoch:
                    # a stale-epoch stream: its primary was deposed by
                    # a promotion it has not witnessed yet
                    return ("err", "fenced: replication record at "
                                   "stale epoch %d refused by %s "
                                   "(epoch %d)"
                            % (rec_epoch, self.address, self._epoch))
                # adopt: the stream IS the primary's authority
                self._epoch = rec_epoch   # mxlint: allow(shared-state-race) — forward-only adopt on the single repl-apply path of a backup; no client arm acks while role is backup, so a momentarily stale reader cannot ack under the old epoch
            if sid != self._repl_stream_id:
                self._repl_stream_id = sid
                self._repl_applied_rseq = 0
            if rseq <= self._repl_applied_rseq:
                self._repl_dup += 1
                return ("ok", "dup")
            self._repl_applied_rseq = rseq
            self._repl_received += 1
            sc = sub[0]
            if sc in ("push", "spush", "init", "set_optimizer",
                      "adopt_key"):
                return self._dispatch(sub, _repl=True)
            if sc == "moved":
                # the primary handed ``key`` away mid-split: mirror the
                # release (ordered after that key's last forwarded push
                # by the key lock), so a promotion of THIS backup still
                # refuses the moved key with the right forward address
                _, key, dst = sub
                with self._lock_for(key):
                    self._moved[key] = dst
                    # cross-key counter: the key lock only serializes
                    # THIS key — concurrent moved records for other
                    # keys bump too, and a lost increment would let two
                    # different maps share a version
                    with self._ctr_lock:
                        self._map_version += 1
                    self._table.pop(key, None)
                    self._clock.pop(key, None)
                    for pair in [p for p in list(self._applied)
                                 if p[1] == key]:
                        self._applied.pop(pair, None)
                return ("ok",)
            if sc == "moved_map":
                # catch-up bulk form: the whole forwarding table as the
                # primary held it at transfer start (later splits ride
                # as individual ``moved`` records after it)
                _, moved, version = sub
                for k, d in moved.items():
                    self._moved[k] = d
                self._map_version = max(self._map_version, int(version))   # mxlint: allow(shared-state-race) — repl records arrive on ONE pinned socket; the serial per-connection handler loop is the stream's total order
                return ("ok",)
            if sc == "opt_states":
                # accumulated updater state (momentum, update counts,
                # live optimizer) — set_optimizer rode the stream
                # first, so the updater exists to restore into
                if self._updater is not None:
                    with self._updater_lock:
                        self._updater.set_states(
                            bytes(_np.asarray(sub[1],
                                              dtype=_np.uint8)))
                return ("ok",)
            if sc == "xfer":
                # state-transfer overwrite: value + clock + the key's
                # push-dedupe seqs, exactly as the primary held them
                _, key, value, clock, applied = sub
                with self._lock_for(key):
                    self._table[key] = _np.array(value, copy=True)
                    self._clock[key] = int(clock)
                    for o, s in applied:
                        prev = self._applied.get((o, key), 0)
                        self._applied[(o, key)] = max(prev, int(s))
                return ("ok",)
            if sc == "stream_commit":
                # the offsets half of a forwarded stream_push frame:
                # the backup mirrors the consumption cursor under the
                # same (origin, seq) watermark, so a promoted backup
                # resumes tailers from exactly the primary's commit
                _, commit, origin, seq = sub
                self._do_stream_commit(tuple(commit), origin, int(seq),
                                       _repl=True)
                return ("ok",)
            if sc == "catchup_done":
                self._catchup_complete = True   # mxlint: allow(shared-state-race) — repl records arrive on ONE pinned socket; the serial per-connection handler loop is the stream's total order
                _log.info("parameter server %s: backup caught up "
                          "(%d keys)", self.address, len(self._table))
                return ("ok",)
            return ("err", "unknown repl record %r" % (sc,))
        if cmd == "promote":
            # client-driven failover: flip this backup to primary. The
            # stream applied every record as it arrived, so the "log
            # replay" already happened continuously — promotion is
            # O(1) and the table serves immediately.
            with self._repl_guard:
                was = self._role
                if was == "backup":
                    self._role = "primary"
                    # mint the fencing epoch (ISSUE 19): monotone,
                    # durable (snapshots carry it), and the line every
                    # split-brain check hangs off — the deposed
                    # incumbent is one epoch behind from this instant
                    self._epoch += 1   # mxlint: allow(shared-state-race) — the promotion mint under _repl_guard; every other writer is a monotone adopt, so readers on any thread see some epoch this server honored, never a torn or regressing value
                    self._promotions += 1
                    self._catchup_complete = True
                    with self._ctr_lock:
                        # record every client-applied ident from this
                        # instant until the deposed peer reconciles:
                        # the exact-dedupe set its replay checks
                        # against (the watermark can't — clients'
                        # post-failover seqs land above the deposed
                        # side's divergence window)
                        self._epoch_applied = set()
                        self._epoch_applied_overflow = False
                    _log.warning(
                        "parameter server %s: promoted backup -> "
                        "primary at epoch %d (old primary %s presumed "
                        "dead or partitioned)",
                        self.address, self._epoch, self._peer_addr)
            if was == "backup":
                _consistency.journal("promote", node=self.address,
                                     epoch=self._epoch)
                if self._ckpt is not None:
                    # the epoch must survive a crash of the NEW primary:
                    # snapshot now, not at the next push interval
                    self.snapshot()
            return ("ok", {"role": self._role, "was": was,
                           "fence_epoch": self._epoch})
        if cmd == "peer_info":
            with self._repl_guard:
                backup = self._backup_addr \
                    if self._repl is not None and not self._repl.dead \
                    else None
            return ("ok", {"role": self._role, "addr": self.address,
                           "backup": backup,
                           "fence_epoch": self._epoch,
                           "fenced": self._fenced,
                           "catchup_complete": self._catchup_complete,
                           "keys": len(self._table)})
        if cmd == "peer_alive":
            # probe-through-peer (ISSUE 19): a client that lost its
            # link to one replica asks the OTHER replica whether the
            # peer is dead or merely unreachable from that client —
            # "dead" justifies promotion, "alive but cut off from you"
            # does not (the client marks it unreachable and degrades)
            info = self._peer_request("peer_info", retries=0,
                                      timeout=1.0)
            peer = info[1] if info is not None else None
            return ("ok", {"role": self._role,
                           "fence_epoch": self._epoch,
                           "peer_alive": peer is not None,
                           "peer_role":
                               peer.get("role") if peer else None,
                           "peer_epoch":
                               int(peer.get("fence_epoch", 0))
                               if peer else None})
        if cmd == "reconcile":
            # heal-time replay of a fenced ex-primary's applied-but-
            # unreplicated window (ISSUE 19). The (origin, key) push
            # watermarks CANNOT dedupe this replay — they assume FIFO
            # per origin, and this primary has already applied the
            # clients' post-failover seqs, which sit above the
            # divergence window's — so each record is deduped exactly:
            #   * forwarded on the dead stream and rseq <= the prefix
            #     we applied for that stream id -> already replicated;
            #   * ident in _epoch_applied (client-applied here since
            #     our promotion) -> the client itself replayed its
            #     unacked copy after failing over;
            #   * otherwise it exists only on the deposed side: apply
            #     (watermark bypassed), forwarding to OUR backup like
            #     any other write.
            if self._role != "primary":
                return ("err", "not_serving: reconcile at a backup")
            _, peer_epoch, sid, entries = msg
            with self._ctr_lock:
                ea = self._epoch_applied
                exact = ea is not None and \
                    not self._epoch_applied_overflow
            if not exact:
                _log.warning(
                    "parameter server %s: reconcile without an exact "
                    "epoch-applied record (%s) — falling back to "
                    "watermark dedupe, replays below the watermark "
                    "are refused", self.address,
                    "overflowed" if ea is not None else "not recording")
            applied = dup = 0
            for rseq, rec in entries:
                rec = tuple(rec)
                if rseq is not None and sid is not None \
                        and sid == self._repl_stream_id \
                        and rseq <= self._repl_applied_rseq:
                    dup += 1      # replicated to us before the cut
                    continue
                if ea is not None and _rec_ident(rec) in ea:
                    dup += 1      # the client replayed it post-failover
                    continue
                if rec[0] == "push":
                    reply = self._do_push(rec, _reconcile=exact)
                elif rec[0] == "spush":
                    reply = self._do_sparse_push(rec, _reconcile=exact)
                else:
                    continue
                if reply[0] == "ok":
                    if len(reply) > 1 and reply[1] == "dup":
                        dup += 1
                    else:
                        applied += 1
            with self._ctr_lock:
                # reconciliation done: the deposed window is settled,
                # stop recording (and free) the epoch-applied idents
                self._epoch_applied = None
                self._epoch_applied_overflow = False
            _log.warning(
                "parameter server %s: reconciled %d records from the "
                "deposed epoch-%s primary (%d applied, %d already "
                "held)", self.address, len(entries), peer_epoch,
                applied, dup)
            return ("ok", {"applied": applied, "dup": dup,
                           "fence_epoch": self._epoch})
        if cmd == "join_backup":
            # a (re)spawned peer asks to become our backup: attach the
            # stream and start the state transfer, after which the
            # pair is redundant again
            if self._role != "primary":
                return ("err", "not_serving: a backup cannot adopt a "
                               "backup")
            if self._fenced:
                return ("err", "fenced: %s was deposed and cannot "
                               "adopt a backup (epoch %d)"
                        % (self.address, self._fenced_at))
            self._attach_backup(msg[1])
            return ("ok", {"stream": self._repl.id,
                           "fence_epoch": self._epoch})
        if cmd == "hello":
            # worker (re-)registration: a fresh store — or a respawned
            # worker's fresh store — announces its origin/rank; the
            # membership epoch lets anyone observe churn
            _, origin, rank = msg[0], msg[1], msg[2] if len(msg) > 2 \
                else None
            cli_epoch = msg[3] if len(msg) > 3 else None
            if cli_epoch is not None and cli_epoch > self._epoch:
                # the rejoin-handshake fencing trigger: a registering
                # client that witnessed a promotion this server missed
                if self._role == "primary":
                    self._fence(cli_epoch,
                                "hello carried a newer epoch")
                else:
                    self._epoch = cli_epoch
            self._gc_workers()
            self._worker_rec(origin, rank=rank)
            # the hello reply is where clients learn the shard's
            # (primary, backup) map: before any backup attached, the
            # configured peer is still the address a failover will find
            backup = self._backup_addr or \
                (self._peer_addr if self._role == "primary" else None)
            with self._workers_lock:
                return ("ok", {"epoch": self._membership_epoch,
                               "workers": len(self._workers),
                               "role": self._role,   # mxlint: allow(shared-state-race) — GIL-atomic observability read inside the hello/membership arm; one momentarily stale reply is harmless
                               "fence_epoch": self._epoch,
                               "fenced": self._fenced,   # mxlint: allow(shared-state-race) — GIL-atomic observability read inside the hello/membership arm; one momentarily stale reply is harmless
                               "backup": backup,
                               # the versioned shard map rides every
                               # hello, so a (re)joining worker starts
                               # with current routing
                               "map_version": self._map_version,   # mxlint: allow(shared-state-race) — GIL-atomic observability read inside the hello/membership arm; one momentarily stale reply is harmless
                               "moved": dict(self._moved)})
        if cmd == "bye":
            # clean departure: membership leaves NOW (no dead-after
            # wait) and the worker's dedupe seqs are reclaimed
            self._drop_worker(msg[1])
            return ("ok",)
        if cmd == "ping":
            # liveness probe: cheapest possible round trip (no table
            # access) so a loaded server still answers heartbeats; a
            # probe carrying the worker's origin also refreshes its
            # membership lease
            if len(msg) > 1 and msg[1] is not None:
                self._worker_rec(msg[1])
            self._gc_workers()
            return ("ok", {"pushes": self._stale_n,
                           "keys": len(self._table),
                           "role": self._role,
                           "fence_epoch": self._epoch,
                           # heartbeat half of map propagation: a bump
                           # makes the client fetch the full shard_map
                           "map_version": self._map_version})
        if cmd == "barrier":
            # optional deadline (seconds) after num_workers: a barrier
            # that cannot complete — a member died mid-epoch — degrades
            # to a counted, logged timeout instead of hanging the fleet.
            # num_workers of 0/None is the ELASTIC form: the target is
            # the CURRENT membership, re-evaluated on every join/leave
            # (the _notify_membership wakeups), so a departed worker
            # releases the survivors by re-count, not by deadline.
            num_workers = msg[1]
            dynamic = not num_workers

            def _target():
                if not dynamic:
                    return num_workers
                with self._workers_lock:
                    return max(1, len(self._workers))

            deadline = None
            if len(msg) > 2 and msg[2]:
                deadline = time.monotonic() + float(msg[2])
            with self._barrier_cv:
                gen = self._barrier_gen
                self._barrier_arrived += 1
                if self._barrier_arrived >= _target():
                    self._barrier_arrived = 0
                    self._barrier_gen += 1
                    self._barrier_cv.notify_all()
                    return ("ok",)
                while self._barrier_gen == gen:
                    if dynamic and self._barrier_arrived >= _target():
                        # membership shrank to (or below) the arrivals:
                        # a re-count release, the healthy elastic path
                        self._barrier_recounts += 1
                        self._barrier_arrived = 0
                        self._barrier_gen += 1
                        self._barrier_cv.notify_all()
                        return ("ok", "recount")
                    wait = 120.0
                    if deadline is not None:
                        wait = deadline - time.monotonic()
                        if wait <= 0:
                            # force-release the generation so every
                            # other waiter unblocks too (they would
                            # otherwise wait for a count that can no
                            # longer be reached)
                            arrived = self._barrier_arrived
                            self._barrier_timeouts += 1
                            self._barrier_arrived = 0
                            self._barrier_gen += 1
                            self._barrier_cv.notify_all()
                            _log.warning(
                                "barrier released by deadline with "
                                "%d/%d arrivals", arrived, _target())
                            return ("ok", "timeout")
                    self._barrier_cv.wait(timeout=wait)
            return ("ok",)
        if cmd == "metrics":
            # the telemetry surface (ISSUE 14): this process's whole
            # registry snapshot — instruments plus views, the
            # "kv.server" view included — in one round trip. Strictly
            # passive (no key locks, no state mutated) and answered by
            # backups too: a backup's telemetry must not require a
            # promotion.
            return ("ok", _obs.REGISTRY.snapshot())
        if cmd == "stats":
            avg = self._stale_sum / self._stale_n if self._stale_n else 0.0
            self._gc_workers()
            with self._workers_lock:
                workers = {
                    o: {"rank": r["rank"], "pushes": r["pushes"],
                        "staleness_max": r["stale_max"],
                        "staleness_avg": (r["stale_sum"] / r["pushes"]
                                          if r["pushes"] else 0.0),
                        "push_gap_max": r["push_gap_max"]}
                    for o, r in self._workers.items()}
                epoch = self._membership_epoch
            with self._repl_guard:
                repl = None
                if self._repl is not None:
                    repl = {"backup": self._backup_addr,
                            "mode": self._repl_mode,
                            "dead": self._repl.dead,
                            "lag": self._repl.lag(),
                            "forwarded": self._repl.forwarded,
                            "dup_acks": self._repl.dup_acks,
                            "catchup": dict(self._catchup)
                            if self._catchup else None}
            with self._pub_cv:
                weight_stream = {
                    "published_version": self._pub_version,
                    "publishes": self._pub_count,
                    "subscribers": dict(self._weight_subs)}
            return ("ok", {"staleness_max": self._stale_max,
                           "staleness_avg": avg,
                           "pushes": self._stale_n,
                           "dup_pushes": self._dup_n,
                           "sparse_pushes": self._sparse_pushes,
                           "sparse_rows": self._sparse_rows,
                           "sparse_keys": len(self._sparse_keys),
                           "snapshots": self._snap_count,
                           "restored_step": self._restored_step,
                           "clocks": dict(self._clock),
                           "workers": workers,
                           "membership_epoch": epoch,
                           "barrier_timeouts": self._barrier_timeouts,
                           "barrier_recounts": self._barrier_recounts,
                           "joins": self._joins,
                           "leaves": self._leaves,
                           "splits": self._splits,
                           "keys_moved_out": self._keys_moved_out,
                           "keys_adopted": self._keys_adopted,
                           "map_version": self._map_version,
                           "moved_keys": len(self._moved),
                           "cursor_requeues": self._cursor_requeues,
                           "stream_commits": self._stream_commits,
                           "stream_dup": self._stream_dup,
                           "stream_segments": len(self._stream_offsets),
                           "role": self._role,
                           "promotions": self._promotions,
                           "fence_epoch": self._epoch,
                           "fenced": self._fenced,
                           "unreplicated": len(self._unreplicated),
                           "repl": repl,
                           "repl_received": self._repl_received,
                           "repl_dup": self._repl_dup,
                           "weight_stream": weight_stream,
                           "catchup_complete": self._catchup_complete})
        if cmd == "publish":
            return self._do_publish(msg)
        if cmd == "weights":
            # ("weights", origin, have_version, wait_s): the weight
            # stream's delivery op — long-poll until a version past the
            # caller's watermark exists (or wait_s elapses), then ship
            # the WHOLE version (full coherent blobs, digest-tagged).
            # A replay/reconnect with the same watermark is a no-op
            # catch-up, never a double apply.
            _, origin, have, wait_s = msg
            have = int(have)
            deadline = time.monotonic() + min(float(wait_s or 0), 60.0)
            with self._pub_cv:
                while self._pub_version <= have and not self._tcp.dying:
                    remain = deadline - time.monotonic()
                    if remain <= 0:
                        break
                    self._pub_cv.wait(timeout=min(remain, 0.5))
                v = self._pub_version
                if origin is not None:
                    self._weight_subs[origin] = max(
                        self._weight_subs.get(origin, -1), have)
                if v <= have:
                    return ("ok", {"version": v, "params": None,
                                   "digest": None})
                # blobs are replaced wholesale per publish, never
                # mutated — safe to pickle outside the lock
                return ("ok", {"version": v, "params": self._published,
                               "digest": self._pub_digest})
        if cmd == "weight_sub":
            # subscriber registration on the weight stream: watermarks
            # (and so lag) surface in stats()['weight_stream']
            _, origin = msg
            with self._pub_cv:
                self._weight_subs.setdefault(origin, -1)
                return ("ok", {"version": self._pub_version})
        if cmd == "stop":
            threading.Thread(target=self.stop, daemon=True).start()
            return ("ok",)
        return ("err", "unknown command %r" % (cmd,))

    def metrics_view(self):
        """The scalar server-side counters as one registry view row —
        what a fleet poller reads per shard without the heavyweight
        per-key clocks/workers tables of the ``stats`` op. Lock-light:
        plain attribute reads of monotone counters (a torn read is at
        worst one tick stale, which telemetry tolerates by design)."""
        with self._repl_guard:
            repl_lag = self._repl.lag() if self._repl is not None \
                and not self._repl.dead else None
        with self._workers_lock:
            n_workers = len(self._workers)
            # this shard's push-count straggler verdict, same rule as
            # the fleet view (_fleet_worker_view) but computable from
            # ONE shard's registry row — what the autoscaling policy
            # reads from fleet.json (mxtpu/fleet/policy.py evicts only
            # workers EVERY live shard calls a straggler, confirmed
            # over several sweeps)
            stragglers = []
            if self._workers:
                lead = max(w.get("pushes", 0)
                           for w in self._workers.values())
                if lead >= _STRAGGLER_MIN:
                    stragglers = sorted(
                        [o, w.get("rank")]
                        for o, w in self._workers.items()
                        if w.get("pushes", 0) * _STRAGGLER_FACTOR
                        < lead)
        return {"addr": self.address, "role": self._role,
                "stragglers": stragglers,
                "pushes": self._stale_n, "dup_pushes": self._dup_n,
                "sparse_pushes": self._sparse_pushes,
                "keys": len(self._table), "workers": n_workers,
                "staleness_max": self._stale_max,
                "joins": self._joins, "leaves": self._leaves,
                "splits": self._splits,
                "keys_moved_out": self._keys_moved_out,
                "keys_adopted": self._keys_adopted,
                "map_version": self._map_version,
                "barrier_timeouts": self._barrier_timeouts,
                "barrier_recounts": self._barrier_recounts,
                "promotions": self._promotions,
                "repl_lag": repl_lag,
                "catchup_complete": self._catchup_complete,
                "published_version": self._pub_version,
                "snapshots": self._snap_count}

    def _do_publish(self, msg):
        """("publish", version, meta, pin): snapshot the CURRENT table
        as one versioned, digest-tagged weight record — write it to the
        versioned snapshot dir (when configured) and wake every
        ``weights`` long-poller. Per-key values are copied under their
        key locks; the published set is one coherent read of the table.
        The version watermark makes a replayed publish a dup, and the
        ``publish.snapshot`` fault point fires BEFORE anything is
        visible, so a dropped/severed/killed publish loses the version
        cleanly — subscribers keep the last COMPLETE one."""
        _, version, meta, pin = msg
        with self._pub_cv:
            v = self._pub_version + 1 if version is None \
                else int(version)
            if v <= self._pub_version:
                return ("ok", {"version": self._pub_version,
                               "digest": self._pub_digest,
                               "dup": True})
        act = _fault.fire("publish.snapshot", op="publish",
                          key="v%d" % v, server=self)
        if act == "drop":
            return ("err", "publish of weight version %d dropped "
                           "(injected) — subscribers keep version %d"
                    % (v, self._pub_version))
        from .checkpoint import weight_digest
        blobs = {}
        for key in list(self._table):
            with self._lock_for(key):
                val = self._table.get(key)
                if val is not None:
                    blobs[str(key)] = _np.array(val, copy=True)
        digest = weight_digest(blobs)
        if self._weight_dir:
            if self._weight_ckpt is None:
                from .checkpoint import CheckpointManager
                self._weight_ckpt = CheckpointManager(
                    self._weight_dir,
                    max_to_keep=int(os.environ.get(
                        "MXTPU_SERVE_WEIGHT_KEEP", "5")),
                    async_save=False, use_orbax=False)
            self._weight_ckpt.save(v, blobs,
                                   metadata=dict(meta or {},
                                                 digest=digest))
            if pin:
                self._weight_ckpt.pin(v)
        with self._pub_cv:
            if v > self._pub_version:
                self._pub_version = v
                self._published = blobs
                self._pub_digest = digest
                self._pub_count += 1
                self._pub_cv.notify_all()
        return ("ok", {"version": v, "digest": digest})

    def _install_optimizer(self, payload):
        opt = sys.modules.get("mxtpu.optimizer")
        if opt is None:
            from . import optimizer as opt
        optimizer = _ModuleUnpickler(io.BytesIO(payload)).load()
        self._updater = opt.get_updater(optimizer)
        self._opt_payload = payload

    # -- snapshot / auto-resume -------------------------------------------
    @staticmethod
    def _tag_key(k):
        # npz/json-safe reversible tagging: table keys are ints or strs
        return ["i", int(k)] if isinstance(k, int) else ["s", str(k)]

    @staticmethod
    def _untag_key(tagged):
        t, v = tagged
        return int(v) if t == "i" else str(v)

    def snapshot(self):   # mxlint: allow(shared-state-race) — reads are GIL-atomic one-shot copies (list(dict.items()), int loads); per-key value consistency is taken under each key lock in the loop above them
        """Write one consistent-enough snapshot of the service state.

        Per-key consistency is exact (value and clock copied under the
        key's lock); cross-key skew of a few pushes is inherent to async
        mode and harmless — a restored table is just a slightly stale
        table, which workers already tolerate. Non-blocking for pushes
        to OTHER snapshots: if a snapshot is already being written this
        one is skipped (the next push-interval boundary fires again)."""
        if self._ckpt is None:
            return False
        if not self._snap_lock.acquire(blocking=False):
            return False
        try:
            params, keys, clocks = {}, [], []
            for key in list(self._table):
                with self._lock_for(key):
                    params["t%d" % len(keys)] = \
                        _np.array(self._table[key], copy=True)
                    keys.append(self._tag_key(key))
                    clocks.append(int(self._clock[key]))
            # stable copies BEFORE the Python-level loops: handler
            # threads insert into these dicts concurrently, and any
            # iteration of the live dict — even list(d.items()) — can
            # die with "dictionary changed size during iteration"
            # (surfaced by the shared-state-race lockset pass; the
            # writers hold per-KEY locks, so there is no lock a reader
            # could take)
            applied = list(_racing_copy(self._applied).items())
            moved = list(_racing_copy(self._moved).items())
            stream_applied = list(
                _racing_copy(self._stream_applied).items())
            stream_offsets = list(
                _racing_copy(self._stream_offsets).items())
            meta = {"keys": keys, "clocks": clocks,
                    "applied": [[o, self._tag_key(k), int(s)]
                                for (o, k), s in applied],
                    # the streaming consumption cursors + their commit
                    # watermarks ride every snapshot: a restarted shard
                    # must keep refusing replayed stream frames and
                    # resuming tailers from the committed offsets
                    "stream_applied": [[o, int(s)]
                                       for o, s in stream_applied],
                    "stream_offsets": [[g, int(sh), int(sg), int(off),
                                        bool(fin)]
                                       for (g, sh, sg), (off, fin)
                                       in stream_offsets],
                    "push_count": int(self._push_count),
                    # the forwarding table survives a restart: a
                    # respawned server must keep refusing split-away
                    # keys (map_stale), not 404 them
                    "moved": [[self._tag_key(k), d]
                              for k, d in moved],
                    # the fencing epoch is durable (ISSUE 19): a
                    # crashed-and-respawned primary restores the epoch
                    # it was promoted at, so a still-running deposed
                    # peer can never out-rank it with a stale epoch
                    "fence_epoch": int(self._epoch),
                    "map_version": int(self._map_version)}
            extras = None
            if self._opt_payload is not None:
                extras = {"optimizer": _np.frombuffer(
                    self._opt_payload, dtype=_np.uint8)}
            self._snap_count += 1
            self._ckpt.save(self._snap_count, params, metadata=meta,
                            extras=extras)
            return True
        finally:
            self._snap_lock.release()

    def _restore_snapshot(self):   # mxlint: allow(shared-state-race) — boot-time restore: start() runs this before the listener/handler threads exist
        step = self._ckpt.latest_step()
        if step is None:
            return
        tree = self._ckpt.restore(step)
        meta = tree["metadata"]
        for i, (tagged, clock) in enumerate(zip(meta["keys"],
                                                meta["clocks"])):
            key = self._untag_key(tagged)
            # owned writable copy: the accumulate path adds in place
            self._table[key] = _np.array(tree["params"]["t%d" % i],
                                         copy=True)
            self._clock[key] = int(clock)
        self._applied = {(o, self._untag_key(k)): int(s)
                         for o, k, s in meta.get("applied", [])}
        self._stream_applied = {o: int(s) for o, s
                                in meta.get("stream_applied", [])}
        self._stream_offsets = {
            (g, int(sh), int(sg)): [int(off), bool(fin)]
            for g, sh, sg, off, fin in meta.get("stream_offsets", [])}
        self._moved = {self._untag_key(k): d
                       for k, d in meta.get("moved", [])}
        self._map_version = int(meta.get("map_version", 0))
        self._epoch = max(self._epoch,
                          int(meta.get("fence_epoch", 1)))
        self._push_count = int(meta.get("push_count", 0))
        self._snap_count = step
        self._restored_step = step
        extras = tree.get("extras") or {}
        if "optimizer" in extras:
            self._install_optimizer(
                bytes(_np.asarray(extras["optimizer"],
                                  dtype=_np.uint8)))


def serve_forever():
    """Server-role process entry (DMLC_ROLE=server, started by
    tools/launch.py -s N). Binds the port given in MXTPU_PS_PORT and
    blocks until a worker sends 'stop'."""
    # serve_forever is reached DURING the mxtpu package import (the
    # kvstore_server role hook fires from _optional_imports) and never
    # returns — so every module and lazy code path a handler thread will
    # need must be warmed NOW, in this thread: any import that names the
    # mxtpu package from another thread blocks on the package's
    # _initializing lock until an import that never finishes does.
    from . import optimizer as _opt
    warm = _opt.get_updater(_opt.SGD(learning_rate=0.01, momentum=0.9,
                                     wd=1e-4))
    warm(0, nd.ones((1,)), nd.ones((1,)))
    port = int(os.environ.get("MXTPU_PS_PORT", "0"))
    srv = ParameterServer(port=port)
    # replicated pairs: settle the role BEFORE serving — the listen
    # socket is already bound (construction), so early client frames
    # queue in the accept backlog instead of being refused, and none
    # can reach a respawned ex-primary before it notices its peer is
    # the authority and demotes
    srv.join_cluster()
    srv.start()
    resumed = "" if srv._restored_step is None else \
        " (resumed from snapshot %d: %d keys)" % (srv._restored_step,
                                                  len(srv._table))
    paired = "" if srv._peer_addr is None else \
        " [%s of pair with %s]" % (srv._role, srv._peer_addr)
    print("mxtpu parameter server listening on %s%s%s"
          % (srv.address, paired, resumed), flush=True)
    # the server role process blocks here until 'stop' BY DESIGN —
    # this is its entire lifecycle, there is nothing to time out to
    srv._thread.join()   # mxlint: allow(blocking-call) — serve_forever entry point


# sockets per server per worker: the server handles each connection on
# its own thread, so k sockets let k in-flight parts unpickle/apply in
# parallel inside ONE server. Default 1 — on a 1-core host extra
# sockets buy nothing (the server CPU, not the socket serialization,
# is the limit there); raise on multi-core servers where handler
# threads can actually overlap.
_CONNS_PER_SERVER = int(os.environ.get("MXTPU_PS_CONNS", "1"))


# retry/backoff knobs for the RPC layer (see module docstring, "Fault
# tolerance"): per-call socket timeout, number of retries after the
# first attempt, and the exponential backoff window between attempts
_REQUEST_TIMEOUT = float(os.environ.get("MXTPU_PS_TIMEOUT", "300"))
_RETRIES = int(os.environ.get("MXTPU_PS_RETRIES", "3"))
_BACKOFF = float(os.environ.get("MXTPU_PS_BACKOFF", "0.05"))
_BACKOFF_MAX = float(os.environ.get("MXTPU_PS_BACKOFF_MAX", "2.0"))
_RECONNECT_TIMEOUT = float(os.environ.get("MXTPU_PS_RECONNECT", "5"))
_DEAD_AFTER = int(os.environ.get("MXTPU_PS_DEAD_AFTER", "3"))

# -- worker liveness (the server-side mirror of the health story) --------
# every barrier arrival waits at most this long before the server
# force-releases the generation — a dead worker degrades a barrier to a
# logged timeout instead of hanging the fleet forever
_BARRIER_TIMEOUT = float(os.environ.get("MXTPU_PS_BARRIER_TIMEOUT", "300"))
# seconds of silence (no push/ping/hello) after which a server garbage-
# collects a worker's membership + buffered dedupe seqs; 0 disables the
# sweep (tests drive exact schedules; production sets a real window)
_WORKER_DEAD_AFTER = float(os.environ.get(
    "MXTPU_PS_WORKER_DEAD_AFTER", "0"))
# straggler verdict: a worker is a straggler when the fleet's max push
# count exceeds factor * its own (once the fleet has pushed enough for
# the ratio to mean anything) — push-count based, so the counters are
# deterministic under the fault matrix, never wall-clock
_STRAGGLER_FACTOR = float(os.environ.get(
    "MXTPU_PS_STRAGGLER_FACTOR", "2.0"))
_STRAGGLER_MIN = int(os.environ.get("MXTPU_PS_STRAGGLER_MIN", "10"))

# -- elasticity (module docstring, "Elasticity") -------------------------
# MXTPU_PS_ELASTIC=1 makes barriers count against the server's CURRENT
# membership — re-evaluated on every join/leave — instead of the
# launch-time fleet size, so a departed worker releases the survivors by
# re-count instead of stranding them until the barrier deadline
_ELASTIC = os.environ.get("MXTPU_PS_ELASTIC", "0") != "0"
# poll interval while the shard cursor waits on another worker's
# outstanding shard (a straggler's assignment requeues on its death)
_CURSOR_POLL = float(os.environ.get("MXTPU_PS_CURSOR_POLL", "0.2"))

# -- partition tolerance (ISSUE 19) --------------------------------------
# before promoting a standby, the client asks it whether it can still
# reach the incumbent (peer_alive). If the standby says yes — the cut is
# client-side only — promotion is suppressed for this grace window and
# the incumbent is marked "unreachable" instead (pulls degrade, pushes
# buffer). After the grace expires, availability wins: promote anyway —
# the fencing epoch makes the aggressive choice safe.
_PARTITION_GRACE = float(os.environ.get("MXTPU_PS_PARTITION_GRACE", "5.0"))
# set to 0 to skip the probe-through-peer check and promote immediately
# on failure, restoring the pre-ISSUE-19 failover behavior
_PARTITION_PROBE = os.environ.get(
    "MXTPU_PS_PARTITION_PROBE", "1") not in ("0", "")
# cap on the deposed primary's applied-but-unreplicated buffer (records
# kept for heal-time reconciliation); beyond it the OLDEST survive —
# the new primary's (origin, seq) watermarks refuse replays anyway
_RECONCILE_MAX = int(os.environ.get("MXTPU_PS_RECONCILE_MAX", "1024"))


def stream_origin(group, shard, seg):
    """The deterministic push identity of one (consumer group, log
    shard, segment) — ISSUE 18's exactly-once anchor. Unlike the
    per-incarnation worker origin (rank + uuid), this derives purely
    from the log position: a kill -9'd trainer's respawn re-computes
    the SAME origin for the same segment, so its replayed frames land
    on the server's existing (origin, seq) watermarks and are refused,
    not re-applied. Doubles as the segment's lease-cursor epoch."""
    return "st|%s|%d|%08d" % (group, int(shard), int(seg))


def stream_commit_seq(offset, final):
    """The monotone commit sequence for a consumption offset within
    one segment: strictly increasing in the offset, with the
    ``final`` (segment fully consumed) flag ordered AFTER a plain
    commit at the same offset — so an empty-tail finalize is never
    refused as a replay of the last record's commit."""
    return (int(offset) << 1) | (1 if final else 0)
# map_stale forwarding bound: a client whose shard map is k versions
# stale needs at most k hops to find a key's current home
_MAP_HOPS = 4


def _stale_dst(err):
    """The new-home address out of a ``map_stale`` refusal, else None
    (the refusal is a routing verdict: the command was NOT executed)."""
    m = re.search(r"map_stale: key .+ moved to (\S+) \(map_version",
                  str(err))
    return m.group(1) if m else None


def _fenced_epoch(err):
    """The higher fencing epoch out of a ``fenced`` refusal, else None.
    Like ``map_stale``, ``fenced`` is a routing verdict: the command
    was NOT executed; the client refetches the map and replays with
    its original (origin, seq) at the fenced-in home."""
    m = re.search(r"fenced: .*\(epoch (\d+)\)", str(err))
    return int(m.group(1)) if m else None


def _rec_ident(rec):
    """(origin, seq, key) identity of a replication/reconcile record,
    or None for record kinds without one (init, set_optimizer, ...)."""
    if rec[0] == "push":
        return (rec[4], rec[5], rec[1])
    if rec[0] == "spush":
        return (rec[5], rec[6], rec[1])
    return None

# every command whose replay is harmless: pull/pull_rows/stats/ping read,
# init is first-writer-wins, set_optimizer re-installs the same payload,
# push dedupes via its (origin, seq) pair (pushpull likewise — a
# replayed apply is refused but the reply still carries the current
# value), and multi only ever carries the preceding commands. Replication traffic is replay-safe too: repl
# records dedupe on the backup's rseq watermark, promote/peer_info are
# naturally idempotent, and a replayed join_backup just restarts the
# catch-up on a fresh stream id. barrier is NOT here — a replayed
# arrival would double-count this worker in the generation.
# The elastic commands replay safely too: shard_map reads, cursor_next
# dedupes on its rid (a retry gets the SAME shard back), cursor_done
# marks into a set, adopt_key refuses clocks at or below its watermark,
# and a replayed split only re-moves keys still local. The streaming
# plane is replay-safe BY CONSTRUCTION: stream_push frames carry a
# deterministic (origin, seq) identity the watermarks refuse, and
# stream_offsets is a read.
_IDEMPOTENT = frozenset(
    ("init", "push", "pushpull", "spush", "spushpull", "pull",
     "pull_rows", "stats", "ping",
     "set_optimizer", "opt_states", "set_opt_states", "multi",
     "hello", "bye", "repl", "promote", "peer_info", "join_backup",
     "peer_alive", "reconcile",
     "shard_map", "cursor_next", "cursor_done", "adopt_key", "split",
     "publish", "weights", "weight_sub", "metrics",
     "stream_push", "stream_offsets"))


class _Pending:
    """One in-flight request on a channel. ``on_partial`` (set before
    the frame is sent) receives streamed partial replies — frames
    tagged ``"+"`` that do NOT retire the pending slot; the terminal
    2-tuple reply still pairs and releases the window as always."""

    __slots__ = ("cid", "event", "reply", "error", "on_partial")

    def __init__(self, cid, on_partial=None):
        self.cid = cid
        self.event = threading.Event()
        self.reply = None
        self.error = None
        self.on_partial = on_partial


class _Channel:
    """One pipelined socket to a server: frames go out under a send lock
    stamped with correlation ids, a receiver thread pairs replies back
    to their waiters, and a bounded window (``MXTPU_PS_WINDOW``) caps
    how many requests ride unacknowledged. Any failure — socket error,
    injected sever, a waiter's deadline — kills the whole channel:
    every in-flight request fails with ConnectionError and the retry
    layer above replays exactly the unacked window (the push seq dedupe
    makes those replays at-most-once)."""

    def __init__(self, conn, sock, window):
        self._conn = conn
        self._sock = sock
        self._window = threading.Semaphore(window)
        self._pending = {}         # cid -> _Pending
        self._lock = threading.Lock()
        self._send_lock = threading.Lock()
        self._next_cid = itertools.count(1)
        self.dead = False
        self._err = None
        self._rx = threading.Thread(target=self._recv_loop, daemon=True,
                                    name="mxtpu-ps-rx")
        self._rx.start()

    def inflight(self):
        with self._lock:
            return len(self._pending)

    def submit(self, msg, timeout, on_partial=None):
        """Register a pending slot and send the frame; returns without
        waiting for the reply — up to the window size of these stream
        back to back on one socket. ``on_partial`` (if given) is called
        from the receiver thread with each streamed partial reply for
        this request; the terminal 2-tuple reply still pairs normally."""
        if not self._window.acquire(timeout=timeout):
            raise ConnectionError(
                "pipelined window stalled %.1fs on %s"
                % (timeout, self._conn.addr))
        p = _Pending(next(self._next_cid), on_partial=on_partial)
        with self._lock:
            if self.dead:
                self._window.release()
                raise ConnectionError("channel closed: %s" % (self._err,))
            self._pending[p.cid] = p
            self._conn._stats.hwm(len(self._pending))
        try:
            act = _fault.fire("worker.send", op=msg[0],
                              key=msg[1] if len(msg) > 1 else None,
                              sock=self._sock, addr=self._conn.addr)
            if act != "drop":      # dropped frame: the peer never sees
                # a sampled trace rides as a third frame element —
                # metadata only, absent (classic 2-tuple) when no
                # trace is active on this thread
                tctx = _obs.wire_ctx()
                frame = (p.cid, msg) if tctx is None \
                    else (p.cid, msg, tctx)
                with self._send_lock:   # it; the waiter's deadline fires
                    _send_frame(self._sock, frame,
                                stats=self._conn._stats)
        except BaseException as e:
            self.fail(e)
            raise
        return p

    def wait(self, p, msg, timeout):
        try:
            _fault.fire("worker.recv", op=msg[0],
                        key=msg[1] if len(msg) > 1 else None,
                        sock=self._sock, addr=self._conn.addr)
        except BaseException as e:
            self.fail(e)
            raise
        if not p.event.wait(timeout):
            # a silent reply (dropped frame, hung server) can only be
            # noticed here; the stream position may be anywhere, so the
            # whole channel dies and the window replays
            self.fail(ConnectionError(
                "no reply within %.1fs for %r from %s"
                % (timeout, msg[0], self._conn.addr)))
        if p.error is not None:
            raise p.error
        return p.reply

    def _recv_loop(self):
        while True:
            try:
                frame = _recv_frame(self._sock, stats=self._conn._stats)
            except socket.timeout:
                continue   # idle tick; waiters enforce their deadlines
            except BaseException as e:
                self.fail(e)
                return
            if isinstance(frame, tuple) and len(frame) == 3 \
                    and frame[2] == "+":
                # streamed partial: delivered to the pending slot's
                # callback without retiring it — the window stays held
                # until the terminal 2-tuple reply pairs.  A partial
                # for an unknown cid (caller already failed/timed out)
                # is dropped silently.
                with self._lock:
                    p = self._pending.get(frame[0])
                if p is not None and p.on_partial is not None:
                    try:
                        p.on_partial(frame[1])
                    except BaseException:   # mxlint: allow(except-swallow) — a caller's partial-frame observer raising must not tear the shared channel under every OTHER in-flight request; the terminal reply still pairs and carries the authoritative full answer
                        pass
                continue
            if not isinstance(frame, tuple) or len(frame) != 2:
                self.fail(ConnectionError("unpaired reply frame"))
                return
            with self._lock:
                p = self._pending.pop(frame[0], None)
            if p is not None:
                p.reply = frame[1]
                p.event.set()
                self._window.release()

    def fail(self, err):
        """Tear the channel down once: close the socket, fail every
        pending waiter. Idempotent (the receiver, a failed submit and a
        timed-out waiter may all race here)."""
        with self._lock:
            if self.dead:
                return
            self.dead = True
            self._err = err
            pend = list(self._pending.values())
            self._pending.clear()
        try:
            # shutdown BEFORE close: close() alone defers the real fd
            # close while the receiver thread is blocked in recv() on
            # this socket, so the thread (and the server's handler for
            # this connection, which never sees our FIN) would linger
            # until the socket timeout ticks — hundreds of zombie
            # threads under a connection-churning load
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        for p in pend:
            p.error = ConnectionError(
                "connection to %s failed: %s: %s"
                % (self._conn.addr, type(err).__name__, err))
            p.event.set()
            self._window.release()


class _ServerConn:
    """One worker's view of one server: a set of pipelined channels
    (``MXTPU_PS_CONNS`` sockets, each with a ``MXTPU_PS_WINDOW``-deep
    in-flight window), the retry/backoff RPC layer, and this worker's
    health bookkeeping for the server: consecutive request/heartbeat
    failures past ``MXTPU_PS_DEAD_AFTER`` mark it ``dead``; any success
    marks it ``ok`` again."""

    def __init__(self, addr, connect_timeout=60.0, token=None,
                 n_socks=None, request_timeout=None, retries=None,
                 stats=None, window=None):
        self.addr = addr
        self._host, _, port = addr.partition(":")
        self._port = int(port)
        self._token = token
        self._timeout = _REQUEST_TIMEOUT if request_timeout is None \
            else float(request_timeout)
        self._retries = _RETRIES if retries is None else int(retries)
        self._window_n = max(1, _WINDOW if window is None else int(window))
        self._own_stats = stats is None   # release our registry series
        self._stats = stats if stats is not None else _CommStats()
        self.state = "ok"
        self.failures = 0          # consecutive failures
        self.last_error = None
        self.last_ping = {}        # last ping reply info (map_version)
        # this pair lineage's fencing epoch as witnessed by THIS worker
        # (ISSUE 19). Epochs are minted per replica pair — comparing
        # epochs across unrelated shards is meaningless — so frames to
        # this server are stamped from here, never from a fleet-wide
        # max (a promotion on shard A must not fence healthy shard B).
        self.fence_epoch = 1
        self._unreach_since = None
        self._health_lock = threading.Lock()
        n_socks = max(1, n_socks if n_socks is not None
                      else _CONNS_PER_SERVER)
        self._channels = [None] * n_socks
        self._ch_locks = [threading.Lock() for _ in range(n_socks)]
        self._rr = itertools.count()
        # eager first connect: the launcher starts servers and workers
        # simultaneously and a server binds only after its (slow) mxtpu
        # import + updater warm-up — on localhost an unbound port
        # refuses instantly, so retry with backoff instead of failing
        # the whole launch. Extra channels connect lazily.
        self._channels[0] = _Channel(
            self, self._connect(time.time() + connect_timeout),
            self._window_n)

    def _connect(self, deadline):
        delay = 0.1
        while True:
            try:
                s = socket.create_connection((self._host, self._port),
                                             timeout=self._timeout)
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                break
            except OSError:
                if time.time() >= deadline:
                    raise
                time.sleep(delay)
                delay = min(delay * 2, 2.0)
        if self._token:
            s.sendall(_auth_blob(self._token))
        return s

    @property
    def n_socks(self):
        return len(self._channels)

    def note_epoch(self, ep):
        """Monotone adopt of a fencing epoch witnessed for this
        server's pair (hello/ping/shard_map replies, fenced refusals)."""
        if ep is not None and int(ep) > self.fence_epoch:
            self.fence_epoch = int(ep)   # mxlint: allow(shared-state-race) — monotone max of a GIL-atomic int; a lost race re-adopts on the next witnessed reply

    def _channel(self, i=None):
        """The channel for slot ``i`` (round-robin when unspecified),
        lazily (re)connected — a failed channel is never reused, its
        replacement gets a fresh socket (a stale reply must not
        mispair even across reconnects: cids are per-channel)."""
        if i is None:
            i = next(self._rr) % len(self._channels)
        with self._ch_locks[i]:
            ch = self._channels[i]
            if ch is None or ch.dead:
                ch = _Channel(
                    self, self._connect(time.time() + _RECONNECT_TIMEOUT),
                    self._window_n)
                self._channels[i] = ch
            return ch

    # -- health bookkeeping ----------------------------------------------
    def _note_ok(self):
        with self._health_lock:
            recovered = self.state == "dead"
            self.state = "ok"
            self.failures = 0
            self.last_error = None
            self._unreach_since = None
        return recovered

    def _note_failure(self, err):
        with self._health_lock:
            self.failures += 1
            self.last_error = "%s: %s" % (type(err).__name__, err)
            if self.failures >= _DEAD_AFTER and \
                    self.state != "unreachable":
                self.state = "dead"

    def mark_dead(self, err):
        with self._health_lock:
            self.failures = max(self.failures, _DEAD_AFTER)
            self.state = "dead"
            self.last_error = "%s: %s" % (type(err).__name__, err)

    def mark_unreachable(self, err):
        """Partition verdict (ISSUE 19): the server is alive — its peer
        can still reach it — but OUR link to it is cut. Distinguished
        from ``dead`` so the health surface, and anything keying off
        it, knows no promotion is warranted: pulls degrade to cached
        values and pushes buffer until the link heals."""
        with self._health_lock:
            self.state = "unreachable"
            self.last_error = "%s: %s" % (type(err).__name__, err)
            if self._unreach_since is None:
                self._unreach_since = time.monotonic()

    def unreachable_for(self):
        """Seconds this server has been in the ``unreachable`` state
        (0.0 when it is not)."""
        with self._health_lock:
            if self.state != "unreachable" or \
                    self._unreach_since is None:
                return 0.0
            return time.monotonic() - self._unreach_since

    def health(self):
        with self._health_lock:
            return {"addr": self.addr, "state": self.state,
                    "failures": self.failures,
                    "last_error": self.last_error}

    # -- the same-process shortcut ---------------------------------------
    def _local_srv(self):
        """The in-process ParameterServer behind this address, if any.
        Its requests skip socket and pickle entirely: zero copies, one
        direct ``_dispatch`` under the same per-key locks, seq dedupe
        and fault-injection points as a wire request — so the whole
        fault matrix holds on this transport too (``MXTPU_PS_LOCAL=0``
        forces the wire; the matrix tests pin it off)."""
        if not _LOCAL_ON:
            return None
        return _LOCAL_SERVERS.get(self.addr)

    def _local_call(self, srv, msg, timeout):
        op = msg[0]
        key = msg[1] if len(msg) > 1 and isinstance(msg[1], (str, int)) \
            else None
        if srv._tcp.dying:
            raise ConnectionError(
                "in-process server %s is down" % self.addr)
        dropped = _fault.fire("worker.send", op=op, key=key,
                              addr=self.addr) == "drop"
        if not dropped:
            _fault.fire("server.recv", op=op, key=key, server=srv)
            reply = srv._dispatch(msg)
            if _fault.fire("server.send", op=op, key=key,
                           server=srv) != "drop":
                _fault.fire("worker.recv", op=op, key=key,
                            addr=self.addr)
                self._stats.add("local_reqs")
                return reply
        # a dropped request/reply frame is silent on the wire too:
        # only the per-call deadline notices, then the retry layer runs
        time.sleep(timeout)
        raise ConnectionError(
            "no reply within %.1fs for %r from %s"
            % (timeout, op, self.addr))

    # -- the RPC layer ---------------------------------------------------
    def _backoff_delay(self, attempt):
        # bounded exponential backoff with DETERMINISTIC per-server
        # jitter: crc32(addr:attempt) spreads a fleet's retries without
        # randomness (the fault tests replay exact schedules)
        base = min(_BACKOFF * (2 ** attempt), _BACKOFF_MAX)
        j = zlib.crc32(("%s:%d" % (self.addr, attempt)).encode()) % 256
        return base * (1.0 + j / 1024.0)

    def request(self, *msg, **kw):
        """Send one command and return its reply, retrying idempotent
        commands through connection faults with bounded exponential
        backoff. ``timeout=`` overrides the per-call reply deadline
        (heartbeats probe with a short one). A sampled trace on this
        thread records the whole call (retries included) as a
        ``kv.client.rpc`` span."""
        if _obs.active_ctx() is None:
            return self._request_impl(msg, kw)
        with _obs.span("kv.client.rpc", op=msg[0], addr=self.addr):
            return self._request_impl(msg, kw)

    def _request_impl(self, msg, kw):
        timeout = kw.pop("timeout", None)
        retries = kw.pop("retries", None)
        assert not kw, kw
        timeout = self._timeout if timeout is None else timeout
        if retries is None:
            retries = self._retries if msg[0] in _IDEMPOTENT else 0
        last = None
        t0 = time.perf_counter()
        for attempt in range(retries + 1):
            if attempt:
                self._stats.add("retransmits")
                time.sleep(self._backoff_delay(attempt - 1))
            try:
                srv = self._local_srv()
                if srv is not None:
                    reply = self._local_call(srv, msg, timeout)
                else:
                    ch = self._channel()
                    reply = ch.wait(ch.submit(msg, timeout), msg, timeout)
            except (ConnectionError, EOFError, OSError) as e:
                last = e
                self._note_failure(e)
                continue
            self._note_ok()
            _KVC_RPC_MS.labels(msg[0]).observe(
                (time.perf_counter() - t0) * 1e3)
            if reply[0] == "err":
                raise RuntimeError("parameter server: %s" % reply[1])
            return reply
        # _note_failure counted every attempt, so an exhausted retry
        # budget >= MXTPU_PS_DEAD_AFTER already flipped state to dead;
        # a single failed probe (retries=0) only increments the count
        raise ConnectionError(
            "parameter server %s unreachable during %r after %d "
            "attempt(s): %s (a close right after connect usually means "
            "MXTPU_PS_TOKEN does not match between this worker and the "
            "server)" % (self.addr, msg[0], retries + 1, last)) from last

    def stream(self, *msg, **kw):
        """Send one command whose reply is a STREAM: zero or more
        partial frames (tagged ``"+"`` on the wire, delivered to
        ``on_partial`` from the receiver thread) followed by one
        terminal reply, which is returned. Never retried here — a
        partially-streamed command is not idempotent at this layer;
        the caller replays with its own dedupe (the serving client
        pins the weight version and dedupes tokens by index)."""
        on_partial = kw.pop("on_partial", None)
        timeout = kw.pop("timeout", None)
        assert not kw, kw
        timeout = self._timeout if timeout is None else timeout
        t0 = time.perf_counter()
        try:
            srv = self._local_srv()
            if srv is not None:
                reply = self._local_stream(srv, msg, timeout, on_partial)
            else:
                ch = self._channel()
                p = ch.submit(msg, timeout, on_partial=on_partial)
                reply = ch.wait(p, msg, timeout)
        except (ConnectionError, EOFError, OSError) as e:
            self._note_failure(e)
            raise
        self._note_ok()
        _KVC_RPC_MS.labels(msg[0]).observe(
            (time.perf_counter() - t0) * 1e3)
        if reply[0] == "err":
            raise RuntimeError("parameter server: %s" % reply[1])
        return reply

    def _local_stream(self, srv, msg, timeout, on_partial):
        """In-process mirror of :meth:`stream`, with the same fault
        points as ``_local_call`` plus one ``server.send`` fire per
        partial frame (a dropped partial is silently skipped, exactly
        like a dropped wire frame — the client recovers the token from
        the terminal reply)."""
        op = msg[0]
        key = msg[1] if len(msg) > 1 and isinstance(msg[1], (str, int)) \
            else None
        if srv._tcp.dying:
            raise ConnectionError(
                "in-process server %s is down" % self.addr)
        dropped = _fault.fire("worker.send", op=op, key=key,
                              addr=self.addr) == "drop"
        if not dropped:
            _fault.fire("server.recv", op=op, key=key, server=srv)

            def emit(partial):
                if on_partial is None:
                    return
                if _fault.fire("server.send", op=op, key=key,
                               server=srv) == "drop":
                    return
                on_partial(partial)

            reply = srv._dispatch_stream(msg, emit)
            if _fault.fire("server.send", op=op, key=key,
                           server=srv) != "drop":
                _fault.fire("worker.recv", op=op, key=key)
                self._stats.add("local_reqs")
                return reply
        time.sleep(timeout)
        raise ConnectionError(
            "no reply within %.1fs for %r from %s"
            % (timeout, op, self.addr))

    def request_all(self, msgs, timeout=None, return_exceptions=False):
        """Pipelined fan-out: submit every message before waiting for
        any reply, so k parts cost one streamed pass instead of k
        request-reply round trips. Replies come back in ``msgs`` order.
        A message whose pipelined pass fails is retried through the
        backoff :meth:`request` path (callers pass only idempotent
        commands; push replays are deduped server-side). With
        ``return_exceptions`` a message's terminal ConnectionError /
        err-reply RuntimeError lands in its result slot instead of
        raising, so push callers can buffer individual parts."""
        timeout = self._timeout if timeout is None else timeout
        if self._local_srv() is not None:
            # same-process dispatch is synchronous — there is no RTT to
            # pipeline away, so each message just runs the retrying
            # request path in order
            out = []
            for m in msgs:
                try:
                    out.append(self.request(*m, timeout=timeout))
                except (ConnectionError, RuntimeError) as e:
                    if not return_exceptions:
                        raise
                    out.append(e)
            return out
        calls = []
        for m in msgs:
            try:
                ch = self._channel()
                calls.append((ch.submit(m, timeout), ch))
            except (ConnectionError, EOFError, OSError) as e:
                self._note_failure(e)
                calls.append(None)
        out = []
        for m, c in zip(msgs, calls):
            reply = None
            if c is not None:
                try:
                    reply = c[1].wait(c[0], m, timeout)
                except (ConnectionError, EOFError, OSError) as e:
                    self._note_failure(e)
            if reply is None:
                self._stats.add("retransmits")   # replay of this msg
                try:
                    reply = self.request(*m, timeout=timeout)
                except (ConnectionError, RuntimeError) as e:
                    if not return_exceptions:
                        raise
                    reply = e
            elif reply[0] == "err":
                err = RuntimeError("parameter server: %s" % reply[1])
                if not return_exceptions:
                    raise err
                reply = err
            else:
                self._note_ok()
            out.append(reply)
        return out

    def ping(self, timeout=2.0, origin=None):
        """One heartbeat probe: no retries, short timeout. The probe
        rides its own correlation id on the pipelined channel, so it can
        never interleave with — or steal the socket from — an in-flight
        transfer (the old pool-slot re-acquisition race); when traffic
        is already in flight the server is alive by definition and no
        probe is sent at all. ``origin`` rides along so the probe also
        refreshes this worker's server-side membership lease."""
        for ch in self._channels:
            if ch is not None and not ch.dead and ch.inflight():
                return True
        try:
            if origin is not None:
                reply = self.request("ping", origin, timeout=timeout,
                                     retries=0)
            else:
                reply = self.request("ping", timeout=timeout, retries=0)
            if len(reply) > 1 and isinstance(reply[1], dict):
                self.last_ping = reply[1]
            return True
        except (ConnectionError, OSError):
            return False

    def close(self):
        for ch in self._channels:
            if ch is not None:
                ch.fail(ConnectionError("store closed"))
        if self._own_stats:
            self._stats.release()


class _ReplicatedConn:
    """One worker's view of one *replicated* key shard: a (primary,
    backup) pair of :class:`_ServerConn`s behind the same interface the
    store already speaks, so every routing/buffering/health path above
    works unchanged. Requests route to the active replica; a terminal
    ``ConnectionError`` (retries exhausted — the failed window) or a
    ``not_serving`` refusal (we were talking to a demoted/stale
    replica) triggers an in-place failover: the standby is told to
    ``promote`` and the request replays there. No stale-pull window,
    no buffered-push limbo — the promoted backup already applied every
    forwarded update.

    The backup address comes from ``MXTPU_PS_BACKUP_ADDRS`` or is
    learned from the shard's ``hello`` reply (the shard→(primary,
    backup) map). A generation counter + failover lock keep a stampede
    of concurrently-failing threads from double-promoting or swapping
    twice."""

    def __init__(self, primary_addr, backup_addr=None, token=None,
                 stats=None, on_failover=None, connect_timeout=60.0):
        self._token = token
        self._own_stats = stats is None
        self._stats = stats if stats is not None else _CommStats()
        self._on_failover = on_failover
        self._addrs = [primary_addr, backup_addr]
        self._conns = [None, None]
        self._active_i = 0
        self._gen = 0              # bumps on every swap
        self.failovers = 0
        # ONE epoch for the pair: primary and backup share a fencing
        # lineage, and a promotion on either side advances it (ISSUE 19)
        self.fence_epoch = 1
        self._lock = threading.Lock()
        self._fo_lock = threading.Lock()
        self._conns[0] = _ServerConn(primary_addr, token=token,
                                     stats=self._stats,
                                     connect_timeout=connect_timeout)

    # -- the _ServerConn surface ------------------------------------------
    @property
    def addr(self):
        with self._lock:
            return self._conns[self._active_i].addr

    @property
    def n_socks(self):
        with self._lock:
            return self._conns[self._active_i].n_socks

    @property
    def last_ping(self):
        with self._lock:
            return getattr(self._conns[self._active_i], "last_ping", {})

    @property
    def state(self):
        """'dead' only when NO replica can serve: the active being dead
        while a standby exists is precisely the situation failover
        handles, and callers that buffer on 'dead' must try instead."""
        with self._lock:
            act = self._conns[self._active_i]
            standby = self._conns[1 - self._active_i]
            standby_addr = self._addrs[1 - self._active_i]
        if act.state != "dead":
            return act.state
        if standby is not None:
            return standby.state
        return "ok" if standby_addr is not None else "dead"

    def note_epoch(self, ep):
        """Monotone adopt of this pair's fencing epoch (hello/ping
        replies, fenced refusals from either replica)."""
        if ep is not None and int(ep) > self.fence_epoch:
            self.fence_epoch = int(ep)   # mxlint: allow(shared-state-race) — monotone max of a GIL-atomic int; a lost race re-adopts on the next witnessed reply

    def _learn_backup(self, addr):
        with self._lock:
            if addr and self._addrs[1] is None \
                    and addr != self._addrs[0]:
                self._addrs[1] = addr

    def _failover(self, gen, err, promote=True):
        """Promote the standby and swap it in, unless another thread
        already moved the generation on. Raises ``err`` when no
        standby is configured or the standby cannot be promoted —
        i.e. the shard is genuinely dead.

        Partition discipline (ISSUE 19): with ``promote=False`` (a
        ``fenced`` refusal — the standby already holds a newer epoch)
        the swap happens WITHOUT minting a promotion. Otherwise the
        standby is first asked whether it can still reach the active
        (``peer_alive``): a peer that is alive-but-cut-off-from-us is
        marked ``unreachable`` instead of deposed — no spurious
        promotion on a client-side link cut — until the
        ``MXTPU_PS_PARTITION_GRACE`` window expires, after which
        availability wins (the fencing epoch makes the aggressive
        choice safe: the deposed side stops acking the moment it
        learns the new epoch)."""
        with self._fo_lock:
            with self._lock:
                if self._gen != gen:
                    return      # raced: a peer thread already swapped
                i = 1 - self._active_i
                addr, conn = self._addrs[i], self._conns[i]
                act = self._conns[self._active_i]
                old_addr = act.addr
            if addr is None:
                raise err
            try:
                if conn is None:
                    conn = _ServerConn(
                        addr, token=self._token, stats=self._stats,
                        connect_timeout=_RECONNECT_TIMEOUT)
                if promote and _PARTITION_PROBE:
                    try:
                        pv = conn.request("peer_alive", timeout=5.0,
                                          retries=0)[1]
                    except (ConnectionError, RuntimeError, OSError):
                        pv = None   # standby mute: classic failover
                    if pv is not None:
                        if pv.get("role") == "primary":
                            # the standby was already promoted (by a
                            # peer client or its own monitor): adopt it
                            promote = False
                        elif pv.get("peer_alive") and \
                                act.unreachable_for() < _PARTITION_GRACE:
                            # the active is alive — its peer reaches it
                            # — so only OUR link is cut: degrade (pulls
                            # serve cached values, pushes buffer)
                            # instead of deposing a healthy primary
                            act.mark_unreachable(err)
                            with self._lock:
                                self._conns[i] = conn
                            raise err
                if promote:
                    conn.request("promote", timeout=5.0, retries=1)
            except (ConnectionError, RuntimeError, OSError) as e:
                if e is err:
                    raise
                raise err from e
            with self._lock:
                self._conns[i] = conn
                self._active_i = i
                self._gen += 1
                self.failovers += 1
        _log.warning(
            "shard failover: %s -> %s (%s: %s); backup %s",
            old_addr, addr, type(err).__name__, err,
            "promoted in-place" if promote
            else "already primary (swapped without promote)")
        cb = self._on_failover
        if cb is not None:
            try:
                cb(self)
            except Exception as e:  # re-registration is best-effort
                _log.debug("failover callback failed: %s", e)

    def request(self, *msg, **kw):
        for attempt in (0, 1):
            with self._lock:
                gen, conn = self._gen, self._conns[self._active_i]
            try:
                reply = conn.request(*msg, **kw)
            except ConnectionError as e:
                # barrier is still never replayed blind: a non-
                # idempotent command's failure surfaces (the server
                # may have half-executed it)
                if attempt or msg[0] not in _IDEMPOTENT:
                    raise
                self._failover(gen, e)
                continue
            except RuntimeError as e:
                # a not_serving refusal means the command was NOT
                # executed, so even non-idempotent commands replay
                # safely on the real primary. Likewise fenced (ISSUE
                # 19): the deposed replica refused without executing;
                # the peer already holds the newer epoch, so swap to it
                # WITHOUT issuing another promote
                if attempt or ("not_serving" not in str(e)
                               and "fenced" not in str(e)):
                    raise
                # a fenced refusal names the deposing epoch: the pair
                # moved on — adopt before swapping to the new primary
                self.note_epoch(_fenced_epoch(e))
                self._failover(gen, e,
                               promote="fenced" not in str(e))
                continue
            if msg[0] == "hello" and len(reply) > 1 \
                    and isinstance(reply[1], dict):
                self._learn_backup(reply[1].get("backup"))
            return reply
        raise ConnectionError("unreachable")   # pragma: no cover

    def request_all(self, msgs, timeout=None, return_exceptions=False):
        with self._lock:
            gen, conn = self._gen, self._conns[self._active_i]
        out = conn.request_all(msgs, timeout=timeout,
                               return_exceptions=True)
        redo = [i for i, r in enumerate(out)
                if isinstance(r, ConnectionError)
                or (isinstance(r, RuntimeError)
                    and ("not_serving" in str(r)
                         or "fenced" in str(r)))]
        if redo:
            first = out[redo[0]]
            self.note_epoch(_fenced_epoch(first))
            try:
                self._failover(gen, first,
                               promote="fenced" not in str(first))
            except (ConnectionError, RuntimeError, OSError):
                pass           # shard genuinely dead: original errors
            else:              # stand and the caller buffers/degrades
                with self._lock:
                    conn = self._conns[self._active_i]
                replay = conn.request_all([msgs[i] for i in redo],
                                          timeout=timeout,
                                          return_exceptions=True)
                for i, r in zip(redo, replay):
                    out[i] = r
        if not return_exceptions:
            for r in out:
                if isinstance(r, Exception):
                    raise r
        return out

    def ping(self, timeout=2.0, origin=None):
        with self._lock:
            gen, conn = self._gen, self._conns[self._active_i]
        if conn.ping(timeout=timeout, origin=origin):
            return True
        # heartbeat-driven failover: a dead active with a live standby
        # promotes NOW, off the training path — no push/pull has to
        # fail first
        try:
            self._failover(gen, ConnectionError(
                "heartbeat probe of %s failed" % conn.addr))
        except (ConnectionError, RuntimeError, OSError):
            return False
        with self._lock:
            conn = self._conns[self._active_i]
        return conn.ping(timeout=timeout, origin=origin)

    def health(self):
        with self._lock:
            act = self._conns[self._active_i]
            d = dict(act.health())
            d["primary"] = self._addrs[0]
            d["backup"] = self._addrs[1]
            d["active"] = act.addr
            d["failed_over"] = self._active_i == 1
            d["failovers"] = self.failovers
            d["replicas"] = [c.health() for c in self._conns
                             if c is not None]
        # the shard-level verdict: 'dead' only when no replica can
        # serve (num_dead must not count a shard failover can save)
        d["state"] = self.state
        return d

    def close(self):
        with self._lock:
            conns = [c for c in self._conns if c is not None]
        for c in conns:
            c.close()
        if self._own_stats:
            self._stats.release()


class AsyncDistKVStore(KVStore):
    """Worker-side 'dist_async' store (reference KVStoreDist with
    sync_mode off). push/pull go to the parameter service; there are no
    collectives and no lockstep across workers."""

    def __init__(self, kv_type="dist_async"):
        super().__init__(kv_type)
        self._rank = int(os.environ.get(
            "MXTPU_PROC_ID", os.environ.get("DMLC_WORKER_ID", "0")))
        self._size = int(os.environ.get(
            "MXTPU_NUM_PROCS", os.environ.get("DMLC_NUM_WORKER", "1")))
        addrs = os.environ.get("MXTPU_PS_ADDRS", "")
        token = os.environ.get("MXTPU_PS_TOKEN") or None
        self._token = token
        self._own_server = None
        if not addrs:
            # single-process: host the table in-process so the mode is
            # runnable (and truly async across threads) without a launcher
            self._own_server = ParameterServer(token=token).start()
            addrs = self._own_server.address
        self._stats = _CommStats()
        addr_list = [a.strip() for a in addrs.split(",") if a.strip()]
        backup_list = [a.strip() for a in os.environ.get(
            "MXTPU_PS_BACKUP_ADDRS", "").split(",")]
        # replicated shards: every address pairs with a backup (from
        # env, or learned at hello) behind a _ReplicatedConn facade
        # that fails over in place; unreplicated launches keep the
        # plain conn — zero new indirection on that path
        self._replicated = int(os.environ.get(
            "MXTPU_PS_REPLICAS", "1")) > 1 or any(backup_list)
        if self._replicated:
            self._conns = [
                _ReplicatedConn(
                    a,
                    backup_list[i] if i < len(backup_list)
                    and backup_list[i] else None,
                    token=token, stats=self._stats,
                    on_failover=self._on_shard_failover)
                for i, a in enumerate(addr_list)]
        else:
            self._conns = [_ServerConn(a, token=token,
                                       stats=self._stats)
                           for a in addr_list]
        self._base_clock = {}      # subkey -> clock of the last pull
        self._parts = {}           # key -> [(subkey, row_lo, row_hi), ...]
        self._shapes = {}          # key -> full array shape
        # routing/layout caches are written from the training thread,
        # the async push executor AND failover replay paths; one leaf
        # lock serializes the writers (reads stay lock-free: dict
        # lookups are GIL-atomic and every entry is immutable once
        # written, so a reader sees either the old or the new value)
        self._cache_lock = threading.Lock()
        # -- elasticity: versioned shard map (module docstring) --
        self._key_overrides = {}   # wire key -> its current home addr
        self._partition_rules = None   # shared PartitionRules spec
        self._map_versions = {}    # server addr -> last-seen map_version
        self._extra_conns = {}     # reshard-born server addr -> conn
        self._extra_guard = threading.Lock()
        self._cursor_rid = itertools.count(1)
        self._lease_epochs = {}    # lease -> fencing epoch granted under
        # -- fault-tolerance state (module docstring, "Fault tolerance") --
        # unique push origin: rank alone is not unique (tests run many
        # stores per process); the server dedupes replays per (origin,key)
        self._origin = "%d-%s" % (self._rank, uuid.uuid4().hex[:8])
        self._seq = itertools.count(1)   # next() is GIL-atomic
        # the newest fencing epoch this client has witnessed (ISSUE
        # 19): rides every push frame and hello, so a deposed primary
        # fences itself on first contact with any client that saw the
        # promotion — monotone, adopted from every reply that carries
        # "fence_epoch" (hello/ping/shard_map/promote)
        self._fleet_epoch = 1
        self._pull_cache_on = os.environ.get(
            "MXTPU_PS_PULL_CACHE", "1") != "0"
        self._pull_cache = {}      # subkey -> (numpy value, clock)
        self._degraded = set()     # subkeys served from cache right now
        self._degraded_lock = threading.Lock()
        self._pending_max = int(os.environ.get(
            "MXTPU_PS_PENDING_MAX", "256"))
        self._pending = {}         # conn -> [(subkey, payload, clock, seq)]
        self._pending_lock = threading.Lock()
        self._extra_stats = {}     # name -> fn; merged into stats()
        #                            (TrainGuard registers its counters)
        self._seq_pool = None      # lazy order-preserving push executor
        from concurrent.futures import ThreadPoolExecutor
        # parts of one array move concurrently: enough workers to keep
        # every socket of every server pool in flight
        total_socks = sum(c.n_socks for c in self._conns)
        self._pool = ThreadPoolExecutor(
            max_workers=max(4, 2 * total_socks),
            thread_name_prefix="mxtpu-ps")
        # liveness: background heartbeat marks servers dead/recovered and
        # flushes buffered pushes on recovery; 0 disables the thread
        # (tests drive _check_health() directly for determinism)
        self._hb_stop = threading.Event()
        self._hb_thread = None
        interval = float(os.environ.get("MXTPU_PS_HEARTBEAT", "5"))
        if interval > 0:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop, args=(interval,),
                daemon=True, name="mxtpu-ps-heartbeat")
            self._hb_thread.start()
        # observability (ISSUE 14): with MXTPU_TELEMETRY=1 this worker
        # exports its registry on its own metrics endpoint (servers
        # answer `metrics` on their main port; workers need this), and
        # the worker-side health scalars ride a registry view either
        # way
        _obs.ensure_exporter()
        self._view_key = _obs.view("kv.worker", self._metrics_view)
        # announce this worker to every reachable server (best-effort:
        # a dead shard learns about us when the heartbeat re-registers)
        self._register_workers(self._conns)

    # -- identity ---------------------------------------------------------
    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._size

    def set_partition_rules(self, rules):
        """Adopt the shared :class:`mxtpu.partition.PartitionRules`
        spec for key->server assignment: every key a rule matches
        (parts of big arrays included) co-locates on the rule group's
        shard, the same grouping that drives ShardedTrainer mesh
        placement and the checkpoint layout — ONE spec, three layouts
        (ISSUE 10). Unmatched keys keep the legacy per-key crc32
        spread. Must be set identically on every worker BEFORE the
        first init/push/pull, like the static key ranges it refines;
        online-reshard overrides still win over the rules (a moved key
        is a moved key)."""
        self._partition_rules = rules

    def _conn(self, key):
        # deterministic cross-process key->server assignment (builtin
        # hash() is salted per process; every worker must agree, like
        # ps-lite's static key ranges) — unless an online reshard moved
        # the key, in which case the learned override wins
        dst = self._key_overrides.get(key)
        if dst is not None:
            return self._conn_for_addr(dst)
        rules = self._partition_rules
        if rules is not None:
            idx = rules.shard_for(key, len(self._conns))
            if idx is not None:
                return self._conns[idx]
        digest = zlib.crc32(str(key).encode("utf-8"))
        return self._conns[digest % len(self._conns)]

    def _conn_for_addr(self, addr):
        """The conn serving ``addr``: one of the launch-time shards, or
        a conn built lazily for a reshard-born server the shard map
        pointed us at (greeted with hello, so membership and that
        server's map are learned there too)."""
        for c in self._conns:
            if addr in getattr(c, "_addrs", ()) or c.addr == addr:
                return c
        with self._extra_guard:
            conn = self._extra_conns.get(addr)
        if conn is not None:
            return conn
        if self._replicated:
            conn = _ReplicatedConn(addr, token=self._token,
                                   stats=self._stats,
                                   on_failover=self._on_shard_failover,
                                   connect_timeout=_RECONNECT_TIMEOUT)
        else:
            conn = _ServerConn(addr, token=self._token,
                               stats=self._stats,
                               connect_timeout=_RECONNECT_TIMEOUT)
        with self._extra_guard:
            live = self._extra_conns.setdefault(addr, conn)
        if live is not conn:   # raced another thread: one conn per addr
            conn.close()
        else:
            self._register_workers([conn])
        return live

    def _routed_request(self, sk, *msg, **kw):
        """One request that follows ``map_stale`` forwarding: a refusal
        names the key's new home — record the override, greet the new
        server, replay there (the transferred dedupe seqs keep push
        replays at-most-once). Bounded hops: a client whose map is k
        versions stale needs at most k.

        ``epoch_at`` names the fencing-epoch slot in ``msg``: it is
        re-stamped from each hop's TARGET conn (epochs are per pair —
        a frame must never carry another shard's epoch)."""
        epoch_at = kw.pop("epoch_at", None)
        conn = self._conn(sk)
        for _ in range(_MAP_HOPS):
            if epoch_at is not None:
                msg = msg[:epoch_at] \
                    + (getattr(conn, "fence_epoch", 1),) \
                    + msg[epoch_at + 1:]
            try:
                return conn.request(*msg, **kw)
            except RuntimeError as e:
                dst = _stale_dst(e)
                if dst is None:
                    raise
                self._stats.add("map_reroutes")
                with self._cache_lock:
                    self._key_overrides[sk] = dst
                conn = self._conn_for_addr(dst)
        raise RuntimeError(
            "shard map for key %r still stale after %d hops"
            % (sk, _MAP_HOPS))

    def _learn_map(self, addr, info):
        """Adopt a server's shard-map advertisement (hello / shard_map
        replies): its map version, and forwarding overrides for every
        key it handed away."""
        self._note_epoch(info.get("fence_epoch"))
        v = info.get("map_version")
        with self._cache_lock:
            if v is not None:
                self._map_versions[addr] = v
            for k, dst in (info.get("moved") or {}).items():
                if dst != addr:
                    self._key_overrides[k] = dst

    def _note_epoch(self, ep):
        """Adopt a fencing epoch witnessed in any server reply — the
        max ever seen; never goes backwards."""
        if ep is None:
            return
        with self._cache_lock:
            if int(ep) > self._fleet_epoch:
                self._fleet_epoch = int(ep)

    def _refresh_map(self, conn):
        """Heartbeat half of map propagation: when a probe reply
        advertises a newer shard-map version, fetch the full map."""
        info = getattr(conn, "last_ping", None) or {}
        self._note_epoch(info.get("fence_epoch"))
        note = getattr(conn, "note_epoch", None)
        if note is not None:
            note(info.get("fence_epoch"))
        v = info.get("map_version")
        if v is None or self._map_versions.get(conn.addr) == v:
            return
        try:
            reply = conn.request("shard_map", retries=0, timeout=5.0)
        except (ConnectionError, RuntimeError, OSError):
            return
        if note is not None:
            note(reply[1].get("fence_epoch"))
        self._learn_map(conn.addr,
                        {"map_version": reply[1].get("version"),
                         "fence_epoch": reply[1].get("fence_epoch"),
                         "moved": reply[1].get("moved")})

    # -- part plumbing ----------------------------------------------------
    def _plan(self, k, shape):
        """Record (and return) the part split for key ``k``. Every worker
        computes the identical plan from the array shape, like ps-lite's
        static key ranges. Recomputed whenever the shape differs from the
        cached one — a failed pre-init push/pull must not poison the plan
        the real init later establishes."""
        plan = self._parts.get(k)
        if plan is None or self._shapes.get(k) != tuple(shape):
            bounds = _part_bounds(shape)
            if len(bounds) == 1:
                plan = [(k, 0, bounds[0][1])]
            else:
                plan = [("%s\x00%d" % (k, i), lo, hi)
                        for i, (lo, hi) in enumerate(bounds)]
            with self._cache_lock:
                self._parts[k] = plan
                self._shapes[k] = tuple(shape)
        return plan

    def _pmap(self, calls):
        """Run request thunks concurrently on the pool; surface the first
        failure. Ordering across thunks is free — they target distinct
        servers/keys. The common single-thunk case runs inline: a pool
        handoff buys nothing there and would tax every small parameter
        on the hot training path. On a pool thread (push_async path)
        run serially instead of nesting submits — a saturated pool
        waiting on its own queue would deadlock, and the pipelined
        channels keep the wire busy regardless."""
        if len(calls) == 1:
            return [calls[0]()]
        if threading.current_thread().name.startswith("mxtpu-ps"):
            return [c() for c in calls]
        futs = [self._pool.submit(c) for c in calls]
        return [f.result() for f in futs]

    # -- core -------------------------------------------------------------
    def init(self, key, value):
        # reference KVStoreDist::InitImpl: rank 0's value is pushed to the
        # servers, then EVERY worker barriers — so a pull after init never
        # races the table creation
        keys, vals = _ctype_key_value(key, value)
        for k, v in zip(keys, vals):
            if isinstance(v, (list, tuple)):
                v = v[0]
            plan = self._plan(k, v.shape)
            if self._rank == 0:
                arr = v.asnumpy()
                self._pmap([
                    (lambda sk=sk, lo=lo, hi=hi:
                     self._conn(sk).request("init", sk,
                                            _slice_part(arr, lo, hi)))
                    for sk, lo, hi in plan])
            for sk, _, _ in plan:
                self._base_clock[sk] = 0
        self.barrier()

    def push(self, key, value, priority=0):
        keys, vals = _ctype_key_value(key, value)
        per_conn = {}          # conn -> {"small": [entries], "big": [..]}
        for k, v in zip(keys, vals):
            if isinstance(v, (list, tuple)):
                merged = v[0].copy()
                for arr in v[1:]:
                    merged._data = merged._data + arr._data
            else:
                merged = v
            # raw numpy values are accepted as-is: the fused Module dist
            # step batch-fetches a whole step's gradients in ONE
            # device_get and pushes the host arrays, instead of paying a
            # per-key d2h dispatch here
            arr = merged.asnumpy() if hasattr(merged, "asnumpy") \
                else _np.asarray(merged)
            for sk, lo, hi in self._plan(k, merged.shape):
                payload = self._wire_payload(sk, _slice_part(arr, lo, hi))
                nbytes = payload.nbytes if isinstance(payload, _np.ndarray) \
                    else payload[2].nbytes
                entry = (sk, payload, self._base_clock.get(sk, 0),
                         next(self._seq))
                lanes = per_conn.setdefault(
                    self._conn(sk), {"small": [], "big": []})
                lanes["small" if nbytes <= _COALESCE_BYTES
                      else "big"].append(entry)
        self._pmap([(lambda c=c, l=l: self._push_conn(c, l))
                    for c, l in per_conn.items()])

    def _push_conn(self, conn, lanes):
        """Everything one push() call sends to one server: big parts as
        individual pipelined requests, small parts coalesced into
        multi-key frames. Each part is seq-stamped for at-most-once
        replay; a part whose shard is dead (or whose request fails
        despite retries) is buffered — original seq and all — and
        replayed by the heartbeat when the server returns. Ordering
        across a buffer flush is relaxed, which async mode already
        tolerates (a buffered push is just a very stale push);
        at-most-once is NOT relaxed."""
        small = lanes["small"]
        if len(small) == 1:        # a lone small part gains nothing
            lanes["big"] += small  # from the multi wrapper
            small = []
        # stamp with the TARGET pair's epoch, not the fleet max: a
        # promotion on another shard must not fence this healthy one
        ep = getattr(conn, "fence_epoch", 1)
        jr = _consistency.enabled()
        msgs, groups = [], []
        for i in range(0, len(small), _COALESCE_MAX):
            chunk = small[i:i + _COALESCE_MAX]
            msgs.append(("multi",
                         [("push", sk, payload, clock, self._origin,
                           seq, ep)
                          for sk, payload, clock, seq in chunk]))
            groups.append((True, chunk))
            self._stats.add("coalesced_frames")
            self._stats.add("coalesced_subs", len(chunk))
        for entry in lanes["big"]:
            sk, payload, clock, seq = entry
            msgs.append(("push", sk, payload, clock, self._origin, seq,
                         ep))
            groups.append((False, [entry]))
        if jr:
            for _, chunk in groups:
                for sk, payload, clock, seq in chunk:
                    _consistency.journal(
                        "invoke", origin=self._origin, seq=seq,
                        key=str(sk), epoch=ep,
                        digest=_consistency.digest(payload))
        if conn.state in ("dead", "unreachable"):
            for _, chunk in groups:
                for entry in chunk:
                    self._buffer_push(conn, *entry)
            return
        replies = conn.request_all(msgs, return_exceptions=True)
        for (is_multi, chunk), reply in zip(groups, replies):
            if isinstance(reply, ConnectionError):
                for entry in chunk:
                    self._buffer_push(conn, *entry)
            elif isinstance(reply, Exception):
                if _stale_dst(reply) is None:
                    raise reply
                for entry in chunk:   # moved key: replay at its new home
                    self._replay_moved_push(entry, reply)
            elif is_multi:         # surface the first sub-error
                for entry, sub in zip(chunk, reply[1]):
                    if sub[0] != "err":
                        if jr:
                            self._journal_ack(entry, ep)
                        continue
                    if _stale_dst(sub[1]) is None:
                        raise RuntimeError(
                            "parameter server: %s" % sub[1])
                    self._replay_moved_push(
                        entry,
                        RuntimeError("parameter server: %s" % sub[1]))
            elif jr:
                self._journal_ack(chunk[0], ep)

    def _journal_ack(self, entry, ep=None):
        """One acked push in the consistency journal (ISSUE 19): the
        server's ok landed back at this client — from here on, losing
        the update is a checkable violation."""
        sk, _payload, clock, seq = entry
        _consistency.journal(
            "ack", origin=self._origin, seq=seq, key=str(sk),
            epoch=self._fleet_epoch if ep is None else ep, clock=clock)

    def _replay_moved_push(self, entry, err):
        """A push refused with ``map_stale``: it was NOT applied — learn
        the key's new home and replay there with the ORIGINAL seq, so a
        push that raced the key's handoff lands exactly once (either the
        pre-move apply transferred with the dedupe seqs, or it applies
        fresh at the destination)."""
        sk, payload, clock, seq = entry
        self._stats.add("map_reroutes")
        with self._cache_lock:
            self._key_overrides[sk] = _stale_dst(err)
        self._routed_request(sk, "push", sk, payload, clock,
                             self._origin, seq, None, epoch_at=6)
        if _consistency.enabled():
            self._journal_ack(entry)

    def push_async(self, key, value, priority=0):
        """Fire-and-track push: ships on the worker pool and returns a
        concurrent.futures.Future, so the caller's compute overlaps the
        wire (the ShardedTrainer gradient-push hook rides this).
        Failures surface at ``.result()``."""
        return self._pool.submit(self.push, key, value, priority)

    def push_pull(self, key, value, out=None, priority=0):
        """Fused push+pull: ONE wire round trip per part applies the
        gradient server-side and returns the post-update value into
        ``out`` — the reference's ps-lite ``PushPull``
        (``kvstore_dist.h`` PushPullDefault), and the per-batch op of
        the fused Module dist fast path. Entries are seq-stamped like
        plain pushes, so a retried/replayed part applies at most once
        while every retry still reads the current value. Failure
        handling composes the push story (dead shard -> buffered with
        the ORIGINAL seq, moved key -> routed replay) with the pull
        story (degraded last-known values)."""
        assert out is not None
        keys, vals = _ctype_key_value(key, value)
        _okeys, outs = _ctype_key_value(key, out)
        per_conn = {}
        plans = []
        for k, v, o in zip(keys, vals, outs):
            if isinstance(v, (list, tuple)):
                merged = v[0].copy()
                for arr_v in v[1:]:
                    merged._data = merged._data + arr_v._data
            else:
                merged = v
            arr = merged.asnumpy() if hasattr(merged, "asnumpy") \
                else _np.asarray(merged)
            plan = self._plan(k, merged.shape)
            plans.append((k, o, plan))
            for sk, lo, hi in plan:
                payload = self._wire_payload(sk, _slice_part(arr, lo, hi))
                nbytes = payload.nbytes if isinstance(payload, _np.ndarray) \
                    else payload[2].nbytes
                entry = (sk, payload, self._base_clock.get(sk, 0),
                         next(self._seq))
                lanes = per_conn.setdefault(
                    self._conn(sk), {"small": [], "big": []})
                lanes["small" if nbytes <= _COALESCE_BYTES
                      else "big"].append(entry)
        results = {}
        for got in self._pmap([(lambda c=c, l=l: self._pushpull_conn(c, l))
                               for c, l in per_conn.items()]):
            results.update(got)
        self._assemble_pulled(plans, results)

    def _pushpull_conn(self, conn, lanes):
        """Everything one push_pull() call exchanges with one server:
        the push lanes of :meth:`_push_conn` (big parts pipelined,
        small parts coalesced), but every sub-command is a fused
        ``pushpull`` whose reply carries the post-update value.
        Returns ``{subkey: (value, clock)}``."""
        out = {}
        small = lanes["small"]
        if len(small) == 1:
            lanes["big"] += small
            small = []
        ep = getattr(conn, "fence_epoch", 1)
        msgs, groups = [], []
        for i in range(0, len(small), _COALESCE_MAX):
            chunk = small[i:i + _COALESCE_MAX]
            msgs.append(("multi",
                         [("pushpull", sk, payload, clock, self._origin,
                           seq, ep)
                          for sk, payload, clock, seq in chunk]))
            groups.append((True, chunk))
            self._stats.add("coalesced_frames")
            self._stats.add("coalesced_subs", len(chunk))
        for entry in lanes["big"]:
            sk, payload, clock, seq = entry
            msgs.append(("pushpull", sk, payload, clock, self._origin,
                         seq, ep))
            groups.append((False, [entry]))
        if conn.state in ("dead", "unreachable"):
            # push half buffers (original seq) for heartbeat replay;
            # pull half degrades to the last-known value
            err = ConnectionError(
                "parameter server %s is dead" % conn.addr)
            for _, chunk in groups:
                for entry in chunk:
                    self._buffer_push(conn, *entry)
                    out[entry[0]] = self._degraded_value(entry[0], err)
            return out
        replies = conn.request_all(msgs, return_exceptions=True)
        for (is_multi, chunk), reply in zip(groups, replies):
            if isinstance(reply, ConnectionError):
                for entry in chunk:
                    self._buffer_push(conn, *entry)
                    out[entry[0]] = self._degraded_value(entry[0], reply)
            elif isinstance(reply, Exception):
                if _stale_dst(reply) is None:
                    raise reply
                for entry in chunk:   # moved key: replay at its new home
                    out[entry[0]] = self._pushpull_moved(entry, reply)
            else:
                subs = reply[1] if is_multi else [reply]
                for entry, sub in zip(chunk, subs):
                    sk = entry[0]
                    if sub[0] == "err":
                        if _stale_dst(sub[1]) is not None:
                            out[sk] = self._pushpull_moved(
                                entry, RuntimeError(
                                    "parameter server: %s" % sub[1]))
                        else:
                            raise RuntimeError(
                                "parameter server: %s" % sub[1])
                    else:
                        out[sk] = self._note_pulled(sk, sub[1], sub[2])
        return out

    def _pushpull_moved(self, entry, err):
        """A pushpull refused with ``map_stale``: learn the key's new
        home and replay there with the ORIGINAL seq — exactly-once
        apply, fresh value from the key's new owner."""
        sk, payload, clock, seq = entry
        self._stats.add("map_reroutes")
        with self._cache_lock:
            self._key_overrides[sk] = _stale_dst(err)
        reply = self._routed_request(sk, "pushpull", sk, payload, clock,
                                     self._origin, seq)
        return self._note_pulled(sk, reply[1], reply[2])

    def push_pull_async(self, key, value, out=None, priority=0):
        """One background job: push, then (optionally) pull the same
        keys — the fused Module dist step's per-batch wire op
        (``module/fused.py``). The push ships this step's gradients;
        the chained pull lands the server's post-update values directly
        into ``out`` (the shared device parameter store NDArrays, or
        merged-gradient buffers), all OFF the training thread so the
        next step's compute overlaps the wire and the device->host
        gradient read never blocks dispatch. Returns a Future; failures
        surface at ``.result()`` (the bounded-inflight window drain).

        Jobs run on a dedicated ONE-worker executor, in submission
        order, each completing (failover replays included) before the
        next starts: the server's per-(origin, key) dedupe is a
        monotone seq WATERMARK, so two concurrent step frames whose
        failover replays landed out of order would have the earlier
        seq wrongly refused as a dup — a lost acknowledged update.
        Serializing the wire jobs preserves per-key seq order end to
        end while the training thread still overlaps compute with the
        in-flight job (the window's whole point); the multi-server
        fan-out INSIDE one job still rides the shared pool."""
        def _job():
            vals = value
            if isinstance(vals, (list, tuple)) and vals and \
                    isinstance(vals[0], nd.NDArray):
                # one batched d2h for the whole step's gradients
                # instead of a per-key asnumpy dispatch chain
                vals = jax.device_get([v._data for v in vals])
            if out is not None:
                self.push_pull(key, vals, out=out, priority=priority)
            else:
                self.push(key, vals, priority)

        return self._ordered_pool().submit(_job)

    def _ordered_pool(self):
        """Lazy one-worker executor for order-sensitive async wire jobs
        (named OUTSIDE the ``mxtpu-ps`` prefix so a job's _pmap fan-out
        may still nest submits into the main pool)."""
        pool = self._seq_pool
        if pool is None:
            from concurrent.futures import ThreadPoolExecutor
            pool = self._seq_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="mxtpu-ordered-push")
        return pool

    # -- row-sparse fast path (ISSUE 13) ----------------------------------
    @staticmethod
    def _as_host(x):
        """Any array-ish (NDArray, jax array, numpy, list) -> numpy."""
        if isinstance(x, nd.NDArray):
            return _np.asarray(jax.device_get(x._data))
        if isinstance(x, _np.ndarray):
            return x
        return _np.asarray(jax.device_get(x))

    def sparse_push_pull(self, key, row_ids, rows, out=None, priority=0,
                         drop_padding=False):
        """Fused row-sparse push+pull — the embedding-table wire op
        (reference ``PushPull`` + ``PullRowSparse`` combined, op
        ``spushpull``): each row-range part owner applies the touched
        rows with the ROW-WISE server optimizer
        (``Optimizer.update_host_rows``) and replies gather-in-kind
        with the same rows' post-update values, all in ONE round trip
        per part. Wire bytes scale with rows touched, never with table
        size; a seq-deduped replay answers with the current row
        values.

        ``row_ids`` must be unique per key (sorted here); with
        ``drop_padding`` ids ``>= table rows`` (the fused step's
        static-shape sentinel) and ``< 0`` are compacted away first.
        ``out`` targets follow ``row_sparse_pull``: row_sparse /
        compact (rows installed), dense of the gathered shape, or
        dense full-table shape (touched rows scattered in); None skips
        the read-back landing (push half still fused on the wire).
        Replies land in ONE batched device_put. Dead shards buffer the
        push half (original seq — the heartbeat flush replays it as an
        ``spush``) and leave the out rows untouched, staleness-marked
        like a degraded pull."""
        keys = key if isinstance(key, (list, tuple)) else [key]
        ids_list = row_ids if isinstance(row_ids, (list, tuple)) \
            else [row_ids]
        rows_list = rows if isinstance(rows, (list, tuple)) else [rows]
        outs = out if isinstance(out, (list, tuple)) else [out] * len(keys)
        per_conn = {}
        metas = []
        for k, rid, rws, o in zip(keys, ids_list, rows_list, outs):
            if k not in self._parts:
                raise KeyError(
                    "sparse_push_pull of uninitialized key %r" % (k,))
            rid_np = self._as_host(rid).astype(_np.int64).reshape(-1)
            rows_np = self._as_host(rws)
            nrows = self._shapes[k][0] if self._shapes[k] else 1
            if drop_padding:
                keep = (rid_np >= 0) & (rid_np < nrows)
                rid_np, rows_np = rid_np[keep], rows_np[keep]
            order = _np.argsort(rid_np, kind="stable")
            rid_np, rows_np = rid_np[order], rows_np[order]
            if rid_np.size:
                if rid_np[0] < 0 or rid_np[-1] >= nrows:
                    raise IndexError(
                        "sparse_push_pull row_ids out of range for "
                        "table of %d rows: [%d, %d]"
                        % (nrows, rid_np[0], rid_np[-1]))
                if (_np.diff(rid_np) == 0).any():
                    raise ValueError(
                        "sparse_push_pull row_ids must be unique "
                        "(dedupe/segment-sum the gradient rows first)")
            sks = []
            for sk, lo, hi in self._parts[k]:
                sel = (rid_np >= lo) & (rid_np < hi)
                if not sel.any():
                    continue
                entry = (sk, rid_np[sel] - lo, rows_np[sel],
                         self._base_clock.get(sk, 0), next(self._seq))
                per_conn.setdefault(self._conn(sk), []).append(entry)
                sks.append(sk)
                self._stats.add("sparse_frames")
                self._stats.add("sparse_rows_sent", int(sel.sum()))
            metas.append((k, o, rid_np, sks))
        results = {}
        for got in self._pmap([(lambda c=c, es=es:
                                self._spushpull_conn(c, es))
                               for c, es in per_conn.items()]):
            results.update(got)
        self._assemble_sparse(metas, results)

    def _spushpull_conn(self, conn, entries):
        """Everything one sparse_push_pull() exchanges with one server:
        pipelined ``spushpull`` frames, one per touched row-range part.
        Returns ``{subkey: (rows, clock) | None}`` — None marks a part
        whose push was buffered for a dead/failed shard (the caller
        leaves those out rows untouched)."""
        out = {}
        ep = getattr(conn, "fence_epoch", 1)
        msgs = [("spushpull", sk, ids, rws, clock, self._origin, seq,
                 ep)
                for sk, ids, rws, clock, seq in entries]
        if conn.state in ("dead", "unreachable"):
            for sk, ids, rws, clock, seq in entries:
                self._buffer_push(conn, sk, (_SP_MARK, ids, rws), clock,
                                  seq)
                with self._degraded_lock:
                    self._degraded.add(sk)
                out[sk] = None
            return out
        replies = conn.request_all(msgs, return_exceptions=True)
        for entry, reply in zip(entries, replies):
            sk, ids, rws, clock, seq = entry
            if isinstance(reply, ConnectionError):
                self._buffer_push(conn, sk, (_SP_MARK, ids, rws), clock,
                                  seq)
                with self._degraded_lock:
                    self._degraded.add(sk)
                out[sk] = None
            elif isinstance(reply, Exception):
                if _stale_dst(reply) is None:
                    raise reply
                out[sk] = self._spushpull_moved(entry, reply)
            elif reply[0] == "err":
                if _stale_dst(reply[1]) is not None:
                    out[sk] = self._spushpull_moved(
                        entry, RuntimeError(
                            "parameter server: %s" % reply[1]))
                else:
                    raise RuntimeError("parameter server: %s" % reply[1])
            else:
                self._base_clock[sk] = reply[2]
                with self._degraded_lock:
                    self._degraded.discard(sk)
                out[sk] = (reply[1], reply[2])
        return out

    def _spushpull_moved(self, entry, err):
        """A spushpull refused with ``map_stale``: learn the rows' new
        home and replay there with the ORIGINAL seq — exactly-once
        apply, fresh row values from the new owner."""
        sk, ids, rws, clock, seq = entry
        self._stats.add("map_reroutes")
        with self._cache_lock:
            self._key_overrides[sk] = _stale_dst(err)
        reply = self._routed_request(sk, "spushpull", sk, ids, rws,
                                     clock, self._origin, seq)
        self._base_clock[sk] = reply[2]
        return (reply[1], reply[2])

    def _assemble_sparse(self, metas, results):
        """Reassemble per-part row replies in ascending-id order and
        land every target in ONE batched host->device transfer; the
        scatter into full-shape targets runs as a cached device
        dispatch (same shapes every step — no retrace)."""
        from .ndarray.sparse import (RowSparseNDArray,
                                     CompactRowSparseNDArray)
        puts = []
        for k, o, rid_np, sks in metas:
            if o is None or not sks:
                continue
            pieces = [results.get(sk) for sk in sks]
            if any(p is None for p in pieces):
                continue        # degraded part: leave the target rows
            rows_full = pieces[0][0] if len(pieces) == 1 \
                else _np.concatenate([p[0] for p in pieces], axis=0)
            tgt0 = o[0] if isinstance(o, (list, tuple)) else o
            tdt = _np.dtype(getattr(tgt0, "dtype", rows_full.dtype))
            if rows_full.dtype != tdt and _half_float(rows_full.dtype):
                # bf16 reply-in-kind (AMP): restore the master dtype
                # host-side, before the one batched device_put
                rows_full = rows_full.astype(tdt)
            puts.append((o, rid_np, rows_full))
        if not puts:
            return
        devs = jax.device_put(
            [rows for _, _, rows in puts]
            + [ids.astype(_np.int32) for _, ids, _ in puts])
        n = len(puts)
        for (o, rid_np, _rows), rows_dev, ids_dev in zip(
                puts, devs[:n], devs[n:]):
            for tgt in (o if isinstance(o, (list, tuple)) else [o]):
                if isinstance(tgt, CompactRowSparseNDArray):
                    tgt._set_rows(rid_np, rows_dev)
                elif tuple(tgt.shape) == tuple(rows_dev.shape) and \
                        not isinstance(tgt, RowSparseNDArray):
                    tgt._data = rows_dev
                else:
                    tgt._data = tgt._data.at[ids_dev].set(
                        rows_dev.astype(tgt._data.dtype))
                    if hasattr(tgt, "_aux"):
                        tgt._aux = None   # metadata recomputes lazily

    def sparse_push_pull_async(self, key, row_ids, rows, out=None,
                               priority=0, drop_padding=False):
        """One background row-sparse wire job on the order-preserving
        executor (the ``push_pull_async`` contract: per-key seq order
        end to end, device->host reads OFF the training thread).
        ``row_ids``/``rows`` may be raw jax arrays straight out of the
        fused grad program — the job device_gets them here. Returns a
        Future; failures surface at ``.result()``."""
        def _job():
            self.sparse_push_pull(key, row_ids, rows, out=out,
                                  priority=priority,
                                  drop_padding=drop_padding)

        return self._ordered_pool().submit(_job)

    def _buffer_push(self, conn, sk, payload, base_clock, seq):
        with self._pending_lock:
            pend = self._pending.setdefault(conn, [])
            if len(pend) >= self._pending_max:
                raise ConnectionError(
                    "parameter server %s dead and its pending-push "
                    "buffer is full (%d; MXTPU_PS_PENDING_MAX)"
                    % (conn.addr, self._pending_max))
            pend.append((sk, payload, base_clock, seq))

    def _wire_payload(self, subkey, part):
        """Dense part, or its 2-bit packed form when compression is on
        (per-part error-feedback residual lives worker-side, as the
        reference's compressed push does). Compressed payloads ride the
        coalesced frames like any other — GradientCompression takes the
        numpy part directly and quantizes small parts without a device
        round trip."""
        if self._compression is None:
            return part
        packed = self._compression.compress(subkey, part)
        return (_GC_MARK, self._compression.threshold,
                _np.asarray(packed), part.shape)

    def _degraded_value(self, sk, err):
        """Graceful-degradation policy for a failed part pull: a shard
        unreachable despite retries (ConnectionError), or back but
        restarted WITHOUT its state (RuntimeError "uninitialized"),
        serves the worker's last-pulled value — the key stays
        staleness-marked in ``degraded_keys()``/``health()`` until a
        live pull lands. Any other server error is a real bug and
        surfaces."""
        if isinstance(err, RuntimeError) and "uninitialized" not in str(err):
            raise err
        cached = self._pull_cache.get(sk) if self._pull_cache_on else None
        if cached is None:
            raise err
        with self._degraded_lock:
            self._degraded.add(sk)
        return (cached[0], cached[1])

    def _note_pulled(self, sk, value, clock):
        if self._pull_cache_on:
            self._pull_cache[sk] = (value, clock)
        with self._degraded_lock:
            self._degraded.discard(sk)
        return (value, clock)

    def _part_nbytes(self, k, lo, hi):
        """Wire-size estimate for a part (assumes 4-byte elements — a
        coalescing heuristic, not an invariant)."""
        shape = self._shapes.get(k) or ()
        if not shape:
            return 4
        per_row = 4
        for d in shape[1:]:
            per_row *= int(d)
        return max(1, hi - lo) * per_row

    def _pull_conn(self, conn, lanes):
        """Everything one pull() call fetches from one server — small
        parts coalesced, big parts individually pipelined. Returns
        ``{subkey: (value, clock)}`` with per-part degradation."""
        small = lanes["small"]
        if len(small) == 1:
            lanes["big"] += small
            small = []
        msgs, groups = [], []
        for i in range(0, len(small), _COALESCE_MAX):
            chunk = small[i:i + _COALESCE_MAX]
            msgs.append(("multi", [("pull", sk) for sk in chunk]))
            groups.append((True, chunk))
            self._stats.add("coalesced_frames")
            self._stats.add("coalesced_subs", len(chunk))
        for sk in lanes["big"]:
            msgs.append(("pull", sk))
            groups.append((False, [sk]))
        out = {}
        replies = conn.request_all(msgs, return_exceptions=True)
        for (is_multi, chunk), reply in zip(groups, replies):
            if isinstance(reply, Exception):
                for sk in chunk:
                    if _stale_dst(reply) is not None:
                        out[sk] = self._pull_moved(sk, reply)
                    else:
                        out[sk] = self._degraded_value(sk, reply)
                continue
            subs = reply[1] if is_multi else [reply]
            for sk, sub in zip(chunk, subs):
                if sub[0] == "err":
                    if _stale_dst(sub[1]) is not None:
                        out[sk] = self._pull_moved(
                            sk, RuntimeError(
                                "parameter server: %s" % sub[1]))
                    else:
                        out[sk] = self._degraded_value(
                            sk, RuntimeError(
                                "parameter server: %s" % sub[1]))
                else:
                    out[sk] = self._note_pulled(sk, sub[1], sub[2])
        return out

    def _pull_moved(self, sk, err):
        """A pull refused with ``map_stale``: follow the forward to the
        key's new home; only if the new home is ALSO unreachable does
        the usual degradation policy apply."""
        self._stats.add("map_reroutes")
        with self._cache_lock:
            self._key_overrides[sk] = _stale_dst(err)
        try:
            reply = self._routed_request(sk, "pull", sk)
        except (ConnectionError, RuntimeError) as e:
            return self._degraded_value(sk, e)
        return self._note_pulled(sk, reply[1], reply[2])

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        assert out is not None
        keys, outs = _ctype_key_value(key, out)
        plans = []
        per_conn = {}
        for k, o in zip(keys, outs):
            tgt0 = o[0] if isinstance(o, (list, tuple)) else o
            plan = self._plan(k, tgt0.shape)
            plans.append((k, o, plan))
            for sk, lo, hi in plan:
                lanes = per_conn.setdefault(
                    self._conn(sk), {"small": [], "big": []})
                lanes["small" if self._part_nbytes(k, lo, hi)
                      <= _COALESCE_BYTES else "big"].append(sk)
        results = {}
        for got in self._pmap([(lambda c=c, l=l: self._pull_conn(c, l))
                               for c, l in per_conn.items()]):
            results.update(got)
        self._assemble_pulled(plans, results)

    def _assemble_pulled(self, plans, results):
        """Reassemble per-part ``results`` into the pull targets and
        rebind them in ONE batched host->device transfer: a multi-key
        pull (the fused Module dist step rebinding every parameter per
        batch) pays one dispatch, not one per key."""
        assembled = []
        for k, o, plan in plans:
            pieces = []
            for sk, _, _ in plan:
                value, clock = results[sk]
                self._base_clock[sk] = clock
                pieces.append(value)
            if len(pieces) == 1:
                full = pieces[0]
            else:
                # assemble into one preallocated buffer: a single copy
                # instead of concatenate-then-asarray's two passes
                full = _np.empty(self._shapes[k], dtype=pieces[0].dtype)
                for (sk, lo, hi), piece in zip(plan, pieces):
                    full[lo:hi] = piece
            if full.dtype == _np.float64:    # nd.array's canonical rule
                full = full.astype(_np.float32)
            elif full.dtype == _np.int64:
                full = full.astype(_np.int32)
            else:
                tgt0 = o[0] if isinstance(o, (list, tuple)) else o
                tdt = _np.dtype(getattr(tgt0, "dtype", full.dtype))
                if full.dtype != tdt and _half_float(full.dtype):
                    # half-width wire reply (bf16 pushpull, AMP):
                    # restore the pull target's master dtype host-side,
                    # before the ONE batched device_put
                    full = full.astype(tdt)
            assembled.append((o, full))
        if not assembled:
            return
        devs = jax.device_put([full for _, full in assembled])
        for (o, _full), dev in zip(assembled, devs):
            for tgt in (o if isinstance(o, (list, tuple)) else [o]):
                tgt._data = dev
                if hasattr(tgt, "_aux"):
                    # sparse-typed target (row_sparse param array): the
                    # pulled value replaced the dense table wholesale —
                    # the compressed metadata recomputes lazily
                    tgt._aux = None

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the requested rows from the server table (reference
        dist server sparse pulls, kvstore_dist_server.h:631-792
        DataHandleRowSparse): each part owner slices its resident rows, so
        only nnz rows cross the wire."""
        from .ndarray.sparse import (RowSparseNDArray, row_sparse_array,
                                     CompactRowSparseNDArray)
        assert out is not None and row_ids is not None
        keys, outs = _ctype_key_value(key, out)
        if isinstance(row_ids, nd.NDArray):
            row_ids = [row_ids] * len(keys)
        for k, o, rid in zip(keys, outs, row_ids):
            if k not in self._parts:
                raise KeyError("row_sparse_pull of uninitialized key %r"
                               % (k,))
            rid_np = rid.asnumpy().astype("int64") \
                if isinstance(rid, nd.NDArray) \
                else _np.asarray(rid, dtype="int64")
            rid_np = _np.unique(rid_np)
            nrows = self._shapes[k][0] if self._shapes[k] else 1
            if rid_np.size and (rid_np[0] < 0 or rid_np[-1] >= nrows):
                raise IndexError(
                    "row_sparse_pull row_ids out of range for table of "
                    "%d rows: [%d, %d]" % (nrows, rid_np[0], rid_np[-1]))
            plan = self._parts[k]

            def fetch(sk, lo, hi):
                ids = rid_np[(rid_np >= lo) & (rid_np < hi)]
                if ids.size == 0:
                    return None
                _, rows, clock = self._routed_request(
                    sk, "pull_rows", sk, (ids - lo))
                self._base_clock[sk] = clock
                return rows

            pieces = [p for p in self._pmap(
                [(lambda sk=sk, lo=lo, hi=hi: fetch(sk, lo, hi))
                 for sk, lo, hi in plan]) if p is not None]
            if pieces:
                gathered = pieces[0] if len(pieces) == 1 \
                    else _np.concatenate(pieces, axis=0)  # rid_np sorted
            else:   # empty row_ids: a valid no-rows pull
                gathered = _np.zeros((0,) + tuple(self._shapes[k][1:]),
                                     "float32")
            garr = nd.array(gathered)
            for tgt in (o if isinstance(o, (list, tuple)) else [o]):
                if isinstance(tgt, CompactRowSparseNDArray):
                    tgt._set_rows(rid_np, garr._data)
                elif isinstance(tgt, RowSparseNDArray):
                    rsp = row_sparse_array((garr, rid_np),
                                           shape=self._shapes[k])
                    tgt._data = rsp._data
                    tgt._aux = {kk: vv.copy()
                                for kk, vv in rsp._ensure_aux().items()}
                elif tgt.shape == garr.shape:
                    tgt._data = garr._data
                elif tuple(tgt.shape) == self._shapes[k]:
                    # dense full-shape target (Module.prepare pulls into
                    # full executor buffers): refresh ONLY the requested
                    # rows — the server sliced row-wise, so a row pull
                    # never ships the whole table (the old fallback
                    # re-fetched the ENTIRE table here, defeating the
                    # sparse wire for exactly the giant-table case
                    # row_sparse_pull exists for)
                    if rid_np.size:
                        tgt._data = tgt._data.at[
                            jnp.asarray(rid_np.astype(_np.int32))].set(
                            garr._data.astype(tgt._data.dtype))
                else:
                    raise TypeError(
                        "row_sparse_pull target must be row_sparse, "
                        "compact, the gathered shape, or the full table "
                        "shape; got dense %r for %d rows"
                        % (tgt.shape, rid_np.size))

    # -- optimizer --------------------------------------------------------
    def set_optimizer(self, optimizer):
        """Serialize the optimizer to every server (reference kvstore.py
        set_optimizer: rank 0 sends command 0 with the pickled optimizer;
        other ranks only note it locally). Barriers afterwards so no
        worker's push can beat the updater installation."""
        if self._rank == 0:
            payload = pickle.dumps(optimizer,
                                   protocol=pickle.HIGHEST_PROTOCOL)
            for c in self._conns:
                c.request("set_optimizer", payload)
        self._optimizer = optimizer
        # updater runs server-side; worker must NOT also apply it
        self._updater = None
        self.barrier()

    def set_updater(self, updater):
        # A worker-side updater would double-apply on top of the server's.
        # The reference ignores set_updater for dist stores (updater_ is
        # only consulted server-side); match that.
        self._updater = None

    def save_optimizer_states(self, fname, dump_optimizer=False):
        """Optimizer states live SERVER-side in dist mode: fetch every
        shard's updater slots (disjoint — each shard only materializes
        its own keys) and write the merged dict in the standard
        ``Updater`` serialization, so ``Module.save_optimizer_states``
        round-trips through the server on the fused dist path."""
        merged = {}
        for c in self._conns:
            reply = c.request("opt_states")
            states = pickle.loads(reply[1])
            if isinstance(states, tuple) and len(states) == 2:
                states = states[0]
            merged.update(states)
        payload = pickle.dumps(
            (merged, self._optimizer) if dump_optimizer else merged,
            protocol=pickle.HIGHEST_PROTOCOL)
        with open(fname, "wb") as fout:
            fout.write(payload)

    def load_optimizer_states(self, fname):
        """Broadcast saved updater states to every shard (each uses
        only its own keys' slots; replicated pairs forward on the
        stream like set_optimizer)."""
        with open(fname, "rb") as fin:
            payload = fin.read()
        for c in self._conns:
            c.request("set_opt_states", payload)

    def publish_version(self, version=None, meta=None, pin=False):
        """Publish every shard's CURRENT table as one weight version
        for the serving fleet (the train→serve stream: serving
        replicas follow via ``weight_sub``/``weights`` long-polls, or
        poll the versioned snapshots each server writes when
        ``MXTPU_SERVE_WEIGHT_DIR`` is set — docs/serving.md "Rollout &
        weight streaming"). Single-shard fleets may leave ``version``
        None (the server bumps its own watermark); multi-shard fleets
        should pass an explicit version so every shard publishes the
        same number and subscribers see one coherent fleet version.
        ``pin=True`` exempts the snapshot from retention — the
        rollback anchor. Returns one info dict per shard
        (``{"version", "digest"}``)."""
        replies = []
        for c in self._conns:
            replies.append(
                c.request("publish", version, meta, pin)[1])
        return replies

    # -- coordination -----------------------------------------------------
    def barrier(self):
        """Fleet barrier with a server-side deadline
        (``MXTPU_PS_BARRIER_TIMEOUT``): when a member died mid-epoch the
        server force-releases the generation and this returns — logged
        and counted in ``stats()['barrier_timeouts']`` — instead of
        hanging every surviving worker forever. In elastic mode
        (``MXTPU_PS_ELASTIC=1``) the target is the server's CURRENT
        membership, re-counted on every join/leave — a departed worker
        releases the survivors by re-count
        (``stats()['barrier_recounts']``), not by deadline."""
        super().barrier()
        # the socket deadline must outlive the server-side one, or the
        # RPC layer would tear the channel down before the degraded
        # release can arrive
        fleet = 0 if _ELASTIC else self._size
        reply = self._conns[0].request(
            "barrier", fleet, _BARRIER_TIMEOUT,
            timeout=_BARRIER_TIMEOUT + 30.0)
        if len(reply) > 1 and reply[1] == "timeout":
            _log.warning(
                "barrier degraded: released by the %gs deadline with "
                "members missing (see kv.stats()['barrier_timeouts'])",
                _BARRIER_TIMEOUT)

    # -- elastic data sharding --------------------------------------------
    def shard_cursor(self, epoch, num_shards, poll=None):
        """Iterate this worker's share of an epoch's ``num_shards`` data
        shards from the SERVER-owned cursor (server 0 is the authority):
        each shard index is handed out exactly once per epoch across the
        whole fleet — however many workers exist, join, or leave while
        the epoch runs — and a dead/departed worker's unfinished shards
        are re-queued for the survivors. The elastic replacement for
        static ``part_index``/``num_parts`` iterator slicing: a joining
        worker calls this and immediately takes work, no relaunch.

        Yields shard indices; a shard is acknowledged as done when the
        loop body finishes (advances past the yield). Workers that find
        the epoch exhausted but unfinished poll every ``poll`` seconds
        (``MXTPU_PS_CURSOR_POLL``) for re-queued work until every shard
        is acknowledged."""
        poll = _CURSOR_POLL if poll is None else float(poll)
        while True:
            reply = self._conns[0].request(
                "cursor_next", self._origin, int(epoch),
                int(num_shards), next(self._cursor_rid))
            shard, pending = reply[1], reply[2]
            # the grant's fencing epoch (ISSUE 19): presented back at
            # cursor_done, so a completion that straddled a partition
            # heal is refused if the shard was re-granted since
            granted = reply[3] if len(reply) > 3 else None
            self._note_epoch(granted)
            if shard is None:
                if pending <= 0:
                    return
                # another worker still owns shards: poll — its death
                # re-queues them (worker-liveness GC / bye), its
                # completion ends the epoch
                time.sleep(poll)
                continue
            yield shard
            self._conns[0].request(
                "cursor_done", self._origin, int(epoch), shard,
                granted)

    # -- streaming data plane (ISSUE 18; docs/streaming.md) ---------------
    def stream_lease(self, lease):
        """Try to take the exclusive fleet-wide lease named by
        ``lease`` (a :func:`stream_origin` string — one log segment).
        Rides the server-owned shard cursor with ``num_shards=1``:
        ``"owned"`` — this worker holds it (a replayed request is
        rid-deduped to the same verdict); ``"wait"`` — another live
        consumer holds it (its death re-queues the lease through the
        worker-liveness machinery); ``"done"`` — already fully
        consumed."""
        reply = self._conns[0].request(
            "cursor_next", self._origin, lease, 1,
            next(self._cursor_rid))
        shard, pending = reply[1], reply[2]
        if shard is not None:
            # remember the grant's fencing epoch for stream_lease_done
            # (a lease completed across a partition heal must not
            # retire a segment that was re-leased in a newer epoch)
            granted = reply[3] if len(reply) > 3 else None
            self._note_epoch(granted)
            with self._cache_lock:
                self._lease_epochs[lease] = granted
            return "owned"
        return "done" if pending <= 0 else "wait"

    def stream_lease_done(self, lease):
        """Acknowledge a held segment lease as fully consumed (the
        cursor_done half of :meth:`stream_lease`; idempotent). A
        ``fenced`` refusal means the lease was re-granted under a newer
        fleet epoch while we were partitioned — the lease is LOST, not
        an error (the new holder finishes the segment; our consumed
        records were already deduped by the frame watermarks)."""
        with self._cache_lock:
            granted = self._lease_epochs.pop(lease, None)
        try:
            self._conns[0].request("cursor_done", self._origin, lease,
                                   0, granted)
        except RuntimeError as e:
            if "fenced" not in str(e):
                raise
            self._note_epoch(_fenced_epoch(e))
            _log.warning("segment lease %s was re-granted under a "
                         "newer epoch while this worker was "
                         "partitioned; yielding it", lease)

    def stream_offsets(self, group):
        """One consumer group's committed consumption cursors:
        ``{(shard, seg): (offset, final)}`` — what a respawned tailer
        resumes from, and the input to the GC watermark."""
        reply = self._conns[0].request("stream_offsets", group)
        return {(int(sh), int(sg)): (int(off), bool(fin))
                for sh, sg, off, fin in reply[1]}

    def stream_push(self, parts, commit, sparse_parts=()):
        """Push gradients AND the consumption offset they were computed
        from as one exactly-once frame (ISSUE 18 tentpole c).

        ``parts``: ``[(key, grad)]`` dense numpy/NDArray grads;
        ``sparse_parts``: ``[(key, row_ids, rows)]`` row-wise (the
        PR-13 fast path); ``commit``: ``(group, shard, seg, offset,
        final)`` from :meth:`StreamingIter.pending_commit`. Both halves
        ride the SAME deterministic (origin, seq) identity derived from
        the commit, so the whole frame is idempotent: a retry — or a
        kill -9'd trainer's respawn recomputing the identical frame
        from the identical records — is refused by the server's
        watermarks. Keys must be single-part (under the part-split
        bound); parts-less calls are pure offset commits. Returns True
        when the server refused every half as a replay."""
        group, shard, seg, offset, final = commit
        origin = stream_origin(group, shard, seg)
        seq = stream_commit_seq(offset, final)
        per_conn = {}
        for k, g in parts:
            g = g.asnumpy() if hasattr(g, "asnumpy") else g
            g = _np.ascontiguousarray(g)
            per_conn.setdefault(self._conn(k), []).append(
                ("d", k, g, self._base_clock.get(k, 0)))
        for k, ids, rows in sparse_parts:
            per_conn.setdefault(self._conn(k), []).append(
                ("s", k, _np.asarray(ids, dtype=_np.int64),
                 _np.ascontiguousarray(rows),
                 self._base_clock.get(k, 0)))
        # the commit rides the lease/offset authority (server 0); when
        # no part routes there, a commit-only frame goes anyway
        home = self._conns[0]
        per_conn.setdefault(home, [])
        replies = self._pmap([
            (lambda c=c, pl=pl:
             c.request("stream_push", origin, seq, pl,
                       commit if c is home else None))
            for c, pl in per_conn.items()])
        return all(len(r) > 1 and r[1] == "dup" for r in replies)

    # -- worker registration ----------------------------------------------
    def _register_workers(self, conns):
        """Best-effort hello to each server: membership + liveness
        lease. A respawned worker's fresh store re-registers the same
        way, which is how the fleet learns the seat is filled again."""
        for c in conns:
            try:
                # the hello carries the epoch we witnessed for THIS
                # pair: a deposed primary that missed the promotion
                # fences the moment any witness re-registers (ISSUE
                # 19). Never the fleet max — epochs are per pair, and
                # another shard's promotion must not fence this one.
                reply = c.request("hello", self._origin, self._rank,
                                  getattr(c, "fence_epoch", 1),
                                  retries=0, timeout=5.0)
            except (ConnectionError, RuntimeError, OSError):
                continue
            if len(reply) > 1 and isinstance(reply[1], dict):
                # the hello reply carries the versioned shard map: a
                # (re)joining worker starts with current routing
                note = getattr(c, "note_epoch", None)
                if note is not None:
                    note(reply[1].get("fence_epoch"))
                self._learn_map(c.addr, reply[1])

    def _on_shard_failover(self, conn):
        """A shard just failed over to its promoted backup: re-announce
        this worker there (membership is ephemeral — the backup only
        saw us through forwarded pushes) and replay any pushes buffered
        while the shard looked dead."""
        self._register_workers([conn])
        self._flush_pending(conn)

    # -- liveness / health ------------------------------------------------
    def _heartbeat_loop(self, interval):
        while not self._hb_stop.wait(interval):
            try:
                self._check_health()
            except Exception as e:   # a probe bug must not kill training
                _log.debug("heartbeat sweep failed: %s", e)

    def _check_health(self, timeout=2.0):
        """One synchronous liveness sweep (the heartbeat thread's body;
        tests call it directly so no wall-clock enters the fault
        matrix): probe every server — the probe carries our origin so
        the membership lease stays fresh — re-register with any server
        that just came back (a respawned shard restored its table but
        not the ephemeral membership), and flush buffered pushes to any
        server that answers."""
        with self._extra_guard:
            extra = list(self._extra_conns.values())
        for conn in list(self._conns) + extra:
            was_dead = conn.state in ("dead", "unreachable")
            if conn.ping(timeout=timeout, origin=self._origin):
                if was_dead:
                    self._register_workers([conn])
                self._refresh_map(conn)
                with self._pending_lock:
                    has_pending = bool(self._pending.get(conn))
                if has_pending:
                    self._flush_pending(conn)
            # a failed probe already advanced the conn's failure count
            # (past MXTPU_PS_DEAD_AFTER it flips to dead on its own)

    def _flush_pending(self, conn):
        """Replay buffered pushes in order with their ORIGINAL seqs —
        the server's dedupe table makes a flush racing a retry, or a
        flush interrupted and re-run, still at-most-once."""
        with self._pending_lock:
            items = self._pending.pop(conn, [])
        for n, (sk, payload, clock, seq) in enumerate(items):
            try:
                # routed: the key may have moved while its shard was
                # down (a reshard away from the dying server is the
                # textbook drill) — the replay follows the map. A
                # row-sparse entry (its payload slot carries the
                # (_SP_MARK, row_ids, rows) tag) replays as an spush.
                if isinstance(payload, tuple) and len(payload) == 3 \
                        and payload[0] == _SP_MARK:
                    self._routed_request(sk, "spush", sk, payload[1],
                                         payload[2], clock,
                                         self._origin, seq,
                                         None, epoch_at=7)
                else:
                    self._routed_request(sk, "push", sk, payload, clock,
                                         self._origin, seq,
                                         None, epoch_at=6)
                if _consistency.enabled():
                    self._journal_ack((sk, payload, clock, seq))
            except ConnectionError:
                with self._pending_lock:   # died again: keep the rest
                    self._pending[conn] = items[n:] \
                        + self._pending.get(conn, [])
                return
            except RuntimeError as e:
                # err reply (e.g. the server restarted WITHOUT its
                # snapshot and the key is gone): this push can never
                # land — drop it loudly rather than retry forever
                _log.warning("dropping undeliverable buffered push "
                             "for %r: %s", sk, e)

    def health(self):
        """Worker-side fleet health: per-server state (the ps-lite
        ``NumDeadNodes`` analogue, but with the *which* and *why*),
        currently-degraded keys, the pending-push backlog, and the
        server-side worker view — per-worker push/staleness counters,
        the straggler verdict and the membership epoch — gathered from
        every reachable server (dead shards are skipped, never waited
        on)."""
        servers = [c.health() for c in self._conns]
        with self._pending_lock:
            npend = sum(len(v) for v in self._pending.values())
        with self._degraded_lock:
            deg = sorted({str(sk).split("\x00")[0]
                          for sk in self._degraded})
        out = {"servers": servers,
               "num_dead": sum(1 for s in servers
                               if s["state"] == "dead"),
               # partitioned, not dead (ISSUE 19): the shard is alive —
               # its peer reaches it — but OUR link is cut; pulls are
               # degrading and pushes are buffering, and no promotion
               # was (or should be) triggered
               "num_unreachable": sum(1 for s in servers
                                      if s["state"] == "unreachable"),
               "fence_epoch": self._fleet_epoch,
               "degraded_keys": deg,
               "pending_pushes": npend,
               "failovers": sum(s.get("failovers", 0)
                                for s in servers)}
        sweeps = self._server_stats_sweep()
        # server-side replication evidence, one row per reachable
        # shard: role, promotion count, forwarding lag, catch-up
        # progress — what an operator (or the E2E parity test) reads
        # to see "backup promoted, old primary rejoined, caught up"
        out["replication"] = [
            {"addr": s.get("addr"), "role": s.get("role"),
             "promotions": s.get("promotions", 0),
             "fence_epoch": s.get("fence_epoch"),
             "fenced": s.get("fenced", False),
             "repl": s.get("repl"),
             "catchup_complete": s.get("catchup_complete", True)}
            for s in sweeps if s.get("role") is not None]
        out.update(self._fleet_worker_view(sweeps))
        return out

    def _server_stats_sweep(self):
        """One 'stats' round trip per reachable server — reshard-born
        servers included — (dead shards are skipped, not waited on)."""
        out = []
        with self._extra_guard:
            extra = list(self._extra_conns.values())
        for c in list(self._conns) + extra:
            if c.state == "dead":
                continue
            try:
                _, srv = c.request("stats", retries=0)
            except (ConnectionError, RuntimeError, OSError):
                continue
            srv = dict(srv)
            srv["addr"] = c.addr
            out.append(srv)
        return out

    @staticmethod
    def _fleet_worker_view(sweeps):
        """Merge the servers' per-worker liveness tables: pushes sum
        across shards, staleness/step-gap take the worst shard, and the
        straggler verdict compares each worker's fleet-wide push count
        against the leader (push-count based — deterministic under the
        fault matrix, no wall clock)."""
        workers = {}
        epochs = {}
        barrier_timeouts = 0
        barrier_recounts = 0
        for srv in sweeps:
            # per-server: the epoch counters are INDEPENDENT — a
            # cross-server max would mix unrelated counters into one
            # meaningless number
            epochs[srv.get("addr")] = srv.get("membership_epoch", 0)
            barrier_timeouts += srv.get("barrier_timeouts", 0)
            barrier_recounts += srv.get("barrier_recounts", 0)
            for o, w in (srv.get("workers") or {}).items():
                agg = workers.setdefault(
                    o, {"rank": w.get("rank"), "pushes": 0,
                        "staleness_max": 0, "push_gap_max": 0.0})
                if agg["rank"] is None:
                    agg["rank"] = w.get("rank")
                agg["pushes"] += w.get("pushes", 0)
                agg["staleness_max"] = max(agg["staleness_max"],
                                           w.get("staleness_max", 0))
                agg["push_gap_max"] = max(agg["push_gap_max"],
                                          w.get("push_gap_max", 0.0))
        stragglers = []
        if workers:
            lead = max(w["pushes"] for w in workers.values())
            if lead >= _STRAGGLER_MIN:
                stragglers = sorted(
                    o for o, w in workers.items()
                    if w["pushes"] * _STRAGGLER_FACTOR < lead)
        elastic = {
            # every worker registers with EVERY server, so fleet-wide
            # join/leave event counts are the busiest server's number,
            # not a sum; split/move/cursor events are per-server
            # disjoint and DO sum
            "joins": max((s.get("joins", 0) for s in sweeps),
                         default=0),
            "leaves": max((s.get("leaves", 0) for s in sweeps),
                          default=0),
            "splits": sum(s.get("splits", 0) for s in sweeps),
            "keys_moved": sum(s.get("keys_moved_out", 0)
                              for s in sweeps),
            "keys_adopted": sum(s.get("keys_adopted", 0)
                                for s in sweeps),
            "cursor_requeues": sum(s.get("cursor_requeues", 0)
                                   for s in sweeps),
            "map_versions": {s.get("addr"): s.get("map_version", 0)
                             for s in sweeps},
        }
        return {"workers": workers, "stragglers": stragglers,
                "membership_epochs": epochs,
                "membership_churn": any(e > 0 for e in epochs.values()),
                "barrier_timeouts": barrier_timeouts,
                "barrier_recounts": barrier_recounts,
                "elastic": elastic}

    def _metrics_view(self):
        """Worker-side health scalars for the registry snapshot: the
        pending-push backlog, degraded keys, failovers — plus every
        ``add_stats_source`` extra (guard, fused-dist window), so the
        one poll a controller makes sees worker defenses too."""
        with self._pending_lock:
            npend = sum(len(v) for v in self._pending.values())
        with self._degraded_lock:
            ndeg = len(self._degraded)
        out = {"rank": self._rank, "origin": self._origin,
               "pending_pushes": npend, "degraded_keys": ndeg,
               "failovers": sum(getattr(c, "failovers", 0)
                                for c in self._conns),
               "servers_dead": sum(1 for c in self._conns
                                   if c.state == "dead")}
        for name, fn in list(self._extra_stats.items()):
            try:
                out[name] = fn()
            except Exception:   # a dying source must not kill the poll
                out[name] = None
        return out

    def add_stats_source(self, name, fn):
        """Merge a caller-side counter source into ``stats()`` under
        ``name`` (TrainGuard publishes its skip/rollback counters this
        way, so worker-side defenses read out next to the comms
        evidence)."""
        self._extra_stats[name] = fn

    def degraded_keys(self):
        """Top-level keys whose last pull was served from the worker's
        cache because their shard was unreachable (staleness mark)."""
        return self.health()["degraded_keys"]

    def get_num_dead_node(self, node_id=0, timeout=60):
        """Reference KVStore::get_num_dead_node via the heartbeat health
        state: how many of this worker's servers are currently dead."""
        return self.health()["num_dead"]

    def stats(self):
        """Comms counters for this store's fast path: wire bytes/frames
        both ways, coalescing (frames and sub-commands), the pipelined
        in-flight high-water mark and retransmits — plus the push
        dedupe/staleness counts of every *reachable* server (dead
        shards are skipped, not waited on). ``retransmits`` > 0 with
        ``dup_pushes`` covering the replays is the observable
        at-most-once evidence under injected severs."""
        s = self._stats.snapshot()
        with self._pending_lock:
            s["pending_pushes"] = sum(len(v)
                                      for v in self._pending.values())
        s["failovers"] = sum(getattr(c, "failovers", 0)
                             for c in self._conns)
        s["dup_pushes"] = 0
        s["server_pushes"] = 0
        s["sparse_pushes"] = 0
        s["sparse_rows"] = 0
        sweeps = self._server_stats_sweep()
        for srv in sweeps:
            s["dup_pushes"] += srv.get("dup_pushes", 0)
            s["server_pushes"] += srv.get("pushes", 0)
            s["sparse_pushes"] += srv.get("sparse_pushes", 0)
            s["sparse_rows"] += srv.get("sparse_rows", 0)
        s["replication"] = [
            {"addr": srv.get("addr"), "role": srv.get("role"),
             "promotions": srv.get("promotions", 0),
             "repl": srv.get("repl"),
             "catchup_complete": srv.get("catchup_complete", True)}
            for srv in sweeps if srv.get("role") is not None]
        s.update(self._fleet_worker_view(sweeps))
        for name, fn in self._extra_stats.items():
            s[name] = fn()
        return s

    def staleness_stats(self):
        """Aggregated staleness evidence from every server: max/avg
        staleness and per-key clocks. max > 0 is the observable proof
        that updates interleaved asynchronously."""
        agg = {"staleness_max": 0, "staleness_avg": 0.0, "pushes": 0,
               "clocks": {}}
        total_w = 0.0
        with self._extra_guard:
            extra = list(self._extra_conns.values())
        for c in list(self._conns) + extra:
            _, s = c.request("stats")
            agg["staleness_max"] = max(agg["staleness_max"],
                                       s["staleness_max"])
            agg["pushes"] += s["pushes"]
            total_w += s["staleness_avg"] * s["pushes"]
            agg["clocks"].update(s["clocks"])
        if agg["pushes"]:
            agg["staleness_avg"] = total_w / agg["pushes"]
        return agg

    def close(self):
        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=5)
            self._hb_thread = None
        if self._seq_pool is not None:
            self._seq_pool.shutdown(wait=True)
            self._seq_pool = None
        self._pool.shutdown(wait=True)
        # clean departure: servers drop this worker's membership and
        # reclaim its dedupe seqs NOW instead of waiting out the
        # MXTPU_PS_WORKER_DEAD_AFTER silence window (and a dynamic
        # barrier re-counts immediately)
        with self._extra_guard:
            extra = list(self._extra_conns.values())
            self._extra_conns = {}
        for c in list(self._conns) + extra:
            if c.state != "dead":
                try:
                    c.request("bye", self._origin, retries=0, timeout=2.0)
                except (ConnectionError, RuntimeError, OSError):
                    pass
        for c in list(self._conns) + extra:
            c.close()
        # give the registry series/view back: closed stores must not
        # count against the cardinality bound forever
        self._stats.release()
        _obs.REGISTRY.unview(self._view_key)
        if self._own_server is not None:
            self._own_server.stop()
            self._own_server = None


def _admin_main(argv):
    """Operator one-shots against a running launch (the shared secret
    comes from ``MXTPU_PS_TOKEN`` in the environment, exactly as the
    launcher exports it):

    * ``--admin split --src host:port --dst host:port [--keys a,b]`` —
      hand half (or exactly ``--keys``) of src's keys to dst online;
    * ``--admin stats --src host:port`` — one server's stats as JSON.

    ``tools/launch.py --scale`` drives the split drill through this.
    """
    import argparse
    import json
    ap = argparse.ArgumentParser(prog="mxtpu.kvstore_async")
    ap.add_argument("--admin", choices=("split", "stats"),
                    required=True)
    ap.add_argument("--src", required=True)
    ap.add_argument("--dst", default=None)
    ap.add_argument("--keys", default=None)
    a = ap.parse_args(argv)
    conn = _ServerConn(a.src,
                       token=os.environ.get("MXTPU_PS_TOKEN") or None,
                       n_socks=1, connect_timeout=30.0)
    try:
        if a.admin == "split":
            if not a.dst:
                ap.error("--admin split requires --dst")
            keys = [k for k in (a.keys or "").split(",") if k] or None
            reply = conn.request("split", a.dst, keys)
        else:
            reply = conn.request("stats")
        print(json.dumps(reply[1], default=str))
    finally:
        conn.close()
    return 0


if __name__ == "__main__":
    if "--admin" in sys.argv:
        sys.exit(_admin_main(sys.argv[1:]))
    serve_forever()
