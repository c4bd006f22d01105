"""Production model serving — the "millions of users" leg of the roadmap.

The reference's deploy surface is the C predict API + amalgamation
bundle (PAPER.md layer 9, ``c_predict_api.h``): one request, one
process, one shape-specialized executor. This package is the operable
rendering of that surface for heavy concurrent traffic, built entirely
out of machinery this tree already trusts:

* :mod:`mxtpu.serving.engine` — loads ``Module.save_checkpoint``
  artifacts and AOT-compiles one DONATED XLA predict program per batch
  bucket through the fused Module path's
  :class:`~mxtpu.module.fused.ProgramCache` (zero per-request retraces
  in steady state, pinned by ``ci/check_serving.py``).
* :mod:`mxtpu.serving.batcher` — the bounded-latency dynamic batcher:
  same-signature requests coalesce into one device dispatch, padded
  into the bucket shapes; a batch flushes when a bucket fills or the
  oldest request has waited ``MXTPU_SERVE_BATCH_DEADLINE_MS``.
  Admission control is a bounded queue (``MXTPU_SERVE_QUEUE_DEPTH``)
  that sheds with a RETRIABLE ``overloaded`` verdict, and per-request
  deadlines ride the wire: an expired request is dropped BEFORE
  dispatch (never after) with the ``expired`` verdict. The same module
  hosts :class:`~mxtpu.serving.batcher.GenerateScheduler`, the
  CONTINUOUS scheduler behind the ``generate`` op: slot-indexed decode
  lanes step every in-flight sequence in one donated-buffer XLA
  dispatch, sequences join/leave at step boundaries without draining
  the batch, and a budget exhausted BETWEEN decode steps frees the
  slot with the ``expired`` verdict (docs/serving.md "Continuous
  batching & generation").
* :mod:`mxtpu.serving.server` — the replica process: kvstore_async's
  PR-2 transport verbatim (zero-copy pickle-5 frames, pipelined
  windows, token auth, the ``MXTPU_PS_LOCAL`` in-process shortcut) —
  no new RPC layer. SIGTERM runs a two-phase graceful drain: stop
  admissions, flush in-flight batches, exit — the shape
  ``tools/launch.py``'s ``_reap`` escalation turns into a clean
  rolling restart.
* :mod:`mxtpu.serving.client` — the PR-4 ``_ReplicatedConn`` failover
  pattern for a symmetric replica set: replicas are learned at hello,
  a window failure health-probes and fails over in place, and the
  replay carries the ORIGINAL request id — acknowledged requests are
  answered exactly once, bit-for-bit identical across replicas (pure
  function of the shared checkpoint).

* :mod:`mxtpu.serving.rollout` — the train→serve loop closed:
  :class:`~mxtpu.serving.rollout.WeightPublisher` writes versioned,
  digest-tagged weight snapshots; :class:`~mxtpu.serving.rollout.
  WeightSync` streams them into live replicas (snapshot polling or the
  parameter server's ``weights`` long-poll stream) with NO recompiles
  — same shapes, program-cache hits — and an atomic version-epoch bump
  between batches; :class:`~mxtpu.serving.rollout.RolloutController`
  drives canary/A-B splits, promote/abort verdicts, zero-downtime
  hot-swap via the drain verdict, and bit-exact rollback to a pinned
  version verified against its recorded digest.

Fault drills ride :mod:`mxtpu.fault` at four serving points —
``serve.request`` (admission), ``serve.batch`` (pre-dispatch),
``serve.swap`` (pre-weight-swap) and ``publish.snapshot`` (the
publisher side) — plus the existing transport points, so
kill/delay/sever serving scenarios replay deterministically
(``tests/test_fault_tolerance.py``, ``tests/test_serving.py``,
``tests/test_rollout.py``). Full architecture and semantics:
``docs/serving.md``; knobs: ``docs/env_vars.md`` (``MXTPU_SERVE_*``);
pinned counts: ``docs/perf_analysis.md`` "Serving".
"""
from __future__ import annotations

from .engine import InferenceEngine, parse_buckets, parse_shape_spec
from .batcher import (DynamicBatcher, GenerateScheduler,
                      RETRIABLE_VERDICTS)
from .server import ModelServer
from .client import ServingClient, Overloaded, DeadlineExceeded
from .rollout import RolloutController, WeightPublisher, WeightSync

__all__ = ["InferenceEngine", "DynamicBatcher", "GenerateScheduler",
           "ModelServer", "ServingClient", "Overloaded",
           "DeadlineExceeded", "RolloutController", "WeightPublisher",
           "WeightSync", "RETRIABLE_VERDICTS", "parse_buckets",
           "parse_shape_spec"]
