"""Serving replica process entry: ``python -m mxtpu.serving``.

Spawned per replica by ``tools/launch.py --serve N`` (which exports
``MXTPU_SERVE_ADDRS`` with the whole replica set) or by hand. Env
contract:

* ``MXTPU_SERVE_MODEL``       checkpoint prefix (``prefix-symbol.json``
                              + ``prefix-%04d.params``) — required
* ``MXTPU_SERVE_EPOCH``       checkpoint epoch (default 0)
* ``MXTPU_SERVE_DATA_SHAPES`` per-sample input shapes,
                              ``name=dims[;name=dims]`` — required
* ``MXTPU_SERVE_PORT``        port to bind (default 0 = OS-assigned)
* ``MXTPU_SERVE_ADDRS``       comma list of ALL replica addresses
                              (advertised to clients at hello)
* ``MXTPU_SERVE_BUCKETS``     batch buckets (default ``1,2,4,8,16,32``)
* ``MXTPU_SERVE_WEIGHT_DIR``  versioned weight-snapshot dir to follow
                              (the WeightPublisher's; also the
                              rollback restore source)
* ``MXTPU_SERVE_WEIGHT_KV``   comma list of parameter-server addresses
                              to follow via the ``weights`` long-poll
                              stream instead of (or next to) the dir
* ``MXTPU_SERVE_WEIGHT_POLL`` weight-sync tick seconds (default 0.5)
* ``MXTPU_SERVE_PREWARM_DIR`` shared AOT program-cache dir: a booting
                              replica imports a peer's exported
                              program menu (cold start becomes a load,
                              not a compile) and the first warm
                              replica exports it (docs/autoscaling.md)
* plus the batching/admission knobs read by
  :mod:`mxtpu.serving.server` (``MXTPU_SERVE_QUEUE_DEPTH``,
  ``MXTPU_SERVE_BATCH_DEADLINE_MS``, ``MXTPU_SERVE_DEADLINE_MS``).

With a weight source configured the replica CATCHES UP to the current
weight version BEFORE it starts admitting (the ``--serve-respawn``
rejoin contract: a revived replica re-hellos already serving current
weights, never stale ones), then follows the stream live.

Lifecycle: SIGTERM triggers the graceful drain — admissions stop (new
predicts get the retriable ``draining`` verdict, steering clients to
the surviving replicas), admitted batches flush, then the process exits
0. This is exactly the TERM half of ``tools/launch.py``'s ``_reap``
escalation, so a reaped serving fleet drains instead of dropping
in-flight work; kill -9 is the crash drill the client failover path
covers.

Admin one-shots (``tools/launch.py --rollout`` drives these)::

    python -m mxtpu.serving --admin rollout --addrs host:p,host:p \
        --action canary|promote|abort|rollback|pin|unpin|status \
        [--version V] [--fraction F] [--model NAME]
"""
from __future__ import annotations

import os
import signal
import sys
import threading


def main():
    prefix = os.environ.get("MXTPU_SERVE_MODEL")
    shapes = os.environ.get("MXTPU_SERVE_DATA_SHAPES")
    if not prefix or not shapes:
        print("mxtpu.serving: MXTPU_SERVE_MODEL and "
              "MXTPU_SERVE_DATA_SHAPES are required", file=sys.stderr)
        return 2
    epoch = int(os.environ.get("MXTPU_SERVE_EPOCH", "0"))
    port = int(os.environ.get("MXTPU_SERVE_PORT", "0"))
    buckets = os.environ.get("MXTPU_SERVE_BUCKETS", "1,2,4,8,16,32")
    weight_dir = os.environ.get("MXTPU_SERVE_WEIGHT_DIR") or None
    weight_kv = os.environ.get("MXTPU_SERVE_WEIGHT_KV") or None
    prewarm_dir = os.environ.get("MXTPU_SERVE_PREWARM_DIR") or None

    import time
    t_boot = time.monotonic()

    from . import InferenceEngine, ModelServer, WeightSync, \
        parse_buckets, parse_shape_spec

    engine = InferenceEngine.from_checkpoint(
        prefix, epoch, parse_shape_spec(shapes),
        buckets=parse_buckets(buckets), warm=False)
    srv = ModelServer(engine, port=port,
                      model_name=os.path.basename(prefix))
    devs = engine.store_devices()
    print("mxtpu serving replica weight store on %s (%s): %s"
          % (devs[0].platform, devs[0].device_kind,
             ",".join(str(d) for d in devs)), flush=True)

    # the prewarm contract (docs/autoscaling.md): the FIRST replica
    # pays the cold compile and publishes its AOT program menu; every
    # later joiner imports it and warm() only compiles what is missing,
    # so time-to-serving is a load, not a compile
    prewarm_path = None
    imported = 0
    if prewarm_dir:
        prewarm_path = os.path.join(
            prewarm_dir, "%s-e%04d.programs"
            % (os.path.basename(prefix), epoch))
        if os.path.exists(prewarm_path):
            imported = engine.prewarm_from(prewarm_path)
            print("mxtpu serving replica prewarmed %d program(s) "
                  "from %s" % (imported, prewarm_path), flush=True)

    sync = None
    if weight_dir or weight_kv:
        sync = WeightSync(srv, weight_dir=weight_dir,
                          kv_addrs=weight_kv)
        # the rejoin contract: current weights BEFORE the first admit
        caught = sync.catch_up()
        print("mxtpu serving replica caught up to weight version %d"
              % caught, flush=True)

    term = threading.Event()

    def _on_term(signum, frame):
        # flag only — drain runs on the main thread, not in the handler
        term.set()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    srv.start()     # warms every bucket program before listening
    if sync is not None:
        sync.start()
    # the measured cold-start number the autoscaling CI pins: wall time
    # from process boot to a fully-warmed, listening replica
    print("mxtpu serving replica time-to-serving %.3fs "
          "(prewarmed=%d compiles=%d)"
          % (time.monotonic() - t_boot, imported,
             engine.cache.compiles), flush=True)
    if prewarm_path and engine.cache.compiles > 0:
        # first replica (or a stale menu): publish the warmed programs
        # for the next joiner — atomic write, identical content on a
        # racing double-export, so last-wins is harmless
        n = engine.export_programs(prewarm_path)
        print("mxtpu serving replica exported %d program(s) to %s"
              % (n, prewarm_path), flush=True)
    print("mxtpu serving replica listening on %s (model=%s buckets=%s)"
          % (srv.address, os.path.basename(prefix),
             ",".join(str(b) for b in engine.buckets)), flush=True)
    while not term.is_set():
        term.wait(timeout=0.5)
    print("mxtpu serving replica %s draining" % srv.address, flush=True)
    if sync is not None:
        sync.stop()
    drained = srv.drain(timeout=float(
        os.environ.get("MXTPU_SERVE_DRAIN_TIMEOUT", "30")))
    srv.stop()
    print("mxtpu serving replica %s stopped (drained=%s)"
          % (srv.address, drained), flush=True)
    return 0


def _admin_main(argv):
    """Operator one-shots against a running serving fleet — the wire
    form of :class:`~mxtpu.serving.rollout.RolloutController` (the
    shared secret comes from ``MXTPU_PS_TOKEN``, as the launcher
    exports it)."""
    import argparse
    import json
    from .rollout import RolloutController
    ap = argparse.ArgumentParser(prog="mxtpu.serving")
    ap.add_argument("--admin", choices=("rollout",), required=True)
    ap.add_argument("--addrs", required=True,
                    help="comma list of serving replica addresses")
    ap.add_argument("--action", required=True,
                    choices=("canary", "promote", "abort", "rollback",
                             "pin", "unpin", "status", "verdict"))
    ap.add_argument("--model", default=None)
    ap.add_argument("--version", type=int, default=None)
    ap.add_argument("--fraction", type=float, default=0.1)
    a = ap.parse_args(argv)
    ctl = RolloutController(a.addrs, model=a.model)
    try:
        if a.action == "canary":
            out = ctl.canary(a.version, a.fraction)
        elif a.action == "promote":
            out = ctl.promote(a.version)
        elif a.action == "abort":
            out = ctl.abort()
        elif a.action == "rollback":
            out = ctl.rollback(a.version)
        elif a.action == "pin":
            out = ctl.pin(a.version)
        elif a.action == "unpin":
            out = ctl.unpin()
        elif a.action == "verdict":
            out = ctl.verdict(a.version)
        else:
            out = ctl.status()
        print(json.dumps(out, default=str))
    finally:
        ctl.close()
    return 0


if __name__ == "__main__":
    if "--admin" in sys.argv:
        sys.exit(_admin_main(sys.argv[1:]))
    sys.exit(main())
