#!/usr/bin/env python
"""Distributed job launcher (reference tools/launch.py:29-111).

The reference shells into dmlc-tracker to spawn ps-lite scheduler/server/
worker processes over ssh/mpi/sge/yarn or locally. mxtpu's distributed
backend is ``jax.distributed`` (single controller per host, collectives
over ICI/DCN), so the launcher's job is to start N worker processes with
the coordinator environment — the `--launcher local` mode forks them on
this host (how the reference's nightly dist tests run without a cluster,
tests/nightly/dist_sync_kvstore.py), and `--launcher ssh` prints/execs
the per-host commands.

Env handed to each worker (read by mxtpu.kvstore / jax.distributed):
  MXTPU_COORDINATOR  host:port of process 0
  MXTPU_NUM_PROCS    world size
  MXTPU_PROC_ID      rank
(Plus DMLC_* aliases for scripts written against the reference.)
"""
from __future__ import annotations

import argparse
import os
import secrets
import signal
import subprocess
import sys
import tempfile
import threading
import time

# the autoscale/scale actuation layer imports mxtpu.fleet (stdlib-only
# modules, but the package import needs the repo root on the path when
# the launcher runs from elsewhere)
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _reap(procs, grace=5.0):
    """Terminate-and-reap with escalation: SIGTERM every live child,
    give the fleet ``grace`` seconds to exit, SIGKILL stragglers, then
    collect every corpse — bounded at each stage, so the launcher can
    never hang on (or zombie-leak) a child that ignores TERM."""
    live = [p for p in procs if p.poll() is None]
    for p in live:
        try:
            p.send_signal(signal.SIGTERM)
        except OSError:
            pass
    deadline = time.time() + grace
    for p in live:
        try:
            p.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            try:
                p.kill()
            except OSError:
                pass
    for p in live:
        try:
            p.wait(timeout=5)
        except subprocess.TimeoutExpired:   # unkillable (D-state): log
            print("warning: pid %d did not die after SIGKILL" % p.pid,
                  file=sys.stderr)


def _free_port(preferred):
    """preferred if bindable, else an OS-assigned free port — a silent
    EADDRINUSE in a server child would surface only as late
    connection-refused errors in whatever workers hash to it."""
    import socket
    for port in (preferred, 0):
        try:
            with socket.socket() as s:
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind(("127.0.0.1", port))
                return s.getsockname()[1]
        except OSError:
            continue
    return preferred


def _spawn_server(name, ps_port, base_env, args, role="primary",
                  peer=None):
    """One async parameter-server child. With a snapshot dir configured,
    the server snapshots its table there and a RESPAWN of the same slot
    restores it — kvstore_async auto-resume — because the respawn reuses
    the same port (workers reconnect via their retry layer) and the same
    per-slot directory. With --ps-replicas 2 each shard is a
    primary/backup pair: MXTPU_PS_PEER/MXTPU_PS_ROLE wire the pair
    together, and a respawned process re-negotiates its role at boot
    (a respawned ex-primary finds its promoted peer and rejoins as the
    new backup, catching up via state transfer)."""
    env = dict(base_env, DMLC_ROLE="server",
               MXTPU_PS_PORT=str(ps_port), JAX_PLATFORMS="cpu",
               MXTPU_PS_ROLE=role)
    if peer:
        env["MXTPU_PS_PEER"] = peer
    if args.ps_snapshot_dir:
        env["MXTPU_PS_SNAPSHOT_DIR"] = os.path.join(
            args.ps_snapshot_dir, "server_%s" % name)
        env["MXTPU_PS_SNAPSHOT_EVERY"] = str(args.ps_snapshot_every)
    proc = subprocess.Popen(
        [sys.executable, "-m", "mxtpu.kvstore_async"], env=env)
    # pid + port on stdout: external failover drills (and the E2E
    # parity test) kill -9 an exact server process by parsing this
    print("ps server %s role=%s pid=%d port=%d"
          % (name, role, proc.pid, ps_port), flush=True)
    return proc


def _child_platform(args, env, role):
    """Env overrides that keep ONE process per chip on this host.

    A chip belongs to one process at a time: a second child that reaches
    for it fails or hangs. So at most one jax-computing child of a local
    launch inherits the caller's platform (and may take the chip) — the
    lone serving replica if the launch has one, else the lone worker.
    Every other worker or replica is a CPU process. To use four chips,
    run four engines in ONE process, each on its own ``mx.tpu(i)``
    (docs/serving.md)."""
    if env.get("JAX_PLATFORMS") == "cpu":
        return {}           # every child is a CPU process already
    slots = max(args.serve, args.serve_max or 0)
    if role == "replica":
        alone = slots == 1
    else:
        alone = slots == 0 and args.num_workers == 1 \
            and not (args.scale or args.autoscale)
    return {} if alone else {"JAX_PLATFORMS": "cpu"}


def _spawn_serving_replica(idx, port, addrs, base_env, args):
    """One model-serving replica child (``python -m mxtpu.serving``).
    Every replica gets the FULL replica set in MXTPU_SERVE_ADDRS so its
    hello replies teach clients where to fail over. With a weight
    source configured (``--serve-weight-dir`` / ``--serve-weight-kv``)
    the replica catches up to the CURRENT weight version before it
    admits and then follows the stream live — which is also what makes
    a ``--serve-respawn`` rejoin well-defined: the revived process
    re-binds its port, catches up, re-hellos, and serves current
    weights. Replicas are reaped with the same ``_reap`` TERM→KILL
    escalation as servers — SIGTERM is their graceful drain (stop
    admissions, flush in-flight batches, exit 0), so a clean launcher
    exit never drops admitted requests."""
    env = dict(base_env, **_child_platform(args, base_env, "replica"),
               MXTPU_SERVE_PORT=str(port),
               MXTPU_SERVE_ADDRS=",".join(addrs),
               MXTPU_SERVE_MODEL=args.serve_model,
               MXTPU_SERVE_EPOCH=str(args.serve_epoch),
               MXTPU_SERVE_DATA_SHAPES=args.serve_data_shapes)
    if args.serve_buckets:
        env["MXTPU_SERVE_BUCKETS"] = args.serve_buckets
    if args.serve_weight_dir:
        env["MXTPU_SERVE_WEIGHT_DIR"] = args.serve_weight_dir
    if args.serve_weight_kv:
        env["MXTPU_SERVE_WEIGHT_KV"] = args.serve_weight_kv
    if args.serve_weight_poll is not None:
        env["MXTPU_SERVE_WEIGHT_POLL"] = str(args.serve_weight_poll)
    env.pop("DMLC_ROLE", None)     # not a parameter-server role process
    env["MXTPU_OBS_ROLE"] = "serving"   # telemetry role label
    proc = subprocess.Popen(
        [sys.executable, "-m", "mxtpu.serving"], env=env)
    # pid + port on stdout: kill -9 failover drills parse this, exactly
    # like the ps-server line
    print("serve replica %d pid=%d port=%d" % (idx, proc.pid, port),
          flush=True)
    return proc


def _parse_scale(spec):
    """``--scale`` drill events: ``;``-separated, each a comma list of
    ``key=value`` — ``after=SECONDS`` or ``at_step=N`` (needs
    ``--scale-progress``) picks the trigger, ``action=`` one of
    add_worker / remove_worker / split_shard, plus ``rank=`` (remove)
    and ``src=`` (split source server slot, default 0)."""
    events = []
    for item in spec.split(";"):
        item = item.strip()
        if not item:
            continue
        ev = {}
        for pair in item.split(","):
            k, _, v = pair.partition("=")
            ev[k.strip()] = v.strip()
        if ev.get("action") not in ("add_worker", "remove_worker",
                                    "split_shard", "add_replica",
                                    "drain_replica"):
            raise SystemExit("scale event %r needs action=add_worker|"
                             "remove_worker|split_shard|add_replica|"
                             "drain_replica" % item)
        if "after" not in ev and "at_step" not in ev:
            raise SystemExit("scale event %r needs after= or at_step="
                             % item)
        events.append(ev)
    return events


def _parse_rollout(spec):
    """``--rollout`` drill events: ``;``-separated, each a comma list
    of ``key=value`` — ``after=SECONDS`` or ``at_step=N`` (needs
    ``--scale-progress``) picks the trigger, ``action=`` one of
    canary / promote / abort / rollback / pin / unpin / status, plus
    ``version=``, ``fraction=`` and ``model=`` as the action needs."""
    events = []
    for item in spec.split(";"):
        item = item.strip()
        if not item:
            continue
        ev = {}
        for pair in item.split(","):
            k, _, v = pair.partition("=")
            ev[k.strip()] = v.strip()
        if ev.get("action") not in ("canary", "promote", "abort",
                                    "rollback", "pin", "unpin",
                                    "status"):
            raise SystemExit("rollout event %r needs action=canary|"
                             "promote|abort|rollback|pin|unpin|status"
                             % item)
        if "after" not in ev and "at_step" not in ev:
            raise SystemExit("rollout event %r needs after= or "
                             "at_step=" % item)
        events.append(ev)
    return events


def _wait_port(host, port, timeout=60.0):
    """Block until something accepts on host:port (a just-spawned
    server is still importing for a few seconds)."""
    import socket
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with socket.create_connection((host, port), timeout=1.0):
                return True
        except OSError:
            time.sleep(0.1)
    return False


def launch_local(args, command):
    procs = []
    base_env = dict(os.environ)
    for role in ("worker", "replica") if args.serve else ("worker",):
        if _child_platform(args, base_env, role):
            print("launch: %s children run as CPU processes (one process "
                  "per chip; docs/serving.md)" % role, flush=True)
    coordinator = "127.0.0.1:%d" % args.port
    if args.autoscale:
        # the closed loop needs its sensor plane: the controller's only
        # input is the aggregator's fleet.json
        args.telemetry = True
    # -s N starts N async parameter-server processes (DMLC_ROLE=server;
    # reference dmlc-tracker starts ps-lite servers the same way); workers
    # find them via MXTPU_PS_ADDRS for create('dist_async')
    server_procs = []
    server_ports = []
    ps_addrs = []
    # per-launch shared secret: the PS wire protocol is pickle, so only
    # processes of THIS launch may speak to the servers (any other local
    # user connecting would otherwise get arbitrary code execution)
    ps_token = secrets.token_hex(16) if args.num_servers else None
    if ps_token:
        base_env["MXTPU_PS_TOKEN"] = ps_token
    # --telemetry: one observability plane for the whole launch
    # (docs/observability.md). Every child inherits MXTPU_TELEMETRY /
    # MXTPU_TELEMETRY_DIR — workers start metrics exporters and drop
    # endpoint files, servers/replicas answer `metrics` on their main
    # ports — and ONE aggregator child polls the fleet into
    # <dir>/fleet.json (+ history), which tools/mxtop.py renders live.
    if args.telemetry:
        if not args.telemetry_dir:
            args.telemetry_dir = tempfile.mkdtemp(prefix="mxtpu_telem_")
        base_env["MXTPU_TELEMETRY"] = "1"
        base_env["MXTPU_TELEMETRY_DIR"] = args.telemetry_dir
        print("telemetry: %s/fleet.json (mxtop: python tools/mxtop.py "
              "--dir %s)" % (args.telemetry_dir, args.telemetry_dir),
              flush=True)
    # -- autoscale plumbing (docs/autoscaling.md): the action mailbox /
    # journal / lease directory, shared by the controller child and this
    # launcher's executor; plus the prewarm dir serving replicas export
    # their AOT program menus into so a controller-added replica boots
    # warm. Provisioned before any child spawns so every env inherits it.
    autoscale_dir = None
    if args.autoscale or args.scale:
        autoscale_dir = args.autoscale_dir or (
            os.path.join(args.telemetry_dir, "autoscale")
            if args.telemetry_dir
            else tempfile.mkdtemp(prefix="mxtpu_autoscale_"))
        os.makedirs(autoscale_dir, exist_ok=True)
        base_env["MXTPU_AUTOSCALE_DIR"] = autoscale_dir
    if args.autoscale and args.serve:
        prewarm_dir = os.path.join(autoscale_dir, "prewarm")
        os.makedirs(prewarm_dir, exist_ok=True)
        base_env.setdefault("MXTPU_SERVE_PREWARM_DIR", prewarm_dir)
    if args.ps_respawn and not args.ps_snapshot_dir:
        # a respawned server with no snapshot restores nothing and every
        # in-flight key 404s — auto-provision the state dir instead
        args.ps_snapshot_dir = tempfile.mkdtemp(prefix="mxtpu_ps_snap_")
        print("ps snapshots in %s" % args.ps_snapshot_dir)
    replicas = max(1, args.ps_replicas)
    # slot metadata drives both the first spawn and every respawn:
    # (name, port, role, peer address). With --ps-replicas 2 the slots
    # are N primaries followed by their N backups, wired pairwise.
    server_slots = []
    backup_addrs = []
    ports = [_free_port(args.port + 1 + s)
             for s in range(args.num_servers * (2 if replicas >= 2
                                                else 1))]
    for s in range(args.num_servers):
        ps_addrs.append("127.0.0.1:%d" % ports[s])
    if replicas >= 2:
        for s in range(args.num_servers):
            backup_addrs.append(
                "127.0.0.1:%d" % ports[args.num_servers + s])
        base_env["MXTPU_PS_REPLICAS"] = str(replicas)
        base_env["MXTPU_PS_REPL_MODE"] = args.ps_repl_mode
    for s in range(args.num_servers):
        peer = backup_addrs[s] if replicas >= 2 else None
        server_slots.append(("%d" % s, ports[s], "primary", peer))
    for s in range(args.num_servers) if replicas >= 2 else []:
        server_slots.append(("%d_backup" % s,
                             ports[args.num_servers + s], "backup",
                             ps_addrs[s]))
    for name, port, role, peer in server_slots:
        server_ports.append(port)
        server_procs.append(_spawn_server(name, port, base_env, args,
                                          role=role, peer=peer))
    if backup_addrs:
        base_env["MXTPU_PS_BACKUP_ADDRS"] = ",".join(backup_addrs)
    # --serve N: a model-serving replica set next to (or instead of)
    # the parameter servers; workers see MXTPU_SERVE_ADDRS and speak
    # mxtpu.serving.ServingClient (docs/serving.md)
    serve_addrs = []
    serve_live = []
    serve_reserve = []   # (idx, port) slots held back for the
    #                      controller's add_replica actuation
    if args.serve:
        if not (args.serve_model and args.serve_data_shapes):
            raise SystemExit("--serve needs --serve-model and "
                             "--serve-data-shapes")
        # --serve-max reserves extra ports up front so the FULL
        # potential replica set is in MXTPU_SERVE_ADDRS from the first
        # hello: clients already know where a scaled-up replica will
        # appear, and failover finds it without a re-hello
        n_slots = max(args.serve, args.serve_max or 0)
        serve_ports = [_free_port(args.port + 201 + i)
                       for i in range(n_slots)]
        serve_addrs = ["127.0.0.1:%d" % p for p in serve_ports]
        serve_live = serve_addrs[:args.serve]
        serve_reserve = [(i, serve_ports[i])
                         for i in range(args.serve, n_slots)]
        base_env["MXTPU_SERVE_ADDRS"] = ",".join(serve_addrs)
        # the serve contract rides to the WORKERS too: a trainer
        # process publishing weights (WeightPublisher into the weight
        # dir, or kv.publish_version) needs the served model prefix
        # and the versioned snapshot dir the replicas follow
        base_env["MXTPU_SERVE_MODEL"] = args.serve_model
        base_env["MXTPU_SERVE_EPOCH"] = str(args.serve_epoch)
        base_env["MXTPU_SERVE_DATA_SHAPES"] = args.serve_data_shapes
        if args.serve_weight_dir:
            base_env["MXTPU_SERVE_WEIGHT_DIR"] = args.serve_weight_dir
        for i, port in enumerate(serve_ports[:args.serve]):
            server_slots.append(("serve%d" % i, port, "serving", i))
            server_ports.append(port)
            server_procs.append(_spawn_serving_replica(
                i, port, serve_addrs, base_env, args))
    # the aggregator child: polls every PS shard / backup / serving
    # replica (workers join via their endpoint files) into fleet.json.
    # Spawned AFTER the target lists exist, reaped with the servers.
    if args.telemetry:
        agg_env = dict(base_env, JAX_PLATFORMS="cpu")
        agg_env.pop("DMLC_ROLE", None)
        targets = ps_addrs + backup_addrs + serve_live
        agg = subprocess.Popen(
            [sys.executable, "-m", "mxtpu.obs.telemetry",
             "--targets", ",".join(targets),
             "--dir", args.telemetry_dir], env=agg_env)
        server_slots.append(("telemetry", 0, "telemetry", None))
        server_ports.append(0)
        server_procs.append(agg)
        print("telemetry aggregator pid=%d targets=%d"
              % (agg.pid, len(targets)), flush=True)

    # -- the autoscale controller child: the policy brain. It only ever
    # READS fleet.json and WRITES action files into the mailbox; this
    # launcher's executor (below) is the sole actuator. Separate process
    # so kill -9 mid-action is a first-class drill: the respawn replays
    # its journal and the executor dedupes (docs/autoscaling.md).
    def _spawn_controller(respawn=False):
        env = dict(base_env, JAX_PLATFORMS="cpu")
        env.pop("DMLC_ROLE", None)
        env["MXTPU_OBS_ROLE"] = "controller"
        if args.autoscale_fault and not respawn:
            env["MXTPU_FAULT_SPEC"] = args.autoscale_fault
        elif respawn:
            # a controller fault drill is one-shot: the respawned
            # controller must replay its journal, not re-die on the
            # same injected kill
            env.pop("MXTPU_FAULT_SPEC", None)
        proc = subprocess.Popen(
            [sys.executable, "-m", "mxtpu.fleet.controller",
             "--dir", autoscale_dir,
             "--fleet", os.path.join(args.telemetry_dir, "fleet.json")],
            env=env)
        print("autoscale controller pid=%d dir=%s"
              % (proc.pid, autoscale_dir), flush=True)
        return proc

    if args.autoscale:
        server_slots.append(("controller", 0, "controller", None))
        server_ports.append(0)
        server_procs.append(_spawn_controller())
    if args.worker_respawn and not args.worker_state_dir:
        # a respawned worker with no state dir restarts from step 0 and
        # double-trains its epoch — auto-provision one, like --ps-respawn
        args.worker_state_dir = tempfile.mkdtemp(prefix="mxtpu_worker_")
        print("worker state in %s" % args.worker_state_dir)
    worker_envs = []
    for rank in range(args.num_workers):
        env = dict(base_env, **_child_platform(args, base_env, "worker"))
        env.update({
            "MXTPU_COORDINATOR": coordinator,
            "MXTPU_NUM_PROCS": str(args.num_workers),
            "MXTPU_PROC_ID": str(rank),
            # reference-compatible aliases
            "DMLC_ROLE": "worker",
            "DMLC_NUM_WORKER": str(args.num_workers),
            "DMLC_NUM_SERVER": str(args.num_servers),
            "DMLC_WORKER_ID": str(rank),
        })
        if ps_addrs:
            env["MXTPU_PS_ADDRS"] = ",".join(ps_addrs)
        if args.worker_state_dir:
            # per-rank checkpoint dir a TrainGuard/CheckpointManager
            # worker saves its state into; the respawn reuses it so the
            # fresh process restores and fast-forwards
            env["MXTPU_WORKER_STATE_DIR"] = os.path.join(
                args.worker_state_dir, "worker_%d" % rank)
        worker_envs.append(env)
        procs.append(subprocess.Popen(command, shell=True, env=env))
    code = 0
    respawns = [0] * len(server_procs)
    worker_respawns = [0] * len(procs)

    # -- the --scale drill: elastic add/remove/split events on a
    # wall-clock or training-progress schedule (docs/fault_tolerance.md
    # "Elasticity"). Runs on its own thread; the monitor loop below
    # waits for it before declaring the launch finished.
    scale_done = threading.Event()
    stop_scale = threading.Event()
    removed = set()    # ranks departed by a remove_worker event: their
    #                    sh -c wrapper dies -15, which is NOT a failure
    drained_slots = set()   # server_slots indices drained on purpose
    actuate_lock = threading.Lock()   # one actuation mutates the fleet
    #                                   at a time (executor thread +
    #                                   --scale thread both actuate)

    def _announce_endpoint(role, addr):
        """Dynamically added children (replicas, split shards) are not
        in the aggregator's static target list — an endpoint file is
        how they join the telemetry plane mid-run."""
        if not args.telemetry_dir:
            return
        epd = os.path.join(args.telemetry_dir, "endpoints")
        os.makedirs(epd, exist_ok=True)
        path = os.path.join(epd,
                            "%s-%s.ep" % (role, addr.replace(":", "-")))
        tmp = path + ".tmp%d" % os.getpid()
        with open(tmp, "w") as f:
            f.write(addr)
        os.replace(tmp, path)

    def _retract_endpoint(role, addr):
        if not args.telemetry_dir:
            return
        try:
            os.unlink(os.path.join(
                args.telemetry_dir, "endpoints",
                "%s-%s.ep" % (role, addr.replace(":", "-"))))
        except OSError:
            pass

    def _act_add_worker(action=None):
        rank = len(procs)
        env = dict(base_env, **_child_platform(args, base_env, "worker"))
        env.update({
            "MXTPU_NUM_PROCS": str(args.num_workers),
            "MXTPU_PROC_ID": str(rank),
            "DMLC_ROLE": "worker",
            "DMLC_NUM_WORKER": str(args.num_workers),
            "DMLC_NUM_SERVER": str(args.num_servers),
            "DMLC_WORKER_ID": str(rank),
            # the joiner contract: skip init/set_optimizer, pull
            # current params, take work from the shard cursor
            "MXTPU_ELASTIC_JOINER": "1",
        })
        # a mid-run joiner CANNOT enter the already-formed
        # jax.distributed group (the coordination service pins its
        # world size at bootstrap) — elasticity rides the PS layer,
        # so the joiner runs single-process XLA and shares the
        # model only through the parameter servers
        env.pop("MXTPU_COORDINATOR", None)
        if ps_addrs:
            env["MXTPU_PS_ADDRS"] = ",".join(ps_addrs)
        if args.worker_state_dir:
            env["MXTPU_WORKER_STATE_DIR"] = os.path.join(
                args.worker_state_dir, "worker_%d" % rank)
        print("scale: adding worker %d" % rank, flush=True)
        worker_envs.append(env)
        worker_respawns.append(0)
        procs.append(subprocess.Popen(command, shell=True, env=env))
        return {"rank": rank}

    def _worker_rank_for_pid(pid):
        """Rank whose process tree contains pid — telemetry snapshots
        carry the python worker's pid, but the tracked Popen is its
        sh -c wrapper."""
        for rank, wp in enumerate(procs):
            if rank in removed or wp.poll() is not None:
                continue
            if wp.pid == pid:
                return rank
            try:
                for task in os.listdir("/proc/%d/task" % wp.pid):
                    with open("/proc/%d/task/%s/children"
                              % (wp.pid, task)) as f:
                        if pid in [int(c) for c in f.read().split()]:
                            return rank
            except OSError:
                continue
        return None

    def _act_remove_worker(action=None):
        action = action or {}
        rank = None
        if action.get("rank") is not None:
            rank = int(action["rank"])
        elif action.get("pid") is not None:
            rank = _worker_rank_for_pid(int(action["pid"]))
        if rank is None:
            live = [r for r, wp in enumerate(procs)
                    if r not in removed and wp.poll() is None]
            if not live:
                raise RuntimeError("no live worker to remove")
            rank = live[-1]
        # SIGTERM is the CLEAN departure: an elastic worker's
        # handler finishes its current shard, byes, and exits 0.
        # Popen(shell=True) makes the tracked pid an sh -c wrapper,
        # so the signal must reach its CHILDREN (the python worker)
        # too, or only the shell dies and training runs on.
        print("scale: removing worker %d (SIGTERM)" % rank,
              flush=True)
        removed.add(rank)
        pid = procs[rank].pid
        kids = []
        try:
            for task in os.listdir("/proc/%d/task" % pid):
                with open("/proc/%d/task/%s/children"
                          % (pid, task)) as f:
                    kids += [int(c) for c in f.read().split()]
        except OSError:
            pass
        for target in kids + [pid]:
            try:
                os.kill(target, signal.SIGTERM)
            except OSError:
                pass
        return {"rank": rank}

    def _act_split_shard(action=None):
        action = action or {}
        idx = len(server_slots)
        port = _free_port(args.port + 101 + idx)
        dst_addr = "127.0.0.1:%d" % port
        slots = [("e%d" % idx, port, "primary", None)]
        if max(1, args.ps_replicas) >= 2:
            # the new shard is born replicated: its backup joins
            # and catches up, and every adopted key mirrors there
            # BEFORE the old primary releases it
            bport = _free_port(args.port + 151 + idx)
            slots = [("e%d" % idx, port, "primary",
                      "127.0.0.1:%d" % bport),
                     ("e%d_backup" % idx, bport, "backup",
                      dst_addr)]
        for name, p_, role, peer in slots:
            server_slots.append((name, p_, role, peer))
            respawns.append(0)
            server_ports.append(p_)
            server_procs.append(_spawn_server(
                name, p_, base_env, args, role=role, peer=peer))
        if not _wait_port("127.0.0.1", port):
            raise RuntimeError(
                "split target %s never came up" % dst_addr)
        src_addr = action.get("src_addr") \
            or ps_addrs[int(action.get("src", 0))]
        admin_env = dict(base_env)
        admin_env.pop("DMLC_ROLE", None)
        admin_env["JAX_PLATFORMS"] = "cpu"
        print("scale: splitting server %s -> %s"
              % (src_addr, dst_addr), flush=True)
        r = subprocess.run(
            [sys.executable, "-m", "mxtpu.kvstore_async",
             "--admin", "split", "--src", src_addr,
             "--dst", dst_addr],
            env=admin_env, capture_output=True, text=True)
        print("scale: split -> %s"
              % (r.stdout.strip() or r.stderr.strip()[-500:]),
              flush=True)
        if r.returncode != 0:
            raise RuntimeError("split admin failed: %s"
                               % r.stderr.strip()[-300:])
        for name, p_, role, peer in slots:
            _announce_endpoint("server", "127.0.0.1:%d" % p_)
        return {"src": src_addr, "dst": dst_addr}

    def _act_add_replica(action=None):
        if not serve_reserve:
            raise RuntimeError(
                "no reserved serving slot left (--serve-max)")
        i, port = serve_reserve.pop(0)
        addr = "127.0.0.1:%d" % port
        print("scale: adding serving replica %d on %s" % (i, addr),
              flush=True)
        server_slots.append(("serve%d" % i, port, "serving", i))
        respawns.append(0)
        server_ports.append(port)
        server_procs.append(_spawn_serving_replica(
            i, port, serve_addrs, base_env, args))
        if not _wait_port("127.0.0.1", port, timeout=180):
            raise RuntimeError("replica %s never came up" % addr)
        _announce_endpoint("serving", addr)
        return {"addr": addr}

    def _act_drain_replica(action=None):
        action = action or {}
        target = None
        for si, (name, port, role, peer) in enumerate(server_slots):
            if role != "serving" or si in drained_slots:
                continue
            sp = server_procs[si]
            if sp.poll() is not None:
                continue
            addr = "127.0.0.1:%d" % port
            if action.get("addr") in (None, addr):
                target = (si, addr, sp)
                if action.get("addr"):
                    break
        if target is None:
            raise RuntimeError("no live serving replica to drain (%r)"
                               % action.get("addr"))
        si, addr, sp = target
        print("scale: draining serving replica %s (SIGTERM)" % addr,
              flush=True)
        drained_slots.add(si)    # respawn loop must not revive it
        sp.send_signal(signal.SIGTERM)   # graceful drain, exits 0
        _retract_endpoint("serving", addr)
        return {"addr": addr}

    # -- the idempotent actuation layer: EVERY fleet mutation (the
    # --scale drill's scripted events AND the --autoscale controller's
    # mailbox actions) goes through ONE ActionExecutor keyed by action
    # id, so a re-issued action after an ambiguous timeout returns the
    # recorded verdict instead of double-applying.
    executor = None
    if args.scale or args.autoscale:
        from mxtpu.fleet.actuator import ActionExecutor
        handlers = {}
        for kind, fn in (("add_worker", _act_add_worker),
                         ("remove_worker", _act_remove_worker),
                         ("split_shard", _act_split_shard),
                         ("add_replica", _act_add_replica),
                         ("drain_replica", _act_drain_replica)):
            def _locked(action=None, _fn=fn):
                with actuate_lock:
                    return _fn(action)
            handlers[kind] = _locked
        executor = ActionExecutor(autoscale_dir, handlers)

    def _do_scale_event(ev, idx):
        # position-derived id: a re-issued event after an ambiguous
        # timeout hits the executor's verdict record, not the handler
        eid = "scale-%d-%s" % (idx, ev["action"])
        v = executor.execute(eid, dict(ev)) or {}
        print("scale: %s -> %s %s"
              % (eid, v.get("verdict"), str(v.get("detail"))[:200]),
              flush=True)

    def _scale_controller(events):
        t0 = time.time()
        try:
            for idx, ev in enumerate(events):
                if "after" in ev:
                    deadline = t0 + float(ev["after"])
                    while time.time() < deadline:
                        if stop_scale.is_set():
                            return
                        time.sleep(0.05)
                else:
                    want = int(ev["at_step"])
                    while True:
                        if stop_scale.is_set():
                            return
                        try:
                            with open(args.scale_progress) as f:
                                step = int(f.read() or 0)
                        except (OSError, ValueError):
                            step = 0
                        if step >= want:
                            break
                        time.sleep(0.05)
                try:
                    _do_scale_event(ev, idx)
                except Exception as e:   # a drill bug must not wedge
                    print("scale: event %r failed: %s" % (ev, e),
                          flush=True)
        finally:
            scale_done.set()

    if args.scale:
        events = _parse_scale(args.scale)
        if any("at_step" in e for e in events) \
                and not args.scale_progress:
            raise SystemExit("--scale with at_step= triggers needs "
                             "--scale-progress FILE")
        threading.Thread(target=_scale_controller, args=(events,),
                         daemon=True).start()
    else:
        scale_done.set()

    # -- the mailbox pump: applies controller-submitted actions through
    # the executor (each at most once) and writes their verdict files
    stop_exec = threading.Event()
    if args.autoscale:
        def _exec_loop():
            while not stop_exec.wait(0.2):
                try:
                    executor.poll()
                except Exception as e:   # an actuator bug must not
                    #                      kill the pump
                    print("autoscale: executor error: %s" % e,
                          flush=True)
        threading.Thread(target=_exec_loop, daemon=True).start()

    # -- the --rollout drill: canary/promote/abort/rollback events on a
    # wall-clock or progress schedule, driven through the serving admin
    # wire (python -m mxtpu.serving --admin rollout). The scriptable
    # form of the continuous-deployment story: a canary split under
    # real traffic, a verdict, a bit-exact rollback — all while the
    # fleet keeps answering (docs/serving.md "Rollout & weight
    # streaming").
    rollout_done = threading.Event()

    def _do_rollout_event(ev):
        cmd = [sys.executable, "-m", "mxtpu.serving",
               "--admin", "rollout", "--addrs", ",".join(serve_addrs),
               "--action", ev["action"]]
        if ev.get("version"):
            cmd += ["--version", ev["version"]]
        if ev.get("fraction"):
            cmd += ["--fraction", ev["fraction"]]
        if ev.get("model"):
            cmd += ["--model", ev["model"]]
        admin_env = dict(base_env)
        admin_env.pop("DMLC_ROLE", None)
        admin_env["JAX_PLATFORMS"] = "cpu"
        print("rollout: %s" % " ".join(cmd[3:]), flush=True)
        r = subprocess.run(cmd, env=admin_env, capture_output=True,
                           text=True)
        print("rollout: %s -> %s"
              % (ev["action"],
                 (r.stdout.strip() or r.stderr.strip())[-500:]),
              flush=True)

    def _rollout_controller(events):
        t0 = time.time()
        try:
            for ev in events:
                if "after" in ev:
                    deadline = t0 + float(ev["after"])
                    while time.time() < deadline:
                        if stop_scale.is_set():
                            return
                        time.sleep(0.05)
                else:
                    want = int(ev["at_step"])
                    while True:
                        if stop_scale.is_set():
                            return
                        try:
                            with open(args.scale_progress) as f:
                                step = int(f.read() or 0)
                        except (OSError, ValueError):
                            step = 0
                        if step >= want:
                            break
                        time.sleep(0.05)
                try:
                    _do_rollout_event(ev)
                except Exception as e:   # a drill bug must not wedge
                    print("rollout: event %r failed: %s" % (ev, e),
                          flush=True)
        finally:
            rollout_done.set()

    if args.rollout:
        if not serve_addrs:
            raise SystemExit("--rollout needs --serve N")
        events = _parse_rollout(args.rollout)
        if any("at_step" in e for e in events) \
                and not args.scale_progress:
            raise SystemExit("--rollout with at_step= triggers needs "
                             "--scale-progress FILE")
        threading.Thread(target=_rollout_controller, args=(events,),
                         daemon=True).start()
    else:
        rollout_done.set()
    try:
        # respawn passes run BEFORE the liveness check: a fleet whose
        # last worker just got kill -9'd must be revived, not reaped
        # (with -n 1 the old any-alive loop condition would exit first)
        while True:
            if args.worker_respawn:
                for i, wp in enumerate(list(procs)):
                    rc = wp.poll()
                    if rc is None or rc == 0 or i in removed:
                        continue   # alive, finished cleanly, or departed
                    if worker_respawns[i] >= args.worker_max_respawns:
                        continue   # budget spent: the exit code stands
                    worker_respawns[i] += 1
                    print("worker %d died (exit %d); respawning "
                          "(%d/%d)" % (i, rc, worker_respawns[i],
                                       args.worker_max_respawns),
                          flush=True)
                    procs[i] = subprocess.Popen(
                        command, shell=True, env=worker_envs[i])
            if args.ps_respawn or args.serve_respawn or args.autoscale:
                for i, sp in enumerate(server_procs):
                    rc = sp.poll()
                    if rc is None or rc == 0:
                        continue   # alive, or clean 'stop' exit
                    name, port, role, peer = server_slots[i]
                    if role == "telemetry":
                        continue   # observability is passive: a dead
                        #            aggregator is a gap, not a respawn
                    if role == "controller":
                        # --autoscale implies the controller must live:
                        # the revived process re-takes the lease (epoch
                        # bump fences any straggler) and replays its
                        # journal — kill -9 mid-action is the drill
                        if not args.autoscale or respawns[i] >= 5:
                            continue
                        respawns[i] += 1
                        print("autoscale controller died (exit %s); "
                              "respawning (%d/5)" % (rc, respawns[i]),
                              flush=True)
                        server_procs[i] = _spawn_controller(
                            respawn=True)
                        continue
                    if i in drained_slots:
                        continue   # departed on purpose, stays down
                    if role != "serving" and (
                            not args.ps_respawn
                            or respawns[i] >= args.ps_max_respawns):
                        continue   # workers' retry layer surfaces it
                    if role == "serving":
                        # without --serve-respawn a crashed serving
                        # replica is the failover drill's subject:
                        # clients re-route to the survivors. WITH it,
                        # the rejoin is well-defined now that weights
                        # are versioned: the revived process re-binds
                        # its port, catches up to the current weight
                        # version BEFORE admitting, and re-hellos.
                        if not args.serve_respawn or \
                                respawns[i] >= args.serve_max_respawns:
                            continue
                        respawns[i] += 1
                        print("serve replica %s died (exit %d); "
                              "respawning on port %d (%d/%d)"
                              % (name, rc, port, respawns[i],
                                 args.serve_max_respawns), flush=True)
                        server_procs[i] = _spawn_serving_replica(
                            peer, port, serve_addrs, base_env, args)
                        continue
                    respawns[i] += 1
                    print("server %s died (exit %d); respawning on port "
                          "%d (%d/%d)" % (name, rc, port, respawns[i],
                                          args.ps_max_respawns),
                          flush=True)
                    # env role is only the opening bid: the respawned
                    # process probes its peer at boot and, if the peer
                    # was promoted meanwhile, rejoins as the new backup
                    server_procs[i] = _spawn_server(
                        name, port, base_env, args, role=role,
                        peer=peer)
            if all(p.poll() is not None for p in procs):
                if not scale_done.is_set() or not rollout_done.is_set():
                    # workers drained before a drill finished: stop
                    # the controllers (bounded) rather than hanging on
                    # a progress file nobody writes anymore
                    stop_scale.set()
                    scale_done.wait(timeout=10)
                    rollout_done.wait(timeout=10)
                if all(p.poll() is not None for p in procs):
                    break
            time.sleep(0.2)
        for i, p in enumerate(procs):
            if i in removed:
                continue   # a drill departure is a clean exit
            code = code or p.returncode
    except KeyboardInterrupt:
        _reap(procs)
        code = 1
    finally:
        stop_exec.set()
        # servers ignore nothing a worker still needs by now: reap with
        # TERM->KILL escalation so a hung server cannot zombie-leak or
        # wedge the launcher's exit
        _reap(server_procs)
    return code


def launch_ssh(args, command):
    hosts = [h.strip() for h in open(args.hostfile) if h.strip()]
    coordinator = "%s:%d" % (hosts[0], args.port)
    procs = []
    for rank in range(args.num_workers):
        host = hosts[rank % len(hosts)]
        envs = ("MXTPU_COORDINATOR=%s MXTPU_NUM_PROCS=%d MXTPU_PROC_ID=%d "
                "DMLC_ROLE=worker DMLC_NUM_WORKER=%d DMLC_NUM_SERVER=%d "
                "DMLC_WORKER_ID=%d"
                % (coordinator, args.num_workers, rank, args.num_workers,
                   args.num_servers, rank))
        remote = "ssh -o StrictHostKeyChecking=no %s 'cd %s && %s %s'" % (
            host, os.getcwd(), envs, command)
        print(remote)
        if not args.dry_run:
            procs.append(subprocess.Popen(remote, shell=True))
    code = 0
    try:
        for p in procs:
            # remote jobs run arbitrarily long; ^C is the operator's
            # abort and is handled below with a bounded reap
            p.wait()   # mxlint: allow(blocking-call) — foreground wait on remote jobs; ^C aborts
            code = code or p.returncode
    except KeyboardInterrupt:
        _reap(procs)
        code = 1
    return code


def _env_exports(args, coordinator_host, rank_expr, sep="; "):
    """The single source of the MXTPU_*/DMLC_* worker env contract; each
    cluster launcher supplies only its scheduler's rank expression."""
    return sep.join([
        "export MXTPU_COORDINATOR=%s:%d MXTPU_NUM_PROCS=%d"
        % (coordinator_host, args.port, args.num_workers),
        "export MXTPU_PROC_ID=%s" % rank_expr,
        "export DMLC_ROLE=worker DMLC_NUM_WORKER=%d DMLC_NUM_SERVER=%d "
        "DMLC_WORKER_ID=$MXTPU_PROC_ID" % (args.num_workers,
                                           args.num_servers),
    ])


def _coordinator_host(args, scheduler):
    """Rank 0's host. mpi derives it from the hostfile when given; the
    scheduler modes (slurm/sge) allocate nodes at submit time, so a
    reachable --coordinator-host must be provided for multi-node jobs."""
    if scheduler == "mpi" and args.hostfile:
        with open(args.hostfile) as f:
            for line in f:
                host = line.split()[0] if line.strip() else ""
                if host:
                    return host
    return args.coordinator_host


def launch_mpi(args, command):
    """mpirun dispatch (reference dmlc-tracker/mpi.py): one rank per
    worker; each rank derives its identity from OMPI/PMI env vars via the
    wrapper below, so the same worker script runs under every launcher."""
    wrapper = "%s; %s" % (
        _env_exports(args, _coordinator_host(args, "mpi"),
                     "${OMPI_COMM_WORLD_RANK:-${PMI_RANK:-0}}"), command)
    cmd = ["mpirun", "-np", str(args.num_workers)]
    if args.hostfile:
        cmd += ["--hostfile", args.hostfile]
    cmd += ["bash", "-c", wrapper]
    print(" ".join("'%s'" % c if " " in c else c for c in cmd))
    if args.dry_run:
        return 0
    return subprocess.call(cmd)


def launch_slurm(args, command):
    """srun dispatch (the modern cluster-scheduler analogue of the
    reference's sge/yarn trackers): SLURM_PROCID provides the rank.
    Multi-node jobs must pass --coordinator-host (a node reachable by all
    ranks) since nodes are allocated by the scheduler at submit time."""
    wrapper = "%s; %s" % (
        _env_exports(args, _coordinator_host(args, "slurm"),
                     "$SLURM_PROCID"), command)
    cmd = ["srun", "--ntasks=%d" % args.num_workers, "bash", "-c", wrapper]
    print(" ".join("'%s'" % c if " " in c else c for c in cmd))
    if args.dry_run:
        return 0
    return subprocess.call(cmd)


def launch_sge(args, command):
    """SGE array-job dispatch (reference dmlc-tracker/sge.py): submits a
    task-array of size N; SGE_TASK_ID (1-based) provides the rank.
    Multi-node jobs must pass --coordinator-host (see launch_slurm)."""
    script = "#!/bin/bash\n#$ -t 1-%d\n#$ -cwd\n#$ -S /bin/bash\n%s\n%s\n" % (
        args.num_workers,
        _env_exports(args, _coordinator_host(args, "sge"),
                     "$((SGE_TASK_ID - 1))", sep="\n"),
        command)
    print(script)
    if args.dry_run:
        return 0
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".sh",
                                     delete=False) as f:
        f.write(script)
        path = f.name
    return subprocess.call(["qsub", "-sync", "y", path])


# Kubernetes / GKE (the modern yarn analogue): no dispatch code needed —
# run the worker as an indexed Job / JobSet with
#   MXTPU_COORDINATOR=<job>-0.<headless-svc>:9327
#   MXTPU_NUM_PROCS=<parallelism>
#   MXTPU_PROC_ID=$JOB_COMPLETION_INDEX
# which is exactly the env contract every launcher above emits. On Cloud
# TPU pods, jax.distributed.initialize() with no args uses the TPU
# metadata server instead and none of this is required.


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-n", "--num-workers", type=int, required=True)
    p.add_argument("-s", "--num-servers", type=int, default=0,
                   help="async parameter-server processes for "
                        "create('dist_async'); sync mode needs none "
                        "(SPMD collectives instead)")
    p.add_argument("--ps-replicas", type=int,
                   default=int(os.environ.get("MXTPU_PS_REPLICAS",
                                              "1")),
                   help="2 pairs every parameter-server shard with a "
                        "hot backup: applied updates replicate over "
                        "the primary's stream, clients fail over in "
                        "place on a primary death, and a respawned "
                        "server rejoins as the new backup "
                        "(docs/fault_tolerance.md, 'Replication & "
                        "failover')")
    p.add_argument("--ps-repl-mode", choices=("sync", "async"),
                   default=os.environ.get("MXTPU_PS_REPL_MODE",
                                          "sync"),
                   help="sync (default): a push is acked only after "
                        "the backup acked the forwarded update — zero "
                        "acknowledged-update loss on a primary kill; "
                        "async: ack immediately, replication lag "
                        "bounded by MXTPU_PS_REPL_LAG_MAX")
    p.add_argument("--ps-respawn", action="store_true",
                   help="local launcher: respawn a crashed parameter "
                        "server on its original port; with snapshots it "
                        "restores its table and workers reconverge")
    p.add_argument("--ps-max-respawns", type=int, default=3,
                   help="respawn budget per server before its death is "
                        "left to the workers' retry layer")
    p.add_argument("--ps-snapshot-dir", default=None,
                   help="base dir for per-server state snapshots "
                        "(server i uses <dir>/server_i); auto-created "
                        "under $TMPDIR when --ps-respawn is on")
    p.add_argument("--ps-snapshot-every", type=int, default=100,
                   help="pushes between server snapshots")
    p.add_argument("--worker-respawn", action="store_true",
                   help="local launcher: respawn a worker that exits "
                        "non-zero (kill -9 included); with a state dir "
                        "the fresh process restores its checkpoint, "
                        "re-registers with the servers and fast-forwards "
                        "its data iterator (mxtpu.resilience.TrainGuard)")
    p.add_argument("--worker-max-respawns", type=int, default=3,
                   help="respawn budget per worker before its death "
                        "is final")
    p.add_argument("--worker-state-dir", default=None,
                   help="base dir for per-worker checkpoints (rank r "
                        "uses <dir>/worker_r, exported as "
                        "MXTPU_WORKER_STATE_DIR); auto-created under "
                        "$TMPDIR when --worker-respawn is on")
    p.add_argument("--scale", default=None,
                   help="local launcher elasticity drill: ';'-separated "
                        "events of 'after=SECS|at_step=N,action="
                        "add_worker|remove_worker|split_shard"
                        "[,rank=R][,src=I]' — add_worker spawns a "
                        "joining worker (MXTPU_ELASTIC_JOINER=1), "
                        "remove_worker SIGTERMs one (clean departure), "
                        "split_shard spawns a fresh server (pair, with "
                        "--ps-replicas 2) and splits server slot I's "
                        "keys onto it online (docs/fault_tolerance.md "
                        "'Elasticity')")
    p.add_argument("--autoscale", action="store_true",
                   help="local launcher: close the loop — spawn the "
                        "autoscaling controller child (python -m "
                        "mxtpu.fleet.controller), which reads the "
                        "telemetry plane's fleet.json and submits "
                        "add/remove-worker, split-shard and add/drain-"
                        "replica actions into the action mailbox; THIS "
                        "launcher executes them idempotently and "
                        "respawns a crashed controller (journal "
                        "replay). Implies --telemetry. Policy knobs "
                        "ride MXTPU_AUTOSCALE_* env vars "
                        "(docs/autoscaling.md)")
    p.add_argument("--autoscale-dir", default=None,
                   help="action mailbox / journal / lease dir (default "
                        "<telemetry-dir>/autoscale); exported as "
                        "MXTPU_AUTOSCALE_DIR")
    p.add_argument("--autoscale-fault", default=None,
                   help="MXTPU_FAULT_SPEC for the controller child "
                        "ONLY (e.g. 'point=ctl.action,kind=kill_worker"
                        ",nth=1' for the kill-mid-action drill); "
                        "dropped on respawn so the drill is one-shot")
    p.add_argument("--serve-max", type=int, default=0,
                   help="reserve serving ports up to this count so the "
                        "autoscale controller can add replicas beyond "
                        "--serve N; the FULL slot set is advertised in "
                        "MXTPU_SERVE_ADDRS from the start (default: "
                        "no headroom)")
    p.add_argument("--serve", type=int, default=0,
                   help="local launcher: start N model-serving replicas "
                        "(python -m mxtpu.serving) and export "
                        "MXTPU_SERVE_ADDRS to the workers; replicas "
                        "drain gracefully on SIGTERM (the _reap "
                        "escalation's TERM phase) and a kill -9'd "
                        "replica is the client-failover drill "
                        "(docs/serving.md)")
    p.add_argument("--serve-model", default=None,
                   help="checkpoint prefix the replicas load "
                        "(prefix-symbol.json + prefix-%%04d.params)")
    p.add_argument("--serve-epoch", type=int, default=0,
                   help="checkpoint epoch for --serve-model")
    p.add_argument("--serve-data-shapes", default=None,
                   help="per-sample input shapes for the served model, "
                        "'name=dims[;name=dims]' (e.g. data=3,32,32)")
    p.add_argument("--serve-buckets", default=None,
                   help="batch buckets the replicas AOT-compile "
                        "(default 1,2,4,8,16,32)")
    p.add_argument("--serve-respawn", action="store_true",
                   help="local launcher: respawn a kill -9'd serving "
                        "replica on its original port — the fresh "
                        "process catches up to the CURRENT weight "
                        "version before admitting, then re-hellos "
                        "(docs/serving.md 'Rollout & weight "
                        "streaming')")
    p.add_argument("--serve-max-respawns", type=int, default=3,
                   help="respawn budget per serving replica before "
                        "its death is left to client failover")
    p.add_argument("--serve-weight-dir", default=None,
                   help="versioned weight-snapshot dir the replicas "
                        "follow (WeightPublisher's output; exported "
                        "as MXTPU_SERVE_WEIGHT_DIR) — also the "
                        "rollback restore source")
    p.add_argument("--serve-weight-kv", default=None,
                   help="comma list of parameter-server addresses the "
                        "replicas follow via the 'weights' long-poll "
                        "stream (exported as MXTPU_SERVE_WEIGHT_KV)")
    p.add_argument("--serve-weight-poll", type=float, default=None,
                   help="weight-sync tick seconds (exported as "
                        "MXTPU_SERVE_WEIGHT_POLL; default 0.5)")
    p.add_argument("--rollout", default=None,
                   help="serving rollout drill: ';'-separated events "
                        "of 'after=SECS|at_step=N,action=canary|"
                        "promote|abort|rollback|pin|unpin|status"
                        "[,version=V][,fraction=F][,model=M]' driven "
                        "through the serving admin wire (python -m "
                        "mxtpu.serving --admin rollout); at_step= "
                        "reads --scale-progress")
    p.add_argument("--scale-progress", default=None,
                   help="progress file written by the training script; "
                        "at_step= scale triggers fire when its integer "
                        "content reaches N")
    p.add_argument("--telemetry", action="store_true",
                   help="local launcher: export MXTPU_TELEMETRY to "
                        "every child (workers start metrics "
                        "exporters) and spawn ONE aggregator that "
                        "polls the fleet's `metrics` ops into "
                        "<telemetry-dir>/fleet.json + history; render "
                        "it live with tools/mxtop.py "
                        "(docs/observability.md)")
    p.add_argument("--telemetry-dir", default=None,
                   help="telemetry rendezvous dir (endpoint files + "
                        "fleet.json); auto-created under $TMPDIR when "
                        "--telemetry is on")
    p.add_argument("--launcher",
                   choices=("local", "ssh", "mpi", "slurm", "sge"),
                   default="local")
    p.add_argument("-H", "--hostfile", default=None)
    p.add_argument("--port", type=int, default=9327)
    p.add_argument("--coordinator-host", default="127.0.0.1",
                   help="host of rank 0 for mpi/slurm/sge modes")
    p.add_argument("--dry-run", action="store_true")
    p.add_argument("command", nargs="+")
    args = p.parse_args()
    command = " ".join(args.command)
    if args.launcher == "local":
        sys.exit(launch_local(args, command))
    if args.launcher == "mpi":
        sys.exit(launch_mpi(args, command))
    if args.launcher == "slurm":
        sys.exit(launch_slurm(args, command))
    if args.launcher == "sge":
        sys.exit(launch_sge(args, command))
    if not args.hostfile:
        sys.exit("ssh launcher requires --hostfile")
    sys.exit(launch_ssh(args, command))


if __name__ == "__main__":
    main()
