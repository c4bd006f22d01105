#!/usr/bin/env python
"""Fast-tier autoscaling smoke (ISSUE 16): the closed loop from
telemetry to actuation, end to end on this host.

  1. **Capacity follows load, both directions**: a scripted diurnal
     window drives the pure policy core — daytime pressure adds a
     replica AND a worker, nighttime idle drains/removes them, bounds
     are never violated, and a non-advancing sweep sequence HOLDS
     (never a panic scale-down).
  2. **Controller kill -9 mid-action**: a real ``python -m
     mxtpu.fleet.controller`` process is SIGKILLed by the
     ``ctl.action`` fault point after journaling an intent and before
     any verdict; a restarted controller (fault spec dropped) replays
     the journal under the ORIGINAL id and the executor's dedupe makes
     the replay exactly-once — the handler runs ONCE across both
     incarnations.
  3. **Zero acknowledged loss across a controller-driven action**: an
     in-process controller sees a hot single shard and issues
     ``split_shard``; the handler splits a REAL parameter server
     online while a worker keeps pushing — every acknowledged push
     lands exactly once (clock arithmetic stays exact) and moved keys
     reroute via ``map_stale``.
  4. **Prewarmed cold start**: a joiner importing the exported AOT
     program menu reaches serving-ready with ZERO compiles in at most
     ``PREWARM_PIN`` of the cold-compile baseline — the CI-pinned
     number behind ``--autoscale`` add-replica admission.

Run: ``JAX_PLATFORMS=cpu python ci/check_autoscale.py`` (wired into
``ci/run_ci.sh fast``). Exit 0 = contract holds.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# phase 4 compares a cold compile with a prewarmed start: give this run a
# compile cache of its own so an earlier run's entries never warm "cold"
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="mxtpu_autoscale_ci_cache_")
os.environ["MXTPU_PS_HEARTBEAT"] = "0"
os.environ["MXTPU_PS_LOCAL"] = "0"       # the drill is about the wire
os.environ["MXTPU_PS_RETRIES"] = "2"
os.environ["MXTPU_PS_BACKOFF"] = "0.01"
os.environ["MXTPU_PS_RECONNECT"] = "0.5"
os.environ["MXTPU_PS_ELASTIC"] = "1"

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

from mxtpu.fleet.actuator import ActionExecutor       # noqa: E402
from mxtpu.fleet.journal import ActionJournal         # noqa: E402
from mxtpu.fleet.policy import (                      # noqa: E402
    PolicyConfig, PolicyState, decide)

PREWARM_PIN = 0.7          # prewarmed time-to-ready / cold compile


def fail(msg):
    print("autoscale check FAILED: %s" % msg)
    return 1


# -- phase 1: the policy follows a diurnal load, both directions --------

def _frame(seq, t, n_work, n_rep, step_s, queue, req_s):
    return {
        "seq": seq, "time": t,
        "workers": {"w%d" % i: {"age": 0, "pid": 1000 + i,
                                "step_s": step_s}
                    for i in range(n_work)},
        "replicas": {"r%d" % i: {"age": 0, "queue": queue if i == 0
                                 else 0, "req_s": req_s,
                                 "resp_s": req_s, "p99": 5.0}
                     for i in range(n_rep)},
        "shards": {"s0": {"age": 0, "push_s": 5.0, "keys": 6,
                          "shard_role": "primary", "stragglers": []}},
        "controllers": {}, "gaps": {},
    }


def phase_policy():
    cfg = PolicyConfig(min_workers=1, max_workers=3,
                       min_replicas=1, max_replicas=3,
                       target_steps_s=30.0, band=0.25,
                       up_queue=8.0, down_queue=1.0,
                       up_rps=50.0, down_rps=5.0,
                       cooldown_s=0.0, rate_max=2, rate_window_s=1.0,
                       confirm_ticks=2, window=8)
    state = PolicyState()
    n_work, n_rep = 1, 1
    window, issued, caps = [], [], []
    for t in range(30):
        day = t < 15
        step_s = 12.0 if day else 25.0   # per-worker throughput
        queue = (12.0 if n_rep == 1 else 2.0) if day else 0.0
        req_s = 20.0 if day else 1.0
        window.append(_frame(t + 1, float(t), n_work, n_rep,
                             step_s, queue, req_s))
        del window[:-cfg.window]
        actions, state = decide(list(window), state, cfg, float(t))
        for a in actions:
            issued.append(a["action"])
            if a["action"] == "add_worker":
                n_work += 1
            elif a["action"] == "remove_worker":
                n_work -= 1
            elif a["action"] == "add_replica":
                n_rep += 1
            elif a["action"] == "drain_replica":
                n_rep -= 1
        if not (cfg.min_workers <= n_work <= cfg.max_workers):
            return fail("worker bounds violated at t=%d: %d"
                        % (t, n_work))
        if not (cfg.min_replicas <= n_rep <= cfg.max_replicas):
            return fail("replica bounds violated at t=%d: %d"
                        % (t, n_rep))
        caps.append((n_work, n_rep))
    for kind in ("add_worker", "add_replica", "remove_worker",
                 "drain_replica"):
        if kind not in issued:
            return fail("diurnal window never issued %s (issued=%r)"
                        % (kind, issued))
    if max(c[0] for c in caps) < 2 or max(c[1] for c in caps) < 2:
        return fail("capacity never followed the daytime load up: %r"
                    % (caps,))
    if caps[-1] != (1, 1):
        return fail("capacity never followed the nighttime load back "
                    "down: %r" % (caps[-1],))
    # a non-advancing sweep seq (aggregator slow) must HOLD, not act
    stale = _frame(window[-1]["seq"], 30.0, n_work, n_rep,
                   0.0, 100.0, 100.0)     # screaming pressure, old seq
    holds_before = state.holds
    actions, state = decide(window + [stale], state, cfg, 30.0)
    if actions or state.holds != holds_before + 1:
        return fail("stale sweep seq did not hold-last-decision "
                    "(actions=%r)" % (actions,))
    print("autoscale phase 1 OK — capacity %r followed the diurnal "
          "window (issued %r), stale telemetry held" % (caps[-1], issued))
    return 0


# -- phase 2: controller killed -9 mid-action, journal replay -----------

def _pressure_doc(seq, queue):
    return {"seq": seq, "time": float(seq),
            "fleet": {"127.0.0.1:9500": {
                "role": "serving", "age_sweeps": 0,
                "metrics": {"serve.batch.queued": {
                    "kind": "gauge", "series": {"()": queue}}}}},
            "history": []}


def phase_kill_replay():
    adir = tempfile.mkdtemp(prefix="mxtpu_autoscale_ci_")
    fleet = os.path.join(adir, "fleet.json")
    stop = threading.Event()
    pressure = {"on": True}

    def feed():
        seq = 0
        while not stop.is_set():
            seq += 1
            tmp = fleet + ".tmp"
            with open(tmp, "w") as f:
                json.dump(_pressure_doc(
                    seq, 20.0 if pressure["on"] else 0.0), f)
            os.replace(tmp, fleet)
            time.sleep(0.05)

    feeder = threading.Thread(target=feed, daemon=True)
    feeder.start()

    applied = {"n": 0}
    executor = ActionExecutor(adir, {
        "add_replica": lambda a: applied.__setitem__(
            "n", applied["n"] + 1) or {"addr": "ci"}})

    def pump():
        while not stop.is_set():
            try:
                executor.poll()
            except OSError:
                pass
            time.sleep(0.05)

    pumper = threading.Thread(target=pump, daemon=True)
    pumper.start()

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "MXTPU_AUTOSCALE_CONFIRM_TICKS": "1",
        "MXTPU_AUTOSCALE_COOLDOWN_S": "0",
        "MXTPU_AUTOSCALE_ACTION_TIMEOUT": "2",
        "MXTPU_AUTOSCALE_ACTION_RETRIES": "0",
        "MXTPU_AUTOSCALE_LEASE_TTL": "1",
        # the drill: SIGKILL the controller at its first actuation —
        # after the journaled intent, before any verdict
        "MXTPU_FAULT_SPEC": "point=ctl.action,kind=kill_worker,nth=1",
    })
    cmd = [sys.executable, "-m", "mxtpu.fleet.controller",
           "--dir", adir, "--fleet", fleet,
           "--interval", "0.05", "--ticks", "200"]
    try:
        p1 = subprocess.Popen(cmd, env=env, cwd=ROOT)
        p1.wait(timeout=120)
        if p1.returncode != -signal.SIGKILL:
            return fail("controller #1 was not SIGKILLed mid-action "
                        "(rc=%r)" % (p1.returncode,))
        journal = ActionJournal(os.path.join(adir, "journal.jsonl"))
        pending = journal.replay()
        if len(pending) != 1 or \
                pending[0][1].get("action") != "add_replica":
            return fail("journal after kill -9 should hold exactly the "
                        "in-flight intent: %r" % (pending,))
        if applied["n"] != 0:
            return fail("the killed attempt must not have applied "
                        "(applied=%d)" % applied["n"])
        aid = pending[0][0]
        pressure["on"] = False   # idle docs: the restart only replays
        env.pop("MXTPU_FAULT_SPEC")   # one-shot drill, like launch.py
        p2 = subprocess.Popen(cmd, env=env, cwd=ROOT)
        p2.wait(timeout=120)
        if p2.returncode != 0:
            return fail("restarted controller exited rc=%r"
                        % (p2.returncode,))
    finally:
        stop.set()
        feeder.join(timeout=5)
        pumper.join(timeout=5)
    if applied["n"] != 1:
        return fail("replay was not exactly-once: handler ran %d "
                    "time(s)" % applied["n"])
    journal = ActionJournal(os.path.join(adir, "journal.jsonl"))
    if journal.replay():
        return fail("journal still pending after replay: %r"
                    % (journal.replay(),))
    with open(os.path.join(adir, "verdicts", aid + ".json")) as f:
        verdict = json.load(f)
    if verdict.get("verdict") != "ok":
        return fail("replayed action verdict %r != ok" % (verdict,))
    print("autoscale phase 2 OK — controller killed -9 mid-action, "
          "restart replayed %s exactly-once (applied=1, verdict=ok)"
          % aid)
    return 0


# -- phase 3: zero acked loss across a controller-driven split ----------

def phase_split_no_loss():
    import mxtpu as mx
    from mxtpu import kvstore_async as ka
    from mxtpu.fleet.controller import Controller

    s0 = ka.ParameterServer().start()
    os.environ["MXTPU_PS_ADDRS"] = s0.address
    os.environ["MXTPU_PROC_ID"] = "0"
    os.environ["MXTPU_NUM_PROCS"] = "1"
    kv = mx.kv.create("dist_async")
    keys = ["w%d" % i for i in range(6)]
    kv.init(keys, [mx.nd.zeros((4,)) for _ in keys])

    counted = {k: 0 for k in keys}
    rounds = {"n": 0}
    stop = threading.Event()

    def pusher():
        while not stop.is_set():
            for k in keys:
                kv.push(k, mx.nd.ones((4,)))
                counted[k] += 1
            rounds["n"] += 1

    servers = {"new": None}

    def split_handler(action):
        s2 = ka.ParameterServer().start()
        servers["new"] = s2
        conn = ka._ServerConn(s0.address)
        reply = conn.request("split", s2.address)
        conn.close()
        return {"src": s0.address, "dst": s2.address,
                "moved": len(reply[1]["moved"])}

    adir = tempfile.mkdtemp(prefix="mxtpu_autoscale_split_")
    executor = ActionExecutor(adir, {"split_shard": split_handler})

    def hot_doc(seq):
        # one hot shard: push_s from the history counter deltas,
        # single-shard rule makes it definitionally hot
        return {"seq": seq, "time": float(seq),
                "fleet": {s0.address: {
                    "role": "server", "age_sweeps": 0,
                    "views": {"kv.server": {
                        "keys": len(keys), "role": "primary",
                        "stragglers": []}}}},
                "history": [
                    {"time": float(seq) - 1.0,
                     "counters": {s0.address: {"pushes": 0}}},
                    {"time": float(seq),
                     "counters": {s0.address: {"pushes": 100}}}]}

    docs = iter(hot_doc(i + 1) for i in range(100))
    ctl = Controller(
        fleet_path=None, directory=adir,
        cfg=PolicyConfig(confirm_ticks=1, cooldown_s=0.0,
                         split_min_push_s=10.0, max_shards=2,
                         target_steps_s=0.0),
        poll_fn=lambda: next(docs),
        sleep=lambda s: (executor.poll(), time.sleep(0.01))[1],
        interval=0.01, action_timeout=30.0, action_retries=0)

    t = threading.Thread(target=pusher, daemon=True)
    t.start()
    deadline = time.monotonic() + 30
    while rounds["n"] < 5:             # the split lands under real load
        if time.monotonic() > deadline:
            stop.set()
            return fail("pusher never got going")
        time.sleep(0.01)
    actions = []
    for _ in range(20):
        actions = ctl.tick()
        if actions:
            break
    if not actions or actions[0]["action"] != "split_shard" \
            or actions[0].get("src_addr") != s0.address:
        stop.set()
        return fail("controller never issued the hot-shard split: %r"
                    % (actions,))
    if executor.applied != 1:
        stop.set()
        return fail("split handler applied %d time(s)"
                    % executor.applied)
    settled = rounds["n"] + 5          # keep pushing PAST the split
    deadline = time.monotonic() + 30
    while rounds["n"] < settled:
        if time.monotonic() > deadline:
            stop.set()
            return fail("pusher wedged after the split")
        time.sleep(0.01)
    stop.set()
    t.join(timeout=30)
    if t.is_alive():
        return fail("pusher never stopped")
    clocks = kv.staleness_stats()["clocks"]
    bad = {k: (clocks.get(k), counted[k]) for k in keys
           if clocks.get(k) != counted[k]}
    if bad:
        return fail("acked updates lost or double-applied across the "
                    "controller-driven split: %r" % (bad,))
    reroutes = kv.stats()["map_reroutes"]
    if reroutes < 1:
        return fail("no push ever rerouted onto the split target")
    total = sum(counted.values())
    kv.close()
    s0.stop()
    if servers["new"] is not None:
        servers["new"].stop()
    print("autoscale phase 3 OK — %d acked pushes across a "
          "controller-driven online split, zero loss, %d reroute(s)"
          % (total, reroutes))
    return 0


# -- phase 4: prewarmed cold start ≤ pinned fraction of cold compile ----

def phase_prewarm():
    import mxtpu as mx
    from mxtpu.serving import InferenceEngine

    IN_DIM, CLASSES, BUCKETS = 12, 4, (4, 8)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (8, IN_DIM))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Uniform(0.1))
    arg_params, aux_params = mod.get_params()

    def mkeng():
        return InferenceEngine(net, arg_params, aux_params,
                               {"data": (IN_DIM,)}, buckets=BUCKETS,
                               warm=False)

    cold_eng = mkeng()
    t0 = time.perf_counter()
    cold_eng.warm()
    cold = time.perf_counter() - t0
    path = os.path.join(tempfile.mkdtemp(prefix="mxtpu_prewarm_ci_"),
                        "menu.programs")
    if cold_eng.export_programs(path) != len(BUCKETS):
        return fail("export did not cover the bucket menu")

    joiner = mkeng()
    t0 = time.perf_counter()
    imported = joiner.prewarm_from(path)
    joiner.warm()                      # only builds what is missing
    warm = time.perf_counter() - t0
    st = joiner.stats()
    if imported != len(BUCKETS):
        return fail("prewarm imported %d/%d buckets"
                    % (imported, len(BUCKETS)))
    if st["compiles"] != 0:
        return fail("prewarmed joiner still compiled %d program(s)"
                    % st["compiles"])
    if warm > PREWARM_PIN * cold:
        return fail("prewarmed start %.3fs exceeds the pin "
                    "(%.2f x cold %.3fs = %.3fs)"
                    % (warm, PREWARM_PIN, cold, PREWARM_PIN * cold))
    print("autoscale phase 4 OK — prewarmed time-to-ready %.3fs vs "
          "cold compile %.3fs (ratio %.2f <= %.2f, imported=%d, "
          "compiles=0)" % (warm, cold, warm / cold, PREWARM_PIN,
                           imported))
    return 0


def main():
    for ph in (phase_policy, phase_kill_replay, phase_split_no_loss,
               phase_prewarm):
        rc = ph()
        if rc:
            return rc
    print("autoscale check OK — policy tracked the diurnal window both "
          "directions, kill -9 replay was exactly-once, the online "
          "split lost nothing, and the prewarmed joiner skipped its "
          "cold compile")
    return 0


if __name__ == "__main__":
    sys.exit(main())
