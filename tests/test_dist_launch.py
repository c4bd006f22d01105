"""Multi-process distributed training via the local launcher (reference
tests/nightly/dist_sync_kvstore.py run through tools/launch.py -n 2
--launcher local: fork worker processes on one host, real cross-process
collectives over jax.distributed)."""
import os
import subprocess
import sys

import pytest


# slow: several processes (every test forks workers, servers or replicas
# through tools/launch.py)
pytestmark = pytest.mark.slow


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.parametrize("nproc", [2, 4])
def test_local_launcher_dist_training(nproc):
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)            # one device per process
    env["JAX_PLATFORMS"] = "cpu"
    # own process group so a timeout can reap the launcher's worker
    # grandchildren too (Popen(shell=True) would otherwise orphan them)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", str(nproc), "--launcher", "local",
         "--port", str(_free_port()),
         sys.executable + " " + os.path.join(root, "tests", "nightly",
                                             "dist_worker.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        import signal
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.communicate()
        raise
    assert proc.returncode == 0, out[-2000:]
    for r in range(nproc):
        assert "RANK_%d_OK" % r in out, out[-2000:]


def test_local_launcher_dist_async_straggler(tmp_path):
    """dist_async through the launcher with real server processes
    (-s 2): fast workers outrun an injected straggler, observed
    staleness > 0, and stale-gradient SGD still converges
    (tests/nightly/async_worker.py asserts all three)."""
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["ASYNC_TEST_DIR"] = str(tmp_path)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "3", "-s", "2", "--launcher", "local",
         "--port", str(_free_port()),
         sys.executable + " " + os.path.join(root, "tests", "nightly",
                                             "async_worker.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        import signal
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.communicate()
        raise
    assert proc.returncode == 0, out[-2000:]
    for r in range(3):
        assert "RANK_%d_OK" % r in out, out[-2000:]
    import json
    with open(tmp_path / "summary.json") as f:
        summary = json.load(f)
    assert summary["staleness"]["staleness_max"] > 0
    assert summary["final_err"] < 0.15


def _run_resilient(tmp_path, tag, fault_spec):
    """One launcher run of tests/nightly/resilient_worker.py: 1 guarded
    worker + 1 parameter server, --worker-respawn armed, fault schedule
    from the env. Returns (launcher stdout, summary dict, params)."""
    import json
    import numpy as np
    root = os.path.join(os.path.dirname(__file__), "..")
    out_dir = tmp_path / ("out_" + tag)
    state_dir = tmp_path / ("state_" + tag)
    out_dir.mkdir()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)            # one device per process
    env["JAX_PLATFORMS"] = "cpu"
    env["RESILIENT_TEST_DIR"] = str(out_dir)
    env["RESILIENT_TOTAL_STEPS"] = "12"
    env["MXTPU_PS_BARRIER_TIMEOUT"] = "60"   # bounded even on a death
    if fault_spec:
        env["MXTPU_FAULT_SPEC"] = fault_spec
    else:
        env.pop("MXTPU_FAULT_SPEC", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "1", "-s", "1", "--launcher", "local",
         "--worker-respawn", "--worker-state-dir", str(state_dir),
         "--port", str(_free_port()),
         sys.executable + " " + os.path.join(root, "tests", "nightly",
                                             "resilient_worker.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        import signal
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.communicate()
        raise
    assert proc.returncode == 0, out[-3000:]
    assert "RANK_0_OK" in out, out[-3000:]
    with open(out_dir / "rank0.json") as f:
        summary = json.load(f)
    with np.load(out_dir / "rank0_params.npz") as z:
        params = {k: z[k] for k in z.files}
    return out, summary, params


def _run_replicated(tmp_path, tag, kill_at_step=None):
    """One launcher run of resilient_worker.py against a replicated
    parameter shard (-s 1 --ps-replicas 2, sync mode, --ps-respawn).
    With ``kill_at_step``, a REAL external ``kill -9`` lands on the
    primary server process as soon as the worker's progress file shows
    that step — mid-training, mid-push-stream, no injection harness.
    Returns (launcher stdout, summary dict, server-table dict)."""
    import json
    import re
    import signal
    import threading
    import time
    import numpy as np
    root = os.path.join(os.path.dirname(__file__), "..")
    out_dir = tmp_path / ("out_" + tag)
    state_dir = tmp_path / ("state_" + tag)
    progress = tmp_path / ("progress_" + tag)
    out_dir.mkdir()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["RESILIENT_TEST_DIR"] = str(out_dir)
    env["RESILIENT_TOTAL_STEPS"] = "12"
    env["RESILIENT_PROGRESS_FILE"] = str(progress)
    env["MXTPU_PS_BARRIER_TIMEOUT"] = "60"
    env.pop("MXTPU_FAULT_SPEC", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "1", "-s", "1", "--ps-replicas", "2",
         "--ps-repl-mode", "sync", "--ps-respawn",
         "--worker-state-dir", str(state_dir),
         "--port", str(_free_port()),
         sys.executable + " " + os.path.join(root, "tests", "nightly",
                                             "resilient_worker.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True)
    lines = []
    reader = threading.Thread(
        target=lambda: lines.extend(iter(proc.stdout.readline, "")),
        daemon=True)
    reader.start()
    try:
        if kill_at_step is not None:
            pid = None
            killed = False
            deadline = time.time() + 300
            while time.time() < deadline and proc.poll() is None:
                if pid is None:
                    for line in list(lines):
                        m = re.search(
                            r"ps server 0 role=primary pid=(\d+)", line)
                        if m:
                            pid = int(m.group(1))
                            break
                if pid is not None and progress.exists():
                    try:
                        step = int(progress.read_text() or 0)
                    except ValueError:
                        step = 0
                    if step >= kill_at_step:
                        os.kill(pid, signal.SIGKILL)
                        killed = True
                        break
                time.sleep(0.05)
            assert killed, "never killed the primary (pid=%r):\n%s" \
                % (pid, "".join(lines[-20:]))
        proc.wait(timeout=420)
    except subprocess.TimeoutExpired:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.wait()
        raise
    finally:
        reader.join(timeout=10)
    out = "".join(lines)
    assert proc.returncode == 0, out[-3000:]
    assert "RANK_0_OK" in out, out[-3000:]
    with open(out_dir / "rank0.json") as f:
        summary = json.load(f)
    with np.load(out_dir / "rank0_table.npz") as z:
        table = {k: z[k] for k in z.files}
    return out, summary, table


def test_ps_failover_matches_uninterrupted(tmp_path):
    """Acceptance scenario (ISSUE 4) — the server-side twin of the
    worker-respawn parity test: kill -9 the PRIMARY parameter server
    mid-training with sync replication on. The worker fails over to
    the promoted backup with zero acknowledged-push loss, the
    launcher respawns the dead process, it rejoins as the new backup
    and catches up — and the final server-side gradient table is
    bit-for-bit identical to an uninterrupted run's."""
    import numpy as np
    out, summary, table = _run_replicated(tmp_path, "killed",
                                          kill_at_step=4)
    assert "server 0 died" in out and "respawning" in out, out[-3000:]
    assert summary["steps"] == 12
    assert np.isfinite(summary["loss"])
    ps = summary["ps"]
    assert ps["failovers"] >= 1, ps
    assert ps["promotions"] >= 1, ps
    # the pair is redundant again: old primary rejoined as backup and
    # finished catch-up with the forwarding stream drained
    row = ps["rows"][0]
    assert row["role"] == "primary"
    assert row["repl"]["catchup"]["done"] and row["repl"]["lag"] == 0, \
        row

    out2, summary2, table2 = _run_replicated(tmp_path, "clean")
    assert summary2["ps"]["failovers"] == 0
    assert summary2["ps"]["promotions"] == 0
    assert set(table) == set(table2)
    for name in table:
        np.testing.assert_array_equal(
            table[name], table2[name],
            err_msg="server table diverged from the uninterrupted "
                    "run at %s — an acknowledged push was lost or "
                    "double-applied across the failover" % name)


def _run_partition(tmp_path, tag, cut, hist_dir=None):
    """One launcher run of tests/nightly/partition_worker.py: 1 worker
    + a replicated parameter shard (-s 1 --ps-replicas 2, sync mode).
    With ``cut`` the worker severs its own client->primary link at the
    wire mid-run (the server-to-server plane stays up — an asymmetric
    partition, no process dies) and heals it after the standby is
    promoted and the deposed primary has rejoined. Returns (launcher
    stdout, summary dict, server-table dict)."""
    import json
    import numpy as np
    root = os.path.join(os.path.dirname(__file__), "..")
    out_dir = tmp_path / ("out_" + tag)
    out_dir.mkdir()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PARTITION_TEST_DIR"] = str(out_dir)
    env["PARTITION_CUT"] = "1" if cut else "0"
    env["MXTPU_PS_BARRIER_TIMEOUT"] = "60"
    # no background heartbeat: every buffered-push flush then happens
    # synchronously in the failover path, so the per-key apply order —
    # and with it the float addition order — is deterministic and the
    # drill table can be compared bit-for-bit against the control's
    env["MXTPU_PS_HEARTBEAT"] = "0"
    env["MXTPU_PS_PARTITION_GRACE"] = "0.6"
    env["MXTPU_PS_RETRIES"] = "2"
    env["MXTPU_PS_BACKOFF"] = "0.02"
    env["MXTPU_PS_RECONNECT"] = "0.5"
    env.pop("MXTPU_FAULT_SPEC", None)
    if hist_dir is not None:
        env["MXTPU_HISTORY_DIR"] = str(hist_dir)
    else:
        env.pop("MXTPU_HISTORY_DIR", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "1", "-s", "1", "--ps-replicas", "2",
         "--ps-repl-mode", "sync",
         "--port", str(_free_port()),
         sys.executable + " " + os.path.join(root, "tests", "nightly",
                                             "partition_worker.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        import signal
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.communicate()
        raise
    assert proc.returncode == 0, out[-3000:]
    assert "PARTITION_RANK_0_OK" in out, out[-3000:]
    with open(out_dir / "rank0.json") as f:
        summary = json.load(f)
    with np.load(out_dir / "rank0_table.npz") as z:
        table = {k: z[k] for k in z.files}
    return out, summary, table


def test_ps_partition_heal_matches_uninterrupted(tmp_path):
    """Acceptance scenario (ISSUE 19) — the network twin of the
    kill -9 failover test: a real asymmetric partition cuts the worker
    off from the primary while both server processes stay alive. The
    grace window suppresses a spurious promotion, then expires;
    availability wins and the standby mints fencing epoch 2. The
    deposed primary — still serving, classic split-brain — hears the
    new epoch over the uncut server-to-server probe link, FENCES
    (refusing client writes), rejoins as the new backup and catches up
    while the client-side cut still stands. After the heal the final
    server table is bit-for-bit identical to an uninterrupted run and
    the journaled history is checker-clean."""
    import numpy as np
    hist = tmp_path / "history"
    hist.mkdir()
    out, summary, table = _run_partition(tmp_path, "cut", cut=True,
                                         hist_dir=hist)
    # the deposed primary refused client writes: split-brain prevention
    assert "FENCED at epoch 1" in out, out[-3000:]
    assert "a peer holds epoch 2" in out, out[-3000:]
    assert "demoted to backup" in out, out[-3000:]
    assert summary["failovers"] == 1, summary
    assert summary["fence_epoch"] == 2, summary
    assert summary["promotions"] >= 1, summary
    row = summary["rows"][0]
    assert row["role"] == "primary" and row["fence_epoch"] == 2, row
    assert row["repl"]["catchup"]["done"] and row["repl"]["lag"] == 0, \
        row

    out2, summary2, table2 = _run_partition(tmp_path, "clean",
                                            cut=False)
    assert "FENCED" not in out2, out2[-3000:]
    assert summary2["failovers"] == 0, summary2
    assert summary2["fence_epoch"] == 1, summary2
    assert summary2["promotions"] == 0, summary2
    assert set(table) == set(table2)
    for name in table:
        np.testing.assert_array_equal(
            table[name], table2[name],
            err_msg="server table diverged from the uninterrupted run "
                    "at %s — an acknowledged push was lost, reordered "
                    "or double-applied across the partition" % name)

    # the offline checker proves the same from the journal: no acked
    # write lost, no double apply, one writer per epoch
    from mxtpu.devtools import consistency
    report = consistency.check(str(hist))
    assert report["ok"], consistency.format_report(report)
    assert sorted(report["epochs"]) == [1, 2], report["epochs"]
    assert report["acked"] > 0, report


def _run_elastic(tmp_path, tag, scale=None, batch_sleep=0.0):
    """One launcher run of tests/nightly/elastic_worker.py: 1 anchor
    worker + 2 parameter servers, MXTPU_PS_ELASTIC=1, data flow from
    the server-owned shard cursor. ``scale`` is a tools/launch.py
    --scale drill spec triggered on the anchor's progress file."""
    import json
    root = os.path.join(os.path.dirname(__file__), "..")
    out_dir = tmp_path / ("out_" + tag)
    progress = tmp_path / ("progress_" + tag)
    out_dir.mkdir()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["ELASTIC_TEST_DIR"] = str(out_dir)
    env["ELASTIC_PROGRESS_FILE"] = str(progress)
    env["ELASTIC_BATCHES"] = "12"
    env["ELASTIC_BATCH_SLEEP"] = str(batch_sleep)
    env["MXTPU_PS_ELASTIC"] = "1"
    env["MXTPU_PS_BARRIER_TIMEOUT"] = "60"
    env.pop("MXTPU_FAULT_SPEC", None)
    cmd = [sys.executable, os.path.join(root, "tools", "launch.py"),
           "-n", "1", "-s", "2", "--launcher", "local",
           "--port", str(_free_port())]
    if scale:
        cmd += ["--scale", scale, "--scale-progress", str(progress)]
    cmd.append(sys.executable + " "
               + os.path.join(root, "tests", "nightly",
                              "elastic_worker.py"))
    proc = subprocess.Popen(
        cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        import signal
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.communicate()
        raise
    assert proc.returncode == 0, out[-4000:]
    assert "RANK_0_OK" in out, out[-4000:]
    with open(out_dir / "summary.json") as f:
        summary = json.load(f)
    return out, summary


def test_elastic_scale_out_matches_static_run(tmp_path):
    """Acceptance scenario (ISSUE 7): a training run where a worker is
    ADDED mid-run, a key shard is SPLIT onto a freshly spawned server,
    and the added worker is REMOVED again converges to the same loss
    band as an uninterrupted static run — with zero acknowledged-update
    loss (every key's applied-update clock lands EXACTLY on the fleet-
    wide work total, across joins, leaves, splits, and map_stale
    reroutes) and kv.stats() showing the join/leave/rebalance counts."""
    # throttled to ~17s of training so the wall-clock drill events all
    # land mid-run: join early, split while both workers push, remove
    # with work still left for the survivor to absorb
    out, summary = _run_elastic(
        tmp_path, "elastic", batch_sleep=0.12,
        scale="after=1,action=add_worker;"
              "after=5,action=split_shard,src=0;"
              "after=13,action=remove_worker,rank=1")
    assert "scale: adding worker 1" in out, out[-4000:]
    assert "scale: splitting server" in out, out[-4000:]
    assert "scale: removing worker 1" in out, out[-4000:]
    assert "worker 1 joined mid-run" in out, out[-4000:]
    assert "RANK_1_OK" in out, out[-4000:]

    # zero acked-update loss + exactly-once: the work total is exact
    # (elastic_worker.py already asserted it worker-side; re-assert
    # from the artifact so the evidence is in THIS test)
    want = 3 * 6 * 12
    assert all(v == want for v in summary["clocks"].values()), \
        summary["clocks"]
    el = summary["elastic"]
    assert el["joins"] >= 2, el          # anchor + the added worker
    assert el["leaves"] >= 1, el         # the removal's bye
    assert el["splits"] == 1, el
    assert el["keys_moved"] >= 1, el
    assert el["keys_adopted"] == el["keys_moved"], el
    assert summary["map_reroutes"] >= 1, summary
    assert summary["barrier_timeouts"] == 0, summary

    out2, summary2 = _run_elastic(tmp_path, "static")
    assert all(v == want for v in summary2["clocks"].values()), \
        summary2["clocks"]
    assert summary2["elastic"]["splits"] == 0
    # the loss band: both runs converge on the same least-squares
    # optimum; neither churn nor resharding moved the trajectory out
    # of the band the static run defines
    assert summary2["final_err"] < 0.15, summary2
    assert summary["final_err"] < 0.15, summary
    assert abs(summary["final_err"] - summary2["final_err"]) < 0.1, \
        (summary["final_err"], summary2["final_err"])


def test_worker_respawn_resumes_and_matches_uninterrupted(tmp_path):
    """Acceptance scenario (ISSUE 3): SIGKILL the worker mid-epoch on an
    exact step schedule; tools/launch.py --worker-respawn respawns it;
    the fresh process restores its TrainGuard checkpoint (params +
    optimizer + RNG + LR schedule + iterator cursor), re-registers with
    the parameter server, fast-forwards, and finishes the remaining
    steps with finite loss and NO hang (the barrier deadline bounds the
    worst case). Fault-matrix parity row: the final parameters must be
    bit-comparable to an uninterrupted run of the same seeded script —
    fast-forward really does land on the same trajectory."""
    import numpy as np
    # kill_worker fires at step-attempt 8 of the FIRST incarnation; the
    # respawn restores the step-6 checkpoint, so its remaining attempts
    # (7..12) never reach the nth=8 event count again — deterministic,
    # no timing involved
    out, summary, params = _run_resilient(
        tmp_path, "killed",
        "kind=kill_worker,point=worker.step,nth=8")
    assert "worker 0 died" in out and "respawning" in out, out[-3000:]
    assert summary["resumed_from"] is not None
    assert summary["steps"] == 12
    assert np.isfinite(summary["loss"])

    out2, summary2, params2 = _run_resilient(tmp_path, "clean", None)
    assert summary2["resumed_from"] is None
    assert summary2["steps"] == 12
    # same step count, same LR-schedule position, same final params:
    # the respawn fast-forwarded instead of re-deriving a new run
    assert summary["lr"] == summary2["lr"]
    assert set(params) == set(params2)
    for name in params:
        np.testing.assert_allclose(
            params[name], params2[name], rtol=1e-6, atol=1e-7,
            err_msg="respawned run diverged from uninterrupted run "
                    "at %s" % name)


# ---------------------------------------------------------------------------
# model serving (ISSUE 8): two REAL replica processes, kill -9 failover
# ---------------------------------------------------------------------------

_SERVING_CKPT_SCRIPT = """
import sys
sys.path.insert(0, sys.argv[2])
import mxtpu as mx
data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
net = mx.sym.SoftmaxOutput(net, name="softmax")
mod = mx.mod.Module(net, data_names=("data",),
                    label_names=("softmax_label",))
mod.bind(data_shapes=[("data", (8, 6))],
         label_shapes=[("softmax_label", (8,))])
mod.init_params(mx.init.Uniform(0.1))
mod.save_checkpoint(sys.argv[1], 0)
print("CKPT_OK")
"""


def _run_serving(tmp_path, tag, prefix, kill_at_progress=None):
    """One launcher run: 2 serving replica processes + 1 client-driver
    worker (tests/nightly/serving_client_driver.py). With
    ``kill_at_progress``, a REAL external kill -9 lands on serving
    replica 0 (the client's initial active route) once the driver's
    progress file shows that many completed requests — mid-stream,
    mid-batch-window, no injection harness. Returns (stdout, summary
    dict, {request index: answer bits})."""
    import json
    import re
    import signal
    import threading
    import time
    import numpy as np
    root = os.path.join(os.path.dirname(__file__), "..")
    out_dir = tmp_path / ("out_" + tag)
    progress = tmp_path / ("progress_" + tag)
    out_dir.mkdir()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["SERVING_TEST_DIR"] = str(out_dir)
    env["SERVING_PROGRESS_FILE"] = str(progress)
    env["SERVING_TOTAL_REQUESTS"] = "40"
    env["SERVING_CLIENT_THREADS"] = "4"
    env["MXTPU_SERVE_BATCH_DEADLINE_MS"] = "25"
    env.pop("MXTPU_FAULT_SPEC", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "1", "--serve", "2",
         "--serve-model", prefix, "--serve-epoch", "0",
         "--serve-data-shapes", "data=6", "--serve-buckets", "8",
         "--port", str(_free_port()),
         sys.executable + " " + os.path.join(
             root, "tests", "nightly", "serving_client_driver.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True)
    lines = []
    reader = threading.Thread(
        target=lambda: lines.extend(iter(proc.stdout.readline, "")),
        daemon=True)
    reader.start()
    try:
        if kill_at_progress is not None:
            pid = None
            killed = False
            deadline = time.time() + 300
            while time.time() < deadline and proc.poll() is None:
                if pid is None:
                    for line in list(lines):
                        m = re.search(r"serve replica 0 pid=(\d+)", line)
                        if m:
                            pid = int(m.group(1))
                            break
                if pid is not None and progress.exists():
                    try:
                        step = int(progress.read_text() or 0)
                    except ValueError:
                        step = 0
                    if step >= kill_at_progress:
                        os.kill(pid, signal.SIGKILL)
                        killed = True
                        break
                time.sleep(0.02)
            assert killed, "never killed replica 0 (pid=%r):\n%s" \
                % (pid, "".join(lines[-20:]))
        proc.wait(timeout=420)
    except subprocess.TimeoutExpired:
        import signal as _sig
        os.killpg(os.getpgid(proc.pid), _sig.SIGKILL)
        proc.wait()
        raise
    finally:
        reader.join(timeout=10)
    out = "".join(lines)
    assert proc.returncode == 0, out[-3000:]
    assert "CLIENT_OK" in out, out[-3000:]
    with open(out_dir / "summary.json") as f:
        summary = json.load(f)
    with np.load(out_dir / "answers.npz") as z:
        answers = {k: z[k] for k in z.files}
    return out, summary, answers


def test_serving_replica_kill_matches_uninterrupted(tmp_path):
    """Acceptance drill (ISSUE 8): two serving replicas under
    concurrent client load, replica 0 killed with a REAL kill -9
    mid-stream. Every acknowledged request is answered exactly once,
    the response table is BIT-FOR-BIT identical to an uninterrupted
    run's (single-bucket determinism), the client's failover counters
    fired, and the surviving replica's server.stats() shows the
    batching story."""
    import numpy as np
    root = os.path.join(os.path.dirname(__file__), "..")
    prefix = str(tmp_path / "served_model")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _SERVING_CKPT_SCRIPT, prefix, root],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "CKPT_OK" in r.stdout, r.stderr[-2000:]

    out, summary, answers = _run_serving(tmp_path, "killed", prefix,
                                         kill_at_progress=8)
    assert summary["answered"] == summary["total"] == 40
    assert summary["exactly_once"] is True
    assert not summary["errors"]
    cli = summary["client"]
    assert cli["failovers"] >= 1, cli
    assert cli["replays"] >= 1, cli
    srv = summary["server"]
    assert srv["counters"]["responses"] >= 1
    assert srv["batcher"]["batches"] >= 1
    # dynamic batching under concurrent load: fewer device dispatches
    # than requests on the surviving replica
    assert srv["batcher"]["batches"] <= srv["batcher"]["batched_requests"]

    out2, summary2, answers2 = _run_serving(tmp_path, "clean", prefix)
    assert summary2["answered"] == 40
    assert summary2["client"]["failovers"] == 0
    assert set(answers) == set(answers2)
    for k in answers:
        np.testing.assert_array_equal(
            answers[k], answers2[k],
            err_msg="response %s diverged from the uninterrupted run "
                    "— an acknowledged request was lost, double-"
                    "answered, or recomputed differently across the "
                    "kill -9 failover" % k)


# ---------------------------------------------------------------------------
# live weight streaming + rollout (ISSUE 11): a real trainer process
# publishing into 2 real serving replicas under concurrent load
# ---------------------------------------------------------------------------

def test_online_rollout_closes_train_serve_loop(tmp_path):
    """Acceptance scenario (ISSUE 11): rank 0 is a REAL trainer process
    that trains and publishes versioned weights; two REAL serving
    replica processes follow the stream (--serve-weight-dir, poll) and
    swap versions live while rank 1's concurrent clients stream
    requests. Mid-stream, a REAL external kill -9 lands on replica 0
    while swaps are in flight; --serve-respawn revives it and it
    catches up to the current weight version BEFORE admitting. The
    acceptance bar: every request answered exactly once across >= 3
    version swaps and the kill; prediction quality (cross-entropy
    against the task's labels) IMPROVES mid-stream; rollback to the
    pinned version reproduces its recorded probe bits BIT-FOR-BIT; and
    the program-cache counters show ZERO predict recompiles after
    warmup on every replica, across every swap."""
    import json
    import re
    import signal
    import threading
    import time
    import numpy as np
    root = os.path.join(os.path.dirname(__file__), "..")
    prefix = str(tmp_path / "served_model")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _SERVING_CKPT_SCRIPT, prefix, root],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "CKPT_OK" in r.stdout, r.stderr[-2000:]

    out_dir = tmp_path / "out"
    weight_dir = tmp_path / "weights"
    progress = tmp_path / "progress"
    out_dir.mkdir()
    env["ROLLOUT_TEST_DIR"] = str(out_dir)
    env["ROLLOUT_PROGRESS_FILE"] = str(progress)
    env["MXTPU_SERVE_BATCH_DEADLINE_MS"] = "10"
    # stretch each replica's 2nd swap window so the external kill has a
    # real mid-swap window to land in (fires per process, delay only)
    env["MXTPU_FAULT_SPEC"] = \
        "kind=delay,point=serve.swap,delay=0.3,nth=2,count=1"
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "2", "--serve", "2", "--serve-respawn",
         "--serve-model", prefix, "--serve-epoch", "0",
         "--serve-data-shapes", "data=6", "--serve-buckets", "8",
         "--serve-weight-dir", str(weight_dir),
         "--serve-weight-poll", "0.1",
         "--port", str(_free_port()),
         sys.executable + " " + os.path.join(
             root, "tests", "nightly", "online_rollout_worker.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True)
    lines = []
    reader = threading.Thread(
        target=lambda: lines.extend(iter(proc.stdout.readline, "")),
        daemon=True)
    reader.start()
    try:
        # the external kill -9: replica 0, once the driver's progress
        # file shows answered requests WITH swaps already in flight
        pid = None
        killed = False
        deadline = time.time() + 300
        while time.time() < deadline and proc.poll() is None:
            if pid is None:
                for line in list(lines):
                    m = re.search(r"serve replica 0 pid=(\d+)", line)
                    if m:
                        pid = int(m.group(1))
                        break
            if pid is not None and progress.exists():
                try:
                    step = int(progress.read_text() or 0)
                except ValueError:
                    step = 0
                if step >= 5:
                    os.kill(pid, signal.SIGKILL)
                    killed = True
                    break
            time.sleep(0.02)
        assert killed, "never killed replica 0 (pid=%r):\n%s" \
            % (pid, "".join(lines[-20:]))
        proc.wait(timeout=420)
    except subprocess.TimeoutExpired:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.wait()
        raise
    finally:
        reader.join(timeout=10)
    out = "".join(lines)
    assert proc.returncode == 0, out[-4000:]
    assert "RANK_0_OK" in out and "RANK_1_OK" in out, out[-4000:]
    # the kill really happened and the launcher revived the replica,
    # which caught up to the current version before admitting
    assert "serve replica serve0 died" in out, out[-4000:]
    assert "respawning on port" in out, out[-4000:]
    assert out.count("caught up to weight version") >= 3, out[-4000:]

    with open(out_dir / "summary.json") as f:
        summary = json.load(f)
    # exactly-once under swaps + kill: every issued request came back
    # exactly once (predict2 delivers one terminal outcome per rid;
    # replays carry the original id), zero errors
    assert summary["answered"] >= 5
    assert summary["errors"] == [], summary["errors"][:3]
    # >= 3 version swaps beyond the pinned initial version
    versions = [v for v in summary["versions"] if v >= 1]
    assert len(versions) >= 4, summary["versions"]
    assert summary["final_version"] >= 4
    # prediction quality improved mid-stream
    losses = {int(k): v for k, v in summary["loss_by_version"].items()}
    assert losses[summary["final_version"]] < losses[1] - 0.05, losses
    # bit-exact rollback to the pinned version
    assert summary["rollback_bit_exact"] is True
    for info in summary["rollback_info"].values():
        assert info["pinned"] == 1, info
    with np.load(out_dir / "probe_bits.npz") as z:
        np.testing.assert_array_equal(z["v1"], z["rollback"])
    # zero predict recompiles after warmup: one AOT program per bucket
    # (single bucket menu), never a retrace across any swap — on every
    # replica including the respawned one
    for addr, rec in summary["compiles"].items():
        assert rec["compiles"] == 1, (addr, rec)
    # the fleet really served off cache hits (a replica that took no
    # traffic after its respawn legitimately posts 0 of its own)
    assert sum(rec["hits"] for rec in
               summary["compiles"].values()) >= 1, summary["compiles"]
    assert any(rec["swaps"] >= 1 for rec in
               summary["compiles"].values()), summary["compiles"]


# ---------------------------------------------------------------------------
# continuous-batching generation (ISSUE 17): kill -9 + live hot-swaps
# under sustained generate streams
# ---------------------------------------------------------------------------

_GEN_CKPT_SCRIPT = """
import sys
sys.path.insert(0, sys.argv[2])
import numpy as np
import mxtpu as mx
from mxtpu.model import save_checkpoint
V, D, S = 17, 16, 32
rng = np.random.RandomState(11)
data = mx.sym.Variable("data")
pos = mx.sym.Variable("pos", shape=(0,), dtype="int32")
kc = mx.sym.Variable("kc", shape=(0, S, D))
vc = mx.sym.Variable("vc", shape=(0, S, D))
emb = mx.sym.Embedding(data=data, input_dim=V, output_dim=D, name="emb")
q = mx.sym.FullyConnected(data=emb, num_hidden=D, flatten=False, name="q")
k = mx.sym.FullyConnected(data=emb, num_hidden=D, flatten=False, name="k")
v = mx.sym.FullyConnected(data=emb, num_hidden=D, flatten=False, name="v")
att = mx.sym.cached_attention(q, k, v, kc, vc, pos, num_heads=2,
                              name="att")
out = mx.sym.FullyConnected(data=att[0], num_hidden=V, flatten=False,
                            name="proj")
sym = mx.sym.Group([out, mx.sym.identity(att[1], name="kc_next"),
                    mx.sym.identity(att[2], name="vc_next")])
f = lambda *s: rng.randn(*s).astype(np.float32) * 0.4
args = {"emb_weight": f(V, D),
        "q_weight": f(D, D), "q_bias": np.zeros(D, "f"),
        "k_weight": f(D, D), "k_bias": np.zeros(D, "f"),
        "v_weight": f(D, D), "v_bias": np.zeros(D, "f"),
        "proj_weight": f(V, D), "proj_bias": np.zeros(V, "f")}
save_checkpoint(sys.argv[1], 0, sym,
                {n: mx.nd.array(a) for n, a in args.items()}, {})
print("CKPT_OK")
"""


def test_generate_kill_and_swap_drill(tmp_path):
    """Acceptance drill (ISSUE 17): two REAL serving replicas host a
    generative LM while a REAL publisher process hot-swaps weight
    versions underneath sustained concurrent generate streams, and a
    REAL external kill -9 lands on replica 0 mid-generation. The
    driver (tests/nightly/generate_drill_worker.py) verifies from its
    per-token frame records: every sequence's streamed indices arrive
    exactly once in order across the failover replay; no sequence
    mixes weight versions (hot-swap tears nothing); and every
    sequence's tokens match a LOCAL greedy recompute from the
    weight-dir snapshot of the exact version that answered it."""
    import json
    import re
    import signal
    import threading
    import time
    root = os.path.join(os.path.dirname(__file__), "..")
    prefix = str(tmp_path / "gen_model")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _GEN_CKPT_SCRIPT, prefix, root],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "CKPT_OK" in r.stdout, r.stderr[-2000:]

    out_dir = tmp_path / "out"
    weight_dir = tmp_path / "weights"
    progress = tmp_path / "progress"
    out_dir.mkdir()
    env["GEN_TEST_DIR"] = str(out_dir)
    env["GEN_PROGRESS_FILE"] = str(progress)
    env["MXTPU_SERVE_GENERATE_SLOTS"] = "8"
    env["MXTPU_SERVE_GENERATE_PREFILL_BUCKETS"] = "8,16"
    # keep every published version resident: a failover replay pins
    # the killed replica's version and must find it on the peer
    env["MXTPU_SERVE_VERSION_KEEP"] = "8"
    env.pop("MXTPU_FAULT_SPEC", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "2", "--serve", "2", "--serve-respawn",
         "--serve-model", prefix, "--serve-epoch", "0",
         "--serve-data-shapes", "data=1", "--serve-buckets", "1",
         "--serve-weight-dir", str(weight_dir),
         "--serve-weight-poll", "0.1",
         "--port", str(_free_port()),
         sys.executable + " " + os.path.join(
             root, "tests", "nightly", "generate_drill_worker.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True)
    lines = []
    reader = threading.Thread(
        target=lambda: lines.extend(iter(proc.stdout.readline, "")),
        daemon=True)
    reader.start()
    try:
        # the external kill -9: replica 0, once the driver finished a
        # few sequences WITH >= 2 weight versions already answering
        pid = None
        killed = False
        deadline = time.time() + 420
        while time.time() < deadline and proc.poll() is None:
            if pid is None:
                for line in list(lines):
                    m = re.search(r"serve replica 0 pid=(\d+)", line)
                    if m:
                        pid = int(m.group(1))
                        break
            if pid is not None and progress.exists():
                try:
                    step = int(progress.read_text() or 0)
                except ValueError:
                    step = 0
                if step >= 4:
                    os.kill(pid, signal.SIGKILL)
                    killed = True
                    break
            time.sleep(0.02)
        assert killed, "never killed replica 0 (pid=%r):\n%s" \
            % (pid, "".join(lines[-20:]))
        proc.wait(timeout=600)
    except subprocess.TimeoutExpired:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.wait()
        raise
    finally:
        reader.join(timeout=10)
    out = "".join(lines)
    assert proc.returncode == 0, out[-4000:]
    assert "RANK_0_OK" in out and "RANK_1_OK" in out, out[-4000:]
    # the kill really happened and the launcher revived the replica
    assert "serve replica serve0 died" in out, out[-4000:]
    assert "respawning on port" in out, out[-4000:]

    with open(out_dir / "summary.json") as f:
        summary = json.load(f)
    # sustained load across the drill, zero client-visible errors
    assert summary["answered"] >= 8, summary
    assert summary["errors"] == [], summary["errors"][:3]
    # exactly-once streaming across the kill -9 failover
    assert summary["exactly_once"] is True
    # zero torn sequences across >= 2 live hot-swaps
    assert summary["torn"] == [], summary["torn"]
    assert len(summary["versions"]) >= 2, summary["versions"]
    assert summary["final_version"] >= 2
    # the oracle recompute: every served sequence bit-matches a local
    # greedy decode from its answering version's weight snapshot
    assert summary["oracle"]["mismatches"] == [], \
        summary["oracle"]["mismatches"][:2]
    # the kill interrupted live streams: the client failed over (and
    # replays, if the kill caught a sequence mid-flight, dedup'd)
    assert summary["client"]["failovers"] >= 1, summary["client"]


# ---------------------------------------------------------------------------
# fleet observability (ISSUE 14): one merged chrome://tracing timeline
# across worker + PS + serving replica, and a live mxtop fleet snapshot
# ---------------------------------------------------------------------------

def test_observability_merged_timeline_and_mxtop(tmp_path):
    """Acceptance (ISSUE 14): a real ``tools/launch.py`` run — 1 worker,
    1 PS shard, 1 serving replica — with ``--telemetry`` and full trace
    sampling. The per-process trace dumps merge into ONE timeline
    covering >= 3 processes whose wire/apply spans are stitched by
    shared trace ids, and ``tools/mxtop.py --once`` renders a live
    fleet snapshot (worker exporter + PS + replica rows) from the same
    run's telemetry dir."""
    import json
    root = os.path.join(os.path.dirname(__file__), "..")
    prefix = str(tmp_path / "served_model")
    trace_dir = tmp_path / "traces"
    telem_dir = tmp_path / "telemetry"
    out_dir = tmp_path / "out"
    for d in (trace_dir, telem_dir, out_dir):
        d.mkdir()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _SERVING_CKPT_SCRIPT, prefix, root],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "CKPT_OK" in r.stdout, r.stderr[-2000:]

    env["OBS_TEST_DIR"] = str(out_dir)
    env["MXTPU_TRACE_SAMPLE"] = "1"
    env["MXTPU_TRACE_DIR"] = str(trace_dir)
    env["MXTPU_TELEMETRY_INTERVAL"] = "0.3"
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "1", "-s", "1", "--serve", "1",
         "--serve-model", prefix, "--serve-epoch", "0",
         "--serve-data-shapes", "data=6", "--serve-buckets", "8",
         "--telemetry", "--telemetry-dir", str(telem_dir),
         "--port", str(_free_port()),
         sys.executable + " " + os.path.join(root, "tests", "nightly",
                                             "obs_worker.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        import signal
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.communicate()
        raise
    assert proc.returncode == 0, out[-3000:]
    assert "OBS_WORKER_OK" in out, out[-3000:]

    # -- ONE merged timeline covering >= 3 processes --------------------
    sys.path.insert(0, root)
    from mxtpu.obs import merge_traces
    merged = merge_traces(str(trace_dir),
                          out=str(tmp_path / "merged.json"))
    spans = [e for e in merged if e.get("ph") == "X"]
    pids = {e["pid"] for e in spans}
    assert len(pids) >= 3, \
        "timeline covers %d processes, want >= 3 (files: %s)" % (
            len(pids), os.listdir(trace_dir))
    by_pid_names = {}
    for e in spans:
        by_pid_names.setdefault(e["pid"], set()).add(e["name"])
    all_names = set().union(*by_pid_names.values())
    # wire/queue/apply spans from every side of the fleet
    assert "module.step" in all_names, all_names
    assert "kv.client.rpc" in all_names, all_names
    assert "kv.server.apply" in all_names, all_names
    assert {"serve.admit", "serve.batch.dispatch"} <= all_names, \
        all_names
    # stitching: one trace id spans worker AND server processes
    by_trace_pids = {}
    for e in spans:
        tid = e.get("args", {}).get("trace")
        if tid:
            by_trace_pids.setdefault(tid, set()).add(e["pid"])
    cross = [t for t, ps in by_trace_pids.items() if len(ps) >= 2]
    assert cross, "no trace id stitches spans across processes"
    # process_name metadata + flow events survived the merge
    assert any(e.get("ph") == "M" for e in merged)
    assert any(e.get("ph") == "s" for e in merged)

    # -- the live telemetry surface: fleet.json + mxtop -----------------
    # the driver captured fleet.json WHILE its exporter was alive (the
    # aggregator's post-exit sweeps legitimately gap the worker row)
    fleet = json.load(open(out_dir / "fleet_live.json"))
    rows = fleet["fleet"]
    live = {a for a, s in rows.items()
            if isinstance(s, dict) and not s.get("gap")}
    assert len(live) >= 3, \
        "fleet snapshot holds %d live rows, want ps + replica + " \
        "worker exporter: %r" % (len(live), sorted(rows))
    roles = {rows[a].get("role") for a in live}
    assert {"server", "worker", "serving"} <= roles, roles
    mx_out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "mxtop.py"),
         "--dir", str(telem_dir), "--once"],
        env=env, capture_output=True, text=True, timeout=120)
    assert mx_out.returncode == 0, mx_out.stderr[-2000:]
    for addr in sorted(rows)[:2]:
        assert addr in mx_out.stdout, mx_out.stdout
    assert "PROC" in mx_out.stdout and "P99MS" in mx_out.stdout


# ---------------------------------------------------------------------------
# closed-loop autoscaling (ISSUE 16): a diurnal load drill where EVERY
# capacity change is controller-initiated
# ---------------------------------------------------------------------------

def test_autoscale_diurnal_closed_loop(tmp_path):
    """Acceptance (ISSUE 16): one ``tools/launch.py --autoscale`` run —
    1 anchor worker, 1 PS shard, 1 live serving replica plus 1 reserved
    slot — where the driver's scripted day/night load makes the
    controller (not a human, not a --scale script) add a worker, add
    the reserved replica (which prewarms from the first replica's
    exported AOT menu), split the hot shard online, and drain the
    replica when the idle band confirms. Mid-day the controller is
    killed -9 between journaling an intent and any verdict
    (``--autoscale-fault``); the respawn replays the journal and the
    executor's dedupe keeps the replayed action exactly-once. The
    driver's ledger proves zero acknowledged-update loss across all of
    it, and the prewarmed joiner's time-to-serving is measured from its
    own transcript."""
    import json
    import re
    root = os.path.join(os.path.dirname(__file__), "..")
    prefix = str(tmp_path / "served_model")
    out_dir = tmp_path / "out"
    telem_dir = tmp_path / "telemetry"
    out_dir.mkdir()
    telem_dir.mkdir()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _SERVING_CKPT_SCRIPT, prefix, root],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "CKPT_OK" in r.stdout, r.stderr[-2000:]

    env["AUTOSCALE_TEST_DIR"] = str(out_dir)
    env["MXTPU_PS_ELASTIC"] = "1"
    env["MXTPU_PS_BARRIER_TIMEOUT"] = "60"
    env["MXTPU_SERVE_BATCH_DEADLINE_MS"] = "10"
    env["MXTPU_TELEMETRY_INTERVAL"] = "0.3"
    env["MXTPU_TELEMETRY_HISTORY"] = "12"   # short rate window: the
    #                                         night decay is fast
    env.update({
        # worker band: any real step rate sits under the target, so
        # one worker is "starving" until the joiner's row is live;
        # min=max=2 makes add_worker reachable and eviction/removal
        # unreachable (the drill's joiners are deliberately idle)
        "MXTPU_AUTOSCALE_TARGET_STEPS_S": "1000",
        "MXTPU_AUTOSCALE_MIN_WORKERS": "2",
        "MXTPU_AUTOSCALE_MAX_WORKERS": "2",
        "MXTPU_AUTOSCALE_MIN_REPLICAS": "1",
        "MXTPU_AUTOSCALE_MAX_REPLICAS": "2",
        "MXTPU_AUTOSCALE_MAX_SHARDS": "2",
        # serving bands: ~8 req/s of day traffic clears up_rps, the
        # night silence falls through down_rps; queue pressure off
        "MXTPU_AUTOSCALE_UP_RPS": "3",
        "MXTPU_AUTOSCALE_DOWN_RPS": "1",
        "MXTPU_AUTOSCALE_UP_QUEUE": "100000",
        "MXTPU_AUTOSCALE_SPLIT_MIN_PUSH_S": "20",
        "MXTPU_AUTOSCALE_INTERVAL": "0.3",
        "MXTPU_AUTOSCALE_CONFIRM_TICKS": "2",
        "MXTPU_AUTOSCALE_COOLDOWN_S": "5",
        "MXTPU_AUTOSCALE_RATE_MAX": "2",
        "MXTPU_AUTOSCALE_RATE_WINDOW_S": "6",
        "MXTPU_AUTOSCALE_ACTION_TIMEOUT": "8",
        "MXTPU_AUTOSCALE_ACTION_RETRIES": "1",
    })
    env.pop("MXTPU_FAULT_SPEC", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "1", "-s", "1", "--serve", "1", "--serve-max", "2",
         "--serve-model", prefix, "--serve-epoch", "0",
         "--serve-data-shapes", "data=6", "--serve-buckets", "8",
         "--autoscale", "--telemetry-dir", str(telem_dir),
         "--autoscale-fault", "point=ctl.action,kind=kill_worker,nth=1",
         "--port", str(_free_port()),
         sys.executable + " " + os.path.join(root, "tests", "nightly",
                                             "autoscale_worker.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        import signal
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.communicate()
        raise
    assert proc.returncode == 0, out[-6000:]
    assert "RANK_0_OK" in out, out[-6000:]

    # every capacity change was CONTROLLER-initiated: no --scale script
    # exists in this run, so each scale: line is a mailbox actuation
    assert "autoscale controller pid=" in out, out[-6000:]
    assert "scale: adding worker 1" in out, out[-6000:]
    assert "worker 1 joined mid-run" in out, out[-6000:]
    assert "scale: adding serving replica" in out, out[-6000:]
    assert "scale: splitting server" in out, out[-6000:]
    assert "scale: draining serving replica" in out, out[-6000:]

    # the kill -9 drill: the controller died on its FIRST actuation
    # (intent journaled, no verdict), the launcher respawned it WITHOUT
    # the fault spec, and the replay re-ran under the ORIGINAL id —
    # applied exactly once across both incarnations
    assert "autoscale controller died" in out, out[-6000:]
    m = re.search(r"replaying in-flight action (a\d+\.\w+)", out)
    assert m, "the respawned controller never replayed the journal:\n" \
        + out[-6000:]
    replayed = m.group(1)
    kind = replayed.split(".", 1)[1]
    applies = out.count("autoscale: applying %s (%s)" % (kind, replayed))
    assert applies == 1, \
        "replayed action %s applied %d times" % (replayed, applies)

    # zero acknowledged-update loss across split + kill + scaling
    with open(out_dir / "summary.json") as f:
        summary = json.load(f)
    assert summary["clocks_exact"] is True, summary
    assert summary["total_acked"] > 0
    assert summary["map_reroutes"] >= 1, summary
    for kind in ("add_worker", "add_replica", "split_shard",
                 "drain_replica"):
        assert summary["verdicts"].get(kind), (kind, summary["verdicts"])

    # the prewarmed joiner: imported the exported menu, compiled
    # NOTHING, and its measured time-to-serving beats the cold boot
    tts = re.findall(r"time-to-serving ([0-9.]+)s \(prewarmed=(\d+) "
                     r"compiles=(\d+)\)", out)
    assert len(tts) >= 2, "want a cold and a prewarmed replica:\n" \
        + out[-6000:]
    cold = [(float(s), int(p), int(c)) for s, p, c in tts if int(p) == 0]
    warm = [(float(s), int(p), int(c)) for s, p, c in tts if int(p) > 0]
    assert cold and warm, tts
    assert warm[0][2] == 0, \
        "prewarmed replica still compiled: %r" % (tts,)
    assert warm[0][0] < cold[0][0], \
        "prewarmed time-to-serving %.3fs did not beat the cold boot " \
        "%.3fs" % (warm[0][0], cold[0][0])


# ---------------------------------------------------------------------------
# crash-safe streaming data plane (ISSUE 18): the serve->train loop
# ---------------------------------------------------------------------------

_STREAM_TRAINER_SCRIPT = """
import json
import os
import sys
import time

sys.path.insert(0, sys.argv[5])
import numpy as np
import mxtpu as mx
from mxtpu.streaming import ContinualTrainer, StreamingIter

root, group, key, step_sleep = sys.argv[1:5]

kv = mx.kv.create("dist_async")
it = StreamingIter(kv, root, group=group, batch_size=4,
                   idle_timeout=2.0, poll=0.02)

def grad_fn(params, records):
    tot = np.zeros((2,), np.float32)
    for rid, feats, label in records:
        tot += feats[0]
    return {key: tot}

tr = ContinualTrainer(kv, it, {key: np.zeros((2,), np.float32)},
                      grad_fn)
while tr.step():
    print("STEP %d" % tr.steps, flush=True)
    time.sleep(float(step_sleep))
print("FINAL %s" % json.dumps([float(x) for x in tr.params[key]]),
      flush=True)
kv.close()
"""


def test_stream_kill9_mid_tail_exactly_once(tmp_path):
    """Acceptance drill (ISSUE 18): a REAL trainer process tails a
    stream through kvstore segment leases and is kill -9'd mid-tail;
    its respawn resumes from the server's committed (segment, offset)
    — no record lost, none trained twice. Proof is arithmetic: the
    per-record clock totals of the interrupted run are BIT-EXACT equal
    to an uninterrupted control over the same log (integer-valued
    float records, deterministic batching — any lost record, any
    double-fold, any nondeterministic batch boundary breaks
    equality)."""
    import json
    import re
    import signal
    import time

    import numpy as np

    from mxtpu import kvstore_async as ka
    from mxtpu.kvstore_async import ParameterServer
    from mxtpu.streaming import StreamWriter, encode_record

    root = os.path.join(os.path.dirname(__file__), "..")
    stream_root = str(tmp_path / "stream")
    w = StreamWriter(stream_root, shard=0)
    for i in range(24):
        w.append(encode_record(
            "r%d" % i, (np.full((2,), i, np.float32),), np.float32(i)))
    w.close()
    expect = float(sum(range(24)))

    # a kill -9'd worker's lease requeues via the liveness sweep the
    # respawn's hello triggers once the window expires
    ka._WORKER_DEAD_AFTER = 0.5
    srv = ParameterServer().start()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_PS_ADDRS"] = srv.address
    env["MXTPU_PROC_ID"] = "0"
    env["MXTPU_NUM_PROCS"] = "1"

    def run_trainer(group, key, step_sleep, kill_after_step=None):
        proc = subprocess.Popen(
            [sys.executable, "-c", _STREAM_TRAINER_SCRIPT,
             stream_root, group, key, str(step_sleep), root],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        final = None
        try:
            for line in iter(proc.stdout.readline, ""):
                m = re.match(r"FINAL (.*)", line)
                if m:
                    final = json.loads(m.group(1))
                s = re.match(r"STEP (\d+)", line)
                if s and kill_after_step is not None \
                        and int(s.group(1)) >= kill_after_step:
                    os.kill(proc.pid, signal.SIGKILL)   # kill -9
                    proc.wait()
                    return None
            proc.wait(timeout=120)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        assert proc.returncode == 0, "trainer failed (final=%r)" % final
        return final

    try:
        # uninterrupted control
        control = run_trainer("ctl", "acc_ctl", "0")
        assert control == [expect, expect], control

        # victim: kill -9 lands mid-tail after the 2nd committed step
        assert run_trainer("v", "acc_v", "0.25",
                           kill_after_step=2) is None
        offs = ka.stream_origin  # (import used below for clarity)
        time.sleep(0.7)          # let the liveness window expire
        victim = run_trainer("v", "acc_v", "0")
        assert victim == control, (victim, control)

        # and the server agrees nothing is left: committed final
        conn = ka._ServerConn(srv.address)
        reply = conn.request("stream_offsets", "v")
        assert reply[0] == "ok" and reply[1][0][3] is True, reply
        stats = conn.request("stats")[1]
        assert stats["stream_commits"] >= 6
        del offs
    finally:
        srv.stop()


def test_stream_shift_corrected_through_serve_train_loop(tmp_path):
    """Acceptance drill (ISSUE 18): the closed serve->train loop. A
    serving replica answers predicts from weights fit to an OLD world
    and emits (features, outcome) per answered request; outcomes come
    from a SHIFTED world. The continual trainer tails the emitted
    stream exactly-once, folds the correction into the kvstore,
    publishes — and the replica's answers move to the shifted world
    within seconds (error drops by >5x), without restarts."""
    import time

    import numpy as np

    import mxtpu as mx
    from mxtpu import kvstore_async as ka
    from mxtpu.kvstore_async import ParameterServer
    from mxtpu.serving import (InferenceEngine, ModelServer,
                               ServingClient, WeightPublisher,
                               WeightSync)
    from mxtpu.streaming import (ContinualTrainer, EmitLog,
                                 StreamingIter, StreamWriter)

    t0 = time.time()
    stream_root = str(tmp_path / "stream")
    weight_dir = str(tmp_path / "weights")

    # linear model y = x @ W.T; the serving fleet starts on W0, the
    # world moved to W_TRUE
    W0 = np.array([[1.0, -1.0]], np.float32)
    W_TRUE = np.array([[2.0, 1.0]], np.float32)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=1, no_bias=True,
                                name="fc")

    eng = InferenceEngine(net, {"fc_weight": mx.nd.array(W0)}, {},
                          {"data": (2,)}, buckets=(8,), warm=False)
    server = ModelServer(eng, model_name="online",
                         batch_deadline_ms_=5,
                         default_budget_ms_=4000.0,
                         weight_dir=weight_dir).start()
    emit = EmitLog(StreamWriter(stream_root, shard=0))
    server.set_emit(emit)
    pub = WeightPublisher(weight_dir)
    sync = WeightSync(server, weight_dir=weight_dir, poll=0.05)
    pub.publish({"fc_weight": W0}, pin=True)
    sync.catch_up()
    cli = ServingClient(addrs=[server.address], budget_ms=4000.0)
    cli.hello()

    srv = ParameterServer().start()
    os.environ["MXTPU_PS_ADDRS"] = srv.address
    os.environ["MXTPU_PROC_ID"] = "0"
    os.environ["MXTPU_NUM_PROCS"] = "1"
    kv = mx.kv.create("dist_async")
    try:
        xs = np.array([[1, 0], [0, 1], [1, 1], [2, 1],
                       [1, 2], [3, 1], [1, 3], [2, 2]], np.float32)
        # serve the OLD world and measure its error on live traffic
        err0 = 0.0
        for x in xs:
            outs, info = cli.predict2(x.reshape(1, 2))
            pred = float(np.asarray(outs[0]).reshape(-1)[0])
            truth = float(x @ W_TRUE[0])
            err0 += abs(pred - truth)
            # the late label arrives and joins server-side
            assert cli.report_outcome(info["rid"],
                                      np.float32(truth)) is True
        emit.close()                      # seal: the batch boundary

        # tail the emitted stream exactly-once and fit the correction
        it = StreamingIter(kv, stream_root, group="online",
                           batch_size=8, idle_timeout=0.5, poll=0.02)

        def grad_fn(params, records):
            X = np.stack([np.ravel(feats[0])
                          for _rid, feats, _l in records])
            y = np.array([float(np.ravel(lab)[0])
                          for _rid, _f, lab in records], np.float32)
            W = params["fc_weight"]
            resid = y - X @ W[0]
            dW, *_ = np.linalg.lstsq(X, resid, rcond=None)
            return {"fc_weight": dW.reshape(1, 2)}

        tr = ContinualTrainer(kv, it, {"fc_weight": W0}, grad_fn,
                              publisher=pub, publish_every=1)
        assert tr.run() == 1
        sync.catch_up()                   # the fleet follows the push

        err1 = 0.0
        for x in xs:
            outs, _info = cli.predict2(x.reshape(1, 2))
            pred = float(np.asarray(outs[0]).reshape(-1)[0])
            err1 += abs(pred - float(x @ W_TRUE[0]))
        elapsed = time.time() - t0
        assert err1 < err0 / 5, (err0, err1)
        assert err1 < 0.5, err1
        assert elapsed < 60, "correction took %.1fs" % elapsed
        # the emit plane accounted every record: 8 joined, 0 shed
        c = emit.counters()
        assert c["joined"] == 8 and c["dropped"] == 0, c
    finally:
        cli.close()
        kv.close()
        srv.stop()
        server.stop()
