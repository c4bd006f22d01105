"""One span, two sinks, one clock (ISSUE 27): ``mxtpu.obs.span`` records
under a live ``jax.profiler`` session as well as under a sampled context,
lands beside the device's operations as a ``TraceAnnotation``, and sits
where the work happens in ``GenerateScheduler``, ``fit`` and the step
programs. Every session here is a short one on the CPU, in a temp dir."""
import contextlib
import glob
import os
import re
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import obs
from mxtpu import profiler as prof
from mxtpu.serving import InferenceEngine
from mxtpu.serving.batcher import GenerateScheduler

from test_serving_generate import _lm_params, _lm_symbol


@contextlib.contextmanager
def session(path):
    """A live jax.profiler session with the host tracer only."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def spans():
    return [e for e in prof.snapshot_events()
            if e.get("cat") == "trace" and e["ph"] == "X"]


def named(name):
    return [e for e in spans() if e["name"] == name]


def inside(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_span_records_under_live_session_alone(tmp_path):
    prof.reset()
    with obs.span("t.before"):
        pass
    with session(tmp_path):
        assert obs.active_ctx() is None
        with obs.span("t.live", rid="r1"):
            # no sampled context is opened: nothing rides a wire
            assert obs.wire_ctx() is None
    with obs.span("t.after"):
        pass
    assert [e["name"] for e in spans()] == ["t.live"]
    ev = spans()[0]
    assert ev["args"]["rid"] == "r1" and ev["args"]["parent"] is None
    assert ev["args"]["trace"].startswith("thread-")
    # the flow pair stitches processes: only a sampled context writes it
    assert [e for e in prof.snapshot_events()
            if e.get("ph") in ("s", "f")] == []


def test_sampled_context_inside_a_session_keeps_its_flow_pair(tmp_path):
    prof.reset()
    with session(tmp_path):
        tok = obs.start_trace()
        with obs.span("t.sampled"):
            pass
        obs.end_trace(tok)
    ev, = spans()
    flows = [e for e in prof.snapshot_events() if e.get("ph") in ("s", "f")]
    assert len(flows) == 2 and {f["id"] for f in flows} == {ev["args"]["trace"]}


def test_parents_nest_and_the_identifier_rides_every_span(tmp_path):
    prof.reset()
    with session(tmp_path):
        with obs.span("t.request", rid="r7"):
            with obs.span("t.prefill", rid="r7"):
                with obs.span("t.read", rid="r7"):
                    pass
            with obs.span("t.adopt", rid="r7"):
                pass
        with obs.span("t.next", rid="r8"):
            pass
    by = {e["name"]: e["args"] for e in spans()}
    assert by["t.request"]["parent"] is None
    assert by["t.prefill"]["parent"] == by["t.request"]["span"]
    assert by["t.read"]["parent"] == by["t.prefill"]["span"]
    assert by["t.adopt"]["parent"] == by["t.request"]["span"]
    assert by["t.next"]["parent"] is None
    assert {a["rid"] for n, a in by.items() if n != "t.next"} == {"r7"}
    assert len({a["trace"] for a in by.values()}) == 1   # one thread


def test_a_span_closed_out_of_turn_leaves_the_stack_alone(tmp_path):
    """``module.step`` of a step whose update never came is closed after the
    next ``module.fit.batch`` has opened: the new batch stays the parent."""
    prof.reset()
    with session(tmp_path):
        left_over = obs.span("t.step", step=0)
        with obs.span("t.batch", step=0):
            left_over.__enter__()
        with obs.span("t.batch", step=1):
            left_over.__exit__(None, None, None)
            with obs.span("t.step", step=1):
                pass
        with obs.span("t.after"):
            pass
    batches = {e["args"]["step"]: e["args"] for e in named("t.batch")}
    steps = {e["args"]["step"]: e["args"] for e in named("t.step")}
    assert steps["0"]["parent"] == batches["0"]["span"]
    assert steps["1"]["parent"] == batches["1"]["span"]
    assert named("t.after")[0]["args"]["parent"] is None


def test_a_sampled_root_hangs_from_the_sessions_enclosing_span(tmp_path):
    prof.reset()
    with session(tmp_path):
        with obs.span("t.batch"):
            tok = obs.start_trace()
            with obs.span("t.step"):
                pass
            obs.end_trace(tok)
            with obs.span("t.next"):
                pass
    by = {e["name"]: e["args"] for e in spans()}
    assert by["t.step"]["parent"] == by["t.batch"]["span"]
    assert by["t.step"]["trace"] != by["t.batch"]["trace"]
    assert by["t.next"]["parent"] == by["t.batch"]["span"]


def test_a_span_whose_annotation_fails_changes_nothing(tmp_path, monkeypatch):
    from mxtpu.obs import trace

    def refuse(*_a, **_k):
        raise RuntimeError("no annotation")

    prof.reset()
    with session(tmp_path):
        with obs.span("t.outer"):
            monkeypatch.setattr(trace, "_Annotation", refuse)
            with pytest.raises(RuntimeError):
                with obs.span("t.broken"):
                    pass
            monkeypatch.undo()
            with obs.span("t.inner"):
                pass
    by = {e["name"]: e["args"] for e in spans()}
    assert "t.broken" not in by
    assert by["t.inner"]["parent"] == by["t.outer"]["span"]


def test_one_clock_for_task_and_span(tmp_path):
    """A profiler Task and an obs span opened one after the other come out
    in that order, on a clock that maps back to perf_counter and lies at
    the epoch."""
    prof.reset()
    prof.set_state("run")
    try:
        with session(tmp_path):
            t0 = time.perf_counter()
            with prof.Task("t.task"):
                time.sleep(0.002)
            with obs.span("t.span"):
                time.sleep(0.002)
            t1 = time.perf_counter()
    finally:
        prof.set_state("stop")
    evs = {e["name"]: e for e in prof.snapshot_events() if e.get("ph") == "X"}
    task, span = evs["t.task"], evs["t.span"]
    assert task["ts"] + task["dur"] <= span["ts"]
    for e in (task, span):
        back = (e["ts"] - prof.EPOCH_OFFSET_US) * 1e-6
        assert t0 <= back <= t1
        assert abs(e["ts"] * 1e-6 - time.time()) < 60.0


def test_a_span_has_its_twin_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    prof.reset()
    with session(tmp_path):
        with obs.span("t.twin", rid="r3"):
            time.sleep(0.001)
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(found[0])
    twins = [e for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name == "mxtpu.t.twin"]
    assert len(twins) == 1
    assert dict(twins[0].stats).get("rid") == "r3"
    ev, = named("t.twin")
    assert abs(twins[0].duration_ns * 1e-3 - ev["dur"]) < 200.0


# ---------------------------------------------------------------------------
# where the work happens
# ---------------------------------------------------------------------------

def test_generate_scheduler_spans(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_SERVE_GENERATE_SLOTS", "4")
    monkeypatch.setenv("MXTPU_SERVE_GENERATE_PREFILL_BUCKETS", "4,8,16")
    engine = InferenceEngine(_lm_symbol(), _lm_params(), {},
                             data_shapes={"data": (1,)}, buckets=(1,))
    sched = GenerateScheduler(engine, 16, slots=4)
    rng = np.random.RandomState(3)
    try:
        # every program once, outside the session
        assert sched.submit("warm", rng.randint(0, 17, 5), 3,
                            None).wait(60)[0] == "ok"
        at0 = sched.stats()
        prof.reset()
        with session(tmp_path):
            reqs = [sched.submit("r%d" % i, rng.randint(0, 17, 3 + i), 4,
                                 None) for i in range(6)]
            assert all(r.wait(60)[0] == "ok" for r in reqs)
        at1 = sched.stats()
    finally:
        sched.stop()
    # one ``serve.gen.step`` a turn of the lane: the next step goes out
    # first, then the step BEFORE it is read and emitted. So a lane's first
    # turn has nothing to read and its last nothing to dispatch.
    steps = sorted(named("serve.gen.step"), key=lambda e: e["ts"])
    kids = ("serve.gen.step.dispatch", "serve.gen.step.read",
            "serve.gen.step.emit")
    by_parent = {}
    for e in spans():
        by_parent.setdefault(e["args"]["parent"], []).append(e)
    turns = []
    for step in steps:
        mine = sorted(by_parent[step["args"]["span"]], key=lambda e: e["ts"])
        turns.append(tuple(e["name"] for e in mine))
        assert turns[-1] in (kids, kids[:1], kids[1:])
        assert all(inside(e, step) for e in mine)
        assert all(a["ts"] + a["dur"] <= b["ts"]
                   for a, b in zip(mine, mine[1:]))
        assert int(step["args"]["active"]) >= 1
    assert turns[0] == kids[:1] and turns[-1] == kids[1:]
    assert kids in turns
    dispatched = at1["steps"] - at0["steps"]
    assert sum(kids[0] in t for t in turns) == dispatched > 0
    assert at1["steps_ahead"] - at0["steps_ahead"] == turns.count(kids)
    # every step was read, once (all six finish by length, which the host
    # counts ahead: no step goes out that nobody waits for)
    assert sum(kids[1] in t for t in turns) == dispatched
    # one set per request, each with the request's id
    admits = {e["args"]["span"]: e for e in named("serve.gen.admit")}
    for name in ("prefill", "first_read", "adopt"):
        got = named("serve.gen." + name)
        assert sorted(e["args"]["rid"] for e in got) == \
            sorted("r%d" % i for i in range(6)), name
        assert all(e["args"]["parent"] in admits for e in got), name
    assert all(int(e["args"]["queued"]) >= 1 for e in admits.values())
    # one thread, one trace (the engine's device runs land on a watcher's)
    assert len({e["tid"] for e in spans()
                if e["name"].startswith("serve.gen.")}) == 1


def _mlp():
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_fit_spans(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_MODULE_FUSED", "1")
    rng = np.random.RandomState(0)
    x = rng.randn(96, 10).astype("float32")
    y = rng.randint(0, 4, 96).astype("float32")
    train = mx.io.NDArrayIter(x, y, batch_size=32, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    prof.reset()
    with session(tmp_path):
        mod.fit(train, optimizer="sgd", num_epoch=1,
                optimizer_params={"learning_rate": 0.05},
                initializer=mx.initializer.Xavier(), eval_metric="acc")
    assert mod._fused is not None
    batches = sorted(named("module.fit.batch"), key=lambda e: e["ts"])
    assert [b["args"]["step"] for b in batches] == ["0", "1", "2"]
    for name in ("module.step", "module.fit.next_batch"):
        got = sorted(named(name), key=lambda e: e["ts"])
        assert len(got) == 3, name
        for child, batch in zip(got, batches):
            assert child["args"]["parent"] == batch["args"]["span"]
            assert child["args"]["step"] == batch["args"]["step"]
            assert inside(child, batch)
    # unsampled: the kvstore wire would have carried nothing
    assert [e for e in prof.snapshot_events()
            if e.get("ph") in ("s", "f")] == []


def test_fit_records_nothing_with_no_session_and_no_sampling(monkeypatch):
    monkeypatch.setenv("MXTPU_MODULE_FUSED", "1")
    monkeypatch.delenv("MXTPU_TRACE_SAMPLE", raising=False)
    rng = np.random.RandomState(0)
    train = mx.io.NDArrayIter(rng.randn(64, 10).astype("float32"),
                              rng.randint(0, 4, 64).astype("float32"),
                              batch_size=32, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    prof.reset()
    mod.fit(train, optimizer="sgd", num_epoch=1,
            initializer=mx.initializer.Xavier(), eval_metric="acc")
    assert spans() == []


def test_lowered_text_carries_operator_and_node():
    """Every operation of a compiled step names the MXNet operator and node
    it came from, forward and transposed."""
    import jax
    import jax.numpy as jnp
    from mxtpu.symbol import eval_graph
    data = mx.sym.Variable("data")
    conv = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3),
                              name="conv_a")
    net = mx.sym.Activation(conv, act_type="relu", name="relu_a")

    def loss(feed):
        outs, _aux = eval_graph(net._outputs, feed, training=True)
        return jnp.sum(outs[0])

    feed = {"data": jnp.ones((2, 3, 8, 8)),
            "conv_a_weight": jnp.ones((4, 3, 3, 3)),
            "conv_a_bias": jnp.zeros((4,))}
    text = jax.jit(jax.grad(loss)).lower(feed).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    conv_ops = {n for n in names if "Convolution/conv_a" in n}
    assert any(n.endswith("conv_general_dilated") and "transpose(" not in n
               for n in conv_ops)
    assert any("transpose(" in n for n in conv_ops)
    assert any("Activation/relu_a" in n for n in names)
