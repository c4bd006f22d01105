"""Driver ``train_fit``: one ``Module.fit`` call is the whole run.

The feed hands ``fit`` the one synthetic batch: first the warm-up steps
(set-up: both of the fused step's programs compile there, and the first three
steps are kept for the comparison), then, with the device drained, the
measured window. ``fit`` drives every step itself; the harness only counts
them and holds the clock. The window closes in ``block_until_ready`` on the
last step's outputs.
"""
from __future__ import annotations

import collections
import time

import numpy as np

CHECKED_STEPS = 3
# How far ``fit`` may run ahead of the device. With one step in flight any
# hiccup of the host longer than a step idles the device (40 s windows then
# spread by 1%, PERF.md section 6); ``fit`` itself queues without bound.
STEPS_IN_FLIGHT = 4
# The plain reference put in the program's place: the control (what the
# program holds in bfloat16 held in scaled float8), the witness (held in
# bfloat16, as the configuration states), and the fault of half of the batch
# left out.
STAND_INS = {"fp8": {"held": "fp8"}, "bf16": {"held": "bf16"},
             "half_batch": {"half": True}}


class WindowFeed:
    """A ``DataIter`` that serves one device batch: ``warm`` times as set-up,
    then for ``seconds`` of wall clock."""

    def __init__(self, mx, module, data, label, warm, run):
        self._mx, self._mod, self._run = mx, module, run
        self._data, self._label = mx.nd.NDArray(data), mx.nd.NDArray(label)
        self.batch_size = int(data.shape[0])
        self._warm = int(warm)
        self.served = 0
        self.t0 = self.t1 = None
        self.stats_at_t0 = None
        self._inflight = collections.deque()

    @property
    def provide_data(self):
        return [self._mx.io.DataDesc("data", self._data.shape, "float32")]

    @property
    def provide_label(self):
        return [self._mx.io.DataDesc("softmax_label", self._label.shape,
                                     "float32")]

    def __iter__(self):
        return self

    def reset(self):
        pass

    def _drain(self):
        for out in self._mod.get_outputs():
            out._data.block_until_ready()

    def _hold_back(self):
        """Let ``fit`` run ``STEPS_IN_FLIGHT`` steps ahead of the device and
        no further: wait for the step that many before the one just
        dispatched. No value is read back. Without it the host queues steps
        without bound and the window would close long after its time."""
        if self.served:
            self._inflight.append(self._mod.get_outputs()[0]._data)
        if len(self._inflight) >= STEPS_IN_FLIGHT:
            self._inflight.popleft().block_until_ready()

    def __next__(self):
        self._hold_back()
        now = time.perf_counter()
        if self.t0 is None and self.served == self._warm:
            self._drain()
            self.stats_at_t0 = fused_stats()
            self._run.window_open()
            self.t0 = time.perf_counter()
        elif self.t0 is not None and now - self.t0 >= self._run.seconds:
            self._drain()
            self.t1 = time.perf_counter()
            self._run.window_close(self.t0, self.t1)
            raise StopIteration
        self.served += 1
        return self._mx.io.DataBatch(
            data=[self._data], label=[self._label], pad=0,
            provide_data=self.provide_data, provide_label=self.provide_label)

    next = __next__


def fused_stats():
    """The fused trainer's own counts, through the program's registry."""
    from mxtpu.obs import metrics
    views = metrics.REGISTRY.snapshot()["views"]
    found = [v for k, v in sorted(views.items())
             if k.startswith("module.fused") and "steps" in v]
    return dict(found[-1]) if found else {}


class FirstSteps:
    """``batch_end_callback``: keeps what the comparison needs of the first
    steps: each step's outputs, the parameters after step 1 and after step 3
    (read before step 4 donates their buffers)."""

    def __init__(self, module, label, mark):
        self._mod, self._mark = module, mark
        self._label = np.asarray(label).astype(np.int64)
        self.losses, self.rows, self.params = [], [], {}

    def __call__(self, param):
        n = param.nbatch
        if n >= CHECKED_STEPS:
            return
        self._mark("step_%d_done" % (n + 1))
        prob = self._mod.get_outputs()[0].asnumpy().astype(np.float64)
        picked = prob[np.arange(len(self._label)), self._label]
        self.rows.append(-np.log(np.maximum(picked, 1e-30)))
        self.losses.append(float(np.mean(self.rows[-1])))
        if n in (0, CHECKED_STEPS - 1):
            args, _aux = self._mod.get_params()
            self.params[n + 1] = {k: v.asnumpy().astype(np.float32)
                                  for k, v in args.items()}


def leaf_norms(tree):
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in tree.items()}


def leaf_gaps(got, want, skip=()):
    """For each leaf the gap between the two norms, against the reference's
    norm of that leaf or of the median leaf, whichever is larger; sorted,
    widest first, as ``(gap, leaf)``."""
    floor = float(np.median(list(want.values())))
    return sorted(((abs(got[k] - w) / max(w, floor, 1e-30), k)
                   for k, w in want.items() if k not in skip), reverse=True)


def describe(gaps):
    vals = [g for g, _k in gaps]
    return {"worst": ["%s=%.3g" % (k, g) for g, k in gaps[:4]],
            "p90": float(np.percentile(vals, 90)),
            "median": float(np.median(vals))}


def compare(run, side, hp, w0, want, got):
    """The numbers compared for ``side`` (the program or a stand-in), as
    ``(name, value)``. ``want`` is the reference's
    ``(losses, per-row losses, params after steps 1 and 3)``, ``got`` the same
    of the side under test. Each step's loss; the first step's loss row by
    row (the mean of the rows' gaps, so that they cannot cancel); the first
    gradient's norm and the parameters' change over the three steps, each by
    the worst leaf and by the median leaf."""
    ref = run.reference
    losses, rows, params_after = want
    got_losses, got_rows, got_params = got
    out = []
    for i, (g, w) in enumerate(zip(got_losses, losses)):
        out.append(("loss_gap_step%d" % (i + 1), abs(g - w) / abs(w)))
    n = min(len(rows[0]), len(got_rows[0]))
    out.append(("loss_rows_gap_step1", float(
        np.mean(np.abs(got_rows[0][:n] - rows[0][:n])) / np.mean(rows[0]))))
    # the gradient as the optimizer got it, from the state after one step:
    # w1 - w0 = -lr * (g + wd * w0)
    def grad_of(w1):
        return {k: -(np.asarray(w1[k], np.float64) - w0[k]) / hp["lr"]
                - ref.weight_decay_of(k, hp["wd"]) * w0[k] for k in w0}

    def change_of(w):
        return {k: np.asarray(w[k], np.float64) - w0[k] for k in w0}

    look = {}

    def by_leaf(name, got_tree, want_tree, skip=()):
        gaps = leaf_gaps(leaf_norms(got_tree), leaf_norms(want_tree), skip)
        out.append((name + "_norm_gap", gaps[0][0]))
        out.append((name + "_norm_gap_median",
                    float(np.median([g for g, _k in gaps]))))
        look[name] = describe(gaps)

    want_g = grad_of(params_after[1])
    by_leaf("grad", grad_of(got_params[1]), want_g)
    # leaves whose reference gradient is nought to rounding are left out of
    # the change: under a thousandth of the median leaf's
    norms = leaf_norms(want_g)
    floor = 1e-3 * float(np.median(list(norms.values())))
    skip = {k for k, v in norms.items() if v < floor}
    by_leaf("delta", change_of(got_params[CHECKED_STEPS]),
            change_of(params_after[CHECKED_STEPS]), skip)
    look["leaves_left_out"] = len(skip)
    run.looks[side] = look
    return out


def follow_reference(run, state, hp, **variant):
    """Run the plain reference over the first steps from its own copy of the
    seed's state; returns ``(losses, per-row losses, {1: params, 3: params})``
    on the host."""
    params, _aux, data, label = state
    losses, rows, after = run.reference.follow(
        run.cfg, params, data, label, CHECKED_STEPS, hp, **variant)
    host = lambda t: {k: np.asarray(v) for k, v in t.items()}
    return losses, rows, {1: host(after[0]), CHECKED_STEPS: host(after[-1])}


def run(run):
    import mxtpu as mx

    cfg, tr = run.cfg, run.traffic
    hp = {"lr": float(cfg["optimizer"]["learning_rate"]),
          "momentum": float(cfg["optimizer"]["momentum"]),
          "wd": float(cfg["optimizer"]["wd"])}
    batch = int(tr["batch_per_chip"])
    with run.span("make_state"):
        params, aux, data, label = run.reference.init_state(cfg, run.seed,
                                                            batch)
    # the fused step donates the parameters' buffers: keep the host's copy,
    # and let the reference make its own state from the seed afterwards
    w0 = {k: np.asarray(v, np.float64) for k, v in params.items()}
    run.mark("state_made")
    mod = mx.mod.Module(run.model.symbol(cfg))
    feed = WindowFeed(mx, mod, data, label, tr["warm_steps"], run)
    probe = FirstSteps(mod, label, run.mark)
    mod.fit(feed, num_epoch=1, kvstore="device",
            eval_metric=mx.metric.create(tr["eval_metric"]),
            optimizer=cfg["optimizer"]["name"],
            optimizer_params={"learning_rate": hp["lr"], "wd": hp["wd"],
                              "momentum": hp["momentum"],
                              "multi_precision": True},
            arg_params={k: mx.nd.NDArray(v) for k, v in params.items()},
            aux_params={k: mx.nd.NDArray(v) for k, v in aux.items()},
            batch_end_callback=[probe])
    t0, t1 = run.window
    stats = fused_stats()
    in_window = {k: stats.get(k, 0) - feed.stats_at_t0.get(k, 0)
                 for k in ("steps", "compiles")}
    steps = feed.served - int(tr["warm_steps"])
    run.counters.update(
        steps=steps, samples=steps * batch, batch=batch,
        fused_steps_in_window=in_window["steps"],
        compiles_in_window=in_window["compiles"],
        macs_per_sample=run.reference.macs_per_image(cfg))
    metrics = {"train_samples_per_s": steps * batch / (t1 - t0)}
    # an eager fallback, or a compile inside the window, is a failed run
    failed = 0 if (in_window["steps"] == steps
                   and in_window["compiles"] == 0) else steps

    def check():
        """Runs once the window has closed and the peak has been read."""
        nonlocal mod, feed
        probe._mod = None
        del mod, feed
        state = run.reference.init_state(cfg, run.seed, batch)
        want = follow_reference(run, state, hp)
        numbers = compare(run, "program", hp, w0, want,
                          (probe.losses, probe.rows, probe.params))
        stood_in = {}
        for kind in run.stand_ins:
            variant = dict(STAND_INS[kind])
            if variant.pop("half", False):
                variant["rows"] = batch // 2
            stood_in[kind] = compare(
                run, kind, hp, w0, want,
                follow_reference(run, state, hp, **variant))
        return numbers, stood_in

    return {"attempted": steps, "failed": failed, "metrics": metrics,
            "check": check}
