"""ResNet-50 training step on one TPU chip: images/sec and MFU.

Mirrors the reference's benchmark methodology
(`example/image-classification/train_imagenet.py` + docs/faq/perf.md:176-185,
measured with batch 32 on 1x P100 = 181.53 img/s): synthetic ImageNet-shaped
data pre-staged on the device, the full training step (forward + backward +
SGD-momentum update) through `parallel.ShardedTrainer` in bf16, steady-state
timing after warm-up (`mxtpu/benchmarking.py`). The input pipeline is off.

One process, one chip. When JAX's default device is not a TPU the script
prints one line to stderr, prints no metric and exits 1: a CPU number is
never written under this metric's name. A `device_kind` with no row in the
peaks table is an error too. The `workloads` benchmark with a cell per
configuration is ROADMAP A1; this file stays one cell until then.

Prints ONE JSON line:
  {"metric", "value", "unit", "vs_baseline", "mfu", "batch", "device": {...}}
"""
from __future__ import annotations

import json
import sys

import numpy as np

BASELINE_IMG_S = 181.53  # ResNet-50 train, batch 32, 1x P100 (perf.md:185)

# ResNet-50 at 224x224: ~4.089 GFLOPs forward per image (2*MACs). A training
# step is fwd + bwd ~= 3x forward (bwd is ~2x fwd).
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 4.089e9

# Peak dense bf16 TFLOP/s of one chip, keyed by jax's `device_kind`.
# "TPU v5 lite" is how jax 0.9.0 names a v5e chip; 197 is Google Cloud's
# published figure ("TPU v5e" documentation). Rows are added with their
# source when a chip is actually run.
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}

BATCH = 32


def peak_tflops(device_kind):
    if device_kind not in PEAK_BF16_TFLOPS:
        raise KeyError(
            "no peak FLOP/s on record for device_kind %r (known: %s); add a "
            "row to PEAK_BF16_TFLOPS with its source — MFU is not reported "
            "against a guessed peak" % (device_kind,
                                        sorted(PEAK_BF16_TFLOPS)))
    return PEAK_BF16_TFLOPS[device_kind]


def run_bench():
    """Images/sec of the ResNet-50 bf16 training step at batch 32."""
    import jax
    import mxtpu as mx
    from mxtpu import gluon
    from mxtpu.benchmarking import timed_steps
    from mxtpu.gluon.model_zoo import vision
    from mxtpu.parallel import MeshContext, ShardedTrainer

    mx.random.seed(0)
    net = vision.get_resnet(1, 50)
    net.initialize(mx.init.Xavier())
    x = np.random.uniform(0, 1, (BATCH, 3, 224, 224)).astype(np.float32)
    y = np.random.randint(0, 1000, (BATCH,)).astype(np.float32)
    net(mx.nd.array(x[:1]))

    # one chip on purpose, whatever the host holds: this is the per-chip cell
    mesh = MeshContext(jax.devices()[:1], data=1)
    st = ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                        "sgd", {"learning_rate": 0.05, "momentum": 0.9,
                                "wd": 1e-4},
                        mesh=mesh, dtype="bfloat16")
    st.step(x, y)                       # compile
    # data pre-staged on device (the prefetching DataLoader's job): IO is
    # excluded, as in the reference's benchmark_score.py
    xd = st._shard_batch([x])[0]
    yd = st._shard_batch([y])[0]
    sec, _ = timed_steps(lambda _s: st.step_async(xd, yd),
                         warmup=3, iters=100)
    return BATCH / sec


def main():
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print("bench: JAX found no TPU (default platform is %r); nothing "
              "was measured" % dev.platform, file=sys.stderr)
        return 1
    peak = peak_tflops(dev.device_kind)
    img_s = run_bench()
    print(json.dumps({
        "metric": "resnet50_train_img_per_sec",
        "value": round(img_s, 2),
        "unit": "images/sec",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "mfu": round(img_s * RESNET50_TRAIN_FLOPS_PER_IMG / (peak * 1e12),
                     4),
        "batch": BATCH,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
