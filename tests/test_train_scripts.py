"""End-to-end tests for the assembled image-classification training path
(reference example/image-classification/train_cifar10.py + common/fit.py:
record-file IO -> augmenters -> fit -> checkpoint -> resume)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxtpu as mx

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "example", "image-classification",
                      "train_cifar10.py")


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run([sys.executable, SCRIPT] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=560)


# slow: several processes (the script runs twice, each with its pool of
# decode workers)
@pytest.mark.slow
def test_cifar_script_trains_checkpoints_and_resumes(tmp_path):
    base = ["--synthetic", "48", "--num-layers", "8", "--batch-size", "8",
            "--disp-batches", "4", "--lr", "0.05", "--data-nthreads", "2",
            "--model-prefix", "ckpt/r8"]
    out = _run(base + ["--num-epochs", "1"], str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    assert (tmp_path / "ckpt" / "r8-0001.params").exists()
    assert (tmp_path / "ckpt" / "r8-symbol.json").exists()
    assert "Validation-accuracy" in out.stderr + out.stdout

    # resume from epoch 1 and train one more epoch
    out = _run(base + ["--num-epochs", "2", "--load-epoch", "1"],
               str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    log = out.stderr + out.stdout
    assert "Loaded model" in log
    assert (tmp_path / "ckpt" / "r8-0002.params").exists()
    # the resumed epoch is epoch 1 (0-based), not a restart from 0
    assert "Epoch[1]" in log and "Epoch[0]" not in log


def test_synthetic_recfile_through_record_iter(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "example",
                                    "image-classification"))
    try:
        from common.data import make_synthetic_recfile
    finally:
        sys.path.pop(0)
    rec = str(tmp_path / "t.rec")
    make_synthetic_recfile(rec, 20, 28, 4)
    it = mx.io.ImageRecordIter(path_imgrec=rec, data_shape=(3, 28, 28),
                               batch_size=5, shuffle=True, rand_crop=True,
                               rand_mirror=True, pad=2,
                               preprocess_threads=2)
    batch = next(it)
    assert batch.data[0].shape == (5, 3, 28, 28)
    assert batch.label[0].shape == (5,)
    labels = batch.label[0].asnumpy()
    assert set(labels.astype(int)).issubset({0, 1, 2, 3})


def test_record_augmentation_surface():
    """The reference record-iter augmentation knobs (affine, pad, hsl)
    produce valid images of unchanged geometry (image_aug_default.cc)."""
    from mxtpu import _image_worker as w
    rng = np.random.RandomState(0)
    img = rng.randint(0, 256, (32, 32, 3)).astype(np.uint8)
    out = w.affine_augment(img, np.random.RandomState(1),
                           max_rotate_angle=10, max_shear_ratio=0.1,
                           min_random_scale=0.8, max_random_scale=1.2,
                           max_aspect_ratio=0.25)
    assert out.shape == img.shape and out.dtype == np.uint8
    padded = w.pad_image(img, 4, fill_value=127)
    assert padded.shape == (40, 40, 3)
    assert (padded[0, 0] == 127).all()
    jit = w.hsl_jitter(img, np.random.RandomState(2), random_h=36,
                       random_s=50, random_l=50)
    assert jit.shape == img.shape and jit.dtype == np.uint8
    # identity config is a no-op passthrough
    assert w.affine_augment(img, rng) is img
    assert w.hsl_jitter(img, rng) is img
    # HLS round-trip is lossless-ish on uint8
    h, l, s = w._rgb_to_hls(img)
    back = w._hls_to_rgb(h, l, s)
    assert np.abs(back.astype(int) - img.astype(int)).max() <= 1
    # hue units are OpenCV's 0-180 scale: a +/-90 jitter bound spans the
    # whole wheel (2 degrees per unit, image_aug_default.cc)
    red = np.zeros((1, 1, 3), np.uint8)
    red[..., 0] = 200

    class FixedRng:
        def uniform(self, lo, hi):
            return hi
    shifted = w.hsl_jitter(red, FixedRng(), random_h=90)
    expect_cyan = np.zeros((1, 1, 3), np.uint8)
    expect_cyan[..., 1] = 200
    expect_cyan[..., 2] = 200
    np.testing.assert_array_equal(shifted, expect_cyan)
