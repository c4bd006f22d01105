#!/usr/bin/env python
"""Train ImageNet-1k from record files through the full real-data pipeline
(reference example/image-classification/train_imagenet.py: record IO ->
augmenters -> fit -> checkpoint; the BASELINE.md headline workload).

Point --data-train / --data-val at imagenet .rec files (build them with
tools/im2rec.py), or pass --benchmark 1 for the synthetic-input
throughput mode the reference also ships.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.dirname(__file__))

from common import data, fit  # noqa: E402


def main(argv=None, **fit_kwargs):
    """Parse ``argv`` (default: the command line) and train; returns the
    trained Module (or None for ``--engine sharded`` / ``--test-io``).
    ``fit_kwargs`` reach ``common.fit.fit`` (e.g. ``batch_end_callback``)."""
    parser = argparse.ArgumentParser(
        description="train imagenet-1k",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    fit.add_fit_args(parser)
    data.add_data_args(parser)
    data.add_data_aug_args(parser)
    data.set_data_aug_level(parser, 3)
    parser.set_defaults(
        network="resnet",
        num_layers=50,
        num_classes=1000,
        num_examples=1281167,
        image_shape="3,224,224",
        min_random_scale=1,
        num_epochs=80,
        lr_step_epochs="30,60",
        dtype="float32",
    )
    args = parser.parse_args(argv)

    from importlib import import_module
    if args.engine == "sharded":
        from mxtpu.gluon.model_zoo import vision
        net = vision.get_resnet(1, args.num_layers,
                                classes=args.num_classes)
    else:
        net = import_module("symbols." + args.network).get_symbol(
            **vars(args))

    return fit.fit(args, net, data.get_rec_iter, **fit_kwargs)


if __name__ == "__main__":
    main()
