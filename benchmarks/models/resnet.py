"""The program's side of the ``resnet`` family: the symbol that
``train_imagenet.py`` trains, from the repo's own symbol library."""
from __future__ import annotations

import importlib.util
import os

_SYMBOLS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "example", "image-classification",
    "symbols", "resnet.py")


def symbol(cfg):
    spec = importlib.util.spec_from_file_location("_bench_resnet_symbols",
                                                  _SYMBOLS)
    lib = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lib)
    return lib.get_symbol(
        num_classes=int(cfg["num_classes"]), num_layers=int(cfg["num_layers"]),
        image_shape=",".join(str(int(x)) for x in cfg["image_shape"]))
