"""The system under test, as the benchmark drives the ImageNet bottleneck
ResNet family: the family's adapter.  With the other families' adapters,
the only modules of ``bench/`` that import the program's models and
engines (``src/repro``).

It turns a configuration file and the benchmark's seeded weights into the
program's own types (``BottleneckConfig``, ``QResNetParams`` with a third
conv a block), and builds the engine a traffic mix names.  The program
decides everything else: backend (its default), kernels, tiling.
"""
from __future__ import annotations

import os
import sys

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", "..",
                                   "src"))
if SRC not in sys.path:
    sys.path.insert(0, SRC)

from repro.compile.params import (  # noqa: E402
    QBlockParams, QConvParams, QLinearParams, QResNetParams)
from repro.core.quant import QSpec  # noqa: E402
from repro.models.resnet import BottleneckConfig  # noqa: E402
from repro.serve import ImageRequest as Request  # noqa: E402
from repro.serve.engine import ResNetEngine  # noqa: E402

__all__ = ["Request", "build_engine", "program_config",
           "program_params"]


def program_config(cfg: dict) -> BottleneckConfig:
    return BottleneckConfig(
        cfg["name"], stage_blocks=tuple(cfg["stage_blocks"]),
        base_width=cfg["base_width"], expansion=cfg["expansion"],
        num_classes=cfg["num_classes"], img=cfg["img"])


def program_params(net, weights: dict) -> QResNetParams:
    """The benchmark's weights (``reference.make_weights`` of this family)
    as the program's typed parameters, with the grids ``net`` states."""
    def conv(c):
        w, b = weights[c.name]
        return QConvParams(wq=w, bq=b, w_spec=QSpec(8, True, c.w_exp),
                           x_spec=QSpec(8, False, c.x_exp),
                           b_spec=QSpec(16, True, c.b_exp))

    blocks = tuple(QBlockParams(conv(b.conv0), conv(b.conv1),
                                conv(b.ds) if b.ds else None, conv(b.conv2))
                   for b in net.blocks)
    w, b = weights["fc"]
    fc = QLinearParams(wq=w, b=b, w_spec=QSpec(8, True, net.fc_w_exp),
                       x_spec=QSpec(8, False, net.act_exp))
    return QResNetParams(stem=conv(net.stem), blocks=blocks, fc=fc)


def build_engine(cfg: dict, net, weights: dict, server: dict, devices):
    """The engine a traffic mix's ``server`` section names:
    ``{"engine": "ResNetEngine", "batch": B, "batch_sizes": [...]}``, on
    the first of ``devices`` (JAX's default device)."""
    del devices
    if server["engine"] != "ResNetEngine":
        raise ValueError(f"unknown engine {server['engine']!r} for the "
                         f"resnet_imagenet family")
    return ResNetEngine(program_config(cfg), program_params(net, weights),
                        batch=server["batch"],
                        batch_sizes=tuple(server["batch_sizes"]))
