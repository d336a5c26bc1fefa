"""The system under test, as the benchmark drives it: the one module of
``bench/`` that imports the program (``src/repro``).

It turns a configuration file and the benchmark's seeded weights into the
program's own types, and builds the engine a traffic mix names.  The
program decides everything else: backend (its default), kernels, tiling.
"""
from __future__ import annotations

import os
import sys

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

from repro.compile.params import (  # noqa: E402
    QBlockParams, QConvParams, QLinearParams, QResNetParams)
from repro.core.quant import QSpec  # noqa: E402
from repro.models.resnet import ResNetConfig  # noqa: E402
from repro.serve import ImageRequest  # noqa: E402
from repro.serve.engine import ResNetEngine, ShardedResNetEngine  # noqa: E402

__all__ = ["ImageRequest", "build_engine", "program_config",
           "program_params"]


def program_config(cfg: dict) -> ResNetConfig:
    return ResNetConfig(cfg["name"], blocks_per_stage=cfg["blocks_per_stage"],
                        base_width=cfg["base_width"],
                        num_classes=cfg["num_classes"], img=cfg["img"],
                        bw_w=cfg["bw_w"], bw_x=cfg["bw_x"], bw_b=cfg["bw_b"])


def program_params(net, weights: dict) -> QResNetParams:
    """The benchmark's weights (``reference.make_weights``) as the
    program's typed parameters, with the grids ``net`` states."""
    def conv(c):
        w, b = weights[c.name]
        return QConvParams(wq=w, bq=b, w_spec=QSpec(8, True, c.w_exp),
                           x_spec=QSpec(8, False, c.x_exp),
                           b_spec=QSpec(16, True, c.b_exp))

    blocks = tuple(QBlockParams(conv(b.conv0), conv(b.conv1),
                                conv(b.ds) if b.ds else None)
                   for b in net.blocks)
    w, b = weights["fc"]
    fc = QLinearParams(wq=w, b=b, w_spec=QSpec(8, True, net.fc_w_exp),
                       x_spec=QSpec(8, False, net.act_exp))
    return QResNetParams(stem=conv(net.stem), blocks=blocks, fc=fc)


def build_engine(cfg: dict, net, weights: dict, server: dict, devices):
    """The engine a traffic mix's ``server`` section names, on ``devices``.

    ``{"engine": "ResNetEngine", "batch": B, "batch_sizes": [...]}`` or
    ``{"engine": "ShardedResNetEngine", "batch": B, "batch_sizes": [...],
    "replicas": R, "slack_ms": S}``."""
    pcfg = program_config(cfg)
    params = program_params(net, weights)
    kind = server["engine"]
    sizes = tuple(server["batch_sizes"])
    if kind == "ResNetEngine":
        return ResNetEngine(pcfg, params, batch=server["batch"],
                            batch_sizes=sizes)
    if kind == "ShardedResNetEngine":
        return ShardedResNetEngine(
            pcfg, params, batch=server["batch"], batch_sizes=sizes,
            replicas=server["replicas"], devices=devices,
            slack_ms=server["slack_ms"])
    raise ValueError(f"unknown engine {kind!r}")
