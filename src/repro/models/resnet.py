"""ResNet8 / ResNet20 for CIFAR-10 — the paper's own networks (§IV) — and
the ImageNet bottleneck family (ResNet-50 v1.5) on the same integer scheme.

Two execution paths share one parameter set:

* ``forward``      — QAT float path (Brevitas-style): pow2-int8 fake-quant on
                     weights and activations, BN in float, STE gradients.
* ``int_forward``  — the integer inference graph the FPGA executes: int8
                     weights/activations, int16 biases (s_b = s_x + s_w),
                     int32 accumulators, requantization by bit shift, and the
                     residual add folded into the next conv's accumulator
                     (paper Fig. 13).  tests/test_resnet.py asserts the two
                     paths agree bit-exactly after BN folding + calibration.

The residual-stream handling mirrors core.graph.optimize(): no Add nodes —
conv1 of each block receives the skip stream as its accumulator init.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant as Q
from repro.core.quant import QSpec


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    name: str
    blocks_per_stage: int
    base_width: int = 16
    num_classes: int = 10
    img: int = 32
    bw_w: int = 8          # weight bits (paper)
    bw_x: int = 8          # activation bits
    bw_b: int = 16         # bias bits
    quant: str = "qat"     # qat | none
    residual_fusion: bool = True

    @property
    def stage_blocks(self) -> Tuple[int, ...]:
        """Blocks in each of the three stages."""
        return (self.blocks_per_stage,) * 3


@dataclasses.dataclass(frozen=True)
class BottleneckConfig:
    """The ImageNet bottleneck family (He et al. 2016, Table 1): a 7x7/2
    stem to ``base_width`` channels and a 3x3/2 max pool, then stages of
    bottleneck blocks (1x1 -> 3x3 -> 1x1, widths ``base_width * 2**stage``,
    outputs ``expansion`` times wider), a 1x1 projection shortcut on every
    stage's first block and the stride on its 3x3 conv (v1.5).  Only the
    integer path serves it: ``compile_model`` with ``pallas`` or
    ``lax-int``."""
    name: str
    stage_blocks: Tuple[int, ...] = (3, 4, 6, 3)
    base_width: int = 64
    expansion: int = 4
    num_classes: int = 1000
    img: int = 224


def block_strides(cfg: "ResNetConfig") -> List[int]:
    out = []
    for stage in range(3):
        for bi in range(cfg.blocks_per_stage):
            out.append(2 if (stage > 0 and bi == 0) else 1)
    return out


RESNET8 = ResNetConfig("resnet8", blocks_per_stage=1)
RESNET20 = ResNetConfig("resnet20", blocks_per_stage=3)
RESNET50 = BottleneckConfig("resnet50")

# static activation exponent grid: inputs in [0,1); post-ReLU activations are
# unsigned 8-bit with exponent -5 (range [0,8)), pre-add signed -5.
X_SPEC = QSpec(8, signed=False, exp=-7)      # input images (u8/255 ~ [0,1))
A_SPEC = QSpec(8, signed=False, exp=-4)      # post-ReLU feature maps
W_EXP = -7


def _conv_init(key, fh, fw, ic, oc):
    fan_in = fh * fw * ic
    w = jax.random.normal(key, (fh, fw, ic, oc), jnp.float32)
    return w * np.sqrt(2.0 / fan_in)


def _bn_init(oc):
    return dict(gamma=jnp.ones((oc,)), beta=jnp.zeros((oc,)),
                mean=jnp.zeros((oc,)), var=jnp.ones((oc,)))


def init_params(cfg: ResNetConfig, key) -> dict:
    ks = iter(jax.random.split(key, 64))
    p = dict(stem=dict(w=_conv_init(next(ks), 3, 3, 3, cfg.base_width),
                       b=jnp.zeros((cfg.base_width,)), bn=_bn_init(cfg.base_width)))
    blocks = []
    ich = cfg.base_width
    for stage in range(3):
        och = cfg.base_width * (2 ** stage)
        for bi in range(cfg.blocks_per_stage):
            stride = 2 if (stage > 0 and bi == 0) else 1
            blk = dict(
                conv0=dict(w=_conv_init(next(ks), 3, 3, ich, och),
                           b=jnp.zeros((och,)), bn=_bn_init(och)),
                conv1=dict(w=_conv_init(next(ks), 3, 3, och, och),
                           b=jnp.zeros((och,)), bn=_bn_init(och)),
            )
            if stride != 1 or ich != och:
                blk["ds"] = dict(w=_conv_init(next(ks), 1, 1, ich, och),
                                 b=jnp.zeros((och,)), bn=_bn_init(och))
            blocks.append(blk)
            ich = och
    p["blocks"] = blocks
    p["fc"] = dict(w=jax.random.normal(next(ks), (ich, cfg.num_classes)) / np.sqrt(ich),
                   b=jnp.zeros((cfg.num_classes,)))
    return p


# ---------------------------------------------------------------------------
# QAT float path
# ---------------------------------------------------------------------------


def _fq_w(w, cfg):
    if cfg.quant != "qat":
        return w
    spec = QSpec(cfg.bw_w, True, W_EXP)
    return Q.fake_quant(w, spec)


def _fq_x(x, cfg, spec=A_SPEC):
    if cfg.quant != "qat":
        return x
    return Q.fake_quant(x, spec)


def _conv(x, w, b, stride=1):
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _bn(x, bn, train, eps=1e-5):
    if train:
        mu = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.var(x, axis=(0, 1, 2))
    else:
        mu, var = bn["mean"], bn["var"]
    return (x - mu) * jax.lax.rsqrt(var + eps) * bn["gamma"] + bn["beta"]


def forward(params, cfg: ResNetConfig, images, train=False):
    """images: (B,H,W,3) float in [0,1).  Returns logits (B,10)."""
    x = _fq_x(images, cfg, X_SPEC)
    h = _bn(_conv(x, _fq_w(params["stem"]["w"], cfg), params["stem"]["b"]),
            params["stem"]["bn"], train)
    h = _fq_x(jax.nn.relu(h), cfg)
    for blk, stride in zip(params["blocks"], block_strides(cfg)):
        skip = h
        y = _bn(_conv(h, _fq_w(blk["conv0"]["w"], cfg), blk["conv0"]["b"],
                      stride), blk["conv0"]["bn"], train)
        y = _fq_x(jax.nn.relu(y), cfg)
        if "ds" in blk:
            skip = _bn(_conv(h, _fq_w(blk["ds"]["w"], cfg), blk["ds"]["b"],
                             stride), blk["ds"]["bn"], train)
            skip = _fq_x(skip, cfg, QSpec(8, True, A_SPEC.exp))
        # paper add-fold: the skip stream is the accumulator init of conv1
        z = _conv(y, _fq_w(blk["conv1"]["w"], cfg), blk["conv1"]["b"],
                  1)
        z = _bn(z, blk["conv1"]["bn"], train)
        h = _fq_x(jax.nn.relu(z + skip), cfg)
    h = jnp.mean(h, axis=(1, 2))
    return h @ _fq_w(params["fc"]["w"], cfg) + params["fc"]["b"]


def loss_fn(params, cfg: ResNetConfig, batch, train=True):
    logits = forward(params, cfg, batch["images"], train=train)
    labels = batch["labels"]
    ll = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(ll, labels[:, None], axis=-1))
    acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return loss, dict(loss=loss, acc=acc)


def calibrate_bn(params, cfg: ResNetConfig, images):
    """Write BN running stats from a calibration batch (paper §III-A: BN is
    folded into the quantized convs *then calibrated*).  Returns params with
    bn.mean/bn.var set so the train=False / folded graphs match training."""
    import copy
    p = jax.tree_util.tree_map(lambda x: x, params)  # shallow-ish copy

    def stats(h):
        return jnp.mean(h, axis=(0, 1, 2)), jnp.var(h, axis=(0, 1, 2))

    def set_bn(bn, h):
        mu, var = stats(h)
        bn["mean"], bn["var"] = mu, var

    x = _fq_x(images, cfg, X_SPEC)
    pre = _conv(x, _fq_w(p["stem"]["w"], cfg), p["stem"]["b"])
    set_bn(p["stem"]["bn"], pre)
    h = _fq_x(jax.nn.relu(_bn(pre, p["stem"]["bn"], False)), cfg)
    for blk, stride in zip(p["blocks"], block_strides(cfg)):
        skip = h
        pre0 = _conv(h, _fq_w(blk["conv0"]["w"], cfg), blk["conv0"]["b"],
                     stride)
        set_bn(blk["conv0"]["bn"], pre0)
        y = _fq_x(jax.nn.relu(_bn(pre0, blk["conv0"]["bn"], False)), cfg)
        if "ds" in blk:
            pred = _conv(h, _fq_w(blk["ds"]["w"], cfg), blk["ds"]["b"],
                         stride)
            set_bn(blk["ds"]["bn"], pred)
            skip = _fq_x(_bn(pred, blk["ds"]["bn"], False), cfg,
                         QSpec(8, True, A_SPEC.exp))
        pre1 = _conv(y, _fq_w(blk["conv1"]["w"], cfg), blk["conv1"]["b"], 1)
        set_bn(blk["conv1"]["bn"], pre1)
        z = _bn(pre1, blk["conv1"]["bn"], False)
        h = _fq_x(jax.nn.relu(z + skip), cfg)
    return p


# ---------------------------------------------------------------------------
# BN folding + integer inference graph (the "hardware" path)
# ---------------------------------------------------------------------------


def fold_params(params) -> dict:
    """Fold BN into conv weights/biases (paper §III-A), drop BN nodes."""
    def fold(c):
        w, b = Q.fold_batchnorm(c["w"], c["b"], c["bn"]["gamma"],
                                c["bn"]["beta"], c["bn"]["mean"],
                                c["bn"]["var"])
        return dict(w=w, b=b)

    out = dict(stem=fold(params["stem"]), fc=dict(params["fc"]), blocks=[])
    for blk in params["blocks"]:
        fb = dict(conv0=fold(blk["conv0"]), conv1=fold(blk["conv1"]))
        if "ds" in blk:
            fb["ds"] = fold(blk["ds"])
        out["blocks"].append(fb)
    return out


def folded_float_forward(folded, cfg: ResNetConfig, images, tap=None):
    """Float reference forward on BN-*folded* params — the graph the integer
    pipeline quantizes, run in float32 with no quantization at all.

    ``tap(site, tensor)`` (optional) is called at every activation site in
    graph order; this is the attachment point for ``repro.quantize``'s
    calibration observers.  Sites:

      * ``"input"``          — the image batch;
      * ``"stem.out"``       — post-ReLU stem output (= block 0's input);
      * ``"block{i}.mid"``   — block i's conv0 output post-ReLU (conv1 input);
      * ``"block{i}.out"``   — block i's output post-add post-ReLU.

    Returns logits (B, num_classes)."""
    def see(site, h):
        if tap is not None:
            tap(site, h)
        return h

    x = see("input", images)
    h = see("stem.out", jax.nn.relu(
        _conv(x, folded["stem"]["w"], folded["stem"]["b"])))
    for i, (blk, stride) in enumerate(zip(folded["blocks"],
                                          block_strides(cfg))):
        y = see(f"block{i}.mid", jax.nn.relu(
            _conv(h, blk["conv0"]["w"], blk["conv0"]["b"], stride)))
        if "ds" in blk:
            skip = _conv(h, blk["ds"]["w"], blk["ds"]["b"], stride)
        else:
            skip = h
        z = _conv(y, blk["conv1"]["w"], blk["conv1"]["b"], 1) + skip
        h = see(f"block{i}.out", jax.nn.relu(z))
    pooled = jnp.mean(h, axis=(1, 2))
    return pooled @ folded["fc"]["w"] + folded["fc"]["b"]


def quantize_params(folded, cfg: ResNetConfig) -> dict:
    """Float folded params -> integer weights/biases per the paper's spec:
    int8 weights (pow2 scale), int16 biases at s_b = s_x + s_w.

    Weight exponents are calibrated PER CONV on the folded weights — BN
    folding rescales weights by gamma/sqrt(var), which can push them far
    outside a fixed 2^-7 grid (paper §III-A calibrates after folding)."""
    def qc(c, x_spec):
        w_exp = Q.calibrate_exp(c["w"], QSpec(cfg.bw_w, True, 0))
        w_spec = QSpec(cfg.bw_w, True, w_exp)
        b_spec = Q.bias_spec(x_spec, w_spec, cfg.bw_b)
        return dict(wq=Q.quantize(c["w"], w_spec),
                    bq=Q.quantize(c["b"], b_spec),
                    w_spec=w_spec, x_spec=x_spec, b_spec=b_spec)

    out = dict(stem=qc(folded["stem"], X_SPEC), blocks=[])
    for blk in folded["blocks"]:
        qb = dict(conv0=qc(blk["conv0"], A_SPEC), conv1=qc(blk["conv1"], A_SPEC))
        if "ds" in blk:
            qb["ds"] = qc(blk["ds"], A_SPEC)
        out["blocks"].append(qb)
    fc_exp = Q.calibrate_exp(folded["fc"]["w"], QSpec(cfg.bw_w, True, 0))
    fc_spec = QSpec(cfg.bw_w, True, fc_exp)
    out["fc"] = dict(wq=Q.quantize(folded["fc"]["w"], fc_spec),
                     b=folded["fc"]["b"], w_spec=fc_spec)
    return out


def int_forward(qparams, cfg: ResNetConfig, images):
    """Pure-integer inference (float ops only at the final classifier).

    The residual add never exists as a node: the skip stream (requantized to
    the product domain of conv1) initializes conv1's int32 accumulator.

    Thin compatibility wrapper over ``repro.compile``'s ``lax-int`` backend —
    the arithmetic lives in one place (``compile.backends``), driven by the
    optimized graph IR, so bit-exactness with the compiled serving path holds
    by construction."""
    from repro.compile import lower_forward
    return lower_forward(cfg, qparams, backend="lax-int")(images)


# ---------------------------------------------------------------------------
# Fused Pallas inference pipeline — the whole integer graph through kernels
# ---------------------------------------------------------------------------


def block_shifts(qb) -> dict:
    """Static pow2 shifts for one quantized block, in the kernels' semantics.

    shift0/shift1 requantize the conv product domain (s_x + s_w) back to
    A_SPEC (positive = rounding right shift); skip_shift aligns the skip
    stream into conv1's product domain (signed: >=0 left shift, <0 rounding
    right shift) — exactly the arithmetic int_forward performs."""
    e0 = qb["conv0"]["x_spec"].exp + qb["conv0"]["w_spec"].exp
    e1 = qb["conv1"]["x_spec"].exp + qb["conv1"]["w_spec"].exp
    out = dict(shift0=A_SPEC.exp - e0, shift1=A_SPEC.exp - e1)
    if "ds" in qb:
        eds = qb["ds"]["x_spec"].exp + qb["ds"]["w_spec"].exp
        out["skip_shift"] = eds - e1
    else:
        out["skip_shift"] = A_SPEC.exp - e1
    return out


def pallas_forward(qparams, cfg: ResNetConfig, images):
    """``int_forward`` lowered entirely through the fused Pallas kernels.

    Stem: conv_stem (conv3x3 + ReLU + shift requant).  Every residual block:
    one resblock_fused call — conv0 (stride 1 or 2), ReLU/requant, the 1x1
    downsample conv on the skip path when present, the add-fold into conv1's
    int32 accumulator, ReLU/requant — with y0 and the skip stream living in
    VMEM for the kernel's lifetime (paper Fig. 13).  Feature maps touch HBM
    exactly once per kernel boundary.  Bit-exact with ``int_forward``
    (asserted in tests/test_pallas_forward.py); float ops only at the final
    average-pool + classifier, identical to int_forward's tail.

    Thin compatibility wrapper over ``repro.compile``'s ``pallas`` backend —
    the kernel sequencing is derived from the optimized graph IR in
    ``compile.backends.PallasBackend``."""
    from repro.compile import lower_forward
    return lower_forward(cfg, qparams, backend="pallas")(images)
