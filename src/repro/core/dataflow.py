"""Dataflow buffer/stream sizing model (paper §III-E/F/G, eqs. 8-23) and the
FPGA throughput/latency predictor used to validate against the paper's Table 3.

This module is pure arithmetic (no jax) so it is trivially testable and usable
by the ILP balancer and the benchmark harness.  It also exposes an HBM-traffic
model for the TPU adaptation: the fused residual block saves exactly the skip
tensor's HBM round trip, which is the TPU analogue of the BRAM saving that
eq. 23 quantifies on the FPGA.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional


# ---------------------------------------------------------------------------
# eq. 16/17 — window (line) buffer sizes
# ---------------------------------------------------------------------------


def window_buffer_size(iw: int, ich: int, fh: int, fw: int,
                       ow_par: int = 1) -> int:
    """Activations retained to produce one input window (eq. 16; eq. 17 for
    ow_par=2 adds fw instead of fw-1)."""
    if ow_par == 1:
        return ((fh - 1) * iw + fw - 1) * ich
    return ((fh - 1) * iw + fw) * ich


def fifo_partition(iw: int, ich: int, fh: int, fw: int) -> List[int]:
    """§III-F Fig. 7: the line buffer is split into fh*fw FIFO slices; S1=ich
    between elements in a row, S2=(iw-fw+1)*ich between rows (so that the total
    equals eq. 16).  Returns the slice sizes."""
    s1 = ich
    s2 = (iw - fw + 1) * ich
    sizes = []
    for r in range(fh):
        for c in range(fw):
            if r == fh - 1 and c == fw - 1:
                sizes.append(0)        # newest element, not buffered
            elif c == fw - 1:
                sizes.append(s2)       # row boundary
            else:
                sizes.append(s1)
    return sizes


# ---------------------------------------------------------------------------
# eq. 18-21 — receptive-field skip buffering (the *unoptimized* cost)
# ---------------------------------------------------------------------------


def receptive_field(fh0: int, fw0: int, fh1: int, fw1: int) -> tuple:
    rh0 = fh1 + fh0 - 1            # eq. 18
    rw0 = fw1 + fw0 - 1            # eq. 19
    return rh0, rw0


def skip_buffer_receptive_field(iw0: int, ich0: int, fh0: int, fw0: int,
                                fh1: int, fw1: int) -> int:
    """eq. 21: B_sc = [iw0*(rh0-1) + rw0] * ich0."""
    rh0, rw0 = receptive_field(fh0, fw0, fh1, fw1)
    return (iw0 * (rh0 - 1) + rw0) * ich0


def skip_buffer_optimized(iw1: int, ich1: int, fh1: int, fw1: int) -> int:
    """eq. 22: after temporal-reuse/loop-merge/add-fold the skip buffer equals
    conv1's window buffer."""
    return window_buffer_size(iw1, ich1, fh1, fw1)


def skip_buffer_ratio(iw0, ich0, fh0, fw0, iw1, ich1, fh1, fw1) -> float:
    """eq. 23: R_sc (= 0.5 for all ResNet8/20 blocks)."""
    return (skip_buffer_optimized(iw1, ich1, fh1, fw1)
            / skip_buffer_receptive_field(iw0, ich0, fh0, fw0, fh1, fw1))


# ---------------------------------------------------------------------------
# eq. 8-11 — per-layer work / parallelism / throughput
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ConvLayer:
    """Static description of one convolution task (symbols of Table 1)."""
    name: str
    ich: int
    ih: int
    iw: int
    och: int
    oh: int
    ow: int
    fh: int = 3
    fw: int = 3
    stride: int = 1
    skip_in: bool = False   # receives a folded residual stream

    @property
    def c(self) -> int:
        """eq. 8 — computations per frame."""
        return self.oh * self.ow * self.och * self.ich * self.fh * self.fw

    @property
    def k(self) -> int:
        return self.fh * self.fw

    @property
    def macs(self) -> int:
        return self.c

    @property
    def weights(self) -> int:
        return self.och * self.ich * self.fh * self.fw

    def cp(self, och_par: int, ow_par: int = 2) -> int:
        """eq. 9 — computation parallelism of the task."""
        return self.k * och_par * ow_par

    def latency_cycles(self, och_par: int, ow_par: int = 2) -> float:
        """cycles per frame = c / cp (perfectly pipelined intra-task loop)."""
        return self.c / self.cp(och_par, ow_par)


def throughput_fps(layer: ConvLayer, och_par: int, freq_hz: float,
                   ow_par: int = 2) -> float:
    """eq. 11 scaled by the clock: Th_i = freq * cp_i / c_i."""
    return freq_hz * layer.cp(och_par, ow_par) / layer.c


# ---------------------------------------------------------------------------
# TPU adaptation: HBM traffic model of a residual block
# ---------------------------------------------------------------------------


def residual_block_hbm_bytes(h: int, w: int, ich: int, och: int,
                             bytes_per_elt: int = 1, fused: bool = True,
                             downsample: bool = False, stride: int = 1) -> int:
    """HBM bytes moved by one residual block (activations only).

    Unfused (naive) dataflow: x is read by conv0 AND by the skip path, the
    intermediate y0 round-trips, conv1 output round-trips to the Add which
    re-reads the skip tensor.  Fused (paper-adapted) kernel: x is read once,
    y0 and the skip live in VMEM, only the block output is written.
    """
    oh, ow = h // stride, w // stride
    x = h * w * ich * bytes_per_elt
    y0 = oh * ow * och * bytes_per_elt
    y1 = oh * ow * och * bytes_per_elt
    skip = (oh * ow * och if downsample else h * w * ich) * bytes_per_elt
    if fused:
        return x + y1                         # read x once, write block output
    # conv0 reads x, writes y0; conv1 reads y0, writes y1; skip path reads x
    # (and writes the downsampled skip); add reads y1+skip, writes out.
    traffic = x + y0 + y0 + y1 + x + y1 + skip + y1
    if downsample:
        traffic += skip
    return traffic


# ---------------------------------------------------------------------------
# TPU adaptation: tiled-kernel HBM traffic + VMEM footprint (repro.tune's
# analytic cost model — the DSP/BRAM budget of §III-E becomes an HBM-traffic/
# VMEM budget)
# ---------------------------------------------------------------------------


def conv_task_hbm_bytes(layer: ConvLayer, batch: int, batch_tile: int,
                        act_bytes: int = 1, w_bytes: int = 1) -> int:
    """HBM bytes one tiled conv kernel moves for a ``batch``: activations
    move exactly once (read input map, write output map), but the filter +
    bias are re-fetched by every batch-grid step — the term the tuner's
    ``batch_tile`` knob amortizes.  ``cout_block`` does not change the total
    (the channel blocks of one batch step partition the filter); it only
    moves the VMEM footprint."""
    acts = batch * (layer.ih * layer.iw * layer.ich
                    + layer.oh * layer.ow * layer.och) * act_bytes
    steps = batch // max(1, batch_tile)
    weights = (layer.weights * w_bytes + layer.och * 4) * steps
    return acts + weights


# The chip's VMEM holds every array in (sublane, lane) tiles over its two
# minor dims: (8, 128) for 32-bit data, (32, 128) for 8-bit.  The CIFAR
# widths (3..64 channels) sit in the lane dim, so a 16-channel int32 map
# occupies 8x its payload.  The VMEM models below count tiled bytes, for
# the dtypes the conv kernels' datapath holds (kernels.common): u8 blocks
# in HBM-facing tiles, int32 activations and accumulators inside, s8 tap
# operands and filters, int32 biases.
_LANES = 128


def tiled_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of an array of ``shape`` with ``itemsize``-byte elements
    once its minor two dims are padded to the chip's tile."""
    shape = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sub = 8 * 4 // itemsize
    rows = -(-shape[-2] // sub) * sub
    lanes = -(-shape[-1] // _LANES) * _LANES
    return math.prod(shape[:-2]) * rows * lanes * itemsize


def _filter_vmem_bytes(fh: int, fw: int, ich: int, och: int) -> int:
    """One s8 filter + its int32 bias, double-buffered by the Pallas
    pipeline (it allocates two buffers per blocked operand)."""
    return 2 * (tiled_bytes((fh, fw, ich, och), 1) + tiled_bytes((och,), 4))


def _conv_work_vmem_bytes(h: int, w: int, ich: int, och: int,
                          stride: int = 1) -> int:
    """Per-image int32 working set of one 3x3 conv: the padded input, one
    tap's operand rows (int32 and their s8 narrowing), the accumulator,
    and for stride 2 the int32 staging copy the strided taps load from."""
    lo = 1 if stride == 1 else 0
    xp = tiled_bytes((h + lo + 1, w + lo + 1, ich), 4)
    rows = (h // stride) * (w // stride)
    tap = tiled_bytes((rows, ich), 4) + tiled_bytes((rows, ich), 1)
    acc = tiled_bytes((h // stride, w // stride, och), 4)
    return xp * (1 if stride == 1 else 2) + tap + acc


def conv_task_vmem_bytes(layer: ConvLayer, batch_tile: int,
                         cout_block: int) -> int:
    """Per-grid-step VMEM footprint of the stem conv kernel: the
    double-buffered u8 input and output tiles, the filter/bias slice, and
    ``batch_tile`` images' working set — every term counted as live at
    once, an upper bound of what the compiler allocates."""
    cb = cout_block or layer.och
    bt = batch_tile
    ihp, iwp = layer.ih + layer.fh - 1, layer.iw + layer.fw - 1
    tiles = 2 * (tiled_bytes((bt, ihp, iwp, layer.ich), 1)
                 + tiled_bytes((bt, layer.oh, layer.ow, cb), 1))
    work = _conv_work_vmem_bytes(layer.ih, layer.iw, layer.ich, cb,
                                 layer.stride)
    return (tiles + _filter_vmem_bytes(layer.fh, layer.fw, layer.ich, cb)
            + bt * work)


def resblock_task_hbm_bytes(h: int, w: int, ich: int, och: int, batch: int,
                            batch_tile: int, downsample: bool = False,
                            stride: int = 1, act_bytes: int = 1,
                            w_bytes: int = 1) -> int:
    """HBM bytes the fused residual-block kernel moves for a ``batch``: the
    eq.-23-style fused activation traffic (read x once, write the block
    output) plus both conv filters (+ the 1x1 downsample filter when present)
    re-fetched per batch-grid step."""
    acts = batch * residual_block_hbm_bytes(
        h, w, ich, och, bytes_per_elt=act_bytes, fused=True,
        downsample=downsample, stride=stride)
    wts = (9 * ich * och + 9 * och * och
           + (ich * och if downsample else 0)) * w_bytes + 2 * och * 4
    steps = batch // max(1, batch_tile)
    return acts + wts * steps


def resblock_task_vmem_bytes(h: int, w: int, ich: int, och: int,
                             batch_tile: int, downsample: bool = False,
                             stride: int = 1) -> int:
    """Per-grid-step VMEM footprint of the fused residual block: the kernel
    runs the chain megakernel's per-block datapath, so this is a one-link
    :func:`chain_task_vmem_bytes`."""
    return chain_task_vmem_bytes(
        [BlockShape(h, w, ich, och, downsample=downsample, stride=stride)],
        batch_tile)


# ---------------------------------------------------------------------------
# TPU adaptation: block-chain streaming (megakernel) HBM traffic + VMEM
# footprint.  The paper's layer-to-layer streaming (§III-D) fuses across
# block boundaries: a chain of consecutive residual blocks executes in one
# kernel, the running activation never leaving VMEM between blocks.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockShape:
    """Static shape of one residual block as a chain link: input map
    ``h x w x ich``, output ``(h//stride) x (w//stride) x och``."""
    h: int
    w: int
    ich: int
    och: int
    downsample: bool = False
    stride: int = 1

    @property
    def oh(self) -> int:
        return self.h // self.stride

    @property
    def ow(self) -> int:
        return self.w // self.stride

    def weight_bytes(self, w_bytes: int = 1) -> int:
        """Both 3x3 filters (+ the 1x1 downsample when present) + biases."""
        wts = 9 * self.ich * self.och + 9 * self.och * self.och
        if self.downsample:
            wts += self.ich * self.och
        return wts * w_bytes + 2 * self.och * 4

    def in_bytes(self, act_bytes: int = 1) -> int:
        return self.h * self.w * self.ich * act_bytes

    def out_bytes(self, act_bytes: int = 1) -> int:
        return self.oh * self.ow * self.och * act_bytes


def chain_saved_hbm_bytes(blocks: List[BlockShape], batch: int,
                          act_bytes: int = 1) -> int:
    """HBM activation bytes the chain fusion removes vs per-block kernels:
    every *interior* boundary activation is written by block j and re-read by
    block j+1 in per-block execution — the chain keeps it in VMEM, saving
    both movements."""
    return 2 * batch * sum(b.out_bytes(act_bytes) for b in blocks[:-1])


def chain_task_hbm_bytes(blocks: List[BlockShape], batch: int,
                         batch_tile: int, stem_och: int = 0,
                         act_bytes: int = 1, w_bytes: int = 1) -> int:
    """HBM bytes one block-chain megakernel moves for a ``batch``: the chain
    input is read once, the chain output written once, and the chain's
    pinned weight set is fetched once per batch-grid step.  ``stem_och > 0``
    fuses the 3x3 stem conv at the chain head (its input becomes the chain
    input; one more interior boundary stays in VMEM).

    Identity (pinned by tests/test_dataflow.py): this equals the sum of the
    per-block ``resblock_task_hbm_bytes`` minus :func:`chain_saved_hbm_bytes`
    — fusion only ever removes interior activation round trips, never
    weight traffic."""
    first = blocks[0]
    if stem_och:
        # the chain input is the image; the stem boundary activation also
        # stays in VMEM (one more interior boundary saved)
        acts = batch * (first.h * first.w * 3 * act_bytes
                        + blocks[-1].out_bytes(act_bytes))
    else:
        acts = batch * (first.in_bytes(act_bytes)
                        + blocks[-1].out_bytes(act_bytes))
    steps = batch // max(1, batch_tile)
    wts = sum(b.weight_bytes(w_bytes) for b in blocks)
    if stem_och:
        wts += 9 * 3 * stem_och * w_bytes + stem_och * 4
    return acts + wts * steps


def chain_task_vmem_bytes(blocks: List[BlockShape], batch_tile: int,
                          stem_och: int = 0) -> int:
    """Per-grid-step VMEM footprint of the chain megakernel — what decides a
    chain cut and its batch tile.  The whole chain's filters are pinned for
    the kernel's lifetime (constant-index BlockSpecs, double-buffered), the
    u8 batch input/output tiles are double-buffered, and the streaming
    working set is the *maximum* over links of the tile's per-block
    intermediates (links execute sequentially): conv0's working set, the
    padded y0, the aligned skip, and conv1's accumulator.  Every term is
    counted as live at once and lane-padded (:func:`tiled_bytes`) — an upper
    bound of what the compiler allocates, checked against it for ResNet20 by
    tests/test_tpu_compile.py."""
    first, last = blocks[0], blocks[-1]
    bt = batch_tile
    if stem_och:
        in_tile = (bt, first.h + 2, first.w + 2, 3)
    else:
        lo = 1 if first.stride == 1 else 0
        in_tile = (bt, first.h + lo + 1, first.w + lo + 1, first.ich)
    tiles = 2 * (tiled_bytes(in_tile, 1)
                 + tiled_bytes((bt, last.oh, last.ow, last.och), 1))
    wts = 0
    work = 0
    if stem_och:
        wts += _filter_vmem_bytes(3, 3, 3, stem_och)
        work = _conv_work_vmem_bytes(first.h, first.w, 3, stem_och)
    for b in blocks:
        wts += (_filter_vmem_bytes(3, 3, b.ich, b.och)
                + _filter_vmem_bytes(3, 3, b.och, b.och))
        if b.downsample:
            wts += _filter_vmem_bytes(1, 1, b.ich, b.och)
        acc = tiled_bytes((b.oh, b.ow, b.och), 4)
        per_img = (_conv_work_vmem_bytes(b.h, b.w, b.ich, b.och, b.stride)
                   + tiled_bytes((b.oh + 2, b.ow + 2, b.och), 4)  # padded y0
                   + 2 * acc)                          # aligned skip + acc1
        work = max(work, per_img)
    return tiles + wts + bt * work


def resnet_block_shapes(blocks_per_stage: int, base: int = 16, img: int = 32
                        ) -> List[BlockShape]:
    """The :class:`BlockShape` chain of a whole ResNet in graph order —
    the block-level view of :func:`resnet_layers`."""
    out = []
    ich, res = base, img
    for stage in range(3):
        och = base * (2 ** stage)
        for b in range(blocks_per_stage):
            stride = 2 if (stage > 0 and b == 0) else 1
            out.append(BlockShape(h=res, w=res, ich=ich, och=och,
                                  downsample=(stride != 1 or ich != och),
                                  stride=stride))
            ich, res = och, res // stride
    return out


# ---------------------------------------------------------------------------
# TPU adaptation: the ImageNet bottleneck family (ResNet-50 v1.5) — the
# fused bottleneck kernel and the strided stem with its max pool.  Unlike
# the CIFAR kernels, which run inside Mosaic's default scoped-VMEM limit,
# these set their limit per call from the models below.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BottleneckShape:
    """One bottleneck block: input ``h x w x ich``, 1x1 to ``mid``, 3x3 at
    ``stride``, 1x1 to ``och``, with a 1x1 projection (at ``stride``) on
    the skip when ``downsample``."""
    h: int
    w: int
    ich: int
    mid: int
    och: int
    stride: int = 1
    downsample: bool = False

    @property
    def oh(self) -> int:
        return self.h // self.stride

    @property
    def ow(self) -> int:
        return self.w // self.stride


def bottleneck_task_vmem_bytes(b: BottleneckShape, batch_tile: int) -> int:
    """Per-grid-step VMEM footprint of the fused bottleneck kernel: the
    double-buffered u8 input and output tiles and filters (constant-index
    blocks, two buffers each), and ``batch_tile`` images' working set with
    every intermediate counted as live at once: the input widened to int32
    with its s8 operand rows, y0 and its SAME-padded copy (and the int32
    staging copy a strided 3x3 loads from), one tap's operand rows, conv1's
    accumulator and y1, the staged input of a strided projection, the
    projection's sum, the aligned skip and conv2's accumulator.  An upper
    bound of what the compiler allocates, checked against it at every
    ResNet-50 block shape by tests/test_tpu_compile.py."""
    bt = batch_tile
    tiles = 2 * (tiled_bytes((bt, b.h, b.w, b.ich), 1)
                 + tiled_bytes((bt, b.oh, b.ow, b.och), 1))
    filters = [(1, 1, b.ich, b.mid), (3, 3, b.mid, b.mid),
               (1, 1, b.mid, b.och)] + \
        ([(1, 1, b.ich, b.och)] if b.downsample else [])
    wts = sum(_filter_vmem_bytes(*f) for f in filters)
    hw, ohw = b.h * b.w, b.oh * b.ow
    lo = 1 if b.stride == 1 else 0
    ypad = tiled_bytes((b.h + lo + 1, b.w + lo + 1, b.mid), 4)
    per_img = (tiled_bytes((b.h, b.w, b.ich), 4)             # x as int32
               + tiled_bytes((hw, b.ich), 1)                  # conv0 rows
               + tiled_bytes((b.h, b.w, b.mid), 4)            # y0
               + ypad * (1 if b.stride == 1 else 2)           # padded y0
               + tiled_bytes((ohw, b.mid), 4)                 # tap rows
               + tiled_bytes((ohw, b.mid), 1)
               + 2 * tiled_bytes((b.oh, b.ow, b.mid), 4)      # acc1, y1
               + tiled_bytes((ohw, b.mid), 1)                 # conv2 rows
               + 3 * tiled_bytes((b.oh, b.ow, b.och), 4))     # skip, acc2
    if b.downsample:
        per_img += tiled_bytes((b.oh, b.ow, b.och), 4)        # projection
        if b.stride != 1:
            per_img += tiled_bytes((b.h, b.w, b.ich), 4)      # staged x
    return tiles + wts + bt * per_img


def stem_pool_task_vmem_bytes(img: int, cin: int, och: int, k: int,
                              batch_tile: int) -> int:
    """Per-grid-step VMEM footprint of the strided stem with its max pool:
    the double-buffered u8 patch tile (four output phases of
    ``(img//4)**2`` rows, ``k*k*cin`` wide) and output tile, the filter,
    and per image the patch rows widened to int32 with their s8 operand,
    the four int32 phase maps with their padded copies, and the pooled
    max."""
    q = img // 4
    kk = k * k * cin
    bt = batch_tile
    tiles = 2 * (tiled_bytes((bt, 4, q * q, kk), 1)
                 + tiled_bytes((bt, q, q, och), 1))
    per_img = (tiled_bytes((q * q, kk), 4) + tiled_bytes((q * q, kk), 1)
               + 4 * tiled_bytes((q, q, och), 4)              # phase maps
               + 4 * tiled_bytes((q + 1, q + 1, och), 4)      # padded
               + 2 * tiled_bytes((q, q, och), 4))             # max, acc
    return tiles + _filter_vmem_bytes(1, 1, kk, och) + bt * per_img


# ---------------------------------------------------------------------------
# TPU adaptation: LM task kinds (matmul / attention / scan) — the byte model
# behind tune.space legality pruning and obs.profile rooflines for the
# generic compiler's transformer / SSM task programs.  Same conventions as
# the conv formulas: act_bytes=1 (int8 streams), int32 accumulators at 4B,
# float interlude operands at 4B.
# ---------------------------------------------------------------------------


def matmul_task_hbm_bytes(M: int, K: int, N: int, bm: int, bn: int, bk: int,
                          acc_init: bool = False, act_bytes: int = 1,
                          w_bytes: int = 1) -> int:
    """HBM bytes one tiled int8 matmul moves: with grid (M/bm, N/bn, K/bk),
    every A tile is re-fetched once per N block and every B tile once per M
    block (the classic tiled-GEMM reuse), the bias once per (M, N) step pair
    — and the folded residual stream (``acc_init``) enters as a full int32
    (M, N) read."""
    bm, bn, bk = (max(1, b) for b in (bm, bn, bk))
    a = M * K * act_bytes * max(1, N // bn)
    b = K * N * w_bytes * max(1, M // bm)
    bias = N * 4 * max(1, M // bm)
    out = M * N * 4
    skip = M * N * 4 if acc_init else 0
    return a + b + bias + out + skip


def matmul_task_vmem_bytes(bm: int, bn: int, bk: int,
                           act_bytes: int = 1, w_bytes: int = 1) -> int:
    """Per-grid-step VMEM footprint of the int8 matmul kernel: one A tile,
    one B tile, the int32 accumulator scratch, and the int32 acc-init /
    output tiles."""
    bm, bn, bk = (max(1, b) for b in (bm, bn, bk))
    return (bm * bk * act_bytes + bk * bn * w_bytes
            + 3 * bm * bn * 4)           # scratch + acc_init + out


def attention_task_hbm_bytes(BH: int, Sq: int, Sk: int, hd: int,
                             bq: int, bk: int, elt_bytes: int = 4) -> int:
    """HBM bytes one flash-attention call moves (per fused (batch*heads)
    instance set): q and o move once, but K and V are re-streamed by every
    q-tile grid step — the term the ``bq`` knob amortizes."""
    bq = max(1, bq)
    q_steps = max(1, Sq // bq)
    qo = 2 * BH * Sq * hd * elt_bytes
    kv = 2 * BH * Sk * hd * elt_bytes * q_steps
    return qo + kv


def attention_task_vmem_bytes(Sk: int, hd: int, bq: int, bk: int,
                              elt_bytes: int = 4) -> int:
    """Per-grid-step VMEM footprint of the flash kernel: one q/o tile pair,
    the streaming K/V tile pair, the (bq, bk) score tile, and the online
    softmax state (m, l, acc)."""
    bq, bk = max(1, bq), max(1, bk)
    return (2 * bq * hd * elt_bytes      # q tile + acc/o tile
            + 2 * bk * hd * elt_bytes    # K/V tiles
            + bq * bk * elt_bytes        # score tile
            + 2 * bq * elt_bytes)        # m, l


def scan_task_hbm_bytes(B: int, S: int, d_inner: int, N: int, bd: int,
                        elt_bytes: int = 4) -> int:
    """HBM bytes one selective-scan call moves: u/dt/y move once, but the
    per-step B_t/C_t projections are re-read by every d_inner block instance
    (grid (B, d_inner/bd)) — the term the ``bd`` knob amortizes — plus the
    A slice and the h state in/out."""
    bd = max(1, bd)
    d_steps = max(1, d_inner // bd)
    seq = 3 * B * S * d_inner * elt_bytes            # u, dt, y
    bc = 2 * B * S * N * elt_bytes * d_steps         # B_t, C_t re-reads
    a = d_inner * N * elt_bytes * B                  # A slice per batch inst
    h = 2 * B * d_inner * N * elt_bytes              # h0 in, h_last out
    return seq + bc + a + h


def scan_task_vmem_bytes(S: int, N: int, bd: int, elt_bytes: int = 4) -> int:
    """Per-grid-step VMEM footprint of the scan kernel: the (bd, N) state +
    A slices pinned for the whole sequence walk, the full-sequence u/dt/y
    stripes of the d block, and the (S, N) B/C streams."""
    bd = max(1, bd)
    return (2 * bd * N * elt_bytes       # A slice + h state
            + 3 * S * bd * elt_bytes     # u, dt, y stripes
            + 2 * S * N * elt_bytes)     # B_t, C_t


# ---------------------------------------------------------------------------
# ResNet layer tables (mirrors graph.build_resnet_graph; used by ILP/benchmarks)
# ---------------------------------------------------------------------------


def resnet_layers(blocks_per_stage: int, base: int = 16, img: int = 32
                  ) -> List[ConvLayer]:
    layers = [ConvLayer("stem", 3, img, img, base, img, img)]
    ich, res, i = base, img, 0
    for stage in range(3):
        och = base * (2 ** stage)
        for b in range(blocks_per_stage):
            stride = 2 if (stage > 0 and b == 0) else 1
            ow = res // stride
            layers.append(ConvLayer(f"c{i}_0", ich, res, res, och, ow, ow,
                                    stride=stride))
            layers.append(ConvLayer(f"c{i}_1", och, ow, ow, och, ow, ow,
                                    skip_in=True))
            if stride != 1 or ich != och:
                layers.append(ConvLayer(f"ds{i}", ich, res, res, och, ow, ow,
                                        fh=1, fw=1, stride=stride))
            ich, res = och, ow
            i += 1
    return layers


def resnet8_layers() -> List[ConvLayer]:
    return resnet_layers(1)


def resnet20_layers() -> List[ConvLayer]:
    return resnet_layers(3)


def total_gops(layers: List[ConvLayer]) -> float:
    """2*MACs in Gops per frame (conv layers only, like the paper's Gops/s)."""
    return 2.0 * sum(l.macs for l in layers) / 1e9
