"""Backend registry: how a lowering plan becomes executable code.

A ``Backend`` is the software analogue of the paper's HLS code generator: it
takes the optimized graph + typed parameters and returns a callable computing
logits from a float image batch.  Backends self-register via decorator —

    @register_backend("my-backend")
    class MyBackend:
        def lower(self, g, cfg, params): ...

— so adding an execution strategy never touches the engine or the compiler
(`serve.ResNetEngine` historically switched backends with if/elif lambdas).

Built-in backends, all lowering the SAME plan (``lowering.plan_model``):

  * ``pallas``  — the fused kernel pipeline: ``conv_stem`` + one
                  ``resblock_fused`` call per residual block (paper Fig. 13
                  dataflow; feature maps touch HBM once per kernel boundary);
                  for the bottleneck family the strided stem with its max
                  pool and one fused bottleneck call per block.
  * ``lax-int`` — the reference integer graph on ``jax.lax`` convs: identical
                  int32 accumulators and shift arithmetic, unfused dataflow.
                  Bit-exact with ``pallas`` by construction.
  * ``float``   — float emulation of the integer graph on the same pow2 grids
                  (dequantized weights, fake-quantized activations): the
                  quantization-error A/B reference, agrees with the integer
                  backends to float rounding error, not bit-exactly.
"""
from __future__ import annotations

from typing import Callable, Dict, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import quant as Q
from repro.compile import lowering
from repro.compile import lm_params as LP
from repro.compile.params import (
    QConvParams, QResNetParams, activation_out_specs)

# the default activation grid is a model-level constant (models.resnet
# defines the network); import the value, not the module, to keep the
# dependency thin.  Per-tensor grids (repro.quantize calibration) override it
# through the specs the params carry — see activation_out_specs.
from repro.models.resnet import A_SPEC


@runtime_checkable
class Backend(Protocol):
    """Lower an optimized graph + typed params into ``images -> logits``."""

    name: str

    def lower(self, g, cfg, params: QResNetParams) -> Callable:
        ...


_REGISTRY: Dict[str, Backend] = {}
_ALIASES = {"int": "lax-int"}   # legacy ResNetEngine name


def register_backend(name: str):
    """Class decorator: instantiate and register a backend under ``name``."""
    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls()
        return cls
    return deco


def get_backend(name: str) -> Backend:
    key = _ALIASES.get(name, name)
    if key not in _REGISTRY:
        raise ValueError(
            f"unknown backend {name!r}; registered: {list_backends()}")
    return _REGISTRY[key]


def list_backends():
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Shared integer arithmetic (one home, so bit-exactness cannot drift)
# ---------------------------------------------------------------------------


def _int_conv(xq, c: QConvParams, stride=1, acc_init=None):
    """int8 x int8 -> int32 accumulator (+ int bias, + folded skip stream)."""
    acc = jax.lax.conv_general_dilated(
        xq.astype(jnp.int32), c.wq.astype(jnp.int32),
        window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)
    acc = acc + c.bq.astype(jnp.int32)
    if acc_init is not None:
        acc = acc + acc_init
    return acc


def _relu_requant(acc, c: QConvParams, out_spec=A_SPEC):
    return Q.requantize_shift(jnp.maximum(acc, 0), c.product_exp, out_spec)


def _int_head(h_u8, fc, in_spec=A_SPEC, pool: str = "avg"):
    """Global average pool + classifier (the paper's host-side tail), shared
    by every integer backend.  The pool sum and the fc dot run in int32, so
    they are exact; one float scale and the float bias follow, element by
    element.  A logit therefore depends on its own row alone, whatever the
    batch shape: a float pool + dot is not, on TPU its precision and
    reduction order change with the compiled shape.

    ``pool="avg"`` dots the per-channel sums and folds the 1/(h*w) into the
    float scale.  ``pool="avg_u8"`` (the bottleneck head) first divides each
    sum by h*w, rounding half up, back onto the u8 activation grid, so the
    fc accumulator stays under 2**31 at any map size."""
    _, h, w, c = h_u8.shape
    wmax = max(-fc.w_spec.qmin, fc.w_spec.qmax)
    pooled = jnp.sum(h_u8.astype(jnp.int32), axis=(1, 2))
    if pool == "avg_u8":
        pooled = (pooled + (h * w) // 2) // (h * w)
        scale = in_spec.scale * fc.w_spec.scale
        bound = in_spec.qmax * wmax * c
    else:
        scale = in_spec.scale * fc.w_spec.scale / (h * w)
        bound = h * w * in_spec.qmax * wmax * c
    assert bound < 2 ** 31, f"int32 head accumulator can overflow ({bound})"
    acc = jnp.dot(pooled, fc.wq.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * scale + fc.b


def _max_pool(h):
    """3x3 stride-2 SAME max pool of a post-ReLU u8 map: the pad is 0,
    which there equals -inf."""
    return jax.lax.reduce_window(h, jnp.zeros((), h.dtype), jax.lax.max,
                                 (1, 3, 3, 1), (1, 2, 2, 1), "SAME")


# ---------------------------------------------------------------------------
# LM task lowering: per-(backend, kind) implementation registry
# ---------------------------------------------------------------------------
#
# The generic compiler (lowering.plan_lm) produces an ordered task program;
# HOW each task kind executes is a per-backend choice registered here.  The
# int8 matmul arithmetic is shared (so pallas and lax-int are bit-exact by
# construction, like the conv pipeline); attention and scan pair the pallas
# kernel with its bit-exact lax mirror.  Adding a node kind = register a
# handler in lowering.TASK_HANDLERS + one impl per backend here.

_TASK_IMPLS: Dict[tuple, Callable] = {}


def register_task_impl(backend_name: str, kind: str):
    """Register ``impl(task, ctx)`` as how ``backend_name`` executes tasks
    of ``kind``.  ``ctx`` is the :class:`_LMContext` of the running forward;
    the impl reads ``ctx.env[task.inputs[i]]`` and writes
    ``ctx.env[task.output]`` (plus its quant spec into ``ctx.specs``)."""
    def deco(fn):
        _TASK_IMPLS[(backend_name, kind)] = fn
        return fn
    return deco


def get_task_impl(backend_name: str, kind: str) -> Callable:
    impl = _TASK_IMPLS.get((backend_name, kind))
    if impl is None:
        have = sorted(k for b, k in _TASK_IMPLS if b == backend_name)
        raise lowering.LoweringError(
            f"backend {backend_name!r} has no impl for task kind {kind!r} "
            f"(has: {have})")
    return impl


class _LMContext:
    """Mutable state one LM forward pass threads through the task impls."""

    def __init__(self, params, cfg, consumer_xspec):
        self.params = params
        self.cfg = cfg
        self.consumer_xspec = consumer_xspec   # tensor -> consuming x_spec
        self.env: Dict[str, jnp.ndarray] = {}  # tensor name -> value
        self.specs: Dict[str, Q.QSpec] = {}    # tensor name -> int8 grid

    def put(self, name, value, spec=None):
        self.env[name] = value
        if spec is not None:
            self.specs[name] = spec

    def out_spec(self, tensor: str) -> Q.QSpec:
        """Grid a float task output quantizes onto: its consumer's input
        grid (every float interlude hands an int8 stream to a matmul)."""
        try:
            return self.consumer_xspec[tensor]
        except KeyError:
            raise lowering.LoweringError(
                f"tensor {tensor!r} has no consuming matmul to define its "
                f"quantization grid") from None


def _lm_matmul_prologue(t, ctx):
    """Shared int32 accumulator init: bias at the product domain, plus the
    folded residual stream shift-aligned into it (the acc_init hook — a pure
    left shift on pow2 grids, so the fold is exact)."""
    mp = ctx.params.matmul(t.layer, t.role)
    x = ctx.env[t.inputs[0]]
    B, S, _ = x.shape
    acc0 = jnp.broadcast_to(mp.bq[None, :].astype(jnp.int32),
                            (B * S, t.dout))
    if t.skip is not None:
        skip = ctx.env[t.skip].astype(jnp.int32).reshape(B * S, t.dout)
        acc0 = acc0 + Q.shift_align(
            skip, ctx.params.skip_exp(t.layer, t.role) - mp.product_exp)
    return mp, x.reshape(B * S, t.din), acc0, (B, S)


def _lm_matmul_epilogue(acc, t, mp, shape, ctx):
    if t.fused_relu:
        acc = jnp.maximum(acc, 0)
    yq = Q.requantize_shift(acc, mp.product_exp, mp.y_spec)
    ctx.put(t.output, yq.reshape(shape + (t.dout,)), mp.y_spec)


@register_task_impl("pallas", "matmul")
def _pallas_matmul(t, ctx):
    from repro.kernels.matmul_int8.ops import matmul_int8_op

    mp, x2d, acc0, shape = _lm_matmul_prologue(t, ctx)
    acc = matmul_int8_op(x2d, mp.wq, acc0, config=t.config)
    _lm_matmul_epilogue(acc, t, mp, shape, ctx)


@register_task_impl("lax-int", "matmul")
def _lax_matmul(t, ctx):
    mp, x2d, acc0, shape = _lm_matmul_prologue(t, ctx)
    acc = jax.lax.dot(x2d.astype(jnp.int32), mp.wq.astype(jnp.int32),
                      preferred_element_type=jnp.int32) + acc0
    _lm_matmul_epilogue(acc, t, mp, shape, ctx)


def _lm_attn_qkv(t, ctx):
    """Dequantize the q/k/v streams off their producing matmuls' grids into
    the (B, S, heads, hd) layout both attention cores consume."""
    B, S, _ = ctx.env[t.inputs[0]].shape
    q, k, v = (Q.dequantize(ctx.env[name], ctx.specs[name])
               for name in t.inputs)
    return (q.reshape(B, S, t.heads, t.head_dim),
            k.reshape(B, S, t.kv_heads, t.head_dim),
            v.reshape(B, S, t.kv_heads, t.head_dim))


def _lm_attn_finish(o, t, ctx):
    B, S = o.shape[:2]
    spec = ctx.out_spec(t.output)
    ctx.put(t.output,
            Q.quantize(o.reshape(B, S, t.heads * t.head_dim), spec), spec)


@register_task_impl("pallas", "attention")
def _pallas_attention(t, ctx):
    from repro.kernels.flash_attention.ops import flash_attention_op

    q, k, v = _lm_attn_qkv(t, ctx)
    o = flash_attention_op(q, k, v, causal=t.causal, config=t.config)
    _lm_attn_finish(o, t, ctx)


@register_task_impl("lax-int", "attention")
def _lax_attention(t, ctx):
    from repro.kernels.flash_attention.ops import attn_tiles
    from repro.kernels.flash_attention.ref import flash_attention_mirror

    q, k, v = _lm_attn_qkv(t, ctx)
    # the kernel wrapper's GQA flattening, op-for-op, around the bit-exact
    # tiled mirror — SAME tile pair, so the two backends cannot drift
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    kr = jnp.repeat(k, H // KV, axis=2)
    vr = jnp.repeat(v, H // KV, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = kr.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd)
    vf = vr.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd)
    bq, bk = attn_tiles(Sq, Sk, t.config)
    o = flash_attention_mirror(qf, kf, vf, causal=t.causal, bq=bq, bk=bk)
    o = o.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
    _lm_attn_finish(o, t, ctx)


def _lm_scan_operands(t, ctx):
    u, dt, Bc, Cc = (Q.dequantize(ctx.env[name], ctx.specs[name])
                     for name in t.inputs[:4])
    dt = jax.nn.softplus(dt)
    A = ctx.params.layers[t.layer].A
    B = u.shape[0]
    h0 = jnp.zeros((B, t.d_inner, t.ssm_state), jnp.float32)
    return u, dt, A, Bc, Cc, h0


def _lm_scan_finish(y, t, ctx):
    if t.gated:
        z = Q.dequantize(ctx.env[t.inputs[4]], ctx.specs[t.inputs[4]])
        y = y * jax.nn.silu(z)
    spec = ctx.out_spec(t.output)
    ctx.put(t.output, Q.quantize(y, spec), spec)


@register_task_impl("pallas", "scan")
def _pallas_scan(t, ctx):
    from repro.kernels.selective_scan.ops import selective_scan_op

    u, dt, A, Bc, Cc, h0 = _lm_scan_operands(t, ctx)
    y, _ = selective_scan_op(u, dt, A, Bc, Cc, h0, config=t.config)
    _lm_scan_finish(y, t, ctx)


@register_task_impl("lax-int", "scan")
def _lax_scan(t, ctx):
    from repro.kernels.selective_scan.ref import selective_scan_ref

    u, dt, A, Bc, Cc, h0 = _lm_scan_operands(t, ctx)
    y, _ = selective_scan_ref(u, dt, A, Bc, Cc, h0)
    _lm_scan_finish(y, t, ctx)


def lower_lm(impl_backend: str, g, cfg, params: LP.QLMParams) -> Callable:
    """Shared LM lowering: plan the optimized graph (``lowering.plan_lm``),
    bind every task to ``impl_backend``'s registered impl, and close over a
    ``tokens -> logits`` forward that runs the task program over a tensor
    environment — float embed in, float unembed (last position only) out.
    Impl binding happens HERE, at lower time, so a backend missing a kind
    fails before any executable is built."""
    plan = lowering.plan_lm(g, params)
    impls = {t.node: get_task_impl(impl_backend, t.kind) for t in plan.tasks}

    # which int8 grid each float-task output quantizes onto: its consuming
    # matmul's input grid (resolved at lower time from the plan)
    consumer_xspec = {
        t.inputs[0]: params.matmul(t.layer, t.role).x_spec
        for t in plan.tasks if isinstance(t, lowering.MatmulTask)}
    hidden_spec = LP.hidden_out_spec(params)

    def forward(tokens):
        ctx = _LMContext(params, cfg, consumer_xspec)
        emb = jnp.take(params.embed, tokens, axis=0)       # (B, S, d) float
        ctx.put(plan.embed, Q.quantize(emb, params.emb_spec),
                params.emb_spec)
        for t in plan.tasks:
            impls[t.node](t, ctx)
        h = Q.dequantize(ctx.env[plan.logits_in], hidden_spec)
        return h[:, -1, :] @ params.unembed                # (B, vocab)

    return forward


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------


@register_backend("lax-int")
class LaxIntBackend:
    """Reference integer graph: lax convs, int32 accumulators, shift requant,
    residual add folded into conv1's accumulator init."""

    def lower(self, g, cfg, params) -> Callable:
        if lowering._is_lm_cfg(cfg):
            return lower_lm("lax-int", g, cfg, params)
        plan = lowering.plan_model(g, params)
        stem_out, block_outs = activation_out_specs(params, A_SPEC)

        def forward(images):
            xq = Q.quantize(images, params.stem.x_spec)
            h = _relu_requant(_int_conv(xq, params.stem, plan.stem.stride),
                              params.stem, stem_out)
            if plan.stem.pool:
                h = _max_pool(h)
            for task in plan.blocks:
                blk = params.blocks[task.index]
                out_spec = block_outs[task.index]
                if task.bottleneck:
                    y = _relu_requant(_int_conv(h, blk.conv0), blk.conv0,
                                      blk.conv1.x_spec)
                    y = _relu_requant(_int_conv(y, blk.conv1, task.stride),
                                      blk.conv1, blk.conv2.x_spec)
                else:
                    y = _relu_requant(_int_conv(h, blk.conv0, task.stride),
                                      blk.conv0, blk.conv1.x_spec)
                sh = blk.shifts_for(out_spec.exp)["skip_shift"]
                if task.has_ds:
                    skip_q = Q.shift_align(
                        _int_conv(h, blk.ds, task.stride), sh)
                else:
                    skip_q = Q.shift_align(h, sh)
                last = blk.last
                h = _relu_requant(_int_conv(y, last, 1, acc_init=skip_q),
                                  last, out_spec)
            return _int_head(h, params.fc,
                             block_outs[-1] if block_outs else stem_out,
                             plan.head.pool)

        return forward


@register_backend("pallas")
class PallasBackend:
    """Fused kernel pipeline: one ``conv_stem`` kernel, then one
    ``resblock_fused`` kernel per residual block (conv0 + ReLU/requant +
    optional 1x1 downsample + add-fold + conv1 + ReLU/requant, all in VMEM).
    Each task's tuned :class:`~repro.tune.KernelConfig` (stamped on the graph
    by ``lowering.annotate_tuning``) selects the kernel's tiling/grid."""

    def lower(self, g, cfg, params) -> Callable:
        if lowering._is_lm_cfg(cfg):
            return lower_lm("pallas", g, cfg, params)
        from repro.kernels.conv_stem.ops import (conv_stem_op,
                                                 conv_stem_pool_op)
        from repro.kernels.resblock_fused.ops import (bottleneck_fused_op,
                                                      resblock_fused_op)

        plan = lowering.plan_model(g, params)
        stem_out, block_outs = activation_out_specs(params, A_SPEC)
        stem_op = conv_stem_pool_op if plan.stem.pool else conv_stem_op

        def forward(images):
            with jax.named_scope("input_quantize"):
                xq = Q.quantize(images, params.stem.x_spec)
            st = params.stem
            h = stem_op(xq, st.wq, st.bq,
                        shift=stem_out.exp - st.product_exp,
                        config=plan.stem.config, name="stem")
            for task in plan.blocks:
                blk = params.blocks[task.index]
                sh = blk.shifts_for(block_outs[task.index].exp)
                wd = bd = None
                if task.has_ds:
                    wd = blk.ds.wq
                    bd = blk.ds.bq.astype(jnp.int32)
                name = lowering.kernel_name(task, cfg.stage_blocks)
                if task.bottleneck:
                    h = bottleneck_fused_op(
                        h, blk.conv0.wq, blk.conv0.bq.astype(jnp.int32),
                        blk.conv1.wq, blk.conv1.bq.astype(jnp.int32),
                        blk.conv2.wq, blk.conv2.bq.astype(jnp.int32),
                        wd, bd, stride=task.stride, config=task.config,
                        name=name, **sh)
                    continue
                h = resblock_fused_op(
                    h, blk.conv0.wq, blk.conv0.bq.astype(jnp.int32),
                    blk.conv1.wq, blk.conv1.bq.astype(jnp.int32),
                    wd, bd, stride=task.stride, config=task.config,
                    name=name, **sh)
            return _int_head(h, params.fc,
                             block_outs[-1] if block_outs else stem_out,
                             plan.head.pool)

        return forward


@register_backend("pallas-stream")
class PallasStreamBackend:
    """Block-chain streaming pipeline: the plan's block sequence is
    partitioned into chains (``lowering.plan_chains``) and each chain runs
    as ONE ``kernels.megakernel`` call — the running activation stays in
    VMEM across every fused block boundary, chain weights pinned in VMEM,
    the stem conv folded into the first chain when the budget allows.  The
    TPU analogue of the paper's whole-network layer-to-layer streaming.

    Chains the VMEM planner cut down to a single block (and a stem left
    unfused) fall back to the per-block kernels — ``resblock_fused`` /
    ``conv_stem`` — so the backend degrades gracefully to exactly the
    ``pallas`` pipeline, never an illegal kernel.

    Instantiate directly (``PallasStreamBackend(cuts=[[0], [1, 2]])``) to
    pin an explicit chain partition — any partition into consecutive runs
    is bit-exact with every other (the chain-cut conformance property)."""

    def __init__(self, cuts=None, fuse_stem: bool = True, vmem_budget=None):
        self.cuts = cuts
        self.fuse_stem = fuse_stem
        self.vmem_budget = vmem_budget

    def lower(self, g, cfg, params) -> Callable:
        if lowering._is_lm_cfg(cfg):
            # no LM megakernel exists; degrade gracefully to the per-task
            # pallas kernels (the singleton-chain fallback, graph-wide)
            return lower_lm("pallas", g, cfg, params)
        if lowering.is_bottleneck_cfg(cfg):
            raise lowering.LoweringError(
                f"backend 'pallas-stream' has no bottleneck chain kernel for "
                f"{cfg.name!r}; use 'pallas' or 'lax-int'")
        from repro.core import dataflow
        from repro.kernels.conv_stem.ops import conv_stem_op
        from repro.kernels.megakernel.megakernel import ChainBlockSpec
        from repro.kernels.megakernel.ops import block_chain_op
        from repro.kernels.resblock_fused.ops import resblock_fused_op
        from repro.tune import space as tspace

        plan = lowering.plan_model(g, params)
        chains = lowering.plan_chains(plan, cfg, cuts=self.cuts,
                                      fuse_stem=self.fuse_stem,
                                      vmem_budget=self.vmem_budget)
        shapes = dataflow.resnet_block_shapes(cfg.blocks_per_stage,
                                              cfg.base_width, cfg.img)
        budget = tspace.VMEM_BUDGET if self.vmem_budget is None \
            else self.vmem_budget

        def chain_config(chain, batch):
            # untuned chains default to the LARGEST VMEM-legal batch tile:
            # chain weights are pinned across grid steps, so bigger tiles
            # only amortize — and they feed the batched tap GEMMs more rows
            if chain.config is not None:
                return chain.config
            legal = tspace.chain_space(
                [shapes[t.index] for t in chain.blocks], batch,
                stem_och=cfg.base_width if chain.stem is not None else 0,
                vmem_budget=budget)
            return max(legal, key=lambda c: c.batch_tile) if legal else None
        stem_out, block_outs = activation_out_specs(params, A_SPEC)
        st = params.stem
        stem_shift = stem_out.exp - st.product_exp
        stage_blocks = cfg.stage_blocks

        # static per-chain schedule: (operand pytree, ChainBlockSpec tuple)
        lowered = []
        for chain in chains:
            ops, specs = [], []
            for task in chain.blocks:
                blk = params.blocks[task.index]
                sh = blk.shifts_for(block_outs[task.index].exp)
                ws = [blk.conv0.wq, blk.conv0.bq.astype(jnp.int32),
                      blk.conv1.wq, blk.conv1.bq.astype(jnp.int32)]
                if task.has_ds:
                    ws += [blk.ds.wq, blk.ds.bq.astype(jnp.int32)]
                ops.append(tuple(ws))
                specs.append(ChainBlockSpec(
                    stride=task.stride, has_ds=task.has_ds, **sh))
            lowered.append((chain, tuple(ops), tuple(specs)))

        def forward(images):
            with jax.named_scope("input_quantize"):
                h = Q.quantize(images, st.x_spec)
            if not chains or chains[0].stem is None:
                # stem not fused into the first chain: per-kernel fallback
                h = conv_stem_op(h, st.wq, st.bq, shift=stem_shift,
                                 config=plan.stem.config, name="stem")
            for chain, ops, specs in lowered:
                if len(specs) == 1 and chain.stem is None:
                    # singleton chain: the megakernel would add nothing —
                    # run the plain fused block
                    task, = chain.blocks
                    blk = params.blocks[task.index]
                    sh = blk.shifts_for(block_outs[task.index].exp)
                    wd = blk.ds.wq if task.has_ds else None
                    bd = blk.ds.bq.astype(jnp.int32) if task.has_ds else None
                    h = resblock_fused_op(
                        h, blk.conv0.wq, blk.conv0.bq.astype(jnp.int32),
                        blk.conv1.wq, blk.conv1.bq.astype(jnp.int32),
                        wd, bd, stride=task.stride, config=task.config,
                        name=lowering.kernel_name(task, stage_blocks), **sh)
                    continue
                stem = (st.wq, st.bq.astype(jnp.int32)) \
                    if chain.stem is not None else None
                h = block_chain_op(
                    h, ops, specs=specs, stem=stem,
                    stem_shift=stem_shift if chain.stem is not None else None,
                    config=chain_config(chain, images.shape[0]),
                    name=lowering.kernel_name(chain, stage_blocks))
            return _int_head(h, params.fc,
                             block_outs[-1] if block_outs else stem_out)

        return forward


@register_backend("float")
class FloatBackend:
    """Float emulation of the integer graph on the same pow2 grids: convs run
    in float on dequantized weights, every activation is fake-quantized onto
    its integer grid, and the skip stream is rounded onto conv1's product
    grid.  Tracks the integer backends to float rounding error — the serving
    A/B reference for quantization loss."""

    def lower(self, g, cfg, params) -> Callable:
        if lowering._is_lm_cfg(cfg):
            raise lowering.LoweringError(
                f"backend 'float' has no LM lowering for config "
                f"{cfg.name!r} (family={cfg.family!r}); use 'pallas' or "
                f"'lax-int'")
        if lowering.is_bottleneck_cfg(cfg):
            raise lowering.LoweringError(
                f"backend 'float' has no bottleneck lowering for "
                f"{cfg.name!r}; use 'pallas' or 'lax-int'")
        plan = lowering.plan_model(g, params)
        stem_out, block_outs = activation_out_specs(params, A_SPEC)

        def fconv(h, c: QConvParams, stride=1):
            wf = Q.dequantize(c.wq, c.w_spec)
            bf = Q.dequantize(c.bq, c.b_spec)
            y = jax.lax.conv_general_dilated(
                h, wf, window_strides=(stride, stride), padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            return y + bf

        def fq(x, spec):
            return Q.dequantize(Q.quantize(x, spec), spec)

        def forward(images):
            h = fq(images, params.stem.x_spec)
            h = fq(jax.nn.relu(fconv(h, params.stem)), stem_out)
            for task in plan.blocks:
                blk = params.blocks[task.index]
                y = fq(jax.nn.relu(fconv(h, blk.conv0, task.stride)),
                       blk.conv1.x_spec)
                grid = Q.QSpec(32, True, blk.conv1.product_exp)
                if task.has_ds:
                    skip = fq(fconv(h, blk.ds, task.stride), grid)
                else:
                    skip = fq(h, grid)
                z = fconv(y, blk.conv1, 1) + skip
                h = fq(jax.nn.relu(z), block_outs[task.index])
            pooled = jnp.mean(h, axis=(1, 2))
            return pooled @ Q.dequantize(params.fc.wq, params.fc.w_spec) \
                + params.fc.b

        return forward
