"""Graph-driven lowering: optimized IR -> ordered task program.

The paper's flow is *parse -> optimize the graph -> generate the accelerator*.
``core.graph`` performs the middle stage; this module performs the front half
of the last stage — and it is GENERIC: a node-kind -> handler registry
(:func:`register_task`) drives a walk over the **topologically sorted**
optimized graph, so any graph whose node kinds are registered lowers to a
task program, not just the ResNet stem->blocks->head chain.

Task kinds:

  * ``StemTask`` / ``BlockTask`` / ``HeadTask`` — the conv pipeline, exactly
    as before (conv pairing handled by the ``conv`` handler's walk state);
  * ``MatmulTask`` — one quantized matmul, optionally with fused ReLU and
    the residual add folded into its accumulator init (``acc_init``: the
    skip stream enters the int32 product domain through a pure shift — the
    paper's Fig. 13 add-fold generalized off the conv pipeline);
  * ``AttentionTask`` / ``ScanTask`` — the float interludes of the LM
    graphs, backed by the ``flash_attention`` / ``selective_scan`` kernels.

Entry points: :func:`plan_model` (conv graphs -> ``LoweringPlan``),
:func:`plan_lm` (LM graphs -> ``LMPlan``).  Both walks are strict: they
*require* the post-optimization invariants (no bn / relu / add nodes; skip
streams wired) and raise :class:`LoweringError` naming the offending node,
its kind, and the failed check — a backend can never silently compile the
unoptimized dataflow, and a failure on a new graph kind is diagnosable from
the message alone.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

from repro.core import graph as G
from repro.compile.params import QResNetParams
from repro.tune.config import KernelConfig


class LoweringError(ValueError):
    """The graph does not satisfy the optimized-IR invariants."""


def _node_err(node: G.Node, check: str) -> "LoweringError":
    """Every strictness failure carries node id + kind + the check."""
    return LoweringError(f"node {node.name!r} (kind={node.op}): {check}")


# ---------------------------------------------------------------------------
# Task records
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StemTask:
    node: str                 # graph node name
    och: int
    config: Optional[KernelConfig] = None   # tuned tiling (None = default)
    stride: int = 1
    pool: Optional[str] = None  # "max": the 3x3/2 max pool that follows


@dataclasses.dataclass(frozen=True)
class BlockTask:
    index: int                # block index (== params.blocks[index])
    conv0: str                # graph node names, for provenance/debugging
    conv1: str
    stride: int
    has_ds: bool              # 1x1 downsample merged into conv0 (loop_merge)
    och: int
    config: Optional[KernelConfig] = None   # tuned tiling (None = default)
    conv2: Optional[str] = None  # a bottleneck's third conv (the add-fold)

    @property
    def bottleneck(self) -> bool:
        return self.conv2 is not None


@dataclasses.dataclass(frozen=True)
class HeadTask:
    pool: str                 # pool kind ("avg")
    num_classes: int


@dataclasses.dataclass(frozen=True)
class MatmulTask:
    """One int8 matmul node: inputs[0] @ W(layer, role) in int32, optional
    fused ReLU, requantized onto the role's output grid.  ``skip`` names the
    tensor whose int8 stream initializes the accumulator (the add-fold);
    None means a plain matmul."""
    kind = "matmul"
    node: str
    layer: int
    role: str
    din: int
    dout: int
    inputs: Tuple[str, ...]
    output: str
    skip: Optional[str] = None
    fused_relu: bool = False
    config: Optional[KernelConfig] = None


@dataclasses.dataclass(frozen=True)
class AttentionTask:
    """Causal (flash) attention over the layer's q/k/v streams."""
    kind = "attention"
    node: str
    layer: int
    heads: int
    kv_heads: int
    head_dim: int
    causal: bool
    inputs: Tuple[str, ...]   # (q, k, v) tensor names
    output: str
    config: Optional[KernelConfig] = None


@dataclasses.dataclass(frozen=True)
class ScanTask:
    """Mamba1 selective scan; ``gated`` multiplies by silu(z) (inputs[4])."""
    kind = "scan"
    node: str
    layer: int
    d_inner: int
    ssm_state: int
    gated: bool
    inputs: Tuple[str, ...]   # (u, dt, B, C[, z]) tensor names
    output: str
    config: Optional[KernelConfig] = None


@dataclasses.dataclass(frozen=True)
class ChainTask:
    """A run of consecutive residual blocks fused into ONE streaming
    megakernel call (``kernels.megakernel``), optionally with the stem conv
    at its head.  The chain's tuned config is its first member's (the
    megakernel's only knob is ``batch_tile``; ``cout_block`` is
    fusion-illegal chain-wide)."""
    blocks: tuple             # Tuple[BlockTask, ...], consecutive indices
    stem: Optional[StemTask] = None

    @property
    def config(self) -> Optional[KernelConfig]:
        if self.stem is not None and self.stem.config is not None:
            return self.stem.config
        return self.blocks[0].config if self.blocks else None

    def describe(self) -> str:
        parts = (["stem"] if self.stem is not None else []) + \
            [f"b{t.index}" for t in self.blocks]
        return "+".join(parts)


@dataclasses.dataclass(frozen=True)
class LoweringPlan:
    stem: StemTask
    blocks: List[BlockTask]
    head: HeadTask


@dataclasses.dataclass(frozen=True)
class LMPlan:
    """An LM graph lowered to an ordered task program: the tasks run in
    topological order over a tensor-name environment, bracketed by the float
    embed / unembed head."""
    tasks: Tuple[object, ...]          # Matmul/Attention/ScanTask, ordered
    embed: str                         # embed node's output tensor
    logits_in: str                     # tensor entering the unembed head
    vocab: int
    seq_len: int


# ---------------------------------------------------------------------------
# Node-kind -> handler registry
# ---------------------------------------------------------------------------

# handler(node, state) -> None; mutates the walk state.  Registered per node
# kind; the walk dispatches every node of the topologically sorted graph
# through this table, so new graph families plug in without touching the
# walk itself.
TASK_HANDLERS: Dict[str, Callable] = {}


def register_task(op: str):
    """Register the lowering handler for one node kind.  Re-registering
    overrides (latest wins) — tests use this to stub custom kinds."""
    def deco(fn):
        TASK_HANDLERS[op] = fn
        return fn
    return deco


@dataclasses.dataclass
class _WalkState:
    """Accumulator the handlers write into while the walk runs."""
    g: G.Graph
    # conv pipeline
    stem: Optional[StemTask] = None
    blocks: List[BlockTask] = dataclasses.field(default_factory=list)
    head_pool: Optional[str] = None
    head_fc: Optional[int] = None
    pending_conv0: Optional[G.Node] = None
    pending_conv1: Optional[G.Node] = None    # a bottleneck's middle conv
    bottlenecks: frozenset = frozenset()      # blocks that have a conv2
    # generic task program
    tasks: List[object] = dataclasses.field(default_factory=list)
    embed: Optional[G.Node] = None
    unembed: Optional[G.Node] = None


def _walk(g: G.Graph) -> _WalkState:
    """THE generic lowering driver: topological sort, then registry
    dispatch per node.  Unregistered kinds fail loudly with the node id."""
    state = _WalkState(g=g, bottlenecks=frozenset(
        n.attrs.get("block") for n in g.nodes
        if n.op == "conv" and n.attrs.get("role") == "conv2"))
    for n in G.topological_sort(g):
        handler = TASK_HANDLERS.get(n.op)
        if handler is None:
            raise _node_err(
                n, f"no lowering handler registered for this kind "
                   f"(registered: {sorted(TASK_HANDLERS)})")
        handler(n, state)
    return state


@register_task("input")
@register_task("output")
def _lower_noop(n: G.Node, state: _WalkState) -> None:
    del n, state


@register_task("conv")
def _lower_conv(n: G.Node, state: _WalkState) -> None:
    """The conv pipeline's stateful walk: the stem, then per residual block
    conv0, conv1 and, in a bottleneck, conv2; the last conv of a block
    carries the folded residual add."""
    role = n.attrs.get("role")
    if role == "stem":
        if not {"bn", "relu"} <= set(n.fused):
            raise _node_err(n, "stem conv must have bn+relu folded in "
                               "(fold_bn/merge_relu did not run)")
        state.stem = StemTask(node=n.name, och=n.attrs["och"],
                              config=n.attrs.get("kcfg"),
                              stride=n.attrs["stride"])
    elif role == "conv0":
        if state.pending_conv0 is not None:
            raise _node_err(
                n, f"conv0 follows unpaired conv0 "
                   f"{state.pending_conv0.name!r}")
        if not n.skip_out:
            raise _node_err(n, "conv0 emits no skip stream — "
                               "loop_merge/temporal_reuse did not run")
        state.pending_conv0 = n
    elif role == "conv1" and n.attrs["block"] in state.bottlenecks:
        # a bottleneck's middle conv: its conv2 closes the block
        c0 = state.pending_conv0
        if c0 is None or c0.attrs["block"] != n.attrs["block"]:
            raise _node_err(n, "conv1 without its conv0 (pairing check)")
        state.pending_conv1 = n
    elif role in ("conv1", "conv2"):
        c0 = state.pending_conv0
        mid = state.pending_conv1
        if c0 is None or c0.attrs["block"] != n.attrs["block"] or \
                (role == "conv2") != (mid is not None):
            raise _node_err(n, f"{role} without its conv0 (pairing check)")
        if n.skip_in is None or "add_fold" not in n.fused:
            raise _node_err(n, f"residual add not folded into {role} "
                               f"(add_fold did not run)")
        if n.skip_in not in c0.outputs[1:]:
            raise _node_err(
                n, f"skip input {n.skip_in!r} is not conv0's forwarded "
                   f"stream {c0.outputs[1:]}")
        # the stride sits on conv0 of a basic block, on the 3x3 (v1.5) of
        # a bottleneck
        strided = c0 if mid is None else mid
        state.blocks.append(BlockTask(
            index=n.attrs["block"], conv0=c0.name,
            conv1=(mid or n).name, stride=strided.attrs["stride"],
            has_ds=any(f.startswith("downsample:") for f in c0.fused),
            och=n.attrs["och"], config=c0.attrs.get("kcfg"),
            conv2=n.name if mid is not None else None))
        state.pending_conv0 = state.pending_conv1 = None
    elif role == "ds":
        raise _node_err(n, "standalone downsample conv survived — "
                           "loop_merge did not run")
    else:
        raise _node_err(n, "conv without a role attr")


@register_task("pool")
def _lower_pool(n: G.Node, state: _WalkState) -> None:
    kind = n.attrs.get("kind", "avg")
    if kind == "max":
        # the stem's max pool: part of the stem task
        if state.stem is None or state.blocks or state.stem.pool:
            raise _node_err(n, "a max pool lowers only directly after the "
                               "stem conv")
        state.stem = dataclasses.replace(state.stem, pool=kind)
    else:
        state.head_pool = kind


@register_task("linear")
def _lower_linear(n: G.Node, state: _WalkState) -> None:
    state.head_fc = n.attrs.get("dout")


@register_task("matmul")
def _lower_matmul(n: G.Node, state: _WalkState) -> None:
    if n.attrs.get("role") is None or n.attrs.get("layer") is None:
        raise _node_err(n, "matmul without role/layer attrs — cannot bind "
                           "to a parameter slot")
    state.tasks.append(MatmulTask(
        node=n.name, layer=n.attrs["layer"], role=n.attrs["role"],
        din=n.attrs["din"], dout=n.attrs["dout"],
        inputs=tuple(n.inputs), output=n.outputs[0],
        skip=n.skip_in, fused_relu="relu" in n.fused,
        config=n.attrs.get("kcfg")))


@register_task("attention")
def _lower_attention(n: G.Node, state: _WalkState) -> None:
    if len(n.inputs) != 3:
        raise _node_err(n, f"attention needs (q, k, v) inputs, got "
                           f"{len(n.inputs)}")
    state.tasks.append(AttentionTask(
        node=n.name, layer=n.attrs["layer"], heads=n.attrs["heads"],
        kv_heads=n.attrs["kv_heads"], head_dim=n.attrs["head_dim"],
        causal=n.attrs.get("causal", True),
        inputs=tuple(n.inputs), output=n.outputs[0],
        config=n.attrs.get("kcfg")))


@register_task("scan")
def _lower_scan(n: G.Node, state: _WalkState) -> None:
    gated = n.attrs.get("gated", False)
    want = 5 if gated else 4
    if len(n.inputs) != want:
        raise _node_err(n, f"scan needs (u, dt, B, C{', z' if gated else ''})"
                           f" inputs, got {len(n.inputs)}")
    state.tasks.append(ScanTask(
        node=n.name, layer=n.attrs["layer"], d_inner=n.attrs["d_inner"],
        ssm_state=n.attrs["ssm_state"], gated=gated,
        inputs=tuple(n.inputs), output=n.outputs[0],
        config=n.attrs.get("kcfg")))


@register_task("embed")
def _lower_embed(n: G.Node, state: _WalkState) -> None:
    state.embed = n


@register_task("unembed")
def _lower_unembed(n: G.Node, state: _WalkState) -> None:
    state.unembed = n


# ---------------------------------------------------------------------------
# Graph builders (dispatch on config kind)
# ---------------------------------------------------------------------------


def is_bottleneck_cfg(cfg) -> bool:
    """An ImageNet bottleneck config (``models.resnet.BottleneckConfig``)."""
    from repro.models.resnet import BottleneckConfig
    return isinstance(cfg, BottleneckConfig)


def _is_lm_cfg(cfg) -> bool:
    return hasattr(cfg, "seq_len") and getattr(cfg, "family", None) in (
        "dense", "ssm")


def model_graph(cfg) -> G.Graph:
    """The (unoptimized) IR for a config — what the paper parses from the
    QONNX export.  ResNet configs build the conv graph; LM configs
    (``compile.lm_params.QLMConfig``) build the transformer / Mamba stack."""
    if _is_lm_cfg(cfg):
        if cfg.family == "dense":
            return G.build_transformer_graph(cfg, cfg.seq_len)
        return G.build_ssm_graph(cfg, cfg.seq_len)
    if is_bottleneck_cfg(cfg):
        return G.build_bottleneck_graph(cfg.stage_blocks, cfg.base_width,
                                        cfg.expansion, cfg.img,
                                        cfg.num_classes)
    return G.build_resnet_graph(cfg.blocks_per_stage, cfg.base_width,
                                cfg.img, cfg.num_classes)


def optimized_graph(cfg) -> G.Graph:
    if _is_lm_cfg(cfg):
        return G.optimize_lm(model_graph(cfg))
    return G.optimize(model_graph(cfg))


def tuning_key(n: G.Node) -> Optional[str]:
    """The tuning-dict key of one lowered graph node (None if the node has
    no tunable task): conv tasks keep the legacy ``stem``/``block{i}`` keys;
    LM tasks are ``layer{i}/{role}`` (e.g. ``layer0/wq``, ``layer1/attn``)."""
    if n.op == "conv":
        role = n.attrs.get("role")
        if role == "stem":
            return "stem"
        if role == "conv0":
            return f"block{n.attrs['block']}"
        return None
    if n.op in ("matmul", "attention", "scan"):
        return f"layer{n.attrs['layer']}/{n.attrs.get('role', n.op)}"
    return None


def annotate_tuning(g: G.Graph, tuning) -> G.Graph:
    """Stamp tuned :class:`KernelConfig`\\ s onto the optimized graph's task
    nodes (``attrs["kcfg"]``) so the plan carries them into the tasks and
    any backend sees the same assignment.  ``tuning`` maps task keys
    (:func:`tuning_key`) to configs — the format ``repro.tune.search``
    returns and the JSON cache stores."""
    if not tuning:
        return g
    for n in g.nodes:
        key = tuning_key(n)
        if key is None:
            continue
        c = tuning.get(key)
        if c is not None:
            if not isinstance(c, KernelConfig):
                c = KernelConfig.from_dict(c)
            n.attrs["kcfg"] = c
    return g


# ---------------------------------------------------------------------------
# Plan entry points
# ---------------------------------------------------------------------------


def _check_optimized(g: G.Graph) -> None:
    for n in g.nodes:
        if n.op in ("bn", "relu", "add"):
            raise _node_err(
                n, f"graph still contains a {n.op} node — run "
                   f"core.graph.optimize() (or optimize_lm) before lowering")


def plan_model(g: G.Graph, params: Optional[QResNetParams] = None) -> LoweringPlan:
    """Walk an optimized conv graph into the ordered task list.

    When ``params`` is given, the plan is cross-checked against the parameter
    containers (block count, downsample presence) so a graph/params mismatch
    fails at compile time, not with silently wrong logits.
    """
    _check_optimized(g)
    state = _walk(g)

    if state.stem is None or state.head_pool is None or state.head_fc is None:
        raise LoweringError(
            "graph is missing stem / pool / classifier nodes "
            "(not a lowered conv graph?)")
    if state.pending_conv0 is not None:
        raise _node_err(state.pending_conv0, "unpaired conv0 at end of walk")
    if state.pending_conv1 is not None:
        raise _node_err(state.pending_conv1, "conv1 without its conv2 at "
                                             "end of walk")

    plan = LoweringPlan(stem=state.stem, blocks=state.blocks,
                        head=HeadTask(pool=state.head_pool,
                                      num_classes=state.head_fc))

    if params is not None:
        if len(params.blocks) != len(plan.blocks):
            raise LoweringError(
                f"graph has {len(plan.blocks)} residual blocks but params "
                f"carry {len(params.blocks)}")
        for t in plan.blocks:
            if params.blocks[t.index].has_ds != t.has_ds:
                raise LoweringError(
                    f"block {t.index} (node {t.conv0!r}): graph "
                    f"downsample={t.has_ds} but params "
                    f"downsample={params.blocks[t.index].has_ds}")
            if (params.blocks[t.index].conv2 is not None) != t.bottleneck:
                raise LoweringError(
                    f"block {t.index} (node {t.conv0!r}): graph "
                    f"bottleneck={t.bottleneck} but params conv2="
                    f"{params.blocks[t.index].conv2 is not None}")
    return plan


def plan_lm(g: G.Graph, params=None) -> LMPlan:
    """Walk an optimized LM graph into the ordered task program.

    Strictness: adds must be folded (``add_fold_matmul``), ReLUs merged,
    embed/unembed present.  When ``params`` (a
    :class:`~repro.compile.lm_params.QLMParams`) is given, every matmul
    task's (layer, role) binding is resolved against it at plan time."""
    _check_optimized(g)
    state = _walk(g)

    if state.embed is None or state.unembed is None:
        raise LoweringError(
            "graph is missing embed / unembed nodes (not an LM graph?)")
    if not state.tasks:
        raise LoweringError("LM graph lowered to zero tasks")
    if state.stem is not None or state.blocks:
        raise LoweringError(
            "graph mixes conv and LM task kinds — no backend lowers both "
            "in one plan")

    if params is not None:
        if len({t.layer for t in state.tasks}) != len(params.layers):
            raise LoweringError(
                f"graph has {len({t.layer for t in state.tasks})} layers "
                f"but params carry {len(params.layers)}")
        for t in state.tasks:
            if isinstance(t, MatmulTask):
                mp = params.matmul(t.layer, t.role)   # raises KeyError
                if mp.wq.shape != (t.din, t.dout):
                    raise LoweringError(
                        f"node {t.node!r} (kind=matmul): weight shape "
                        f"{tuple(mp.wq.shape)} != graph ({t.din}, {t.dout})")

    return LMPlan(tasks=tuple(state.tasks),
                  embed=state.embed.outputs[0],
                  logits_in=state.unembed.inputs[0],
                  vocab=state.unembed.attrs["dout"],
                  seq_len=state.embed.attrs["seq_len"])


def kernel_name(task, stage_blocks) -> str:
    """The device-op name of a task's kernel: its place in the network, so
    a profile finds it across compiles and refactors.  ``stem``;
    ``s<stage>b<block>`` for a residual block (stages from 1, blocks from
    0), with ``_ds`` when its 1x1 downsample is fused; ``[stem_]<first>``
    or ``[stem_]<first>_to_<last>`` for a chain.  ``stage_blocks`` is the
    number of blocks in each stage (``cfg.stage_blocks``)."""
    if isinstance(task, StemTask):
        return "stem"
    if isinstance(task, ChainTask):
        names = [kernel_name(t, stage_blocks) for t in task.blocks]
        name = names[0] if len(names) == 1 else f"{names[0]}_to_{names[-1]}"
        return name if task.stem is None else f"stem_{name}"
    block = task.index
    for stage, n in enumerate(stage_blocks):
        if block < n:
            return f"s{stage + 1}b{block}" + ("_ds" if task.has_ds else "")
        block -= n
    raise LoweringError(f"block {task.index} is past the last stage of "
                        f"{tuple(stage_blocks)}")


def plan_chains(plan: LoweringPlan, cfg, cuts=None, fuse_stem: bool = True,
                vmem_budget: Optional[int] = None) -> List[ChainTask]:
    """Partition the plan's block sequence into streaming chains — the front
    half of the ``pallas-stream`` backend.

    ``cuts`` (optional) is an explicit partition as lists of block indices;
    it must be consecutive runs covering every block exactly once (any such
    partition is arithmetically legal — the chain-cut conformance property —
    so an explicit cut is only shape-checked, not budget-checked).  Without
    it the greedy VMEM-budget planner (``tune.space.chain_cut_points``)
    picks the longest legal runs: chain weights are pinned in VMEM, so a
    chain is cut where its pinned set + streaming working set would exceed
    the budget.  ``fuse_stem`` pulls the stem conv into the first chain when
    that chain stays legal with it; otherwise the stem runs as its own
    ``conv_stem`` kernel."""
    from repro.core import dataflow
    from repro.tune import space as tspace

    budget = tspace.VMEM_BUDGET if vmem_budget is None else vmem_budget
    shapes = dataflow.resnet_block_shapes(cfg.blocks_per_stage,
                                          cfg.base_width, cfg.img)
    if len(shapes) != len(plan.blocks):
        raise LoweringError(
            f"config yields {len(shapes)} block shapes but the plan has "
            f"{len(plan.blocks)} blocks")

    stem_och = cfg.base_width if fuse_stem else 0
    if cuts is None:
        # legality at batch_tile=1 is the binding constraint (any batch
        # bucket admits bt=1), so the partition is bucket-independent
        cuts = tspace.chain_cut_points(shapes, batch=1, stem_och=stem_och,
                                       vmem_budget=budget)
    else:
        seen = [i for run in cuts for i in run]
        if seen != list(range(len(plan.blocks))):
            raise LoweringError(
                f"chain cuts {cuts} are not a partition of blocks "
                f"0..{len(plan.blocks) - 1} into consecutive runs")

    chains = []
    for run in cuts:
        stem = None
        if fuse_stem and run and run[0] == 0:
            # the stem joins the first chain only if the joined chain still
            # has a legal tiling; otherwise it stays a separate kernel
            if tspace.chain_space([shapes[i] for i in run], batch=1,
                                  stem_och=cfg.base_width,
                                  vmem_budget=budget):
                stem = plan.stem
        chains.append(ChainTask(
            blocks=tuple(plan.blocks[i] for i in run), stem=stem))
    return chains
