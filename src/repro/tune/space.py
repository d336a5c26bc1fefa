"""Design-space enumeration: the legal ``KernelConfig`` set per graph task.

The software CDSE front half (paper §III-E, Algorithm 1's candidate set):
for every task of the lowering plan — the stem kernel and one fused
residual-block kernel per block — enumerate the tiling knobs that are

  1. **divisor-legal**: ``batch_tile | N`` and ``cout_block | Cout`` so the
     Pallas grid tiles the iteration space exactly, and a channel block is
     the full width or a multiple of the chip's 128 lanes (Mosaic refuses
     any other minor block dim);
  2. **VMEM-legal**: the per-grid-step footprint, counted in the chip's
     lane-padded tiles (``core.dataflow.conv_task_vmem_bytes`` /
     ``resblock_task_vmem_bytes`` / ``chain_task_vmem_bytes``), fits the
     per-core budget, the TPU analogue of the BRAM cap;
  3. **balance-pruned**: channel blocks below the eq. 12-14 balanced unroll
     (``core.ilp.balanced_och_par``) are dropped — a task tiled below its
     balanced ``och_par`` is the pipeline bottleneck by construction, so
     Algorithm 1 would never pick it;
  4. **fusion-legal**: ``resblock_fused`` never enumerates ``cout_block`` —
     conv1 consumes all of conv0's channels, so splitting Cout would push
     the intermediate back through HBM (the traffic the fusion removes).

Structure-only: nothing here touches jax or weights, so the space for a
model is enumerable in microseconds and trivially testable.
"""
from __future__ import annotations

from typing import Dict, List

from repro.core import dataflow, ilp
from repro.tune.config import KernelConfig

# Per-core VMEM budget: Mosaic's default scoped-VMEM limit on v5e is 16 MiB;
# 12 MiB leaves headroom for its own scratch.  The lane-padded models put a
# ResNet20 32x32x16 stage near 3-4 MiB per image, so this cap sets the
# batch tile.
VMEM_BUDGET = 12 * 2**20


def divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def stem_space(layer: dataflow.ConvLayer, batch: int, cout_floor: int = 1,
               vmem_budget: int = VMEM_BUDGET) -> List[KernelConfig]:
    """Legal (batch_tile, cout_block) grid for the stem conv kernel."""
    floor = min(cout_floor, layer.och)
    out = []
    for bt in divisors(batch):
        for cb in divisors(layer.och):
            if cb < floor:
                continue                      # balance-pruned (eq. 12-14)
            if cb != layer.och and cb % 128:
                continue                      # Mosaic: lane blocks of 128
            if dataflow.conv_task_vmem_bytes(layer, bt, cb) > vmem_budget:
                continue
            out.append(KernelConfig(batch_tile=bt, cout_block=cb))
    return out


def block_space(layer0: dataflow.ConvLayer, batch: int,
                downsample: bool = False,
                vmem_budget: int = VMEM_BUDGET) -> List[KernelConfig]:
    """Legal batch tilings for one fused residual block (``layer0`` is the
    block's conv0 row of ``dataflow.resnet_layers``).  Channel blocking is
    fusion-illegal here (rule 4)."""
    out = []
    for bt in divisors(batch):
        vmem = dataflow.resblock_task_vmem_bytes(
            layer0.ih, layer0.iw, layer0.ich, layer0.och, bt,
            downsample=downsample, stride=layer0.stride)
        if vmem <= vmem_budget:
            out.append(KernelConfig(batch_tile=bt))
    return out


def chain_space(blocks, batch: int, stem_och: int = 0,
                vmem_budget: int = VMEM_BUDGET) -> List[KernelConfig]:
    """Legal batch tilings for one block-chain megakernel (``blocks`` is a
    list of :class:`~repro.core.dataflow.BlockShape` chain links, in order;
    ``stem_och > 0`` fuses the stem at the head).  A chain whose pinned
    weights + streaming working set exceed the VMEM budget at *every* batch
    tile is unschedulable — the empty list tells the planner to cut it
    shorter.  Channel blocking is fusion-illegal, as for the single fused
    block (rule 4)."""
    out = []
    for bt in divisors(batch):
        vmem = dataflow.chain_task_vmem_bytes(blocks, bt, stem_och=stem_och)
        if vmem <= vmem_budget:
            out.append(KernelConfig(batch_tile=bt))
    return out


def chain_cut_points(blocks, batch: int, stem_och: int = 0,
                     vmem_budget: int = VMEM_BUDGET) -> List[List[int]]:
    """Greedy longest-legal partition of a model's block sequence into
    chains: extend the open chain while :func:`chain_space` still has a
    legal tiling, else cut.  ``blocks`` is the whole-model
    ``dataflow.resnet_block_shapes`` list; returns lists of block indices.
    Any partition into runs of consecutive blocks is *arithmetically* legal
    (asserted by the conformance chain-cut property test); this picks the
    one that minimizes HBM boundary traffic under the VMEM cap."""
    cuts, open_chain = [], []
    for i, _ in enumerate(blocks):
        cand = open_chain + [i]
        och = stem_och if (not cuts and cand[0] == 0) else 0
        if chain_space([blocks[j] for j in cand], batch, stem_och=och,
                       vmem_budget=vmem_budget):
            open_chain = cand
            continue
        if open_chain:
            cuts.append(open_chain)
        # a single block over budget still has to run somewhere: emit it as
        # a singleton chain (the backend falls back to resblock_fused)
        open_chain = [i]
    if open_chain:
        cuts.append(open_chain)
    return cuts


def model_space(cfg, batch: int,
                vmem_budget: int = VMEM_BUDGET
                ) -> Dict[str, List[KernelConfig]]:
    """Per-task legal configs for a ResNetConfig at one batch bucket.

    Keys match the lowering plan: ``"stem"`` and ``"block{i}"``.  Every
    returned config is bit-exact with the default by the kernel contract
    (asserted config-by-config in tests/test_tune.py).
    """
    layers = dataflow.resnet_layers(cfg.blocks_per_stage, cfg.base_width,
                                    cfg.img)
    balanced = dict(zip((l.name for l in layers),
                        ilp.balanced_och_par(layers, pow2=True)))
    spaces = {"stem": stem_space(layers[0], batch,
                                 cout_floor=balanced["stem"],
                                 vmem_budget=vmem_budget)}
    by_name = {l.name: l for l in layers}
    n_blocks = 3 * cfg.blocks_per_stage
    for i in range(n_blocks):
        l0 = by_name[f"c{i}_0"]
        spaces[f"block{i}"] = block_space(
            l0, batch, downsample=f"ds{i}" in by_name,
            vmem_budget=vmem_budget)
    return spaces


def matmul_space(M: int, K: int, N: int, acc_init: bool = False,
                 vmem_budget: int = VMEM_BUDGET) -> List[KernelConfig]:
    """Legal (bm, bn, bk) MXU tilings for one int8 matmul task: divisor-
    legal over every grid dim, VMEM-legal per grid step
    (``dataflow.matmul_task_vmem_bytes``)."""
    del acc_init   # the acc-init tile is in the footprint unconditionally
    out = []
    for bm in divisors(M):
        for bn in divisors(N):
            for bk in divisors(K):
                if dataflow.matmul_task_vmem_bytes(bm, bn, bk) > vmem_budget:
                    continue
                out.append(KernelConfig(bm=bm, bn=bn, bk=bk))
    return out


def attention_space(Sq: int, Sk: int, head_dim: int,
                    vmem_budget: int = VMEM_BUDGET) -> List[KernelConfig]:
    """Legal (bq, bk) tile pairs for one flash-attention task, carried on
    the matmul knob names (``bm`` = query tile, ``bk`` = kv tile — the
    ``kernels.flash_attention.ops.attn_tiles`` mapping)."""
    out = []
    for bq in divisors(Sq):
        for bk in divisors(Sk):
            if dataflow.attention_task_vmem_bytes(
                    Sk, head_dim, bq, bk) > vmem_budget:
                continue
            out.append(KernelConfig(bm=bq, bk=bk))
    return out


def scan_space(seq_len: int, d_inner: int, ssm_state: int,
               vmem_budget: int = VMEM_BUDGET) -> List[KernelConfig]:
    """Legal d_inner blockings (``cout_block`` = the kernel's ``bd`` knob)
    for one selective-scan task."""
    out = []
    for bd in divisors(d_inner):
        if dataflow.scan_task_vmem_bytes(
                seq_len, ssm_state, bd) > vmem_budget:
            continue
        out.append(KernelConfig(cout_block=bd))
    return out


def lm_model_space(cfg, batch: int,
                   vmem_budget: int = VMEM_BUDGET
                   ) -> Dict[str, List[KernelConfig]]:
    """Per-task legal configs for an LM config (``compile.lm_params.
    QLMConfig``) at one batch bucket.  Keys match ``lowering.tuning_key``:
    ``layer{i}/{role}`` for every matmul / attention / scan task of the
    optimized graph.  Matmul M is the flattened token count
    (``batch * seq_len``)."""
    M = batch * cfg.seq_len
    spaces: Dict[str, List[KernelConfig]] = {}
    for i in range(cfg.num_layers):
        if cfg.family == "dense":
            qkv = cfg.num_heads * cfg.head_dim
            kv = cfg.num_kv_heads * cfg.head_dim
            dims = dict(wq=(cfg.d_model, qkv), wk=(cfg.d_model, kv),
                        wv=(cfg.d_model, kv), wo=(qkv, cfg.d_model),
                        up=(cfg.d_model, cfg.d_ff),
                        down=(cfg.d_ff, cfg.d_model))
            spaces[f"layer{i}/attn"] = attention_space(
                cfg.seq_len, cfg.seq_len, cfg.head_dim,
                vmem_budget=vmem_budget)
        else:
            dims = dict(wu=(cfg.d_model, cfg.d_inner),
                        wz=(cfg.d_model, cfg.d_inner),
                        wdt=(cfg.d_model, cfg.d_inner),
                        wb=(cfg.d_model, cfg.ssm_state),
                        wc=(cfg.d_model, cfg.ssm_state),
                        wo=(cfg.d_inner, cfg.d_model))
            spaces[f"layer{i}/scan"] = scan_space(
                cfg.seq_len, cfg.d_inner, cfg.ssm_state,
                vmem_budget=vmem_budget)
        for role, (din, dout) in dims.items():
            spaces[f"layer{i}/{role}"] = matmul_space(
                M, din, dout, acc_init=role in ("wo", "down"),
                vmem_budget=vmem_budget)
    return spaces


def space_size(spaces: Dict[str, List[KernelConfig]]) -> int:
    """Cardinality of the joint design space (product over tasks) — what an
    exhaustive search would have to time on device."""
    total = 1
    for cands in spaces.values():
        total *= max(1, len(cands))
    return total
