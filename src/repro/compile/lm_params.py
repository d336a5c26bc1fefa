"""Typed int8 parameter containers + serving config for LM graphs.

The LM counterpart of ``compile.params``: the generic graph->task compiler
(``compile.lowering.plan_lm``) binds each matmul/attention/scan node to a
slot in these containers via the node's ``(layer, role)`` attrs, exactly
like the conv pipeline binds ``(role, block)`` to ``QResNetParams``.

Arithmetic contract (the paper's pow2-int8 scheme applied to a residual
LM stream):

  * every activation lives on a signed-int8 pow2 grid (``QSpec``); the
    residual stream keeps ONE grid per layer boundary so the add-fold is a
    pure shift;
  * a matmul task is ``acc = x_q @ w_q + b_q (+ shift_align(skip))`` in
    int32 at the product domain ``x_exp + w_exp``, then (optional fused
    ReLU and) ``requantize_shift`` onto the output grid — identical
    construction to the conv tasks, so pallas and lax-int are bit-exact
    the same way;
  * attention and scan are float interludes: dequantize the int8 operands,
    run the kernel (or its bit-exact lax mirror), quantize the result onto
    the consuming matmul's input grid;
  * embed / unembed run in float (the paper's host-side head), mirroring
    ``compile.backends._int_head``.

``init_lm_params`` generates seeded synthetic weights — the serving/
conformance fixture; accuracy-bearing weights would come from
``repro.quantize`` calibration, which is out of scope here.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import QSpec

# default signed-int8 activation grid for LM streams (range ~±4 at exp -5);
# the per-matrix weight grids are calibrated at init time
LM_A_SPEC = QSpec(bits=8, signed=True, exp=-5)


@dataclasses.dataclass(frozen=True)
class QLMConfig:
    """What ``compile_model``/the engine need to serve one LM: identity,
    family (selects the graph builder), the reduced shape, and the fixed
    sequence length every executable is compiled for.  Built from a
    ``repro.configs`` ModelConfig via :func:`lm_config`."""

    name: str
    family: str                  # "dense" (transformer) | "ssm" (mamba1)
    seq_len: int
    num_layers: int
    d_model: int
    vocab_size: int
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    d_inner: int = 0
    ssm_state: int = 0


def lm_config(model_cfg, seq_len: int) -> QLMConfig:
    """Project a ``repro.configs.base.ModelConfig`` onto the serving config
    the generic compiler consumes."""
    if model_cfg.family not in ("dense", "ssm"):
        raise ValueError(
            f"{model_cfg.name}: family {model_cfg.family!r} has no LM "
            f"lowering (supported: dense, ssm)")
    return QLMConfig(
        name=model_cfg.name, family=model_cfg.family, seq_len=int(seq_len),
        num_layers=model_cfg.num_layers, d_model=model_cfg.d_model,
        vocab_size=model_cfg.vocab_size, num_heads=model_cfg.num_heads,
        num_kv_heads=model_cfg.num_kv_heads or model_cfg.num_heads,
        head_dim=model_cfg.head_dim, d_ff=model_cfg.d_ff,
        d_inner=model_cfg.d_inner, ssm_state=model_cfg.ssm_state)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QMatmulParams:
    """One quantized matmul task: ``acc = x_q @ wq + bq`` in int32 at the
    product domain (``x_spec.exp + w_spec.exp``), requantized onto
    ``y_spec``.  ``bq`` is int32 at the product domain (the LM bias skips
    the conv pipeline's int16 stop-over — same domain, wider storage)."""

    wq: jnp.ndarray              # (din, dout) int8
    bq: jnp.ndarray              # (dout,) int32 at s_b = s_x + s_w
    w_spec: QSpec
    x_spec: QSpec
    y_spec: QSpec

    @property
    def product_exp(self) -> int:
        return self.x_spec.exp + self.w_spec.exp

    def tree_flatten(self):
        return (self.wq, self.bq), (self.w_spec, self.x_spec, self.y_spec)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QTransformerLayerParams:
    """One decoder block; field names ARE the graph node roles."""

    wq: QMatmulParams
    wk: QMatmulParams
    wv: QMatmulParams
    wo: QMatmulParams            # add-fold target: skip = block input
    up: QMatmulParams            # fused ReLU
    down: QMatmulParams          # add-fold target: skip = post-attn stream

    def tree_flatten(self):
        return (self.wq, self.wk, self.wv, self.wo, self.up, self.down), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QSSMLayerParams:
    """One Mamba1 block; field names ARE the graph node roles (``A`` binds
    to the ``scan`` node)."""

    wu: QMatmulParams
    wz: QMatmulParams
    wdt: QMatmulParams
    wb: QMatmulParams
    wc: QMatmulParams
    wo: QMatmulParams            # add-fold target: skip = block input
    A: jnp.ndarray               # (d_inner, ssm_state) float32, negative

    def tree_flatten(self):
        return (self.wu, self.wz, self.wdt, self.wb, self.wc, self.wo,
                self.A), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QLMParams:
    """The full LM: float embedding table, quantized layer stack, float
    unembedding.  One container for both families — the layer type carries
    the distinction."""

    embed: jnp.ndarray           # (vocab, d) float32
    layers: Tuple[Union[QTransformerLayerParams, QSSMLayerParams], ...]
    unembed: jnp.ndarray         # (d, vocab) float32
    emb_spec: QSpec = LM_A_SPEC  # grid the embedded tokens quantize onto

    def tree_flatten(self):
        return (self.embed, self.layers, self.unembed), (self.emb_spec,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        embed, layers, unembed = children
        return cls(embed, tuple(layers), unembed, *aux)

    def matmul(self, layer: int, role: str) -> QMatmulParams:
        """The parameter slot of one matmul node — the (layer, role) binding
        the lowering registry uses."""
        p = getattr(self.layers[layer], role, None)
        if not isinstance(p, QMatmulParams):
            raise KeyError(
                f"layer {layer} has no matmul role {role!r} "
                f"(layer type {type(self.layers[layer]).__name__})")
        return p

    def skip_exp(self, layer: int, role: str) -> int:
        """Exponent of the skip stream entering the (layer, role) matmul's
        accumulator — the residual-fold alignment.  ``wo``'s skip is the
        block input (the qkv/in-proj input grid); ``down``'s skip is the
        post-attention stream (``wo``'s output grid)."""
        lp = self.layers[layer]
        if role == "wo":
            first = lp.wq if isinstance(lp, QTransformerLayerParams) else lp.wu
            return first.x_spec.exp
        if role == "down":
            return lp.wo.y_spec.exp
        raise KeyError(f"role {role!r} is not an add-fold target")


def hidden_out_spec(params: QLMParams) -> QSpec:
    """Grid of the final hidden state entering the unembed head."""
    last = params.layers[-1]
    if isinstance(last, QTransformerLayerParams):
        return last.down.y_spec
    return last.wo.y_spec


# ---------------------------------------------------------------------------
# Synthetic seeded init (serving/conformance fixture)
# ---------------------------------------------------------------------------


def _q_matmul(rng, din: int, dout: int, a_spec: QSpec,
              y_spec: Optional[QSpec] = None) -> QMatmulParams:
    w = rng.normal(0.0, 1.0 / np.sqrt(din), (din, dout)).astype(np.float32)
    # per-matrix pow2 weight grid covering the sampled range
    amax = max(float(np.max(np.abs(w))), 1e-12)
    w_exp = int(np.ceil(np.log2(amax / 127.0)))
    w_spec = QSpec(bits=8, signed=True, exp=w_exp)
    wq = np.clip(np.round(w / 2.0 ** w_exp), -128, 127).astype(np.int8)
    b = rng.normal(0.0, 0.05, (dout,)).astype(np.float32)
    prod_exp = a_spec.exp + w_exp
    bq = np.round(b / 2.0 ** prod_exp).astype(np.int32)
    return QMatmulParams(wq=jnp.asarray(wq), bq=jnp.asarray(bq),
                         w_spec=w_spec, x_spec=a_spec,
                         y_spec=y_spec or a_spec)


def init_lm_params(cfg: QLMConfig, seed: int = 0,
                   a_spec: QSpec = LM_A_SPEC) -> QLMParams:
    """Seeded synthetic parameters for ``cfg``: every activation grid is
    ``a_spec`` (one residual grid end-to-end — the legacy fixed-grid layout
    of the conv pipeline), weight grids calibrated per matrix."""
    rng = np.random.default_rng(seed)
    layers = []
    for _ in range(cfg.num_layers):
        if cfg.family == "dense":
            qkv = cfg.num_heads * cfg.head_dim
            kv = cfg.num_kv_heads * cfg.head_dim
            layers.append(QTransformerLayerParams(
                wq=_q_matmul(rng, cfg.d_model, qkv, a_spec),
                wk=_q_matmul(rng, cfg.d_model, kv, a_spec),
                wv=_q_matmul(rng, cfg.d_model, kv, a_spec),
                wo=_q_matmul(rng, qkv, cfg.d_model, a_spec),
                up=_q_matmul(rng, cfg.d_model, cfg.d_ff, a_spec),
                down=_q_matmul(rng, cfg.d_ff, cfg.d_model, a_spec)))
        else:
            A = -rng.uniform(0.5, 1.5,
                             (cfg.d_inner, cfg.ssm_state)).astype(np.float32)
            layers.append(QSSMLayerParams(
                wu=_q_matmul(rng, cfg.d_model, cfg.d_inner, a_spec),
                wz=_q_matmul(rng, cfg.d_model, cfg.d_inner, a_spec),
                wdt=_q_matmul(rng, cfg.d_model, cfg.d_inner, a_spec),
                wb=_q_matmul(rng, cfg.d_model, cfg.ssm_state, a_spec),
                wc=_q_matmul(rng, cfg.d_model, cfg.ssm_state, a_spec),
                wo=_q_matmul(rng, cfg.d_inner, cfg.d_model, a_spec),
                A=jnp.asarray(A)))
    embed = rng.normal(0.0, 1.0, (cfg.vocab_size, cfg.d_model))
    unembed = rng.normal(0.0, 1.0 / np.sqrt(cfg.d_model),
                         (cfg.d_model, cfg.vocab_size))
    return QLMParams(embed=jnp.asarray(embed, jnp.float32),
                     layers=tuple(layers),
                     unembed=jnp.asarray(unembed, jnp.float32),
                     emb_spec=a_spec)
