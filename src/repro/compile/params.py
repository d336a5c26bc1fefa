"""Typed, pytree-registered quantized-parameter containers.

`models.resnet.quantize_params` historically produced nested dicts keyed by
magic strings (``qp["blocks"][i]["conv0"]["wq"]``).  These containers give the
same data a typed spine the compiler can walk:

  * ``QConvParams``   — one folded+quantized conv: int8 weights, int16 bias,
                        and the three :class:`~repro.core.quant.QSpec` domains
                        (weight, input activation, bias).  The specs are pytree
                        *aux data* — static under ``jax.jit``, so a change of
                        quantization grid recompiles while a change of weights
                        does not.
  * ``QLinearParams`` — the final classifier (int8 weights, float bias).
  * ``QBlockParams``  — one residual block: conv0, conv1, optional
                        downsample, and conv2 for a bottleneck block.
  * ``QResNetParams`` — the whole network; ``from_dict``/``to_dict`` adapt the
                        legacy dict layout both ways (bit-identical arrays).

Every container is a frozen dataclass registered as a pytree node, so the
whole parameter set can be mapped, donated, sharded, or closed over by a
jitted executable exactly like any other JAX pytree.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.quant import QSpec


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QConvParams:
    """One quantized conv task: ``acc = conv(x, wq) + bq`` in int32, with the
    product domain at exponent ``x_spec.exp + w_spec.exp`` (= ``b_spec.exp``)."""

    wq: jnp.ndarray             # (fh, fw, ich, och) int8
    bq: jnp.ndarray             # (och,) int16 at s_b = s_x + s_w
    w_spec: QSpec
    x_spec: QSpec
    b_spec: QSpec

    @property
    def product_exp(self) -> int:
        """Exponent of the int32 accumulator domain (s_x + s_w)."""
        return self.x_spec.exp + self.w_spec.exp

    def tree_flatten(self):
        return (self.wq, self.bq), (self.w_spec, self.x_spec, self.b_spec)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @classmethod
    def from_dict(cls, d: dict) -> "QConvParams":
        return cls(wq=d["wq"], bq=d["bq"], w_spec=d["w_spec"],
                   x_spec=d["x_spec"], b_spec=d["b_spec"])

    def to_dict(self) -> dict:
        return dict(wq=self.wq, bq=self.bq, w_spec=self.w_spec,
                    x_spec=self.x_spec, b_spec=self.b_spec)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QLinearParams:
    """The classifier head: int8 weights, float bias (the tail runs in float,
    identical to the paper's host-side final layer).

    ``x_spec`` is the activation grid of the head's *input* feature map (the
    last residual block's output).  ``None`` means the model-level default
    grid (``models.resnet.A_SPEC``) — the legacy fixed-grid layout.  The
    ``repro.quantize`` calibration pipeline sets it per-model from observed
    activation statistics."""

    wq: jnp.ndarray             # (din, dout) int8
    b: jnp.ndarray              # (dout,) float32
    w_spec: QSpec
    x_spec: Optional[QSpec] = None

    def tree_flatten(self):
        return (self.wq, self.b), (self.w_spec, self.x_spec)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @classmethod
    def from_dict(cls, d: dict) -> "QLinearParams":
        return cls(wq=d["wq"], b=d["b"], w_spec=d["w_spec"],
                   x_spec=d.get("x_spec"))

    def to_dict(self) -> dict:
        out = dict(wq=self.wq, b=self.b, w_spec=self.w_spec)
        if self.x_spec is not None:
            out["x_spec"] = self.x_spec
        return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QBlockParams:
    """One residual block after graph optimization: two fused conv tasks and,
    for stage-entry blocks, the 1x1 downsample merged into conv0's task.  A
    bottleneck block has a third conv, ``conv2`` (1x1), and the skip folds
    into its accumulator instead of conv1's."""

    conv0: QConvParams
    conv1: QConvParams
    ds: Optional[QConvParams] = None
    conv2: Optional[QConvParams] = None

    @property
    def has_ds(self) -> bool:
        return self.ds is not None

    @property
    def last(self) -> QConvParams:
        """The conv whose accumulator the skip stream initializes."""
        return self.conv1 if self.conv2 is None else self.conv2

    def shifts(self, a_exp: int) -> dict:
        """Fixed-grid variant of :meth:`shifts_for` — every activation on
        one global grid at exponent ``a_exp`` (the legacy
        ``models.resnet.A_SPEC`` layout).  Refuses calibrated per-tensor
        params: their conv input grids differ from ``a_exp`` and the fixed
        formula would silently produce wrong requantization."""
        for c in (self.conv0, self.conv1):
            if c.x_spec.exp != a_exp:
                raise ValueError(
                    f"shifts({a_exp}) on per-tensor params (conv input grid "
                    f"exp {c.x_spec.exp}); use shifts_for()")
        return self.shifts_for(a_exp)

    def shifts_for(self, out_exp: int) -> dict:
        """Per-tensor generalization of :meth:`shifts`: every shift is derived
        from the specs the params themselves carry rather than one global
        activation exponent.  ``out_exp`` is the exponent of the *block
        output* grid (= the next consumer's ``conv0.x_spec``, or the head's
        input spec for the last block):

          * shift0      — conv0's product domain -> conv1's input grid
            (``conv1.x_spec``), since conv1 consumes conv0's output;
          * shift1      — conv1's product domain -> the block output grid;
          * skip_shift  — the skip stream's domain (ds product domain, or the
            block *input* grid ``conv0.x_spec`` when there is no downsample)
            -> conv1's product domain (the add-fold accumulator init).

        With the legacy fixed-grid params (every activation on ``A_SPEC``)
        this equals ``shifts(A_SPEC.exp)`` exactly.

        A bottleneck block adds ``shift2`` (conv2's product domain -> the
        block output grid); ``shift1`` then lands on conv2's input grid and
        the skip aligns into conv2's product domain."""
        last = self.last
        out = dict(shift0=self.conv1.x_spec.exp - self.conv0.product_exp)
        if self.conv2 is None:
            out["shift1"] = out_exp - self.conv1.product_exp
        else:
            out["shift1"] = self.conv2.x_spec.exp - self.conv1.product_exp
            out["shift2"] = out_exp - self.conv2.product_exp
        if self.ds is not None:
            out["skip_shift"] = self.ds.product_exp - last.product_exp
        else:
            out["skip_shift"] = self.conv0.x_spec.exp - last.product_exp
        return out

    def tree_flatten(self):
        return (self.conv0, self.conv1, self.ds, self.conv2), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @classmethod
    def from_dict(cls, d: dict) -> "QBlockParams":
        opt = {k: QConvParams.from_dict(d[k]) for k in ("ds", "conv2")
               if k in d}
        return cls(conv0=QConvParams.from_dict(d["conv0"]),
                   conv1=QConvParams.from_dict(d["conv1"]), **opt)

    def to_dict(self) -> dict:
        out = dict(conv0=self.conv0.to_dict(), conv1=self.conv1.to_dict())
        for k in ("ds", "conv2"):
            if getattr(self, k) is not None:
                out[k] = getattr(self, k).to_dict()
        return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QResNetParams:
    """The full quantized network, in graph order: stem, residual blocks,
    classifier."""

    stem: QConvParams
    blocks: Tuple[QBlockParams, ...]
    fc: QLinearParams

    def tree_flatten(self):
        return (self.stem, self.blocks, self.fc), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        stem, blocks, fc = children
        return cls(stem, tuple(blocks), fc)

    @classmethod
    def from_dict(cls, qp: dict) -> "QResNetParams":
        """Adapter from the legacy ``quantize_params`` nested-dict layout."""
        return cls(stem=QConvParams.from_dict(qp["stem"]),
                   blocks=tuple(QBlockParams.from_dict(b)
                                for b in qp["blocks"]),
                   fc=QLinearParams.from_dict(qp["fc"]))

    def to_dict(self) -> dict:
        return dict(stem=self.stem.to_dict(),
                    blocks=[b.to_dict() for b in self.blocks],
                    fc=self.fc.to_dict())


def activation_out_specs(params: QResNetParams, default: QSpec):
    """Derive the *output* activation :class:`QSpec` of each task in graph
    order from the specs the consumers carry — the single source of truth all
    backends share for per-tensor activation grids:

      * the stem's output grid is block 0's input grid (``conv0.x_spec``);
      * block ``i``'s output grid is block ``i+1``'s input grid;
      * the last block's output grid is the head's input spec
        (``fc.x_spec``), falling back to ``default`` (the model-level
        ``A_SPEC``) for legacy fixed-grid params.

    Returns ``(stem_out, block_outs)`` with ``len(block_outs) ==
    len(params.blocks)``.  With legacy params every entry equals ``default``.
    """
    head = params.fc.x_spec if params.fc.x_spec is not None else default
    if not params.blocks:
        return head, ()
    block_outs = tuple(b.conv0.x_spec for b in params.blocks[1:]) + (head,)
    return params.blocks[0].conv0.x_spec, block_outs


def ensure_typed(qparams):
    """Accept the legacy dict layout or a typed container (conv or LM)."""
    from repro.compile.lm_params import QLMParams
    if isinstance(qparams, (QResNetParams, QLMParams)):
        return qparams
    if isinstance(qparams, dict):
        return QResNetParams.from_dict(qparams)
    raise TypeError(
        f"expected QResNetParams, QLMParams, or a quantize_params() dict, "
        f"got {type(qparams).__name__}")
