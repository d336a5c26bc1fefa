"""The ImageNet bottleneck family (ResNet-50 v1.5) on the integer path: the
fused bottleneck kernel and the strided stem with its max pool against
their oracles, the whole tiny net through ``pallas`` against ``lax-int``
and against the benchmark's plain reference, the lowering of ResNet-50's
graph and its kernel names, and the u8-pooled head at ResNet-50's width.
All on the CPU, the kernels in interpret mode."""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.compile import compile_model, lowering
from repro.compile.backends import _int_head
from repro.compile.params import QLinearParams
from repro.core import graph as G
from repro.core.quant import QSpec
from repro.kernels.conv_stem.ops import conv_stem_pool_op
from repro.kernels.conv_stem.ref import conv_stem_pool_ref
from repro.kernels.resblock_fused.ops import bottleneck_fused_op
from repro.kernels.resblock_fused.ref import bottleneck_ref
from repro.models import resnet as R

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _w(r, *shape):
    return jnp.asarray(r.integers(-40, 41, shape), jnp.int8)


def _b(r, c):
    return jnp.asarray(r.integers(-600, 601, (c,)), jnp.int16)


@pytest.mark.parametrize("cin,mid,cout,stride,proj", [
    (32, 8, 32, 1, False),          # identity skip
    (64, 64, 256, 1, True),         # stage-1 entry: projection at stride 1
    (32, 16, 64, 2, True),          # stage entry: projection at stride 2
    (256, 256, 128, 2, True),       # stride 2 over two 128-lane chunks
], ids=["identity", "proj_s1_64_256", "proj_s2", "proj_s2_chunked"])
def test_bottleneck_kernel_matches_ref(cin, mid, cout, stride, proj):
    r = np.random.default_rng(cin + mid + stride)
    x = jnp.asarray(r.integers(0, 256, (2, 8, 8, cin)), jnp.uint8)
    args = (x, _w(r, 1, 1, cin, mid), _b(r, mid), _w(r, 3, 3, mid, mid),
            _b(r, mid), _w(r, 1, 1, mid, cout), _b(r, cout))
    if proj:
        args += (_w(r, 1, 1, cin, cout), _b(r, cout))
    kw = dict(stride=stride, shift0=9, shift1=10, shift2=9, skip_shift=2)
    got = bottleneck_fused_op(*args, **kw)
    want = bottleneck_ref(*args, **kw)
    assert got.shape == (2, 8 // stride, 8 // stride, cout)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the outputs use the u8 range: neither all zero nor all saturated
    assert 0.05 < float(jnp.mean(got > 0)) and \
        float(jnp.mean(got == 255)) < 0.5


def test_stem_pool_kernel_matches_ref():
    r = np.random.default_rng(7)
    x = jnp.asarray(r.integers(0, 256, (2, 32, 32, 3)), jnp.uint8)
    w = jnp.asarray(r.integers(-128, 128, (7, 7, 3, 16)), jnp.int8)
    b = jnp.asarray(r.integers(-3000, 3001, (16,)), jnp.int16)
    got = conv_stem_pool_op(x, w, b, shift=9)
    want = conv_stem_pool_ref(x, w, b, shift=9)
    assert got.shape == (2, 8, 8, 16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 0.05 < float(jnp.mean(got > 0))


# ---------------------------------------------------------------------------
# The tiny bottleneck net end to end
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    """64x64 input, stage blocks (1, 1, 1, 1), base width 8, 10 classes:
    the benchmark family's reference and adapter, seeded weights."""
    sys.path.insert(0, REPO)
    from bench import families
    from bench.traffic import generate
    with open(os.path.join(REPO, "bench", "configs", "resnet50.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny", img=64, stage_blocks=[1, 1, 1, 1], base_width=8,
               num_classes=10)
    fam = families.load(cfg)
    net = fam.reference.build_net(cfg)
    weights = fam.reference.make_weights(net, 3)
    images = generate.image_pool(5, 4, cfg["img"], cfg["in_channels"])
    return dict(cfg=cfg, ref=fam.reference, prog=fam.program, net=net,
                weights=weights, images=images)


def _logits(t, backend):
    cm = compile_model(t["prog"].program_config(t["cfg"]),
                       t["prog"].program_params(t["net"], t["weights"]),
                       backend=backend, batch_sizes=(4,))
    return np.asarray(cm(t["images"]))


def test_tiny_pallas_equals_lax_int(tiny):
    got = _logits(tiny, "pallas")
    want = _logits(tiny, "lax-int")
    np.testing.assert_array_equal(got, want)
    assert np.unique(got, axis=0).shape[0] == len(got)


def test_tiny_lax_int_equals_plain_reference(tiny):
    ref = tiny["ref"].reference_logits(tiny["net"], tiny["weights"],
                                       tiny["images"], block=4)
    np.testing.assert_array_equal(_logits(tiny, "lax-int"), ref)


def test_tiny_other_backends_refuse_the_bottleneck(tiny):
    for backend in ("float", "pallas-stream"):
        with pytest.raises(lowering.LoweringError, match="bottleneck"):
            _logits(tiny, backend)


# ---------------------------------------------------------------------------
# ResNet-50's graph, plan and kernel names
# ---------------------------------------------------------------------------


def test_resnet50_plan():
    g = lowering.optimized_graph(R.RESNET50)
    assert not [n for n in g.nodes if n.op in ("bn", "relu", "add")]
    plan = lowering.plan_model(g)
    assert (plan.stem.stride, plan.stem.pool) == (2, "max")
    assert plan.head.pool == "avg_u8" and plan.head.num_classes == 1000
    assert len(plan.blocks) == 16 and all(t.bottleneck for t in plan.blocks)
    # Table 1, 50-layer: stages of 3/4/6/3 blocks to 256/512/1024/2048,
    # a projection and (v1.5) the 3x3's stride on each stage's first block
    expect = [(2 if (s > 0 and b == 0) else 1, b == 0, 256 << s)
              for s, n in enumerate((3, 4, 6, 3)) for b in range(n)]
    assert [(t.stride, t.has_ds, t.och) for t in plan.blocks] == expect
    macs = sum(a["fh"] * a["fw"] * a["ich"] * a["och"] * a["oh"] * a["ow"]
               for a in (n.attrs for n in lowering.model_graph(
                   R.RESNET50).nodes if n.op == "conv"))
    assert macs + 2048 * 1000 == 4_089_184_256


def test_kernel_names_for_3_4_6_3():
    plan = lowering.plan_model(lowering.optimized_graph(R.RESNET50))
    names = [lowering.kernel_name(t, R.RESNET50.stage_blocks)
             for t in plan.blocks]
    assert names == ["s1b0_ds", "s1b1", "s1b2",
                     "s2b0_ds", "s2b1", "s2b2", "s2b3",
                     "s3b0_ds", "s3b1", "s3b2", "s3b3", "s3b4", "s3b5",
                     "s4b0_ds", "s4b1", "s4b2"]
    assert lowering.kernel_name(plan.stem, (3, 4, 6, 3)) == "stem"
    with pytest.raises(lowering.LoweringError, match="past the last stage"):
        lowering.kernel_name(plan.blocks[-1], (3, 4, 6, 2))
    # ResNet20's names as before: three stages of three
    plan20 = lowering.plan_model(lowering.optimized_graph(R.RESNET20))
    assert [lowering.kernel_name(t, R.RESNET20.stage_blocks)
            for t in plan20.blocks] == \
        ["s1b0", "s1b1", "s1b2", "s2b0_ds", "s2b1", "s2b2", "s3b0_ds",
         "s3b1", "s3b2"]


def test_residual_blocks_found_with_three_conv_branches():
    g = G.merge_relu(G.fold_bn(G.build_bottleneck_graph((2, 1), 8, 4, 32,
                                                        10)))
    blocks = G.find_residual_blocks(g)
    assert [(b.conv0.name, [m.name for m in b.mid], b.conv1.name,
             b.downsample.name if b.downsample else None) for b in blocks] \
        == [("conv0_0", ["conv0_1"], "conv0_2", "ds0"),
            ("conv1_0", ["conv1_1"], "conv1_2", None),
            ("conv2_0", ["conv2_1"], "conv2_2", "ds2")]


def test_params_without_conv2_are_refused():
    from repro.compile.params import QBlockParams, QResNetParams
    sys.path.insert(0, REPO)
    from bench import families
    with open(os.path.join(REPO, "bench", "configs", "resnet50.json")) as f:
        cfg = json.load(f)
    cfg.update(img=64, stage_blocks=[1, 1, 1, 1], base_width=8,
               num_classes=10)
    fam = families.load(cfg)
    net = fam.reference.build_net(cfg)
    params = fam.program.program_params(
        net, fam.reference.make_weights(net, 0))
    basic = QResNetParams(params.stem, tuple(
        QBlockParams(b.conv0, b.conv1, b.ds) for b in params.blocks),
        params.fc)
    g = lowering.optimized_graph(fam.program.program_config(cfg))
    with pytest.raises(lowering.LoweringError, match="bottleneck"):
        lowering.plan_model(g, basic)


# ---------------------------------------------------------------------------
# The head at ResNet-50's width
# ---------------------------------------------------------------------------


def _head_fc(c=2048, classes=8):
    r = np.random.default_rng(0)
    return QLinearParams(wq=jnp.asarray(r.integers(-128, 128, (c, classes)),
                                        jnp.int8),
                         b=jnp.zeros((classes,), jnp.float32),
                         w_spec=QSpec(8, True, -9))


def test_u8_pooled_head_at_7x7x2048():
    fc = _head_fc()
    a = QSpec(8, False, -4)
    h = jnp.full((2, 7, 7, 2048), 255, jnp.uint8)
    # the int32 sum path would overflow: 49 * 255 * 128 * 2048 >= 2**31
    with pytest.raises(AssertionError, match="overflow"):
        _int_head(h, fc, a)
    got = _int_head(h, fc, a, pool="avg_u8")
    # a constant map averages to itself
    want = (np.asarray(fc.wq, np.int64).sum(0) * 255).astype(np.float32) \
        * np.float32(a.scale * fc.w_spec.scale)
    np.testing.assert_array_equal(np.asarray(got[0]), want)
    # (s + 24) // 49 rounds half up onto the u8 grid: 25 ones of 49 -> 1,
    # 24 -> 0
    fc1 = QLinearParams(wq=jnp.ones((2048, 1), jnp.int8),
                        b=jnp.zeros((1,), jnp.float32),
                        w_spec=QSpec(8, True, 0))
    for ones, want in ((25, 2048.0), (24, 0.0)):
        h = (jnp.arange(49) < ones).astype(jnp.uint8).reshape(1, 7, 7, 1)
        h = jnp.broadcast_to(h, (1, 7, 7, 2048))
        assert float(_int_head(h, fc1, QSpec(8, False, 0),
                               pool="avg_u8")[0, 0]) == want
