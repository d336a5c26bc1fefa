"""The plain reference against the program's ``lax-int`` backend, bit for
bit, on the CPU at a small batch, for both configurations; and the
control (int4) far from it."""
import json
import os

import numpy as np
import pytest

from bench import check, program, reference
from bench.traffic import generate

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["resnet20", "resnet8"])
@pytest.mark.parametrize("seed", [0, 2 ** 32 - 1, 2 ** 33 + 5])
def test_reference_equals_lax_int(name, seed):
    from repro.compile import compile_model
    cfg = load(name)
    net = reference.build_net(cfg)
    w = reference.make_weights(net, seed)
    x = generate.image_pool(seed, 8, cfg["img"], cfg["in_channels"])
    ref = reference.reference_logits(net, w, x, block=8)
    cm = compile_model(program.program_config(cfg),
                       program.program_params(net, w), backend="lax-int",
                       batch_sizes=(8,))
    got = np.asarray(cm(x))
    assert np.array_equal(ref, got)
    assert check.logit_gap(got, ref) == 0.0
    # the logits differ from image to image, so a mixed-up answer shows
    assert np.unique(ref, axis=0).shape[0] == len(x)


def test_weights_are_seeded():
    net = reference.build_net(load("resnet8"))
    a = reference.make_weights(net, 3)["stem"][0]
    b = reference.make_weights(net, 3)["stem"][0]
    c = reference.make_weights(net, 4)["stem"][0]
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.int8


def test_activations_use_their_range():
    # the seeded weights keep the last map neither dead nor saturated
    cfg = load("resnet20")
    net = reference.build_net(cfg)
    w = reference.make_weights(net, 1)
    x = generate.image_pool(1, 8, cfg["img"], cfg["in_channels"])
    logits = reference.reference_logits(net, w, x, block=8)
    assert np.isfinite(logits).all() and logits.std(axis=0).min() > 0.01
