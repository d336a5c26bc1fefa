"""The control: the plain reference at int4, put in the program's place,
has to come out as not correct; at the cells' sizes it is run on the chip
by ``bench/calibrate.py``, here at a size a test run holds."""
import json
import os

import numpy as np
import pytest

from bench import check, families
from bench.traffic import generate

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("name", ["resnet20", "resnet8"])
@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7, 99])
def test_control_fails(name, seed):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    reference = families.load(cfg).reference
    net = reference.build_net(cfg)
    w = reference.make_weights(net, seed)
    pool = generate.image_pool(seed, 32, cfg["img"], cfg["in_channels"])
    served = [True] * len(pool)
    c = check.check(reference, net, w, pool, served, seed, 16,
                    cfg["limits"], bits=4)
    assert c["logit_max_abs_diff"]["value"] > 0.1
    assert c["logit_max_abs_diff"]["value"] > \
        c["logit_max_abs_diff"]["limit"]
    assert c["unserved"]["value"] == 0


def test_gap_of_nan_is_infinite():
    assert check.logit_gap(np.array([np.nan]), np.array([0.0])) == np.inf
