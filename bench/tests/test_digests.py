"""The numbers the harness reads, pinned by digest: for each configuration
its weights at ``weight_seed``, an 8-image pool at two seeds, the checked
sample a run draws from those seeds, the reference logits and the int4
control on that pool, and its ``Work``.  Recomputed here through
``families.load``; the recorded file comes from a CPU run of the harness
as it stood before the families were split out of it.

    PYTHONPATH=src python -m bench.tests.test_digests \\
        > bench/tests/data/resnet_cifar_digests.json

records them anew: only for a change meant to move what a run reads."""
import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest

from bench import families, work
from bench.traffic import generate

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RECORDED = os.path.join(ROOT, "bench", "tests", "data",
                        "resnet_cifar_digests.json")
CONFIGS = ["resnet20", "resnet8"]
POOL_SEEDS = [0, 2 ** 33 + 5]
POOL = 8
# the checked sample as the cells draw it: 1024 of 4096 served requests
SERVED, SAMPLE = 4096, 1024


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def digests(name: str) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    ref = families.load(cfg).reference
    net = ref.build_net(cfg)
    weights = ref.make_weights(net, cfg["weight_seed"])
    out = {"work": dataclasses.asdict(work.count(net)),
           "weights": digest(*[a for k in sorted(weights)
                               for a in weights[k]])}
    for s in POOL_SEEDS:
        pool = generate.image_pool(s, POOL, cfg["img"], cfg["in_channels"])
        out[f"pool.{s}"] = digest(pool)
        out[f"sample.{s}"] = digest(generate.sample(s, SERVED, SAMPLE))
        out[f"logits.{s}"] = digest(ref.reference_logits(net, weights, pool))
        out[f"control.{s}"] = digest(
            ref.reference_logits(net, weights, pool, bits=4))
    return out


@pytest.mark.parametrize("name", CONFIGS)
def test_digests_unchanged(name):
    with open(RECORDED) as f:
        recorded = json.load(f)[name]
    assert digests(name) == recorded


if __name__ == "__main__":
    print(json.dumps({n: digests(n) for n in CONFIGS}, indent=1,
                     sort_keys=True))
