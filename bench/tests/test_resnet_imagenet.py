"""The ImageNet bottleneck family (``resnet50``): found by name, its work
counted from Table 1's shapes, its reference pinned by digest, its seeded
weights keeping the residual stream in range, the control far from it, its
per-kernel roofline reader on a synthetic trace, and a whole run of
the cell's harness on the CPU at a tiny size.

    PYTHONPATH=src python -m bench.tests.test_resnet_imagenet \\
        > bench/tests/data/resnet_imagenet_digests.json

records the digests anew: only for a change meant to move what a run
reads."""
import dataclasses
import json
import os
import time
import types

import jax
import numpy as np
import pytest

from bench import check, families, run, trace, work
from bench.tests.test_digests import digest
from bench.traffic import generate

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RECORDED = os.path.join(ROOT, "bench", "tests", "data",
                        "resnet_imagenet_digests.json")
POOL_SEEDS = [0, 2 ** 33 + 5]
POOL = 2
# the checked sample as the cell draws it: 256 of 512 served requests
SERVED, SAMPLE = 512, 256


def load():
    with open(os.path.join(ROOT, "bench", "configs", "resnet50.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def r50():
    cfg = load()
    ref = families.load(cfg).reference
    net = ref.build_net(cfg)
    return cfg, ref, net, ref.make_weights(net, cfg["weight_seed"])


def test_family_is_known():
    assert "resnet_imagenet" in families.known()
    assert families.load(load()).name == "resnet_imagenet"


def test_work_from_table_1(r50):
    _, _, net, _ = r50
    w = work.count(net)
    assert w.macs_per_image == 4_089_184_256
    assert w.weight_bytes == 25_560_032
    assert w.in_bytes_per_image == 224 * 224 * 3 * 4
    assert w.out_bytes_per_image == 4 * 1000


def digests() -> dict:
    cfg = load()
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
        out[f"logits.{s}"] = digest(
            ref.reference_logits(net, weights, pool, block=POOL))
        out[f"control.{s}"] = digest(
            ref.reference_logits(net, weights, pool, bits=4, block=POOL))
    return out


def test_digests_unchanged():
    with open(RECORDED) as f:
        recorded = json.load(f)["resnet50"]
    assert digests() == recorded


def test_stream_neither_saturates_nor_dies_and_control_fails(r50):
    cfg, ref, net, weights = r50
    x = generate.image_pool(7, 2, cfg["img"], cfg["in_channels"])
    h = np.asarray(ref.last_map(net, weights, x))
    assert h.shape == (2, 7, 7, 2048)
    # the last map of 16 blocks: ~28% zeros, ~0.2% at 255
    assert 0.15 < np.mean(h == 0) < 0.45
    assert 0.0 < np.mean(h == 255) < 0.02
    gap = check.logit_gap(ref.reference_logits(net, weights, x, block=2),
                          ref.reference_logits(net, weights, x, bits=4,
                                               block=2))
    assert gap > 1.0


# ---------------------------------------------------------------------------
# The per-kernel roofline reader, on a synthetic trace
# ---------------------------------------------------------------------------


def _run(top_ops):
    cell = types.SimpleNamespace(config=load(),
                                 family=families.load(load()))
    return types.SimpleNamespace(
        cell=cell, window=types.SimpleNamespace(completed=2560, calls=10,
                                                seconds=10.0),
        trace=trace.TraceSummary(window_s=10.0, devices=[], gaps=[],
                                 top_ops=top_ops),
        peaks=work.peaks("TPU v5 lite"))


def test_bottleneck_reader():
    read = run.load_reader("bottleneck_roofline.offline")
    # s4b1: 7x7 maps, 2048 -> 512 -> 512 -> 2048, no projection
    macs = 49 * (2048 * 512 + 9 * 512 * 512 + 512 * 2048)
    ops_s = 2560 * 2 * macs / 393e12
    wbytes = 2048 * 512 + 9 * 512 * 512 + 512 * 2048 + 2 * (512 + 512 + 2048)
    bytes_s = (2560 * 2 * 49 * 2048 + 10 * wbytes) / 819e9
    assert ops_s > bytes_s                      # compute-bound
    assert read(_run([("s4b1.7", 2.0), ("copy.1", 5.0), ("stem.1", 1.0),
                      ("s9b9.1", 1.0)])) == pytest.approx(100 * ops_s / 2.0)
    # s1b1: 56x56 maps, 256 -> 64 -> 64 -> 256, memory-bound
    macs1 = 56 * 56 * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    w1 = 256 * 64 + 9 * 64 * 64 + 64 * 256 + 2 * (64 + 64 + 256)
    ops1 = 2560 * 2 * macs1 / 393e12
    bytes1 = (2560 * 2 * 56 * 56 * 256 + 10 * w1) / 819e9
    assert bytes1 > ops1
    got = read(_run([("s4b1.7", 2.0), ("s1b1", 1.0), ("s1b0_ds.3", 0.0)]))
    s1b0 = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    least0 = max(2560 * 2 * s1b0 / 393e12,
                 (2560 * (56 * 56 * 64 + 56 * 56 * 256)
                  + 10 * (64 * 64 + 9 * 64 * 64 + 2 * 64 * 256
                          + 2 * (64 + 64 + 256 + 256))) / 819e9)
    assert got == pytest.approx(100 * (ops_s + bytes1 + least0) / 3.0)
    assert read(_run([("fusion.3", 1.0)])) is None
    assert read(types.SimpleNamespace(trace=None)) is None


def test_reader_finds_nothing_in_a_cifar_cell():
    r = _run([("s1b0.1", 1.0), ("stem.1", 1.0)])
    with open(os.path.join(ROOT, "bench", "configs", "resnet20.json")) as f:
        r.cell.config = json.load(f)
    assert run.load_reader("bottleneck_roofline.offline")(r) is None


# ---------------------------------------------------------------------------
# A whole run of the cell's harness on the CPU, at a tiny size
# ---------------------------------------------------------------------------


def tiny_cell():
    cell = run.load_cell("resnet50-offline")
    cell.config = dict(cell.config, img=64, stage_blocks=[1, 1, 1, 1],
                       base_width=8, num_classes=10)
    server = dict(cell.mix["server"], batch=4, batch_sizes=[4])
    cell.mix = dict(cell.mix, server=server, pool_images=16, sample=8)
    return cell


@pytest.mark.parametrize("fault", [None, "altered"])
def test_tiny_run_is_checked(fault):
    from bench.tests.test_faults import break_with
    out = run.run_cell(tiny_cell(), 2 ** 31 + 11, 0.5, False, jax.devices(),
                       t_start=time.monotonic(),
                       break_program=None if fault is None
                       else break_with(fault))
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0 and set(out["metrics"]) == {"fps",
                                                             "setup_s"}


if __name__ == "__main__":
    print(json.dumps({"resnet50": digests()}, indent=1, sort_keys=True))
