"""Operations and bytes per image, against the hand counts and against the
program's own graph of the network."""
import json
import os

import pytest

from bench import reference, work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def net(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return reference.build_net(json.load(f))


@pytest.mark.parametrize("name,macs", [("resnet20", 40_813_184),
                                       ("resnet8", 12_501_632)])
def test_macs_match_hand_count(name, macs):
    # stem 442,368; a 16-wide stride-1 conv 2,359,296; each stage entry
    # conv0 1,179,648 + its 1x1 downsample 131,072; fc 640
    w = work.count(net(name))
    assert w.macs_per_image == macs
    assert w.ops_per_image == 2 * macs


@pytest.mark.parametrize("name", ["resnet20", "resnet8"])
def test_macs_match_program_graph(name):
    from bench import program
    from repro.compile.lowering import model_graph
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        cfg = program.program_config(json.load(f))
    macs = 0
    for n in model_graph(cfg).nodes:
        a = n.attrs
        if n.op == "conv":
            macs += a["fh"] * a["fw"] * a["ich"] * a["och"] * a["oh"] * a["ow"]
        elif n.op == "linear":
            macs += a["din"] * a["dout"]
    assert macs == work.count(net(name)).macs_per_image


def test_bytes():
    w = work.count(net("resnet8"))
    assert w.in_bytes_per_image == 32 * 32 * 3 * 4
    assert w.out_bytes_per_image == 40
    assert w.bytes(10, 2) == 10 * (12288 + 40) + 2 * w.weight_bytes


def test_peaks():
    p = work.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
