"""The families contract: a configuration's ``family`` key chooses every
family-specific step, by name, with no default; a family that exists only
as new files is found and run with no edit to a harness module; the
references and the check import nothing of the program, and the harness
names no family."""
import ast
import glob
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import pytest

from bench import families, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
HARNESS = ["run.py", "check.py", "calibrate.py", "sweep.py"]


@pytest.mark.parametrize("cfg", [{"name": "x", "family": "no_such_family"},
                                 {"name": "x"}])
def test_unknown_family_is_an_error_that_lists_the_known(cfg):
    with pytest.raises(ValueError) as e:
        families.load(cfg)
    assert "resnet_cifar" in str(e.value)
    assert str(families.known()) in str(e.value)


TOY_REFERENCE = '''
from bench.families.resnet_cifar import reference as base

CALLS = []
forward = base.forward


def build_net(cfg):
    CALLS.append("build_net")
    return base.build_net(cfg)


def make_weights(net, seed):
    CALLS.append("make_weights")
    return base.make_weights(net, seed)


def reference_logits(net, weights, images, bits=8, block=256):
    CALLS.append("reference_logits")
    return base.reference_logits(net, weights, images, bits, block)
'''

TOY_PROGRAM = '''
from bench.families.resnet_cifar import program as base

CALLS = []


def Request(rid, image):
    CALLS.append("Request")
    return base.Request(rid=rid, image=image)


def build_engine(cfg, net, weights, server, devices):
    CALLS.append("build_engine")
    return base.build_engine(cfg, net, weights, server, devices)
'''


@pytest.fixture
def toy_root(tmp_path, monkeypatch):
    """A checkout-like root with one cell whose configuration names
    ``toyfam``, a family that exists only in ``tmp_path``."""
    fams = tmp_path / "families"
    (fams / "toyfam").mkdir(parents=True)
    (fams / "toyfam" / "reference.py").write_text(TOY_REFERENCE)
    (fams / "toyfam" / "program.py").write_text(TOY_PROGRAM)
    with open(os.path.join(BENCH, "configs", "resnet8.json")) as f:
        cfg = dict(json.load(f), name="toy", family="toyfam")
    (tmp_path / "toy.json").write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(dict(
        configs=[dict(name="toy", file="toy.json")],
        workloads=[dict(name="toy-offline", config="toy", traffic="offline",
                        chips=1)],
        end_to_end=[dict(name="fps", unit="images/s"),
                    dict(name="setup_s", unit="s")],
        per_layer=[])))
    monkeypatch.setattr(families, "__path__",
                        list(families.__path__) + [str(fams)])
    yield tmp_path
    for m in [m for m in sys.modules if m.startswith("bench.families.toyfam")]:
        del sys.modules[m]


def test_family_of_new_files_only_is_loaded_and_run(toy_root):
    assert "toyfam" in families.known()
    cell = run.load_cell("toy-offline", root=str(toy_root))
    assert cell.family.name == "toyfam"
    assert cell.family.reference.__file__.startswith(str(toy_root))
    server = dict(cell.mix["server"], batch=8, batch_sizes=[8])
    cell.mix = dict(cell.mix, server=server, pool_images=64, sample=16)
    out = run.run_cell(cell, 2 ** 31 + 11, 0.5, False, jax.devices(),
                       t_start=time.monotonic())
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"fps", "setup_s"}
    assert cell.family.reference.CALLS == [
        "build_net", "make_weights", "reference_logits"]
    assert cell.family.program.CALLS[0] == "build_engine"
    assert "Request" in cell.family.program.CALLS


def _imports(path):
    tree = ast.parse(open(path).read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""
            yield from (f"{node.module}.{a.name}" for a in node.names)


def test_references_and_check_import_no_program():
    paths = glob.glob(os.path.join(BENCH, "families", "*", "reference.py"))
    assert paths
    paths += [os.path.join(BENCH, "families", "__init__.py"),
              os.path.join(BENCH, "families", "common.py"),
              os.path.join(BENCH, "check.py")]
    for p in paths:
        for mod in _imports(p):
            assert mod.split(".")[0] != "repro", (p, mod)
            assert not mod.endswith(".program"), (p, mod)


@pytest.mark.parametrize("name", HARNESS)
def test_harness_names_no_family(name):
    path = os.path.join(BENCH, name)
    text = open(path).read()
    for family in families.known():
        assert family not in text, (name, family)
    for mod in _imports(path):
        assert not mod.startswith("bench.families."), (name, mod)
        assert mod not in ("bench.reference", "bench.program"), (name, mod)


def test_control_never_imports_the_program():
    # the control reading and the check, as calibrate.py runs them, in a
    # fresh process: the program adapter stays unloaded
    code = textwrap.dedent('''
        import sys
        from bench import calibrate, run
        cell = run.load_cell("resnet8-offline")
        cell.mix = dict(cell.mix, pool_images=16, sample=8)
        c = calibrate.control_reading(cell, 2 ** 31 + 5)
        assert c["logit_max_abs_diff"]["value"] > 0.1, c
        loaded = [m for m in sys.modules
                  if m.split(".")[0] == "repro" or m.endswith(".program")]
        assert not loaded, loaded
        print("ok")
    ''')
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr[-2000:]
