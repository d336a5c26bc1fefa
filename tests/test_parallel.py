"""Distribution-layer tests.  Multi-device cases run in a subprocess with
--xla_force_host_platform_device_count (the main test process must keep the
default single device, per the dry-run isolation rule)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import dataflow, ilp
from repro.parallel import pp

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8) -> str:
    prog = ("import os\n"
            f"os.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(code))
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


# ---------------------------------------------------------------------------
# stage partitioner (paper Alg. 1 applied to PP)
# ---------------------------------------------------------------------------


def test_partition_stages_balances():
    costs = [1, 1, 1, 1, 4, 1, 1, 1]
    bounds = pp.partition_stages(costs, 2)
    # stage0 = [0..4) cost 4  / stage1 = [4..8) cost 7?  DP picks better:
    starts = bounds + [len(costs)]
    stage_costs = [sum(costs[starts[i]:starts[i + 1]])
                   for i in range(len(bounds))]
    assert max(stage_costs) <= 7  # optimum is 7 for this instance
    assert bounds[0] == 0


def test_partition_stages_equal_work():
    costs = [2.0] * 12
    bounds = pp.partition_stages(costs, 4)
    assert bounds == [0, 3, 6, 9]
    assert abs(pp.bubble_fraction(8, 4) - 3 / 11) < 1e-9


def test_partition_matches_ilp_balance_philosophy():
    """Same law as the dataflow ILP: slowest stage limits throughput —
    max-stage-cost of the DP partition <= naive contiguous split."""
    layers = dataflow.resnet20_layers()
    costs = [l.c for l in layers]
    bounds = pp.partition_stages(costs, 4)
    starts = bounds + [len(costs)]
    dp_max = max(sum(costs[starts[i]:starts[i + 1]]) for i in range(4))
    k = len(costs) // 4
    naive = [costs[i * k:(i + 1) * k if i < 3 else len(costs)]
             for i in range(4)]
    naive_max = max(sum(c) for c in naive)
    assert dp_max <= naive_max


# ---------------------------------------------------------------------------
# subprocess multi-device: sharding rules, pipeline, collectives
# ---------------------------------------------------------------------------


def test_params_shardings_divisibility():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.parallel import sharding as shd
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        tree = dict(
            embed=jax.ShapeDtypeStruct((4096, 512), jnp.float32),
            blocks=dict(w=jax.ShapeDtypeStruct((8, 1024, 512), jnp.float32)),
            norm=dict(scale=jax.ShapeDtypeStruct((64,), jnp.float32)),
        )
        sh = shd.params_shardings(tree, mesh)
        print(sh["embed"].spec, "|", sh["blocks"]["w"].spec, "|",
              sh["norm"]["scale"].spec)
    """)
    emb, w, scale = [s.strip() for s in out.strip().split("|")]
    assert "model" in emb
    assert "data" in w and "model" in w
    assert "data" not in scale and "model" not in scale  # replicated


def test_pipeline_step_matches_serial():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel import pp
        mesh = jax.make_mesh((4,), ("stage",))
        ws = [0.5, 1.5, -2.0, 3.0]

        def stage_fn(idx, x):
            w = jnp.asarray(ws)[idx]
            return x * w + 1.0

        f = pp.pipeline_step(stage_fn, mesh, "stage", n_micro=6)
        xs = jnp.arange(6 * 3, dtype=jnp.float32).reshape(6, 3)
        y = f(xs)
        ref = xs
        for w in ws:
            ref = ref * w + 1.0
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-6)
        print("PIPE_OK")
    """, devices=4)
    assert "PIPE_OK" in out


@pytest.mark.slow
def test_collective_matmul_matches_dense():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.collectives import collective_matmul
        mesh = jax.make_mesh((4,), ("model",))
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (8, 16))
        w = jax.random.normal(jax.random.fold_in(key, 1), (16, 12))
        y = collective_matmul(x, w, mesh, "model")
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                   rtol=1e-5, atol=1e-5)
        print("CM_OK")
    """, devices=4)
    assert "CM_OK" in out


@pytest.mark.slow
def test_compressed_grad_allreduce():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.parallel.collectives import (compressed_psum_grads,
                                                init_error_state)
        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        key = jax.random.PRNGKey(0)
        g = jax.random.normal(key, (4, 8, 256))  # per-device grads

        def f(g_loc, e_loc):
            out, new_e = compressed_psum_grads(
                dict(w=g_loc[0]), dict(w=e_loc[0]), "data", block=128)
            return out["w"][None], new_e["w"][None]

        e0 = jnp.zeros((4, 8, 256))
        out, e1 = shard_map(f, mesh=mesh,
                            in_specs=(P("data"), P("data")),
                            out_specs=(P("data"), P("data")),
                            check_vma=False)(g, e0)
        ref = np.asarray(jnp.sum(g, 0))
        got = np.asarray(out[0])
        rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
        assert rel < 0.05, rel      # int8 wire: ~2 decimal digits
        # error feedback captured the residual
        assert float(jnp.abs(e1).max()) > 0
        print("AR_OK rel=%.4f" % rel)
    """, devices=4)
    assert "AR_OK" in out


@pytest.mark.slow
def test_dryrun_minicell_subprocess():
    """End-to-end: one real dry-run cell on the production 16x16 mesh."""
    out = run_sub("""
        from repro.launch.dryrun import run_cell
        res = run_cell("internvl2-1b", "decode_32k", multi_pod=False,
                       want_hlo=True)
        assert res["chips"] == 256
        assert res["an_step_s"] > 0
        print("CELL_OK", res["bottleneck"], res["an_bottleneck"])
    """, devices=512)
    assert "CELL_OK" in out


def test_params_shardings_degrade_gracefully_on_reduced_mesh():
    """A data-only serving mesh has no 'model' axis: the sharding rules must
    replicate instead of naming an absent axis (regression: axis_size used
    to KeyError, then a too-permissive fallback emitted P(..., 'model') and
    NamedSharding construction raised)."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.parallel import sharding as shd
        mesh = jax.make_mesh((4,), ("data",))
        tree = dict(
            embed=jax.ShapeDtypeStruct((4096, 512), jnp.float32),
            blocks=dict(w=jax.ShapeDtypeStruct((8, 1024, 512), jnp.float32)),
            norm=dict(scale=jax.ShapeDtypeStruct((64,), jnp.float32)),
        )
        sh = shd.params_shardings(tree, mesh)           # must not raise
        specs = [str(s.spec) for s in jax.tree_util.tree_leaves(sh)]
        assert not any("model" in s for s in specs), specs
        rep = shd.replicated_shardings(tree, mesh)
        assert all(s.spec == P() for s in jax.tree_util.tree_leaves(rep))
        f = shd.input_sharding_factory(mesh)
        s = f((8, 128), ("batch", "heads"))             # no 'model' axis
        # degenerate model axis (size 1): the last-dim FSDP fallback must
        # still shard instead of silently replicating (regression)
        mesh2 = jax.make_mesh((4, 1), ("data", "model"))
        spec2 = shd.param_spec("blocks/w", (1023, 512), mesh2)
        assert spec2 == P(None, "data"), spec2
        print("REDUCED_OK", s.spec)
    """, devices=4)
    assert "REDUCED_OK" in out


def test_input_sharding_factory_rules():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.parallel.sharding import input_sharding_factory
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        f = input_sharding_factory(mesh)
        s1 = f((8, 128), ("batch", "seq"))      # batch divisible
        s2 = f((1, 128), ("batch", "seq"))      # batch=1 -> seq sharded
        print(s1.spec); print(s2.spec)
    """, devices=8)
    lines = out.strip().splitlines()
    assert "pod" in lines[0] and "data" in lines[0]
    assert "pod" in lines[1] and "data" in lines[1]
