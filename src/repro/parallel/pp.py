"""Pipeline parallelism — the paper's ILP balancing applied at pod scale.

The dataflow accelerator's law "throughput = slowest concurrent task" is the
same law that governs a synchronous training pipeline: step time is set by
the slowest stage.  ``partition_stages`` reuses the balance objective of
core.ilp (Algorithm 1) to assign contiguous layer ranges to stages,
minimizing the maximum per-stage work c_i — solved exactly by DP.

``pipeline_step`` is a GPipe-style schedule over a mesh axis using
shard_map + ppermute: microbatches flow stage->stage; bubbles =
(n_stages - 1) / (n_micro + n_stages - 1).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map


def partition_stages(costs: Sequence[float], n_stages: int) -> List[int]:
    """Contiguous partition of per-layer costs minimizing max stage cost.
    Returns stage boundaries (start index per stage).  Exact DP."""
    n = len(costs)
    prefix = np.concatenate([[0.0], np.cumsum(costs)])

    def stage_cost(i, j):
        return prefix[j] - prefix[i]

    # dp[s][j] = min over i of max(dp[s-1][i], cost(i, j))
    dp = np.full((n_stages + 1, n + 1), np.inf)
    choice = np.zeros((n_stages + 1, n + 1), np.int64)
    dp[0][0] = 0.0
    for s in range(1, n_stages + 1):
        for j in range(1, n + 1):
            for i in range(s - 1, j):
                v = max(dp[s - 1][i], stage_cost(i, j))
                if v < dp[s][j]:
                    dp[s][j] = v
                    choice[s][j] = i
    bounds = []
    j = n
    for s in range(n_stages, 0, -1):
        i = int(choice[s][j])
        bounds.append(i)
        j = i
    return list(reversed(bounds))


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_step(stage_fn: Callable, mesh, axis: str, n_micro: int):
    """GPipe forward over mesh axis ``axis``.

    stage_fn(stage_idx, x) -> x, applied per stage; activations move between
    stages with ppermute.  Returns f(xs) where xs has a leading microbatch
    dim; per-device output is the final stage's stream.
    """
    n_stages = mesh.shape[axis]

    def shard_fn(xs):
        # xs local: (n_micro, mb, ...) identical on all stages
        idx = jax.lax.axis_index(axis)

        def body(carry, t):
            inflight = carry        # activations currently at this stage
            x_in = jnp.where(t < n_micro, xs[jnp.minimum(t, n_micro - 1)],
                             jnp.zeros_like(xs[0]))
            # stage 0 injects microbatch t; others use what arrived
            x = jnp.where(idx == 0, x_in, inflight)
            y = stage_fn(idx, x)
            # send to next stage
            y_next = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)])
            out = jnp.where(idx == n_stages - 1, y, jnp.zeros_like(y))
            return y_next, out

        ticks = n_micro + n_stages - 1
        _, outs = jax.lax.scan(body, jnp.zeros_like(xs[0]),
                               jnp.arange(ticks))
        # only the last stage holds real outputs (zeros elsewhere) — one
        # psum replicates them so out_specs=P(None) is well defined
        outs = jax.lax.psum(outs, axis)
        # outputs for microbatch m emerge at tick m + n_stages - 1
        return outs[n_stages - 1:]

    return shard_map(shard_fn, mesh=mesh,
                     in_specs=P(None),
                     out_specs=P(None),
                     check_vma=False)
