"""A whole run without the chip, with the timed path broken underneath:
``correct`` has to come out false for each fault a serving cell can have,
and true when nothing is broken.  Runs the cells' own harness on the CPU
at a small batch (the Pallas kernels in interpret mode)."""
import time

import jax
import numpy as np
import pytest

from bench import run


class Broken:
    """Stands in for the engine's compiled model or replica pool."""

    def __init__(self, inner, fault):
        self.inner, self.fault = inner, fault

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def _apply(self, x, out):
        out = np.array(out)
        if self.fault == "altered":      # one logit of each answer, 1 ulp
            out[:, 0] = np.nextafter(out[:, 0], np.inf)
        elif self.fault == "half_batch":  # rows past the half copy the first
            h = (len(out) + 1) // 2
            out[h:] = out[:len(out) - h]
        return out

    def __call__(self, x):
        return self._apply(x, self.inner(x))

    def run(self, index, x):
        return self._apply(x, self.inner.run(index, x))


def small_cell(name):
    cell = run.load_cell(name)
    server = dict(cell.mix["server"])
    if server["engine"] == "ResNetEngine":
        server.update(batch=8, batch_sizes=[8])
    else:
        server.update(batch=4, batch_sizes=[1, 4])
        cell.mix = dict(cell.mix, arrivals=dict(process="poisson",
                                                rate_hz=200.0))
    cell.mix = dict(cell.mix, server=server, pool_images=64, sample=16)
    cell.config = dict(cell.config)
    return cell


def break_with(fault):
    def brk(engine):
        if fault == "dropped" and hasattr(engine, "sched"):
            # the coalescer loses the second half of every batch it takes
            take = engine.sched.coalescer.take

            def dropping():
                batch = take()
                return batch[:(len(batch) + 1) // 2]
            engine.sched.coalescer.take = dropping
        elif fault == "dropped":          # every other request never queued
            submit, n = engine.submit, [0]

            def dropping(req):
                n[0] += 1
                if n[0] % 2 == 0:
                    submit(req)
            engine.submit = dropping
        elif hasattr(engine, "pool"):
            engine.pool = Broken(engine.pool, fault)
        else:
            engine.model = Broken(engine.model, fault)
    return brk


CELLS = ["resnet8-offline", "resnet20-poisson"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [None, "altered", "half_batch", "dropped"])
def test_fault_is_caught(cell, fault):
    c = small_cell(cell)
    out = run.run_cell(c, 2 ** 31 + 3, 0.5, False, jax.devices(),
                       t_start=time.monotonic(),
                       break_program=None if fault is None
                       else break_with(fault))
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"
