"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window served, drawn from the seed, is run through the
plain reference of the configuration's family (the ``reference`` module
``families.load`` returns, ``bench/families/<family>/reference.py``) on the
same images, and two numbers are compared, each with its limit from the
configuration file's ``limits``:

* ``logit_max_abs_diff``: the largest |served logit - reference logit|
  over the sample.  The integer pipeline is exact and its last step is one
  float multiply by a power of two and one float add, so a sound program
  reads 0 and the limit is 0.
* ``unserved``: requests sent that never got an answer.

It imports nothing of the program, and names no family.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import generate


def logit_gap(served: np.ndarray, ref: np.ndarray) -> float:
    d = np.abs(np.asarray(served, np.float64) - np.asarray(ref, np.float64))
    return float(np.nan_to_num(d, nan=np.inf).max())


def check(reference, net, weights, pool: np.ndarray, served: list,
          seed: int, sample: int, limits: dict, bits: int = 8) -> dict:
    """``reference``: the family's reference module, ``net`` and
    ``weights`` made by it.  ``served[i]``: the logits request ``i`` got
    (None if it got none); request ``i`` carried ``pool[i % len(pool)]``.
    ``bits`` < 8 compares the control (the reference at that precision)
    in the program's place."""
    done = np.array([i for i, s in enumerate(served) if s is not None])
    unserved = len(served) - len(done)
    idx = done[generate.sample(seed, len(done), sample)] if len(done) else done
    gap = float("inf")
    if len(idx):
        images = pool[idx % len(pool)]
        ref = reference.reference_logits(net, weights, images)
        got = np.stack([served[i] for i in idx]) if bits == 8 else \
            reference.reference_logits(net, weights, images, bits=bits)
        gap = logit_gap(got, ref)
    return {"logit_max_abs_diff": dict(value=gap,
                                       limit=limits["logit_max_abs_diff"]),
            "unserved": dict(value=unserved, limit=limits["unserved"])}
