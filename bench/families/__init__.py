"""The model families the benchmark runs, one package each, chosen by the
configuration file's ``family`` key.

``bench/families/<family>/`` holds two modules:

* ``reference.py``, the plain reference, written from the configuration
  alone; it imports nothing of the program under test:
  ``build_net(cfg)`` (a ``Net`` that meets the contract ``work.count``
  reads), ``make_weights(net, seed)``, ``forward(net, weights, images,
  bits=8)`` and ``reference_logits(net, weights, images, bits=8,
  block=...)``, where ``bits`` < 8 is the control;
* ``program.py``, the adapter, the only modules of ``bench/`` that import
  the program's models and engines (``spans.py`` opens the program's
  tracing session, the same for every family): ``build_engine(cfg, net,
  weights, server, devices)`` and the request type ``Request(rid=...,
  image=...)``.

The integer helpers families share are in ``common.py``.  A family is
looked up by name in this package's ``__path__`` (the search path), so a
new one is new files only; the harness names none.
"""
from __future__ import annotations

import dataclasses
import importlib
import os
import types
from typing import List


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    reference: types.ModuleType

    @property
    def program(self) -> types.ModuleType:
        """The program adapter, imported on first use, so that the tools
        that use the reference alone never import the program."""
        return importlib.import_module(f"{__name__}.{self.name}.program")


def known() -> List[str]:
    """Every family on the search path: a directory with a reference."""
    return sorted({d for p in __path__ for d in os.listdir(p)
                   if os.path.isfile(os.path.join(p, d, "reference.py"))})


def load(cfg: dict) -> Family:
    """The family ``cfg["family"]`` names; an unknown or missing family is
    an error, never a default."""
    name = cfg.get("family")
    names = known()
    if name not in names:
        raise ValueError(f"configuration {cfg.get('name')!r} names family "
                         f"{name!r}; known families: {names}")
    return Family(name,
                  importlib.import_module(f"{__name__}.{name}.reference"))
