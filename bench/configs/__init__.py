"""The benchmark's model configurations: one JSON file each, and the module
of its model family beside them.

A configuration names its family's module by ``"reference": "<stem>"``
(``bench/configs/<stem>.py``; ``reference``, the conv stack, where it
names none).  The module is the plain reference of its family and all the
harness knows of it:

* ``n_params(cfg)``, ``WEIGHT_KINDS``, ``CONTROLS``: what the file tests
  hold each configuration to;
* ``make_params(cfg, seed)``: the weights, on the device;
* ``offline_engine(params, cfg, *, batch, chunk, seed)``: the program's
  batch engine for those weights, from its normal entry;
* ``offline_reads(params, cfg, x, *, operands)``: the reference's
  ``int32`` tokens of each row of ``x``;
* ``forward_work(cfg, rows, samples)``, ``macs_per_sample(cfg)`` and
  ``FORWARD_MODULE``: the work of the forward and the jitted module that
  runs it, for the rooflines and ``mfu``.
"""
from __future__ import annotations

import importlib


def model_of(cfg: dict):
    """The module of ``cfg``'s model family."""
    return importlib.import_module(
        f"{__name__}.{cfg.get('reference', 'reference')}")
