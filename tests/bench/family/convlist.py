"""A second model family for the benchmark's tests: a tiny conv basecaller
written as a list of ``[kernel, stride, out channels]`` layers under
``layers``, with none of the conv stack's keys.

Copied into a checkout as ``bench/configs/convlist.py`` beside its
configuration, it shows that a family enters the offline driver's cells by
new files alone.  It maps its layers onto the program's
``repro.engine.build("basecall", ...)`` itself, and borrows the conv
stack's plain reference for the arithmetic.
"""
from __future__ import annotations

from bench.configs import reference as conv

WEIGHT_KINDS = ("he_layers",)
CONTROLS = ("bf16",)
FORWARD_MODULE = conv.FORWARD_MODULE


def _as_conv_stack(cfg: dict) -> dict:
    kernels, strides, channels = zip(*cfg["layers"])
    return {**cfg, "kernels": list(kernels), "strides": list(strides),
            "channels": list(channels), "in_channels": 1,
            "weights": {**cfg["weights"], "kind": "he_normal"}}


def n_params(cfg: dict) -> int:
    cin, total = 1, 0
    for k, _, cout in cfg["layers"]:
        total += k * cin * cout + cout
        cin = cout
    return total


def macs_per_sample(cfg: dict) -> float:
    return conv.macs_per_sample(_as_conv_stack(cfg))


def forward_work(cfg: dict, rows: int, samples: int) -> dict:
    return conv.forward_work(_as_conv_stack(cfg), rows, samples)


def make_params(cfg: dict, seed: int):
    return conv.make_params(_as_conv_stack(cfg), seed)


def offline_engine(params, cfg: dict, *, batch: int, chunk: int, seed: int):
    from repro.core import basecaller as bc
    from repro.engine import build

    kernels, strides, channels = zip(*cfg["layers"])
    return build("basecall", params=params,
                 cfg=bc.BasecallerConfig(kernels=kernels, channels=channels,
                                         strides=strides, in_channels=1),
                 batch=batch, chunk=chunk, seed=seed)


def offline_reads(params, cfg: dict, x, *, operands: str = "f32"):
    return conv.offline_reads(params, _as_conv_stack(cfg), x,
                              operands=operands)
