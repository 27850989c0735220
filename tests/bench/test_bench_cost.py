"""The benchmark's FLOP and byte counts against XLA's own, at small widths
on the CPU."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import cost
from benchtools import REPO

SMALL = {"kernels": [5, 7, 1], "strides": [1, 2, 1], "channels": [8, 16, 5],
         "in_channels": 1}


def _xla_conv_flops(rows, t, k, s, ci, co):
    f = jax.jit(lambda x, w: jax.lax.conv_general_dilated(
        x, w, (s,), "VALID", dimension_numbers=("NWC", "WIO", "NWC")))
    c = f.lower(jnp.zeros((rows, t, ci)), jnp.zeros((k, ci, co))).compile()
    ca = c.cost_analysis()
    return (ca[0] if isinstance(ca, list) else ca)["flops"]


@pytest.mark.parametrize("padding,rows,samples", [
    ("stream", 3, 256), ("same", 2, 250)])
def test_conv_flops_match_xla(padding, rows, samples):
    """Layer by layer, each as one XLA convolution over its padded input
    (padded as the stream carry or the centred padding pads it)."""
    xla, t = 0.0, samples
    for k, s, ci, co in cost.layers(SMALL):
        if padding == "stream":
            t_in, t = t + k - s, t // s
        else:
            t_out = -(-t // s)
            t_in, t = max((t_out - 1) * s + k, t), t_out
        xla += _xla_conv_flops(rows, t_in, k, s, ci, co)
    assert xla == 2 * cost.conv_macs(SMALL, rows, samples, padding)


def test_paper_cnn_macs_per_sample():
    cfg = json.loads((REPO / "bench/configs/cnn460k.json").read_text())
    assert cost.macs_per_sample(cfg) == 133_088
    assert cost.n_params(cfg) == 460_261
    # 2,560 lanes x 256 samples: the tick's FLOPs
    assert cost.tick(cfg, 2560, 256)["flops"] == 2 * 2560 * 256 * 133_088


def _nbytes(*trees):
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(trees))


def test_tick_bytes_are_the_step_arguments_and_results():
    """The least bytes of a tick are exactly what the program's step reads
    and writes: its arguments and its results, once each."""
    from repro.core import basecaller as bc
    from repro.kernels import fabric
    from repro.realtime import runtime as rt

    cfg = dict(SMALL)
    bcc = bc.BasecallerConfig(kernels=tuple(cfg["kernels"]),
                              channels=tuple(cfg["channels"]),
                              strides=tuple(cfg["strides"]))
    lanes, chunk = 8, 64
    params = bc.init(jax.random.key(0), bcc)
    step = rt.build_step_fn(bcc, fabric.FabricPolicy("reference"),
                            fused=True)
    lane = rt.init_lane_state(bcc, lanes)
    args = (params, lane, jnp.zeros((lanes, chunk)),
            jnp.zeros((lanes, chunk // 2)), jnp.zeros((lanes,)))
    out = jax.eval_shape(step, *args)
    assert cost.tick(cfg, lanes, chunk)["bytes"] == _nbytes(args, out)


def test_forward_bytes_are_the_forward_arguments_and_results():
    from repro.core import basecaller as bc
    from repro.kernels import fabric

    bcc = bc.BasecallerConfig(kernels=tuple(SMALL["kernels"]),
                              channels=tuple(SMALL["channels"]),
                              strides=tuple(SMALL["strides"]))
    params = bc.init(jax.random.key(0), bcc)
    pol = fabric.FabricPolicy("reference")
    args = (params, jnp.zeros((4, 250)))
    out = jax.eval_shape(lambda p, x: bc.apply(p, x, bcc, fabric=pol), *args)
    assert cost.forward(SMALL, 4, 250)["bytes"] == _nbytes(args, out)


def test_least_time_names_its_bound():
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert cost.least_time({"flops": 1000, "bytes": 10}, peak) == (
        10.0, "compute")
    assert cost.least_time({"flops": 10, "bytes": 1000}, peak) == (
        100.0, "memory")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        cost.peaks("TPU v99")
    assert cost.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert np.isclose(cost.peaks("TPU v5 lite")["bytes_per_s"], 819e9)
