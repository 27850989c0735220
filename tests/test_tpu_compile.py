"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode on the CPU cannot see what Mosaic refuses (strided value
slices, ``cumsum``, value ``dynamic_slice`` ...).  These tests compile each
kernel of the Read-Until main path at its real widths for one chip of a
described ``v5e:2x2`` topology — no chip is attached, nothing runs — and
check that the program holds a Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a test-collection
decision made while importing would differ between xdist workers.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import basecaller as bc
from repro.data.flowcell import step_basecaller
from repro.kernels import fabric
from repro.realtime import PREFIX_ALIGN_CFG, PolicyConfig

LANES, CHUNK = 512, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _pallas(op, *args, **kwargs):
    """The op's compiled-kernel target with its real tuning."""
    spec = fabric.op_spec(op)
    tune = fabric.resolved_tuning(op, args, kwargs)
    out, _ = spec.pallas(*args, interpret=False, tune=tune, **kwargs)
    return out


# paper layers 1, 2 and 4 as the 512-lane stream step feeds them:
# (K, stride, Cin, Cout, input rows = chunk rows + K - stride carry)
@pytest.mark.parametrize("ksize,stride,cin,cout,rows", [
    (5, 1, 1, 64, CHUNK + 4),
    (7, 2, 64, 64, CHUNK + 5),
    (9, 2, 96, 192, CHUNK // 2 + 7),
], ids=["conv1", "conv2_stride2", "conv4_stride2"])
def test_conv1d_paper_layers_compile(one_chip, ksize, stride, cin, cout,
                                     rows):
    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def conv(x, w, b):
        return _pallas("conv1d", x, w, b, stride=stride, activation="relu")

    hlo = _compile(conv, s((LANES, rows, cin)), s((ksize, cin, cout)),
                   s((cout,)))
    assert "tpu_custom_call" in hlo


def test_head_matmul_compiles(one_chip):
    """The paper CNN's 1x1 head as a GEMM: (lanes * frames, 128) x (128, 5)."""
    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def head(a, b, bias):
        return _pallas("matmul", a, b, bias)

    hlo = _compile(head, s((LANES * CHUNK // 4, 128)), s((128, 5)), s((5,)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("which", ["paper", "step"])
def test_fused_stream_compiles(one_chip, which):
    """The fused tick at 512 lanes x chunk 256: paper widths
    64/64/96/192/128/5 in f32, and the step decoder's 5/5."""
    if which == "paper":
        cfg = bc.BasecallerConfig()
        params = jax.eval_shape(lambda: bc.init(jax.random.key(0), cfg))
    else:
        cfg, params = step_basecaller()

    def s(a, dtype=None):
        shape = getattr(a, "shape", a)
        return jax.ShapeDtypeStruct(shape, dtype or a.dtype,
                                    sharding=one_chip)

    n_frames = CHUNK // cfg.total_stride
    conv = jax.eval_shape(lambda: bc.init_stream_state(cfg, LANES))
    precisions = ("auto",) * len(cfg.kernels)

    def step(rows, pads, reset, prev, bases, ticks, conv, params):
        return _pallas("fused_stream", rows, pads, reset, prev, bases,
                       ticks, conv, params, cfg=cfg, precisions=precisions)

    hlo = _compile(step, s((LANES, CHUNK), jnp.float32),
                   s((LANES, n_frames), jnp.float32),
                   s((LANES,), jnp.float32), s((LANES,), jnp.int32),
                   s((LANES,), jnp.int32), s((LANES,), jnp.int32),
                   [s(c) for c in conv], jax.tree.map(s, params))
    assert "tpu_custom_call" in hlo


def test_banded_align_compiles_at_mapper_shapes(one_chip):
    """The prefix mapper's banded extension: every lane's mapping window
    against each candidate reference window, local alignment."""
    cfg = PREFIX_ALIGN_CFG
    m = PolicyConfig().map_prefix_bases
    pairs = LANES * cfg.max_candidates

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def align(q, t):
        return _pallas("banded_align", q, t, band=2 * cfg.band,
                       match=cfg.match, mismatch=cfg.mismatch, gap=cfg.gap,
                       local=True)

    hlo = _compile(align, s((pairs, m)), s((pairs, m + 2 * cfg.band)))
    assert "tpu_custom_call" in hlo
