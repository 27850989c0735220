"""Operations and bytes of the algorithm, from shapes alone.

Counts are of what the computation needs, not of how the program runs it:
they do not change when a layer moves onto a Pallas kernel or back.

* FLOPs are 2 x the multiply-accumulates of every conv layer at the
  call's shapes (a k=1 head is a conv too).  Bias, ReLU, argmax and the
  CTC collapse are not counted.
* Bytes are the least HBM traffic: the call's inputs read once and its
  outputs written once, at their stored widths; intermediate activations
  are free.
* The least time is the larger of FLOPs over the peak rate and bytes over
  the peak bandwidth; ``bound`` says which of the two it is.
"""
from __future__ import annotations

import json
import math
import pathlib

F32 = 4
I32 = 4
PEAKS = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layers(cfg: dict):
    """(kernel, stride, cin, cout) per conv layer of a config file."""
    cins = [cfg["in_channels"]] + list(cfg["channels"][:-1])
    return list(zip(cfg["kernels"], cfg["strides"], cins, cfg["channels"]))


def n_params(cfg: dict) -> int:
    return sum(k * ci * co + co for k, _, ci, co in layers(cfg))


def macs_per_sample(cfg: dict) -> float:
    """Multiply-accumulates per input sample (frames shrink by each
    stride)."""
    total, rate = 0.0, 1.0
    for k, s, ci, co in layers(cfg):
        rate /= s
        total += rate * k * ci * co
    return total


def conv_macs(cfg: dict, rows: int, samples: int, padding: str) -> int:
    """MACs of the conv stack over ``rows`` x ``samples``.  ``stream``:
    each layer emits ``T // stride`` frames; ``same``: ``ceil(T / s)``."""
    t, total = samples, 0
    for k, s, ci, co in layers(cfg):
        t = t // s if padding == "stream" else -(-t // s)
        total += rows * t * k * ci * co
    return total


def tick(cfg: dict, lanes: int, chunk: int) -> dict:
    """One Read-Until tick over every lane: basecall, CTC collapse and the
    per-lane counters.  Reads the chunk, frame pads, reset mask, params,
    conv carries and the three per-lane counters; writes tokens, lengths,
    carries and counters."""
    frames = chunk // math.prod(cfg["strides"])
    flops = 2 * conv_macs(cfg, lanes, chunk, "stream")
    carries = sum((k - s) * ci for k, s, ci, _ in layers(cfg)) * lanes * F32
    counters = 3 * lanes * I32
    read = (lanes * chunk * F32 + lanes * frames * F32 + lanes * F32
            + n_params(cfg) * F32 + carries + counters)
    write = lanes * frames * I32 + lanes * I32 + carries + counters
    return {"flops": flops, "bytes": read + write}


def forward(cfg: dict, rows: int, samples: int) -> dict:
    """The offline forward of a batch: signal and params in, logits out."""
    frames = samples
    for s in cfg["strides"]:
        frames = -(-frames // s)
    flops = 2 * conv_macs(cfg, rows, samples, "same")
    read = rows * samples * F32 + n_params(cfg) * F32
    write = rows * frames * cfg["channels"][-1] * F32
    return {"flops": flops, "bytes": read + write}


def least_time(work: dict, peak: dict) -> tuple[float, str]:
    """(seconds, "compute" | "memory") at the device's peaks."""
    t_flop = work["flops"] / peak["flops_per_s"]
    t_byte = work["bytes"] / peak["bytes_per_s"]
    return (t_flop, "compute") if t_flop >= t_byte else (t_byte, "memory")
