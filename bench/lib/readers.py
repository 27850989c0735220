"""Arithmetic the metric readers share.  A reader returns None where the
run gave it nothing to read, and the metric is then left out."""
from __future__ import annotations

from bench.configs import model_of
from bench.lib import cost
from bench.lib import trace as trace_lib


def stage_ms_per_tick(obs: dict, stage: str):
    ticks = obs.get("busy_ticks")
    if not ticks or stage not in obs.get("stage_s", {}):
        return None
    return obs["stage_s"][stage] / ticks * 1e3


def device_idle_pct(obs: dict):
    s = obs.get("trace_summary")
    if s is None or s["window_s"] <= 0:
        return None
    return (1.0 - s["busy_s"] / s["window_s"]) * 100.0


def module_s_per_call(obs: dict, module: str):
    """Device seconds of one execution of ``module`` on one chip."""
    tr = obs.get("trace")
    if tr is None:
        return None
    lo, hi = obs["trace_summary"]["bounds_ns"]
    total, calls = trace_lib.module_time(tr, module, lo, hi)
    chips = obs["chips_used"]
    if not calls or total <= 0:
        return None
    return total / (chips * calls)


def roofline_pct(obs: dict, module: str, work: dict):
    t = module_s_per_call(obs, module)
    if t is None:
        return None
    least, _ = cost.least_time(work, cost.peaks(obs["device_kind"]))
    return least / t * 100.0


def mfu_pct(obs: dict, samples: float):
    if not samples or obs["window_s"] <= 0:
        return None
    peak = cost.peaks(obs["device_kind"])["flops_per_s"]
    flops = 2.0 * samples * model_of(obs["cfg"]).macs_per_sample(obs["cfg"])
    return flops / (obs["window_s"] * obs["chips_used"] * peak) * 100.0
