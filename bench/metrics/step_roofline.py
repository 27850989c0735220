"""Least time of one Read-Until tick's basecall work at the chip's peaks
(conv FLOPs, or the least HBM bytes, whichever bounds it) over the device
time of one execution of the jitted step (``jit_step``) on one chip."""
from bench.lib import cost
from bench.lib.readers import roofline_pct

MODULE = "jit_step"


def read(obs):
    if "lanes" not in obs:
        return None
    lanes = obs["lanes"] // obs["chips_used"]
    return roofline_pct(obs, MODULE, cost.tick(obs["cfg"], lanes,
                                               obs["chunk"]))
