"""Least time of the six-layer forward of one offline batch at the chip's
peaks over the device time of one execution of its jitted program
(``jit__apply_jit``)."""
from bench.lib import cost
from bench.lib.readers import roofline_pct

MODULE = "jit__apply_jit"


def read(obs):
    if "batch" not in obs:
        return None
    return roofline_pct(obs, MODULE, cost.forward(obs["cfg"], obs["batch"],
                                                  obs["chunk"]))
