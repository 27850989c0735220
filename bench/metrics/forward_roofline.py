"""Least time of the forward of one offline batch at the chip's peaks over
the device time of one execution of its jitted program, both as the
configuration's model family gives them (``forward_work``,
``FORWARD_MODULE``: ``jit__apply_jit`` for the conv stack)."""
from bench.configs import model_of
from bench.lib.readers import roofline_pct


def read(obs):
    if "batch" not in obs:
        return None
    model = model_of(obs["cfg"])
    return roofline_pct(obs, model.FORWARD_MODULE,
                        model.forward_work(obs["cfg"], obs["batch"],
                                           obs["chunk"]))
