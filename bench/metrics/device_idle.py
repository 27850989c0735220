"""Share of the traced window in which no operation ran on the device
(mean over the chips used; profiler trace)."""
from bench.lib.readers import device_idle_pct


def read(obs):
    return device_idle_pct(obs)
