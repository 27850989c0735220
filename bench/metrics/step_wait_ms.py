"""Host time per busy tick spent waiting on the device step's tokens,
lengths and base counts (the program's ``basecall.wait`` stage)."""
from bench.lib.readers import stage_ms_per_tick


def read(obs):
    return stage_ms_per_tick(obs, "basecall.wait")
