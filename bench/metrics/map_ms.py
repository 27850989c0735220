"""Host time of the prefix mapper and decision rule per busy tick (the
program's ``map`` stage timer)."""
from bench.lib.readers import stage_ms_per_tick


def read(obs):
    return stage_ms_per_tick(obs, "map")
