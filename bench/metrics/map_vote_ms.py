"""Host time of the prefix mapper's diagonal voting per busy tick (the
program's ``map.vote`` stage)."""
from bench.lib.readers import stage_ms_per_tick


def read(obs):
    return stage_ms_per_tick(obs, "map.vote")
