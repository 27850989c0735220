"""Host time of the prefix mapper's seed search per busy tick: seed
extraction, ``backward_search`` and the hits' readback (the program's
``map.search`` stage)."""
from bench.lib.readers import stage_ms_per_tick


def read(obs):
    return stage_ms_per_tick(obs, "map.search")
