"""Host time of the prefix mapper's banded extension per busy tick: the
padded genome's copy, the window gather, ``banded_align`` and its
readback, and the scoring (the program's ``map.extend`` stage)."""
from bench.lib.readers import stage_ms_per_tick


def read(obs):
    return stage_ms_per_tick(obs, "map.extend")
