"""Host time of the prefix mapper's index upload per busy tick:
``index.device_arrays()``, the suffix array's int64-to-int32 conversion
included (the program's ``map.upload`` stage)."""
from bench.lib.readers import stage_ms_per_tick


def read(obs):
    return stage_ms_per_tick(obs, "map.upload")
