"""Host time of the flowcell loop's sense stage per busy tick (the
program's ``sense`` stage timer)."""
from bench.lib.readers import stage_ms_per_tick


def read(obs):
    return stage_ms_per_tick(obs, "sense")
