"""Host time per offline batch of ``engine.step()``'s per-row readback and
bookkeeping (the program's ``readback`` stage)."""


def read(obs):
    n = obs.get("batches_run")
    st = obs.get("stage_s", {})
    if not n or "readback" not in st:
        return None
    return st["readback"] / n * 1e3
