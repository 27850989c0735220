"""Host time per offline batch spent in ``engine.step()`` outside its
``basecall`` and ``decode`` stages: stacking the rows, the per-row token
readback and bookkeeping (host clock minus the program's stage timers)."""


def read(obs):
    n = obs.get("batches_run")
    if not n:
        return None
    st = obs["stage_s"]
    return (obs["step_s"] - st.get("basecall", 0.0)
            - st.get("decode", 0.0)) / n * 1e3
