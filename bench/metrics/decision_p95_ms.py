"""decision_p95_ms: 95th percentile, over every read decided in a paced
window, of the time from when the chunk that completed its evidence was
due to when the decision was made (host clock)."""
import numpy as np


def read(obs):
    lat = obs.get("decision_ms")
    if not lat:
        return None
    return float(np.percentile(np.asarray(lat), 95))
