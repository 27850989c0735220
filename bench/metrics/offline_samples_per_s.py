"""offline_samples_per_s: raw read samples basecalled per second of the
window, each read sample counted once (host clock)."""


def read(obs):
    if "offline_samples" not in obs:
        return None
    return obs["offline_samples"] / obs["window_s"]
