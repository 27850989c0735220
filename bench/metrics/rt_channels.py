"""rt_channels: channels kept in real time by a closed Read-Until loop --
signal samples basecalled in the window, over its seconds, over one
channel's sample rate (host clock)."""


def read(obs):
    if obs["traffic"].get("pacing") != "closed" or "samples" not in obs:
        return None
    return obs["samples"] / obs["window_s"] / obs["sample_rate_hz"]
