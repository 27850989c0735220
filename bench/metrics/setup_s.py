"""setup_s: process start to the first timed tick or batch (host clock)."""


def read(obs):
    return obs["setup_s"]
