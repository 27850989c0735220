"""FLOPs of the read samples basecalled in the window, each once (2 x the
model family's ``macs_per_sample``: the conv MACs for the conv stack),
over the window's seconds times the chips' bf16 peak."""
from bench.lib.readers import mfu_pct


def read(obs):
    return mfu_pct(obs, obs.get("samples"))
