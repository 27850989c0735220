"""The benchmark's own flowcell: a pore lifecycle over a pre-synthesised pool.

Each channel has a ``ready_at`` clock in flowcell samples, first drawn
uniformly from ``[0, start_spread_samples)``: the channels start out of
phase, as they run in the middle of a sequencing run, so that a short
warm-up reaches the flowcell's steady occupancy.  A channel that is ready
captures the next molecule; when the runtime resolves the read it reports the
samples the pore still spends on it (the eject latency, or the whole rest
of an accepted read), and the channel is ready again after that plus
``recovery_samples``.  Molecules come from a fixed pool in a seeded order
and are reused under fresh read ids once drawn, so the source never runs
dry and no signal is made while the loop runs.

The object speaks the runtime's source protocol: ``config.channels``,
``exhausted``, ``next_read(channel, now)`` and ``read_done(channel, now,
hold)``.  For the check it keeps, per read id, the molecule, the channel
and the number of device steps dispatched before the capture
(``dispatched()``, which the driver points at its step recorder).
"""
from __future__ import annotations

import dataclasses
import types

import numpy as np

from bench.lib.signals import Pool


@dataclasses.dataclass
class Read:
    """What the runtime reads of a captured molecule."""
    signal: np.ndarray
    read_id: int
    on_target: bool
    position: int

    @property
    def total_samples(self) -> int:
        return int(len(self.signal))


class PoolFlowcell:
    def __init__(self, pool: Pool, channels: int, *, recovery_samples: int,
                 start_spread_samples: int, rng: np.random.Generator):
        self.pool = pool
        self.config = types.SimpleNamespace(channels=channels)
        self.recovery = recovery_samples
        self._ready_at = rng.integers(0, start_spread_samples, channels)
        self._order = rng.permutation(len(pool))
        self.molecule_of: list[int] = []      # read id -> pool index
        self.channel_of: list[int] = []       # read id -> channel
        self.captured_at: list[int] = []      # read id -> steps before it
        self.dispatched = lambda: 0

    @property
    def exhausted(self) -> bool:
        return False

    def next_read(self, channel: int, now_samples: int):
        if now_samples < self._ready_at[channel]:
            return None
        rid = len(self.molecule_of)
        m = int(self._order[rid % len(self._order)])
        self.molecule_of.append(m)
        self.channel_of.append(channel)
        self.captured_at.append(self.dispatched())
        return Read(signal=self.pool.molecule(m), read_id=rid,
                    on_target=bool(self.pool.on_target[m]),
                    position=int(self.pool.starts[m]))

    def read_done(self, channel: int, now_samples: int,
                  hold_samples: int) -> None:
        self._ready_at[channel] = (now_samples + max(int(hold_samples), 0)
                                   + self.recovery)
