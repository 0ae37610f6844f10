"""Mesh: device time per cycle in collective ops (all-gather,
all-reduce, all-to-all, collective-permute, reduce-scatter, by HLO
opcode, synchronous or started and awaited), averaged over the chips.
Only a cell on several chips has any."""

from tracereduce import COLLECTIVE


def read(view):
    if view.chips < 2:
        return None
    t = view.device_seconds(lambda name: COLLECTIVE.match(name) is not None,
                            lines=("ops", "async"))
    if t <= 0 or not view.cycles:
        return None
    return t / view.cycles * 1e3
