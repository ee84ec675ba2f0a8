"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / (traced window),
averaged over the chips the cell uses."""
from perfbench import readers


def read(facts, cfg, mix):
    return readers.idle_pct(facts)
