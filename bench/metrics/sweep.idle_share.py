"""sweep.idle_share: the share of the measured window in which no
operation ran on the chip, from the profiler trace (%)."""
from bench.trace import idle_share


def read(run):
    return idle_share(run.trace)
