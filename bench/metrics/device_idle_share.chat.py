"""Device: 1 - (union of device operation intervals) / traced window, %,
averaged over the devices the cell uses."""

from bench.readings import idle_share


def read(run):
    return idle_share(run)
