"""Share of the traced window in which no operation ran on the device
(1 - busy / window, from the device trace)."""
from bench.readers import device_idle


def read(r):
    return device_idle(r)
