"""1 - union of device-op intervals / profiled window, in percent."""
from benchmark import readers


def read(facts):
    return readers.device_idle_pct(facts)
