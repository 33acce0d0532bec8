"""Share of device-busy time inside Mosaic (Pallas) kernels: custom calls
whose target is tpu_custom_call. Says how much of the device's time the
repository's own kernels can move; their roofline shares need names inside
the program (PERF.md)."""
from benchmark import readers


def read(facts):
    return readers.pallas_share_pct(facts)
