"""Per step, ms in all-reduce (or other collective) operations during
which nothing else runs on the device. Cells on several chips only."""
from benchmark import readers


def read(facts):
    return readers.exposed_collective_ms_per_step(facts)
