"""Median device-busy ms inside the engine steps that returned "decode"."""
from benchmark import readers


def read(facts):
    return readers.span_device_ms(facts, "engine.step:decode")
