"""Positions a live slot holds: the ``elephas.engine.decode`` span's
``kv_positions`` over its ``n_active``, median over decode spans. The
depth of the contexts the traffic keeps live, which is what it offers the
cache and the decode attention."""
from benchmark import mla_work


def read(facts):
    return mla_work.kv_live_positions(facts)
