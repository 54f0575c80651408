"""Scatter and caches: mean per dispatch (ms) of the ``runtime.scatter``
stage, which slices each answer out of the batch, fills both cache tiers
and finishes the request. Moves ``goodput_rps``."""
from bench import stages


def read(run):
    return stages.per_dispatch_ms(run, lambda name: name == "runtime.scatter")
