"""Solver iterations of one fit (``n_iter_``), mean over the fits."""
from benchmark.metrics._lib import mean_fact


def read(ctx):
    return mean_fact(ctx, "n_iter")
