"""What every estimator family's driver shares: building the estimator a
configuration names, placing the cell's data, and the Check record."""

from __future__ import annotations

import importlib

from benchmark import datagen


def load_class(path):
    mod, _, name = path.rpartition(".")
    return getattr(importlib.import_module(mod), name)


def new_estimator(cfg, **extra):
    """A NEW estimator from zero, exactly as the configuration states it."""
    est = cfg["estimator"]
    return load_class(est["class"])(**{**est["params"], **extra})


class Check:
    """Failures and the facts measured on the way (a failed check keeps what
    it had established). ``need`` raises nothing: every check runs."""

    def __init__(self):
        self.failures, self.facts = [], {}

    def need(self, cond, msg):
        if not cond:
            self.failures.append(msg)
        return bool(cond)


def place(cfg, traffic, chips, seed, mesh):
    """The cell's data as the program will receive it: ShardedArrays born
    row-sharded on the mesh. Returns a dict with X, y (None where the
    distribution has no labels), hp (the generator's host parameters),
    n_rows, d, x_bytes, seed, chips, mesh."""
    from dask_ml_tpu.parallel import as_sharded

    d = int(cfg["n_features"])
    n = int(traffic["rows_per_chip"]) * int(chips)
    hp = datagen.host_params(cfg["data"], d, seed)
    X, y = datagen.make_resident(cfg["data"], n, d, seed, mesh, hp)
    return {"hp": hp, "n_rows": n, "d": d, "x_bytes": 4 * n * d, "seed": seed,
            "chips": int(chips), "mesh": mesh, "X": as_sharded(X, mesh=mesh),
            "y": None if y is None else as_sharded(y, mesh=mesh)}


def device_rows(X, m=None):
    """The first ``m`` rows (all when None) of the cell's X (or y, or a
    label vector) as a device array, for the reference. The rows come from
    the FIRST shard alone: slicing the global array would all-gather every
    row onto every chip first."""
    if m is None:
        return X.data
    first = min(X.data.addressable_shards,
                key=lambda s: s.index[0].start or 0)
    if first.data.shape[0] < m:
        raise ValueError(f"the first shard holds {first.data.shape[0]} rows, "
                         f"fewer than the {m} sample rows")
    return first.data[:m]
