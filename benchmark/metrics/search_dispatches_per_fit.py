"""Tracked-program calls inside one fit of the search: the program
registry's delta over the harness's ``fit`` (``fit["programs"]``: the split,
every cohort scan, every round's scoring), mean over the window's fits."""
import statistics


def read(ctx):
    vals = [sum(f["programs"].values()) for f in ctx["fits"]
            if f.get("programs")]
    return statistics.fmean(vals) if vals else None
