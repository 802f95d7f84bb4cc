"""Tracked-program calls in one fit: the sum of the program registry's
``calls`` deltas over the last traced fit (``obs_programs=True``)."""


def read(ctx):
    progs = [f["programs"] for f in ctx["fits"] if f.get("programs")]
    return sum(progs[-1].values()) if progs else None
