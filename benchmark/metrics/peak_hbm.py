"""Peak device memory in use on the fullest chip
(``memory_stats()["peak_bytes_in_use"]``), GiB, read before the check."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 2**30 or None
