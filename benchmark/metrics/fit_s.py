"""Wall time of one whole ``fit`` by a new estimator to the stated tolerance,
entry call to ``block_until_ready``: the mean over the window's cycles without
their lowest and highest tenth (``harness.trimmed_mean``; host clock)."""


def read(ctx):
    return ctx["fit_s"]
