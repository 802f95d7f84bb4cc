"""Rows of X over the median wall time of one ``predict_proba``/``predict``
over the whole X, result in the form the API returns it, per chip."""


def read(ctx):
    if not ctx["predict_s"]:
        return None
    return ctx["n_rows"] / ctx["predict_s"] / ctx["chips"]
