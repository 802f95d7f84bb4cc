"""The host half of ``predict_proba``, ms: the ``predict.host`` span
(``expit``, ``1 - p``, ``np.stack`` over every row, after the decision
values are in hand); mean over the window's predicts."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.mean(1e3 * kids["predict.host"]["wall_s"]
                       for _, kids in _spans.predicts(ctx)
                       if "predict.host" in kids)
