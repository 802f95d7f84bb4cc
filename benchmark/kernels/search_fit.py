"""What ANY implementation of one FIT of the adaptive search must move and
compute on one chip, from the configuration's numbers alone (``main_kernel.
schedule``: ``max_iter``, ``aggressiveness``, ``test_size``, the design
dtype's ``design_itemsize``) — never from what the program dispatched.

Bytes: the survivor of bracket 0 alone makes ``max_iter`` steps one after
another, each over a whole training block, so no schedule reads fewer than
``max_iter`` blocks at the design dtype's width; and X arrives in float32, so
whatever builds a narrower design reads it once. FLOP: every model step is
``eta = X_b w`` and ``g = X_b^T r`` (2 FLOP an entry each), the search's
``partial_fit`` calls of them; every recorded score is one held-out product
(2 FLOP an entry), one for each model alive at each rung. Merged groups, a
second read of a block, a gathered split, per-group scoring of the whole
stack: all of that is the implementation's, shows as a low share, and the
share cannot pass 100 %."""

import math


def _brackets(max_iter, eta):
    s_max = int(math.floor(math.log(max_iter, eta)))
    for s in range(s_max, -1, -1):
        yield (int(math.ceil((s_max + 1) * eta ** s / (s + 1))),
               max(1, int(max_iter * eta ** -s)))


def schedule(max_iter, eta):
    """(model steps, recorded scores) of the whole search."""
    steps = scores = 0
    for n, r in _brackets(max_iter, eta):
        before = 0
        while True:
            steps += n * (r - before)
            scores += n
            if r >= max_iter:
                break
            before, n, r = r, max(1, n // eta), min(r * eta, max_iter)
    return steps, scores


def cost(rows_per_chip, d, params):
    n, d = int(rows_per_chip), int(d)
    max_iter, eta = int(params["max_iter"]), int(params["aggressiveness"])
    n_test = int(math.ceil(n * float(params["test_size"])))
    block = -(-(n - n_test) // 8)          # one chip: 8 blocks
    steps, scores = schedule(max_iter, eta)
    return {"bytes": max_iter * block * d * int(params["design_itemsize"])
            + n * d * 4,
            "flops": steps * 4 * block * d + scores * 2 * n_test * d}
