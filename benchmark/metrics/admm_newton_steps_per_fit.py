"""Local Newton steps one fit actually ran (each one touch of every row of
X: eta, the residual product, the weighted Gram), counted inside the ADMM
program — an int32 in the outer loop's carry, the slowest block's steps an
outer iteration — and read from the ``fit.solve`` span's ``local_steps``;
mean over the window's fits. None where the program has no such counter."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.mean(kids["fit.solve"].get("local_steps")
                       for _, kids in _spans.fits(ctx) if "fit.solve" in kids)
