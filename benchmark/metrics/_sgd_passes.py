"""What the ``sgd`` family's span readers share: the window's PASSES. One
``Incremental.fit`` / ``partial_fit`` is one pass under a root span of that
name which carries the pass record (``path``, ``steps``, ``dispatches``, ...:
``Incremental.pass_info_``); a fit of the cell is several. The window's
passes are the LAST roots of those names, as many as the harness's fits made
(the warm-up's precede them). Nothing to read — an empty ring, a program
whose wrapper opens no such span — gives an empty list."""
from benchmark.metrics import _spans

ROOTS = ("fit", "partial_fit")


def passes(ctx):
    """[(root record, {child span name: record})], oldest first."""
    n = sum(int(f["facts"].get("passes") or 0) for f in ctx["fits"])
    ring = _spans._ring() if n else []
    found = [call for name in ROOTS for call in _spans.calls(name, n, ring)
             if "path" in call[0]]
    return sorted(found, key=lambda call: call[0]["t_start_ns"])[-n:]
