"""One case of this directory that the cell ``sgd_incremental`` cannot
satisfy, marked here — strictly, and by its one node id — beside the accepted
file a ``model_config`` PR may not edit, until a ``benchmark`` PR repairs the
test (PERF.md, section 7).

``test_span_metrics.py::test_span_metrics_in_a_traced_rehearsal`` is
parametrised over EVERY cell of ``BENCHMARK.json`` and asks of each that one
``harness`` fit be ONE program call: one root span named ``fit`` that covers
the harness's ``fit_s`` to 2 %, whose children are flat phases with a
``fit.solve`` among them, and that ``fit_prep_ms`` read it. A fit of
``sgd_incremental`` is the deployment's loop — ``Incremental.fit`` then four
``Incremental.partial_fit``: five public calls, five roots (``fit``,
``partial_fit`` x 4), each with the children ``pass.validate`` /
``pass.grid`` / ``pass.solve`` — so no span of the program can cover it, and
the cell's own readers (``sgd_pass_ms``, ``sgd_grid_ms``,
``sgd_dispatches_per_pass``) take the passes instead; ``test_sgd_metrics.py``
holds them to the same sums. The case did not exist before the cell did.
``strict``: the day the case passes, the mark fails and goes."""

import pytest

_CASE = ("tests/bench_harness/test_span_metrics.py::"
         "test_span_metrics_in_a_traced_rehearsal[sgd_incremental]")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid == _CASE:
            item.add_marker(pytest.mark.xfail(
                reason="a fit of sgd_incremental is five program calls, "
                       "not one root span (see this conftest's docstring)",
                strict=True))
