"""One reader per metric, found by the metric's name in ``BENCHMARK.json``.

Each module defines ``read(ctx) -> float | None`` over the harness's
:class:`chipbench.harness.Context`; None means it found nothing to read,
and the harness then leaves the metric out of the result line.
"""
