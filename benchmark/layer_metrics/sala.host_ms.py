"""Median host milliseconds of a boundary less its fetch: the host's own
work a boundary, over the window's ``llm.step`` trees the ring holds
whole."""

from benchmark.engine_spans import host_ms as read  # noqa: F401
