"""Idle share of the device over the window's joins, from the profiler
trace: ``100 * (1 - busy / window)``."""

from chipbench.readers import device_idle as read  # noqa: F401
