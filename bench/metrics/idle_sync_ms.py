"""Executor: device-idle ms per window wave while the host waits in a
device->host round trip (innermost span ``gopt.sync.*`` or ``gopt.d2h``),
profiler trace."""
from pathlib import Path

import span_reduce


def read(run: dict):
    return span_reduce.idle_ms_per_wave(
        run, Path(__file__).resolve().parents[2], "sync")
