"""Executor: device-idle ms per window wave while the host runs the
engine (innermost span ``gopt.op.*``, ``gopt.pattern``, ``gopt.tail`` or
``gopt.deliver``: engine Python and eager dispatch), profiler trace."""
from pathlib import Path

import span_reduce


def read(run: dict):
    return span_reduce.idle_ms_per_wave(
        run, Path(__file__).resolve().parents[2], "ops")
