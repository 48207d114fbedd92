"""Planner/engine set-up: device-idle ms per window wave while the
innermost span is ``gopt.execute`` (replan check, binding, engine
construction), profiler trace."""
from pathlib import Path

import span_reduce


def read(run: dict):
    return span_reduce.idle_ms_per_wave(
        run, Path(__file__).resolve().parents[2], "execute")
