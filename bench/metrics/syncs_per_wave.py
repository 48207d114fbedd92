"""Executor: device->host control-plane round trips per wave of the
window: the program's ``gopt.sync.*`` spans inside the ``gopt.wave`` spans
that overlap ``bench.window``, over those waves, in the traced run (one
span per ``sync:<label>`` event of ``KernelStats``)."""
from pathlib import Path

import span_reduce


def read(run: dict):
    sp = span_reduce.for_run(run, Path(__file__).resolve().parents[2])
    if sp is None or not sp["waves"]:
        return None
    return sp["syncs"] / sp["waves"]
