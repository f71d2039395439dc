"""Executor host stages (``core/plan.py`` route + merge): mean ms a batch,
from the ``Telemetry`` plan spans summed over the batch's knob groups."""

from bench.lib.readers import stage_ms


def read(rec):
    return stage_ms(rec, ("route", "merge"))
