"""Candidate generation (``core/plan.py`` into ``kernels/ops.py`` or
``quant/twostage.py``, host-device copies included): mean ms a batch, from
the ``Telemetry`` candidates span (the q8 re-rank's share taken out)."""

from bench.lib.readers import stage_ms


def read(rec):
    return stage_ms(rec, ("candidates",))
