"""Jitted wrapper for the SSD scan kernel (model-layout convenience)."""
from __future__ import annotations

from repro.kernels.ssd_scan import kernel as K


def ssd_scan(x, dt, A, B, C, D=None, *, chunk: int = 128, interpret=None):
    return K.ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=interpret)
