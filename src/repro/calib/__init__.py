"""Measured calibration of :class:`~repro.core.hw.Target` constants.

The loop the ROADMAP asks for: measure what this host actually does
(:mod:`repro.calib.measure` — isolated GEMM / elementwise / DMA-proxy
microbenchmarks plus bench_block-style whole-block wall-clock), fit
effective per-level bandwidth / DMA setup and per-engine FLOP/s by
non-negative least squares over the shared roofline model
(:mod:`repro.calib.fit`), and emit a preset-shaped calibrated target —
``Target.calibrated(measurements, base=...)`` — with per-measurement
residuals and a CI drift gate (:func:`drift_gate`).

Typical use::

    from repro.calib import microbench_sweep, measure_block, calibrate

    ms = microbench_sweep() + measure_block("llama3.2-3b", m=256)
    result = calibrate(ms)          # or hw.Target.calibrated(ms)
    print(result.summary())
    target = result.target          # plan with the calibrated machine
"""
from .fit import (CalibrationResult, Residual, calibrate, drift_gate,
                  nnls)
from .measure import (COMPUTE, TRANSFER, Measurement, SegmentFeatures,
                      features_from_chain, measure_block,
                      measure_dma_proxy, measure_elementwise,
                      measure_gemms, microbench_sweep, modeled_measurement_s,
                      wallclock_s)

__all__ = [
    "COMPUTE", "TRANSFER", "Measurement", "SegmentFeatures",
    "modeled_measurement_s", "features_from_chain",
    "wallclock_s",
    "measure_gemms", "measure_elementwise", "measure_dma_proxy",
    "microbench_sweep", "measure_block",
    "nnls", "Residual", "CalibrationResult", "calibrate", "drift_gate",
]
