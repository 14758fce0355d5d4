"""One measured-cost controller for every adaptive decision (DESIGN.md §9).

The subsystem has three layers:

* ``measure``    — shared timing loop + JSON persistence + device identity
  (also used by the block autotuner, so tunings and fits share one cache
  directory and one device-keying scheme);
* ``model``      — :class:`CostModel`: per-(device, impl, kind) affine fits
  ``t ≈ a + b·ops`` in the measured-ops basis ``roofline.count_job_ops``
  defines, calibrated online and persisted;
* ``controller`` — :class:`CostController`: the decision primitives
  (``choose_width`` / ``should_remine`` / ``choose_fusion`` /
  ``should_speculate``) plus per-decision telemetry.

Consumers: ``core/policy.MeasuredPolicy`` (pass combining),
``core/drivers.mine`` (calibration + speculative-join sizing),
``stream/miner.StreamMiner`` (re-mine trigger),
``serving/rules_engine.RuleServeEngine`` (micro-batch fusion under a
latency budget).
"""

from .controller import CostController, Decision
from .measure import JsonStore, cache_dir, costmodel_store, device_key, time_once
from .model import AffineFit, CostModel, default_model

__all__ = [
    "AffineFit", "CostModel", "CostController", "Decision", "JsonStore",
    "cache_dir", "costmodel_store", "default_model", "device_key",
    "time_once",
]
