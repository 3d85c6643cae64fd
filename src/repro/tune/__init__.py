"""Measurement-driven kernel autotuning (the PR-7 observability loop closed).

``repro.tune`` turns the repo's hard-coded tile shapes and sweep schedules
into measured decisions: a per-kernel-family :class:`KernelConfig` search
space, an autotuner that times candidates through the ``obs.trace`` timed
spans (device-synced), and a persistent
:class:`TuningCache` keyed by kernel × backend × diffusion model ×
size-bucket. The runtime backends consult :func:`resolve_spec` behind the
``RunSpec.tuning`` knob ("off" | "cached" | "auto"); tuning is
performance-only by the kernel contract — seed sets and sketch matrices are
bit-identical across every config (tier-1 property-tested).

See docs/tuning.md for the search space, cache schema, and how measured
shard profiles / planner stats seed the candidates.
"""
from repro.tune.autotuner import (autotune, families_for,
                                  measure_fused_family,
                                  measure_schedule_family,
                                  measure_sweep_family, resolve_spec)
from repro.tune.cache import (CACHE_ENV, DEFAULT_CACHE_PATH, TuningCache,
                              cache_key, default_cache, reset_default_cache,
                              size_bucket)
from repro.tune.config import (DEFAULT_CONFIGS, KERNEL_FAMILIES,
                               SWEEP_FAMILIES, KernelConfig, default_config,
                               fused_candidates, schedule_candidates,
                               spec_overrides, sweep_candidates)

__all__ = [
    "KernelConfig", "KERNEL_FAMILIES", "SWEEP_FAMILIES", "DEFAULT_CONFIGS",
    "sweep_candidates", "schedule_candidates", "fused_candidates",
    "spec_overrides", "default_config",
    "TuningCache", "cache_key", "size_bucket", "default_cache",
    "reset_default_cache", "CACHE_ENV", "DEFAULT_CACHE_PATH",
    "autotune", "resolve_spec", "families_for",
    "measure_sweep_family", "measure_schedule_family", "measure_fused_family",
]
