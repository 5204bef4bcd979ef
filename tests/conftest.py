# NOTE: no XLA_FLAGS here — tests must see exactly 1 CPU device. Multi-device
# behaviour is tested via subprocesses (tests/test_distributed.py) that set
# --xla_force_host_platform_device_count themselves.
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolate_obs_state():
    """Write-isolate the process telemetry state per test: counter values
    (obs.registry.REGISTRY — including ``core.plan.fused_trace_counts``) and
    the process tracer's ring and profiler switches are restored after
    every test, so no test can leak metric mutations or a left-enabled
    tracer into another.

    NOTE the asymmetry this creates: counters roll back, jit/lru caches do
    NOT — a test asserting an absolute trace count ≥ 1 after an operation
    whose graph an earlier test already traced will see 0. Assert on
    *deltas within the test*, or use configs/specs unique to the test."""
    from repro.obs import registry as obs_registry
    from repro.obs import trace as obs_trace

    state = obs_registry.REGISTRY.dump_state()
    was_enabled = obs_trace.TRACER.enabled
    was_profiling = obs_trace.TRACER.profiling
    try:
        yield
    finally:
        obs_registry.REGISTRY.restore_state(state)
        if not was_profiling:
            obs_trace.TRACER.disable_profiling()
        if not was_enabled:
            obs_trace.TRACER.disable()
            obs_trace.TRACER.clear()
