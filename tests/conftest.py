import os

# Force CPU with 8 virtual devices so mesh/distributed tests run hermetically
# (pinned both in the env, for child processes, and in jax.config below).
if not os.environ.get("PADDLE_TPU_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

if not os.environ.get("PADDLE_TPU_TEST_TPU"):
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "fault: fault-injection / resilience tests (deterministic "
        "write failures, corruption, SIGTERM, NaN injection)")
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    config.addinivalue_line(
        "markers", "lint: static-analysis gates (graftlint over the repo; "
        "pure AST, no tracing)")
    config.addinivalue_line(
        "markers", "obs: observability/telemetry tests (metrics registry, "
        "spans, step events, interposed counters)")
    config.addinivalue_line(
        "markers", "serving: serving-runtime tests (bucketing, continuous "
        "batching, KV-cache decode, deadlines/load shedding, retrace "
        "flatness)")
    config.addinivalue_line(
        "markers", "sharding: FSDP/tensor-parallel sharded-training tests "
        "(2D-mesh parameter/optimizer-state sharding through the unified "
        "train step)")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(1234)
    np.random.seed(1234)
    yield


@pytest.fixture(autouse=True, scope="module")
def _module_telemetry_isolation():
    """Reset the observability spine between test MODULES.

    Tier-1 runs alphabetically (-p no:randomly): a module that enables
    telemetry, installs crash hooks, or leaves counters/cost-ledger
    entries behind silently changes what the next module observes — e.g.
    test_mission_control installing the flight recorder's excepthooks
    made test_cost_flight's install_crash_hooks() a no-op, so its
    monkeypatched threading.excepthook clobbered the live hook and
    load_dump() returned None. Module scope keeps intra-module state
    (many modules share setup within themselves) while giving every
    module a pristine spine."""
    yield
    import paddle_tpu.observability as obs
    from paddle_tpu.observability import endpoint, flush, timeseries
    flush.stop_rank_flusher(final_flush=False)
    timeseries.clear()
    endpoint.stop_active_server()
    obs.flight.uninstall_crash_hooks()
    obs.reset()
    from paddle_tpu.serving import admission
    admission.reset_tenant_stats()
    if os.environ.get("PADDLE_TPU_TELEMETRY") != "1":
        obs.disable()
