"""Test harness configuration.

Multi-device testing without TPUs (SURVEY.md §4 lesson — the reference
can only test Spark logic in local[4] mode): force an 8-device CPU mesh
so all pjit/shard_map code paths run in-process.  Must happen before the
first ``import jax`` anywhere in the test session.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import pytest

from predictionio_tpu.data.storage import Storage, set_storage


@pytest.fixture(autouse=True)
def _reset_resilience():
    """Per-test isolation for the resilience subsystem's process-global
    state: circuit breakers are keyed by endpoint (ephemeral test ports
    recycle!), chaos rules are process-wide, and the SLO monitor's burn
    gauges feed admission control — a previous test's open circuit,
    active fault, or deliberately-slow traffic must never shed the next
    test's requests."""
    from predictionio_tpu.obs import anomaly, dataobs, journal, slo
    from predictionio_tpu.resilience import chaos, policy

    def reset():
        policy.reset_breakers()
        chaos.reset()
        slo.MONITOR.clear()
        slo.MONITOR.evaluate()  # no samples -> burn gauges back to 0
        journal.JOURNAL.reset()
        journal.SHED_EPISODES.reset()
        anomaly.SENTINEL.reset()
        dataobs.DATAOBS.reset()

    reset()
    yield
    reset()


@pytest.fixture()
def memory_storage():
    """Fresh in-memory storage installed as the process singleton."""
    storage = Storage.from_env(
        {
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "meta",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "events",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "models",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        }
    )
    set_storage(storage)
    yield storage
    set_storage(None)
