"""Test harness: force an 8-device virtual CPU mesh so every sharding path
(dp/fsdp/tp/sp/ep/pp) is exercised without TPU hardware — the reference's
CPU-only-CI strategy (SURVEY.md §4) translated to JAX."""

import os

# The tests run on the CPU whatever the ambient environment says: the
# environment for subprocesses, the config below for this process (a
# pytest plugin may have imported jax before this file runs).
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("DLROVER_LOG_LEVEL", "WARNING")
# The AOT compile-for-topology tests load libtpu's compile-only client,
# which (without this) retries the GCE metadata service 30x per env var
# on images with no metadata endpoint — minutes of curl backoff inside
# the tier-1 budget.  The tests never touch a real device.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Seeded-violation fixtures for the static analyzer: parsed by
# tests/test_analysis.py, never collected (the DLR003 mini projects
# contain their own tests/test_chaos.py, which would collide with the
# real one under pytest's module namespace).
collect_ignore = ["analysis_fixtures"]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long multi-process / large-world tests, excluded from the "
        "tier-1 `-m 'not slow'` run",
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection scenarios (tests/test_chaos.py); the fast "
        "ones run in tier-1, long stalls are additionally marked slow",
    )
    config.addinivalue_line(
        "markers",
        "telemetry: event-log / spans / metrics / goodput-accountant "
        "tests (tests/test_telemetry.py)",
    )
    config.addinivalue_line(
        "markers",
        "analysis: static-analyzer tests (tests/test_analysis.py) — "
        "stdlib-only, no jax needed",
    )
    config.addinivalue_line(
        "markers",
        "wus: weight-update-sharding tests (tests/test_wus.py) — "
        "CPU-mesh numerical equivalence + HLO layout evidence; the "
        "multi-process variants are additionally marked slow",
    )
    config.addinivalue_line(
        "markers",
        "packing: sequence-packing / segment-sparse attention tests "
        "(tests/test_packing.py) — packer properties, no-leak masking "
        "across every attention path, mask-aware cost model",
    )
    config.addinivalue_line(
        "markers",
        "kv: sharded embedding service tests (tests/test_kv_service.py)"
        " — routing, batching, cache coherence, elastic reshard; the "
        "real-process chaos drill is additionally marked slow",
    )
    config.addinivalue_line(
        "markers",
        "kv_ha: kv replication / lease-fenced failover tests "
        "(tests/test_kv_replication.py) — stream edge cases, "
        "bounded-staleness routing, fencing, the freshness SLO burn, "
        "and the tier-1 real-process promotion drill",
    )
    config.addinivalue_line(
        "markers",
        "serve: inference gateway tests (tests/test_serving_gateway.py,"
        " tests/test_serving_fleet.py) — block-pool invariants, "
        "prefix-cache and chunked-prefill equivalence, admission "
        "control, servput closure, replica-fleet failover (warm-standby"
        " promotion, health ejection, autoscaler, brownout ladder); "
        "the legacy real-process SIGKILL replay drill is additionally "
        "marked slow, the fleet promotion drill runs in tier-1",
    )
    config.addinivalue_line(
        "markers",
        "tracing: request-scoped tracing + SLO burn-rate engine tests "
        "(tests/test_tracing.py) — wire propagation, causal "
        "reconstruction, exemplars, burn alerts; the real-process "
        "SIGKILL reconstruction drill is additionally marked slow",
    )
    config.addinivalue_line(
        "markers",
        "observer: fleet observer tests (tests/test_observer.py) — "
        "metrics federation, black-box canaries, MAD anomaly "
        "correlation, dashboard; the real-process divergence drill "
        "runs in tier-1",
    )


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture()
def isolated_ipc(monkeypatch):
    """Per-test checkpoint-IPC namespace + fresh saver singleton.

    Pre-resets too: a stale factory thread from an earlier suite would
    early-return start_async_saving_ckpt while serving the OLD uid's
    socket, so the new uid's SaverConfig would never be consumed.
    Modules that touch the flash-checkpoint saver opt in with a thin
    autouse wrapper.
    """
    import time as _time

    from dlrover_tpu.checkpoint.ckpt_saver import AsyncCheckpointSaver

    AsyncCheckpointSaver.reset()
    monkeypatch.setenv(
        "DLROVER_JOB_UID", f"t{os.getpid()}_{_time.time_ns()}"
    )
    yield
    AsyncCheckpointSaver.reset()
