"""Test harness configuration.

Reference parity: python/ray/tests/conftest.py (ray_start_regular :588,
ray_start_cluster :678, shutdown_only :505). TPU-specific (SURVEY.md §4.3):
tests run on a virtual 8-device CPU mesh via
XLA_FLAGS=--xla_force_host_platform_device_count=8 — the analog of
cluster_utils.Cluster for collective/pjit tests.
"""
import os
import tempfile

# Must happen before any jax import anywhere in the test process.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache: the suite rebuilds many identical
# tiny-model engines (and forks replica subprocesses that do the same),
# so duplicate compiles of identical HLO dominate wall time. Entries are
# content-addressed on serialized HLO + compile options + jax version,
# so reuse within and across runs is safe. Env (not jax.config) so
# subprocess replicas inherit it. min_compile_time must drop to 0 or
# the sub-second tiny-model compiles are never persisted.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "ray_tpu_xla_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import contextlib  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def ray_start_regular():
    import ray_tpu as ray
    if ray.is_initialized():
        ray.shutdown()
    ray.init(num_cpus=2, object_store_memory=256 * 1024 * 1024)
    yield ray
    ray.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    yield cluster
    cluster.shutdown()


@pytest.fixture
def shutdown_only():
    import ray_tpu as ray
    yield ray
    if ray.is_initialized():
        ray.shutdown()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running convergence/regression tests")


@contextlib.contextmanager
def own_store_agent(ray, name, store_capacity=256 << 20, num_cpus=2,
                    timeout=30):
    """Spawn a REAL own-store node agent joined to `ray`'s head; yields
    the registered NodeID hex; terminates the agent on exit. Shared by
    every test that needs a second store (data plane, DAG channels,
    collectives)."""
    import os
    import subprocess
    import sys
    import time

    info = ray.head_address()
    env = dict(os.environ)
    env["RTPU_AUTHKEY"] = info["authkey"]
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.core.node_agent",
         "--head", info["address"], "--num-cpus", str(num_cpus),
         "--name", name, "--own-store",
         "--store-capacity", str(store_capacity)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        deadline = time.time() + timeout
        node_id = None
        while time.time() < deadline and node_id is None:
            for row in ray.nodes():
                if row["NodeName"] == name and row["Alive"]:
                    node_id = row["NodeID"]
            time.sleep(0.2)
        assert node_id, f"own-store agent {name!r} never registered"
        yield node_id
    finally:
        proc.terminate()
        proc.wait(timeout=10)
