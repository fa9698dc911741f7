"""Test config: 8-device CPU mesh + isolated state dir.

The tests run on the CPU, whatever the machine has: eight virtual
devices for the sharding tests, Pallas kernels in interpret mode.
`jax.config.update('jax_platforms', 'cpu')` pins that even where the
environment says otherwise (a TPU host sets JAX_PLATFORMS=tpu,cpu); it
must happen before any backend initialization, hence at conftest
import. What only a chip can say is `chip_smoke.py`'s and
`skypilot_tpu/ops/kernel_check.py`'s job, not a test's.
"""
import os
import sys

# 8 virtual CPU devices for sharding tests (must precede backend init).
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=8').strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

# Daemons launched DIRECTLY by this test process (agents from
# in-process sky.launch, the API server fixture, controllers from an
# in-process scheduler) get PR_SET_PDEATHSIG so a killed pytest run
# cannot leak them. The value is this process's pid: intermediaries
# (request workers, controllers, the server) inherit the env but
# don't match it, so THEIR daemons keep production survival semantics
# (a cluster must outlive its launch request; a kill-9'd controller's
# cluster must stay adoptable). Under xdist each worker's conftest
# import re-pins it to that worker.
os.environ['SKYPILOT_DAEMON_PDEATHSIG'] = str(os.getpid())

import pytest  # noqa: E402

# The slow tier splits into `compile` (real XLA compiles) and `e2e`
# (live processes / full pipelines); classification is per-file here
# so `-m 'slow and compile'` / `-m 'slow and e2e'` select sub-tiers
# without per-test decorator churn.
_E2E_FILES = {
    'test_chaos.py', 'test_serve.py', 'test_job_pools.py',
    'test_api_server.py', 'test_e2e_local.py', 'test_managed_jobs.py',
    'test_batch.py', 'test_load.py', 'test_auth.py',
    'test_server_daemons.py', 'test_backward_compat.py',
    'test_sdk_async.py',
}
_COMPILE_FILES = {
    'test_hf_recipes.py', 'test_models.py', 'test_ring_attention.py',
    'test_spec_batching.py', 'test_generate.py', 'test_hf_import.py',
    'test_paged_attention.py', 'test_flash_dispatch.py',
    'test_multislice.py', 'test_prefix_caching.py', 'test_pipeline.py',
    'test_pipeline_schedule.py',
    'test_tp_serving.py', 'test_tp_sharded_pool.py',
    'test_pp_serving.py',
    'test_profile_trace.py', 'test_fused_xent.py',
}


def pytest_collection_modifyitems(config, items):
    del config
    unclassified = set()
    for item in items:
        if 'slow' not in item.keywords:
            continue
        fname = os.path.basename(str(item.fspath))
        if 'e2e' not in item.keywords and fname in _E2E_FILES:
            item.add_marker(pytest.mark.e2e)
        if 'compile' not in item.keywords and fname in _COMPILE_FILES:
            item.add_marker(pytest.mark.compile)
        if not ({'e2e', 'compile'} & set(item.keywords)):
            unclassified.add(fname)
    if unclassified:
        # Exhaustiveness gate: a slow test neither tier selects would
        # silently lose all CI coverage.
        raise pytest.UsageError(
            f'slow tests in {sorted(unclassified)} are in neither '
            f'_E2E_FILES nor _COMPILE_FILES (tests/conftest.py) — add '
            f'the file to a sub-tier or mark the tests explicitly.')


@pytest.fixture(scope='session', autouse=True)
def _reap_leaked_daemons():
    """End-of-session sweep: SIGTERM any still-running skypilot_tpu
    module processes that are DESCENDANTS of this pytest process (a
    fixture that failed mid-teardown can strand agents/replicas).
    Scoped to descendants so concurrent sessions are untouched."""
    yield
    try:
        import psutil
        me = psutil.Process()
        for child in me.children(recursive=True):
            try:
                cmd = ' '.join(child.cmdline())
            except (psutil.NoSuchProcess, psutil.AccessDenied):
                continue
            if 'skypilot_tpu.' in cmd and 'python' in cmd:
                try:
                    child.terminate()
                except psutil.NoSuchProcess:
                    pass
    except Exception:  # pylint: disable=broad-except
        pass


@pytest.fixture()
def isolated_state(tmp_path, monkeypatch):
    """Point SKYPILOT_TPU_HOME at a fresh dir; clear db caches."""
    home = tmp_path / 'sky-home'
    monkeypatch.setenv('SKYPILOT_TPU_HOME', str(home))
    from skypilot_tpu import global_state
    global_state._db_for.cache_clear()  # pylint: disable=protected-access
    yield str(home)
    global_state._db_for.cache_clear()  # pylint: disable=protected-access


@pytest.fixture(scope='session')
def cpu_mesh8():
    from skypilot_tpu.parallel import mesh as mesh_lib
    return mesh_lib.make_mesh(mesh_lib.MeshConfig(data=2, fsdp=2, tensor=2))
