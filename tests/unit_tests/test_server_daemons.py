"""Server maintenance daemons (reference: sky/server/daemons.py)."""
import os
import time

import pytest

from skypilot_tpu.server import daemons as daemons_lib
from skypilot_tpu.server.requests import executor


def test_request_gc_drops_old_terminal_rows(isolated_state):
    rid_old = executor.schedule_request('old', 'noop', {})
    rid_new = executor.schedule_request('new', 'noop', {})
    rid_live = executor.schedule_request('live', 'noop', {})
    # Old + finished long ago; new + finished now; live still pending.
    executor._set_status(rid_old, executor.RequestStatus.SUCCEEDED)
    executor._set_status(rid_new, executor.RequestStatus.FAILED)
    executor._db().execute(
        'UPDATE requests SET finished_at=? WHERE request_id=?',
        (time.time() - 10 * 86400, rid_old))
    log_path = executor._log_path(rid_old)
    with open(log_path, 'w', encoding='utf-8') as f:
        f.write('x')

    removed = executor.gc_requests(retention_seconds=86400)
    assert removed == 1
    assert executor.get_request(rid_old) is None
    assert executor.get_request(rid_new) is not None  # inside retention
    assert executor.get_request(rid_live) is not None  # not terminal
    assert not os.path.exists(log_path)


def test_daemons_run_on_interval_and_survive_failures(monkeypatch):
    calls = {'status': 0, 'sweep': 0}

    def failing_status():
        calls['status'] += 1
        raise RuntimeError('boom')  # must not kill the thread

    monkeypatch.setattr(daemons_lib, '_refresh_cluster_status',
                        failing_status)
    monkeypatch.setattr(daemons_lib, '_sweep_controllers',
                        lambda: calls.__setitem__(
                            'sweep', calls['sweep'] + 1))
    d = daemons_lib.ServerDaemons(status_interval=0.2,
                                  liveness_interval=0.2,
                                  gc_interval=3600,
                                  poll=0.05)
    d.start()
    try:
        deadline = time.time() + 5
        while time.time() < deadline and (calls['status'] < 2 or
                                          calls['sweep'] < 2):
            time.sleep(0.05)
    finally:
        d.stop()
    # Both jobs ran repeatedly; the failing one kept being rescheduled.
    assert calls['status'] >= 2
    assert calls['sweep'] >= 2


@pytest.mark.slow
@pytest.mark.e2e
def test_preempted_cluster_flips_out_of_up(isolated_state):
    """A Local cluster whose agents
    die flips out of UP after one daemon tick with NOBODY calling
    status(refresh=True) from the outside."""
    import skypilot_tpu as sky
    from skypilot_tpu import check, core
    from skypilot_tpu.utils import subprocess_utils
    from skypilot_tpu.utils.status_lib import ClusterStatus

    check.check(quiet=True)
    task = sky.Task(name='boot', run='true')
    task.set_resources(sky.Resources(infra='local',
                                     accelerators='tpu-v5e-16'))
    _, handle = sky.launch(task, cluster_name='t-daemon',
                           _quiet_optimizer=True)
    try:
        assert core.status(['t-daemon'])[0]['status'] == ClusterStatus.UP

        # "Preempt": kill every agent process out-of-band, by pid.
        from skypilot_tpu.provision.local import instance as local_instance
        meta = local_instance._load_meta(handle.cluster_name_on_cloud)
        for host in meta['hosts']:
            subprocess_utils.kill_process_tree(host['agent_pid'])
        deadline = time.time() + 10
        while time.time() < deadline and any(
                subprocess_utils.process_alive(h['agent_pid'])
                for h in meta['hosts']):
            time.sleep(0.2)

        # Plain status (no refresh) still believes UP...
        assert core.status(['t-daemon'])[0]['status'] == ClusterStatus.UP
        # ...until one daemon tick reconciles it.
        d = daemons_lib.ServerDaemons(status_interval=3600,
                                      liveness_interval=3600,
                                      gc_interval=3600)
        d.tick_all()
        assert core.status(['t-daemon'])[0]['status'] == \
            ClusterStatus.STOPPED
    finally:
        try:
            core.down('t-daemon')
        except Exception:  # pylint: disable=broad-except
            pass


def test_zero_interval_disables_only_that_job(monkeypatch):
    calls = {'sweep': 0}
    monkeypatch.setattr(daemons_lib, '_refresh_cluster_status',
                        lambda: (_ for _ in ()).throw(
                            AssertionError('status job must be disabled')))
    monkeypatch.setattr(daemons_lib, '_sweep_controllers',
                        lambda: calls.__setitem__(
                            'sweep', calls['sweep'] + 1))
    d = daemons_lib.ServerDaemons(status_interval=0,
                                  liveness_interval=0.1,
                                  gc_interval=0, poll=0.02)
    d.start()
    try:
        deadline = time.time() + 5
        while time.time() < deadline and calls['sweep'] < 2:
            time.sleep(0.02)
    finally:
        d.stop()
    assert calls['sweep'] >= 2
