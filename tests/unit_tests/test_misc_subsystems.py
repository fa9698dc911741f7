"""Smaller subsystems: spot placer, queue autoscaler, usage, volumes,
workspaces, recipes, config layering, timeline."""
import json
import os
import time

import pytest

from skypilot_tpu.serve.spot_placer import DynamicFallbackSpotPlacer


def test_spot_placer_avoids_hot_locations():
    locations = [('gcp', 'us-east5', 'us-east5-a'),
                 ('gcp', 'us-central2', 'us-central2-b'),
                 ('gcp', 'europe-west4', 'europe-west4-b')]
    placer = DynamicFallbackSpotPlacer(locations)
    first = placer.select(now=0)
    placer.handle_active(first)
    placer.handle_preemption(first)
    nxt = placer.select(now=time.time())
    assert nxt != first
    assert not placer.all_hot()
    for loc in locations:
        placer.handle_preemption(loc)
    assert placer.all_hot()
    # Still returns *something* (caller decides on-demand fallback).
    assert placer.select() in locations


def test_queue_length_autoscaler():
    from skypilot_tpu.serve.autoscalers import (
        AutoscalerDecisionOperator, QueueLengthAutoscaler)
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec(min_replicas=1, max_replicas=5,
                          target_qps_per_replica=1,
                          upscale_delay_seconds=0,
                          downscale_delay_seconds=0)
    a = QueueLengthAutoscaler(spec, target_queue_per_replica=2)
    a.collect_request_information(10)
    d = a.evaluate(num_ready=1, num_launching=0, now=100)
    assert d.operator == AutoscalerDecisionOperator.SCALE_UP
    assert a.target_num_replicas == 5
    for _ in range(10):
        a.request_done()
    d = a.evaluate(num_ready=5, num_launching=0, now=200)
    assert d.operator == AutoscalerDecisionOperator.SCALE_DOWN
    assert a.target_num_replicas == 1


def test_usage_records_redacted_events(isolated_state, monkeypatch):
    monkeypatch.delenv('SKYPILOT_DISABLE_USAGE_COLLECTION', raising=False)
    from skypilot_tpu.usage import usage_lib
    with usage_lib.entrypoint('launch', cloud='gcp',
                              accelerator='tpu-v5e-16'):
        pass
    with pytest.raises(ValueError):
        with usage_lib.entrypoint('launch'):
            raise ValueError('secret path /home/x')
    path = os.path.join(isolated_state, 'usage', 'usage.jsonl')
    with open(path, 'r', encoding='utf-8') as f:
        events = [json.loads(line) for line in f]
    assert len(events) == 2
    assert events[0]['name'] == 'launch'
    assert events[1]['error'] == 'ValueError'
    # Redaction: the message (with its path) is NOT recorded.
    assert 'secret' not in json.dumps(events)


def test_usage_opt_out(isolated_state, monkeypatch):
    monkeypatch.setenv('SKYPILOT_DISABLE_USAGE_COLLECTION', '1')
    from skypilot_tpu.usage import usage_lib
    usage_lib.record_event('x')
    assert not os.path.exists(
        os.path.join(isolated_state, 'usage', 'usage.jsonl'))


def test_volumes_crud(isolated_state):
    """Registry CRUD on the Local provider (real backing dir; the GCP
    PD path is fake-API-tested in test_gce_provisioner)."""
    from skypilot_tpu import exceptions
    from skypilot_tpu.volumes import core as volumes_core
    vol = volumes_core.apply('data', 500, 'local')
    assert vol['status'] == 'READY' and os.path.isdir(vol['path'])
    rows = volumes_core.ls()
    assert rows[0]['name'] == 'data' and rows[0]['size_gb'] == 500
    volumes_core.delete('data')
    assert volumes_core.ls() == []
    assert not os.path.isdir(vol['path'])
    with pytest.raises(exceptions.SkyError):
        volumes_core.delete('data')


def test_workspaces(isolated_state, monkeypatch, tmp_path):
    cfg = tmp_path / 'cfg.yaml'
    cfg.write_text(
        'workspaces:\n'
        '  ml-team:\n'
        '    allowed_clouds: [GCP]\n')
    monkeypatch.setenv('SKYPILOT_TPU_CONFIG', str(cfg))
    from skypilot_tpu.workspaces import core as ws
    assert ws.active_workspace() == 'default'
    assert ws.allowed_clouds('default') is None
    assert ws.allowed_clouds('ml-team') == ['gcp']
    monkeypatch.setenv('SKYPILOT_WORKSPACE', 'ml-team')
    assert ws.active_workspace() == 'ml-team'
    import skypilot_tpu.exceptions as exc
    with pytest.raises(exc.SkyError):
        ws.get_workspace('nope')


def test_recipes_registry():
    from skypilot_tpu.recipes import core as recipes_core
    names = {r['name'] for r in recipes_core.list_recipes()}
    assert {'nanogpt', 'llama3_8b_fsdp', 'mixtral_ep',
            'managed_job_checkpoint'}.issubset(names)
    path = recipes_core.get_recipe_path('nanogpt')
    assert os.path.exists(path)
    with pytest.raises(FileNotFoundError):
        recipes_core.get_recipe_path('nope')


def test_config_layering(isolated_state, monkeypatch, tmp_path):
    from skypilot_tpu import sky_config
    server_cfg = os.path.join(isolated_state, 'config.yaml')
    os.makedirs(isolated_state, exist_ok=True)
    with open(server_cfg, 'w', encoding='utf-8') as f:
        f.write('gcp:\n  project_id: base\n  labels: {team: a}\n')
    user_cfg = tmp_path / 'user.yaml'
    user_cfg.write_text('gcp:\n  project_id: override\n')
    monkeypatch.setenv('SKYPILOT_TPU_CONFIG', str(user_cfg))
    assert sky_config.get_nested(('gcp', 'project_id')) == 'override'
    assert sky_config.get_nested(('gcp', 'labels')) == {'team': 'a'}
    with sky_config.override({'gcp': {'project_id': 'runtime'}}):
        assert sky_config.get_nested(('gcp', 'project_id')) == 'runtime'
    assert sky_config.get_nested(('gcp', 'project_id')) == 'override'


def test_timeline_tracing(tmp_path, monkeypatch):
    from skypilot_tpu.utils import timeline
    out = tmp_path / 'trace.json'
    monkeypatch.setattr(timeline, '_enabled_path', str(out))
    monkeypatch.setattr(timeline, '_events', [])

    @timeline.event
    def traced():
        time.sleep(0.01)

    traced()
    with timeline.Event('manual', 'note'):
        pass
    timeline.save()
    data = json.loads(out.read_text())
    names = {e['name'] for e in data['traceEvents']}
    assert any('traced' in n for n in names), names  # qualname form
    assert 'manual' in names


def test_user_registry(isolated_state):
    from skypilot_tpu.users import core as users_core
    users_core.record_request('alice')
    users_core.record_request('alice')
    users_core.record_request('bob')
    users_core.record_request('unknown')  # ignored
    rows = {r['name']: r for r in users_core.ls()}
    assert set(rows) == {'alice', 'bob'}
    assert rows['alice']['request_count'] == 2
    assert rows['alice']['role'] == 'user'
    users_core.set_role('alice', 'admin')
    rows = {r['name']: r for r in users_core.ls()}
    assert rows['alice']['role'] == 'admin'
    import pytest as _pytest
    with _pytest.raises(ValueError):
        users_core.set_role('bob', 'root')


def test_spot_autoscaler_mix_and_fallback():
    """SpotRequestRateAutoscaler splits the target into spot + on-demand
    (base floor + dynamic back-fill; reference autoscalers.py:933)."""
    from skypilot_tpu.serve import autoscalers
    from skypilot_tpu.serve import service_spec as spec_lib

    spec = spec_lib.SkyServiceSpec(
        min_replicas=4, max_replicas=4,
        base_ondemand_fallback_replicas=1,
        dynamic_ondemand_fallback=True,
        autoscaler='spot_request_rate')
    scaler = autoscalers.SpotRequestRateAutoscaler(spec)
    scaler.target_num_replicas = 4

    # Healthy: 3 spot up -> 3 spot + 1 base on-demand.
    mix = scaler.desired_mix(num_ready_spot=3)
    assert (mix.spot, mix.ondemand) == (3, 1)
    # Two spot replicas preempted -> back-fill with on-demand.
    mix = scaler.desired_mix(num_ready_spot=1)
    assert (mix.spot, mix.ondemand) == (3, 3)
    # Spot fully recovered -> back-fills retire, floor remains.
    mix = scaler.desired_mix(num_ready_spot=3)
    assert mix.ondemand == 1

    # Without dynamic fallback: floor only, no back-fill.
    spec2 = spec_lib.SkyServiceSpec(
        min_replicas=4, max_replicas=4,
        base_ondemand_fallback_replicas=2)
    scaler2 = autoscalers.SpotRequestRateAutoscaler(spec2)
    scaler2.target_num_replicas = 4
    mix = scaler2.desired_mix(num_ready_spot=0)
    assert (mix.spot, mix.ondemand) == (2, 2)


def test_instance_aware_lb_weights():
    """instance_aware LB sends traffic proportional to capacity."""
    from skypilot_tpu.serve.load_balancing_policies import (
        InstanceAwareLeastLoadPolicy)
    lb = InstanceAwareLeastLoadPolicy()
    lb.set_ready_replicas(['big:80', 'small:80'])
    lb.set_replica_weights({'big:80': 4.0, 'small:80': 1.0})
    picks = [lb.select_replica() for _ in range(10)]  # no completions
    # With 4x the capacity, 'big' should absorb ~4x the in-flight load.
    assert picks.count('big:80') == 8 and picks.count('small:80') == 2


def test_spot_placer_full_cycle_release():
    """handle_release frees capacity without marking preemption."""
    from skypilot_tpu.serve.spot_placer import DynamicFallbackSpotPlacer
    locs = [('gcp', 'us-central1', 'a'), ('gcp', 'us-central1', 'b')]
    placer = DynamicFallbackSpotPlacer(locs)
    first = placer.select()
    placer.handle_active(first)
    # Next selection balances onto the other location.
    second = placer.select()
    assert second != first
    placer.handle_release(first)
    assert not placer.all_hot()


def test_cross_cloud_transfer_plans():
    """Transfer planning (reference: sky/data/data_transfer.py:40-194):
    small jobs stream via CLI, big S3->GCS jobs become server-side
    Storage Transfer Service requests."""
    from skypilot_tpu.data import transfer as transfer_lib

    plan = transfer_lib.transfer('s3://src-b', 'gs://dst-b',
                                 size_gigabytes=1, run=False)
    assert plan['method'] == 'stream'
    assert 'gcloud storage rsync' in plan['command']

    plan = transfer_lib.transfer('s3://src-b', 'gs://dst-b',
                                 size_gigabytes=500, project_id='proj',
                                 run=False)
    assert plan['method'] == 'sts'
    body = plan['request_body']
    assert body['transferSpec']['awsS3DataSource']['bucketName'] == 'src-b'
    assert body['transferSpec']['gcsDataSink']['bucketName'] == 'dst-b'
    assert body['projectId'] == 'proj'

    # gs->s3 always streams (STS pulls INTO GCS only).
    plan = transfer_lib.transfer('gs://a', 's3://b', size_gigabytes=500,
                                 project_id='proj', run=False)
    assert plan['method'] == 'stream'

    import pytest as _pytest
    from skypilot_tpu import exceptions as exc
    with _pytest.raises(exc.StorageSpecError):
        transfer_lib.transfer('ftp://x', 'gs://y', run=False)


def test_s3_mount_commands():
    from skypilot_tpu.data import storage as storage_lib
    st = storage_lib.Storage(source='s3://datasets',
                             mode=storage_lib.StorageMode.MOUNT)
    cmd = storage_lib.mount_command(st, '/data')
    assert 'rclone mount' in cmd and ':s3,env_auth=true:datasets' in cmd
    cached = storage_lib.Storage(
        source='s3://datasets', mode=storage_lib.StorageMode.MOUNT_CACHED)
    cmd = storage_lib.mount_command(cached, '/data')
    assert '--vfs-cache-mode writes' in cmd


def test_azure_store_commands():
    from skypilot_tpu.data import storage as storage_lib
    st = storage_lib.Storage(source='az://ckpts',
                             mode=storage_lib.StorageMode.MOUNT)
    assert st.store == storage_lib.StoreType.AZURE
    cmd = storage_lib.mount_command(st, '/data')
    assert ':azureblob,env_auth=true:ckpts' in cmd
    copy = storage_lib.Storage(source='az://ckpts',
                               mode=storage_lib.StorageMode.COPY)
    cmd = storage_lib.mount_command(copy, '/data')
    assert 'az storage blob download-batch' in cmd
    # Sub-path urls: the az CLI takes a bare container name; the
    # sub-path must become a --pattern filter, not part of -s.
    sub = storage_lib.Storage(source='az://ckpts/run1',
                              mode=storage_lib.StorageMode.COPY)
    cmd = storage_lib.mount_command(sub, '/data')
    assert '-s ckpts ' in cmd and "--pattern 'run1/*'" in cmd


def test_r2_store_commands(monkeypatch):
    from skypilot_tpu.data import storage as storage_lib
    monkeypatch.setenv('R2_ACCOUNT_ID', 'acct123')
    st = storage_lib.Storage(source='r2://models',
                             mode=storage_lib.StorageMode.MOUNT)
    assert st.store == storage_lib.StoreType.R2
    cmd = storage_lib.mount_command(st, '/models')
    # rclone connection-string values with ':' must be quoted.
    assert 'endpoint="https://acct123.r2.cloudflarestorage.com"' in cmd
    copy = storage_lib.Storage(source='r2://models',
                               mode=storage_lib.StorageMode.COPY)
    cmd = storage_lib.mount_command(copy, '/models')
    assert '--endpoint-url' in cmd and 'aws s3 sync' in cmd
    # No hardcoded profile: env credentials by default, profile opt-in.
    assert '--profile' not in cmd


def test_r2_requires_account_id(monkeypatch):
    import pytest as _pytest
    from skypilot_tpu import exceptions as exc
    from skypilot_tpu.data import storage as storage_lib
    monkeypatch.delenv('R2_ACCOUNT_ID', raising=False)
    st = storage_lib.Storage(source='r2://models',
                             mode=storage_lib.StorageMode.MOUNT)
    with _pytest.raises(exc.StorageSpecError):
        storage_lib.mount_command(st, '/models')


def test_storage_yaml_roundtrip_new_stores():
    from skypilot_tpu.data import storage as storage_lib
    for url, store in (('az://c1', 'AZURE'), ('r2://b1', 'R2')):
        st = storage_lib.Storage.from_yaml_config({'source': url})
        assert st.store.value == store
        assert storage_lib.Storage.from_yaml_config(
            st.to_yaml_config()).bucket_url == url


def test_hf_store_download_only():
    from skypilot_tpu.data import storage as storage_lib
    st = storage_lib.Storage(source='hf://meta-llama/Llama-3-8B',
                             mode=storage_lib.StorageMode.COPY)
    assert st.store == storage_lib.StoreType.HF
    cmd = storage_lib.mount_command(st, '/models/llama')
    assert 'huggingface-cli download' in cmd
    assert 'meta-llama/Llama-3-8B' in cmd
    assert '--repo-type dataset' not in cmd

    ds = storage_lib.Storage(source='hf://datasets/allenai/c4',
                             mode=storage_lib.StorageMode.COPY)
    dcmd = storage_lib.mount_command(ds, '/data/c4')
    assert '--repo-type dataset' in dcmd and 'allenai/c4' in dcmd

    import pytest as _pytest
    from skypilot_tpu import exceptions as exc
    with _pytest.raises(exc.StorageSpecError):
        storage_lib.Storage(source='hf://org/model')  # MOUNT default
    with _pytest.raises(exc.StorageSpecError):
        storage_lib.Storage(name='only-name',
                            store=storage_lib.StoreType.HF,
                            mode=storage_lib.StorageMode.COPY)


def test_jobgroup_hosts_block_and_injection(isolated_state, monkeypatch,
                                            tmp_path):
    """The managed hosts block is idempotent (marker replacement) and
    lands in SKYPILOT_HOSTS_FILE when /etc/hosts is not the target."""
    from skypilot_tpu.jobs import groups, state

    jid_a = state.submit_job('actor', {'name': 'actor'}, 'failover', 0, 'u')
    jid_b = state.submit_job('learner', {'name': 'learner'}, 'failover',
                             0, 'u')
    db = groups._db()
    for jid in (jid_a, jid_b):
        db.execute('UPDATE managed_jobs SET job_group=? WHERE job_id=?',
                   ('rl', jid))
    groups.publish_address(jid_a, '10.0.0.5')
    groups.publish_address(jid_b, '10.0.0.9')

    block = groups.hosts_block('rl')
    assert '10.0.0.5 actor.rl actor' in block
    assert '10.0.0.9 learner.rl learner' in block

    hosts = tmp_path / 'hosts'
    hosts.write_text('127.0.0.1 localhost\n')
    monkeypatch.setenv('SKYPILOT_HOSTS_FILE', str(hosts))

    class FakeRunner:
        def run(self, cmd, require_outputs=False, **kw):
            import subprocess
            p = subprocess.run(['bash', '-c', cmd], capture_output=True,
                               text=True)
            return p.returncode, p.stdout, p.stderr

    class FakeHandle:
        def get_command_runners(self):
            return [FakeRunner()]

    landed = groups.install_hosts_entries(FakeHandle(), 'rl')
    # The env-var contract is the fixed absolute path (valid on every
    # host); the SKYPILOT_HOSTS_FILE target ALSO gets the block.
    assert landed == '/tmp/skypilot-jobgroup-rl.hosts'
    assert 'actor.rl' in open(landed, encoding='utf-8').read()
    content = hosts.read_text()
    assert content.startswith('127.0.0.1 localhost')
    assert content.count('actor.rl') == 1

    # Recovery republish: new IP replaces the block, no duplication.
    groups.publish_address(jid_a, '10.0.0.77')
    groups.install_hosts_entries(FakeHandle(), 'rl')
    content = hosts.read_text()
    assert '10.0.0.77 actor.rl actor' in content
    assert '10.0.0.5' not in content
    assert content.count('actor.rl') == 1
    assert content.count('localhost') == 1

    # Cleanup strips the block and the fixed-path file (pool workers
    # are reused; stale name->IP mappings must not leak).
    groups.remove_hosts_entries(FakeHandle(), 'rl')
    assert not os.path.exists(landed)
    after = hosts.read_text()
    assert 'actor.rl' not in after and 'localhost' in after


def test_instance_aware_autoscaler_mixed_fleet():
    """Mixed v5e+v5p fleet scales on NORMALIZED QPS (reference:
    sky/serve/autoscalers.py:605): capacity comes from the
    per-accelerator map, upscale sizes by the largest class, and
    downscale covers the load with the biggest replicas first."""
    from skypilot_tpu.serve.autoscalers import (
        Autoscaler, AutoscalerDecisionOperator,
        InstanceAwareRequestRateAutoscaler)
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec(min_replicas=1, max_replicas=10,
                          target_qps_per_replica={'tpu-v5e-8': 4.0,
                                                  'tpu-v5p-8': 10.0},
                          upscale_delay_seconds=0,
                          downscale_delay_seconds=0)
    a = Autoscaler.make(spec)
    assert isinstance(a, InstanceAwareRequestRateAutoscaler)
    assert a.capacity_of('tpu-v5e-8') == 4.0
    assert a.capacity_of('tpu-v5p-8') == 10.0
    assert a.capacity_of('unknown-hw') == 10.0  # best-known class

    # 17.5 QPS against one ready v5e (4 qps): overflow 13.5 sized by
    # the LARGEST class (10) -> +2 replicas above the current 1.
    now = 1000.0
    a.collect_request_information(
        int(17.5 * a._QPS_WINDOW_SECONDS), timestamp=now)
    d = a.evaluate(num_ready=1, num_launching=0, now=now,
                   ready_capacities=[4.0])
    assert d.operator == AutoscalerDecisionOperator.SCALE_UP
    assert a.target_num_replicas == 3

    # Same 17.5 QPS with [10, 4, 4, 4] ready: 10+4+4 > 17.5 -> 3
    # replicas cover it (largest first); the 4th is surplus.
    d = a.evaluate(num_ready=4, num_launching=0, now=now,
                   ready_capacities=[4.0, 10.0, 4.0, 4.0])
    assert d.operator == AutoscalerDecisionOperator.SCALE_DOWN
    assert a.target_num_replicas == 3

    # A uniform v5p fleet needs only 2 replicas for the same load.
    d = a.evaluate(num_ready=4, num_launching=0, now=now,
                   ready_capacities=[10.0, 10.0, 10.0, 10.0])
    assert a.target_num_replicas == 2

    # No ready replicas but live load: size by the largest class
    # (ceil(17.5/10) = 2), never stall at zero.
    d = a.evaluate(num_ready=0, num_launching=0, now=now,
                   ready_capacities=[])
    assert a.target_num_replicas == 2


def test_instance_aware_composes_with_spot_mix():
    """The instance-aware scaler inherits the spot floor/backfill mix
    (unified, where the reference keeps separate classes)."""
    from skypilot_tpu.serve.autoscalers import (
        Autoscaler, InstanceAwareRequestRateAutoscaler)
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec(min_replicas=2, max_replicas=8,
                          target_qps_per_replica={'tpu-v5e-8': 4.0},
                          base_ondemand_fallback_replicas=1,
                          dynamic_ondemand_fallback=True,
                          upscale_delay_seconds=0,
                          downscale_delay_seconds=0)
    a = Autoscaler.make(spec)
    assert isinstance(a, InstanceAwareRequestRateAutoscaler)
    a.target_num_replicas = 4
    mix = a.desired_mix(num_ready_spot=1)
    # 1 on-demand floor + (3 spot target - 1 ready) dynamic backfill.
    assert mix.spot == 3 and mix.ondemand == 3


def test_service_spec_qps_map_roundtrip_and_validation():
    import pytest as _pytest

    from skypilot_tpu import exceptions
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec.from_yaml_config({
        'readiness_probe': '/health',
        'replica_policy': {
            'min_replicas': 1, 'max_replicas': 4,
            'target_qps_per_replica': {'tpu-v5e-8': 4,
                                       'tpu-v5p-8': '10'},
        }})
    assert spec.target_qps_per_replica == {'tpu-v5e-8': 4.0,
                                           'tpu-v5p-8': 10.0}
    assert spec.autoscaling_enabled
    round_tripped = SkyServiceSpec.from_yaml_config(spec.to_yaml_config())
    assert round_tripped.target_qps_per_replica == \
        spec.target_qps_per_replica
    with _pytest.raises(exceptions.InvalidTaskYAMLError):
        SkyServiceSpec(target_qps_per_replica={'v5e': -1})
    with _pytest.raises(exceptions.InvalidTaskYAMLError):
        SkyServiceSpec(target_qps_per_replica={})


def test_instance_aware_no_ratchet_while_launching():
    """In-flight launches are credited at the largest-class capacity:
    repeated evaluations during a slow provision must NOT ratchet the
    target toward max_replicas."""
    from skypilot_tpu.serve.autoscalers import Autoscaler
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec(min_replicas=1, max_replicas=10,
                          target_qps_per_replica={'tpu-v5e-8': 4.0,
                                                  'tpu-v5p-8': 10.0},
                          upscale_delay_seconds=0,
                          downscale_delay_seconds=0)
    a = Autoscaler.make(spec)
    now = 1000.0
    a.collect_request_information(int(20 * a._QPS_WINDOW_SECONDS),
                                  timestamp=now)
    a.evaluate(num_ready=1, num_launching=0, now=now,
               ready_capacities=[4.0])
    first_target = a.target_num_replicas  # 1 + ceil(16/10) = 3
    assert first_target == 3
    # The two launches are now in flight; the target must hold.
    for _ in range(5):
        a.collect_request_information(0, timestamp=now)
        a.evaluate(num_ready=1, num_launching=2, now=now,
                   ready_capacities=[4.0])
    assert a.target_num_replicas == first_target


def test_group_name_validation(isolated_state):
    from skypilot_tpu import exceptions
    from skypilot_tpu.jobs import groups
    bad = "x'; rm -rf $HOME; echo '"
    with pytest.raises(exceptions.SkyError, match='hostname-safe'):
        groups.launch_group(bad, [{'name': 'a', 'run': 'true'}], user='u')
    with pytest.raises(exceptions.SkyError, match='hostname-safe'):
        groups.launch_group('ok', [{'name': 'has space', 'run': 'true'}],
                            user='u')


def test_hosts_markers_are_group_scoped(isolated_state, monkeypatch,
                                        tmp_path):
    """Two groups sharing one hosts file must not wipe each other."""
    from skypilot_tpu.jobs import groups, state
    for grp, nm, ip in (('g1', 'actor', '10.0.0.1'),
                        ('g2', 'worker', '10.0.0.2')):
        jid = state.submit_job(nm, {'name': nm}, 'failover', 0, 'u')
        groups._db().execute(
            'UPDATE managed_jobs SET job_group=? WHERE job_id=?',
            (grp, jid))
        groups.publish_address(jid, ip)

    hosts = tmp_path / 'hosts'
    hosts.write_text('127.0.0.1 localhost\n')
    monkeypatch.setenv('SKYPILOT_HOSTS_FILE', str(hosts))

    class FakeRunner:
        def run(self, cmd, require_outputs=False, **kw):
            import subprocess
            p = subprocess.run(['bash', '-c', cmd], capture_output=True,
                               text=True)
            return p.returncode, p.stdout, p.stderr

    class FakeHandle:
        def get_command_runners(self):
            return [FakeRunner()]

    groups.install_hosts_entries(FakeHandle(), 'g1')
    groups.install_hosts_entries(FakeHandle(), 'g2')
    content = hosts.read_text()
    assert 'actor.g1' in content and 'worker.g2' in content
    groups.remove_hosts_entries(FakeHandle(), 'g2')
    content = hosts.read_text()
    assert 'actor.g1' in content          # g1 untouched
    assert 'worker.g2' not in content
    os.path.exists(groups.hosts_file_path('g1')) and \
        os.remove(groups.hosts_file_path('g1'))


def test_hosts_markers_dotted_group_name(isolated_state, monkeypatch,
                                         tmp_path):
    """'.' is legal in group names and a regex wildcard: removing
    group 'a.b' must not strip group 'aXb''s managed block (the awk
    marker patterns escape ERE metacharacters)."""
    from skypilot_tpu.jobs import groups, state
    for grp, nm, ip in (('a.b', 'actor', '10.0.0.1'),
                        ('aXb', 'worker', '10.0.0.2')):
        jid = state.submit_job(nm, {'name': nm}, 'failover', 0, 'u')
        groups._db().execute(
            'UPDATE managed_jobs SET job_group=? WHERE job_id=?',
            (grp, jid))
        groups.publish_address(jid, ip)

    hosts = tmp_path / 'hosts'
    hosts.write_text('127.0.0.1 localhost\n')
    monkeypatch.setenv('SKYPILOT_HOSTS_FILE', str(hosts))

    class FakeRunner:
        def run(self, cmd, require_outputs=False, **kw):
            import subprocess
            p = subprocess.run(['bash', '-c', cmd], capture_output=True,
                               text=True)
            return p.returncode, p.stdout, p.stderr

    class FakeHandle:
        def get_command_runners(self):
            return [FakeRunner()]

    groups.install_hosts_entries(FakeHandle(), 'aXb')
    groups.install_hosts_entries(FakeHandle(), 'a.b')
    content = hosts.read_text()
    assert 'worker.aXb' in content and 'actor.a.b' in content
    groups.remove_hosts_entries(FakeHandle(), 'a.b')
    content = hosts.read_text()
    assert 'worker.aXb' in content        # aXb untouched
    assert 'actor.a.b' not in content
    for g in ('a.b', 'aXb'):
        if os.path.exists(groups.hosts_file_path(g)):
            os.remove(groups.hosts_file_path(g))


def test_instance_aware_cold_start_from_zero():
    """min_replicas=0 + traffic: the instance-aware scaler must still
    produce a nonzero target with no ready/launching replicas."""
    from skypilot_tpu.serve.autoscalers import (Autoscaler,
                                                AutoscalerDecisionOperator)
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec(min_replicas=0, max_replicas=5,
                          target_qps_per_replica={'tpu-v5e-8': 4.0},
                          upscale_delay_seconds=0,
                          downscale_delay_seconds=0)
    a = Autoscaler.make(spec)
    a.target_num_replicas = 0
    now = 1000.0
    a.collect_request_information(int(6 * a._QPS_WINDOW_SECONDS),
                                  timestamp=now)
    d = a.evaluate(num_ready=0, num_launching=0, now=now,
                   ready_capacities=[])
    assert d.operator == AutoscalerDecisionOperator.SCALE_UP
    assert a.target_num_replicas == 2  # ceil(6/4)


def test_instance_aware_scales_to_zero_when_idle():
    """min_replicas=0 + NO traffic: the cover walk must not pin one
    ready replica alive forever (parity with the scalar scaler's
    ceil(0/x) == 0 path)."""
    from skypilot_tpu.serve.autoscalers import (Autoscaler,
                                                AutoscalerDecisionOperator)
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec(min_replicas=0, max_replicas=5,
                          target_qps_per_replica={'tpu-v5e-8': 4.0},
                          upscale_delay_seconds=0,
                          downscale_delay_seconds=0)
    a = Autoscaler.make(spec)
    a.target_num_replicas = 1
    now = 1000.0  # no requests collected: qps == 0
    d = a.evaluate(num_ready=1, num_launching=0, now=now,
                   ready_capacities=[4.0])
    assert d.operator == AutoscalerDecisionOperator.SCALE_DOWN
    assert a.target_num_replicas == 0


def test_hosts_legacy_unscoped_block_is_migrated(isolated_state,
                                                 monkeypatch, tmp_path):
    """Blocks written under the pre-scoping markers are stripped on the
    first scoped install (they would shadow refreshed entries)."""
    from skypilot_tpu.jobs import groups, state
    jid = state.submit_job('actor', {'name': 'actor'}, 'failover', 0, 'u')
    groups._db().execute(
        'UPDATE managed_jobs SET job_group=? WHERE job_id=?', ('g1', jid))
    groups.publish_address(jid, '10.0.0.9')

    hosts = tmp_path / 'hosts'
    hosts.write_text('127.0.0.1 localhost\n'
                     '# >>> skypilot-jobgroup >>>\n'
                     '10.0.0.1 actor.g1 actor\n'
                     '# <<< skypilot-jobgroup <<<\n')
    monkeypatch.setenv('SKYPILOT_HOSTS_FILE', str(hosts))

    class FakeRunner:
        def run(self, cmd, require_outputs=False, **kw):
            import subprocess
            p = subprocess.run(['bash', '-c', cmd], capture_output=True,
                               text=True)
            return p.returncode, p.stdout, p.stderr

    class FakeHandle:
        def get_command_runners(self):
            return [FakeRunner()]

    groups.install_hosts_entries(FakeHandle(), 'g1')
    content = hosts.read_text()
    assert '10.0.0.1' not in content        # legacy block gone
    assert '10.0.0.9 actor.g1 actor' in content
    assert content.count('actor.g1') == 1
    os.remove(groups.hosts_file_path('g1'))


def test_launch_daemon_pdeathsig_reaps_on_parent_kill(tmp_path):
    """With SKYPILOT_DAEMON_PDEATHSIG (test runs set it), a daemon dies
    when its launcher dies — a killed pytest run cannot strand
    agents/controllers."""
    import signal
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(f"""
        import os, sys, time
        sys.path.insert(0, {repr(os.getcwd())})
        # pid-matched: only daemons launched by THE PINNED PROCESS
        # get the parent-death tie.
        os.environ['SKYPILOT_DAEMON_PDEATHSIG'] = str(os.getpid())
        from skypilot_tpu.utils import subprocess_utils
        pid = subprocess_utils.launch_daemon(
            ['sleep', '600'], {repr(str(tmp_path / 'd.log'))})
        print(pid, flush=True)
        time.sleep(600)
    """)
    launcher = subprocess.Popen([sys.executable, '-c', script],
                                stdout=subprocess.PIPE, text=True)
    daemon_pid = int(launcher.stdout.readline())
    from skypilot_tpu.utils.subprocess_utils import process_alive
    assert process_alive(daemon_pid)
    launcher.kill()           # simulate a killed test run
    launcher.wait(timeout=10)
    deadline = time.time() + 10
    while time.time() < deadline and process_alive(daemon_pid):
        time.sleep(0.2)
    assert not process_alive(daemon_pid)


def test_log_aggregator_selection_and_config(isolated_state, monkeypatch,
                                             tmp_path):
    """logs.store gcp/aws selects a streaming aggregator with a
    fluent-bit pipeline tailing run.log AND per-rank logs; bucket URLs
    keep the driver's archive path (None here)."""
    from skypilot_tpu import logs as logs_lib

    cfg = tmp_path / 'cfg.yaml'
    monkeypatch.setenv('SKYPILOT_TPU_CONFIG', str(cfg))

    cfg.write_text('logs:\n  store: gs://bucket/logs\n')
    assert logs_lib.get_aggregator() is None  # driver handles buckets

    cfg.write_text('logs:\n  store: gcp\n  gcp:\n    project_id: p1\n')
    agg = logs_lib.get_aggregator()
    assert isinstance(agg, logs_lib.StackdriverAggregator)
    conf = agg.fluentbit_config('my-cluster')
    assert 'job_logs/*/*.log' in conf          # run.log + rank-N.log
    assert 'job_id' in conf and 'rank' in conf  # labels lifted from path
    assert 'stackdriver' in conf
    assert 'export_to_project_id p1' in conf
    assert 'cluster my-cluster' in conf
    cmds = agg.setup_commands('my-cluster')
    assert any('fluent-bit' in c for c in cmds)
    assert any('metadata.google.internal' in c or
               'GOOGLE_APPLICATION_CREDENTIALS' in c for c in cmds)

    cfg.write_text('logs:\n  store: aws\n  aws:\n    region: eu-west-1\n'
                   '    log_group_name: tpu-logs\n')
    agg = logs_lib.get_aggregator()
    assert isinstance(agg, logs_lib.CloudwatchAggregator)
    conf = agg.fluentbit_config('c2')
    assert 'cloudwatch_logs' in conf and 'eu-west-1' in conf
    assert 'tpu-logs' in conf

    cfg.write_text('logs: {}\n')
    assert logs_lib.get_aggregator() is None


def test_queue_autoscaler_target_from_spec():
    """target_queue_per_replica flows YAML -> spec -> autoscaler."""
    from skypilot_tpu.serve.autoscalers import QueueLengthAutoscaler
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec.from_yaml_config({
        'readiness_probe': '/',
        'autoscaler': 'queue_length',
        'replica_policy': {'min_replicas': 1, 'max_replicas': 5,
                           'target_qps_per_replica': 1,
                           'target_queue_per_replica': 9}})
    a = QueueLengthAutoscaler(spec)
    assert a.target_queue_per_replica == 9.0
    # Explicit constructor arg still overrides.
    assert QueueLengthAutoscaler(
        spec, target_queue_per_replica=2).target_queue_per_replica == 2
