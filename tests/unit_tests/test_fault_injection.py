"""Chaos suite: deterministic fault injection + the hardening it
drives (deadlines, load shedding, crash-only engine containment,
health probes, drain, jobs recovery).

Determinism contract: every test uses seeded/counting fault plans
(rule firing is a pure function of the plan and the hit sequence) and
no wall-clock sleep beyond ~100ms. The acceptance invariants from the
robustness PR:

  (a) a poisoned decode step leaves every slot's output bit-identical
      (the fault fires before the dispatch and before RNG is
      consumed);
  (b) a poisoned prefill chunk fails exactly ONE request;
  (c) saturated requests shed with 429 + Retry-After while /readyz
      reflects draining/dead/saturated states;
  (d) with no plan installed, every point is a no-op and greedy
      serving output is byte-identical to the unarmed engine.
"""
import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from skypilot_tpu.robustness import faults
from skypilot_tpu.robustness.errors import (DeadlineExceededError,
                                            EngineDeadError,
                                            QueueSaturatedError)
from skypilot_tpu.utils import common_utils


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """A leaked plan would inject faults into every later test in the
    process — clear unconditionally."""
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# plan machinery (no jax)
# ---------------------------------------------------------------------------
def test_unknown_point_rejected_at_install():
    with pytest.raises(ValueError, match='unknown point'):
        faults.install_plan({'rules': [{'point': 'engine.nope'}]})
    with pytest.raises(ValueError, match='unknown action'):
        faults.install_plan({'rules': [
            {'point': 'engine.decode_step', 'action': 'explode'}]})
    with pytest.raises(ValueError, match='non-empty'):
        faults.install_plan({'rules': []})


def test_no_plan_points_are_noops():
    assert not faults.active()
    for name in faults.KNOWN_POINTS:
        assert faults.point(name) is None
    assert faults.stats() == {}


def test_counting_triggers_every_nth_after_times():
    faults.install_plan({'rules': [
        {'point': 'engine.decode_step', 'action': 'raise',
         'exc': 'RuntimeError', 'message': 'boom',
         'after': 2, 'every_nth': 3, 'times': 2}]})
    fired = []
    for i in range(1, 15):
        try:
            faults.point('engine.decode_step')
        except RuntimeError:
            fired.append(i)
    # Eligible hits start after 2; every 3rd eligible = hits 5, 8,
    # then the times=2 cap holds.
    assert fired == [5, 8]
    assert faults.stats()['engine.decode_step'] == {'hits': 14,
                                                    'fired': 2}


def test_at_trigger_fires_on_exact_hits():
    faults.install_plan({'rules': [
        {'point': 'http.handler', 'action': 'drop', 'at': [3, 7]}]})
    out = [faults.point('http.handler') for _ in range(8)]
    assert [i + 1 for i, o in enumerate(out) if o is faults.DROP] == \
        [3, 7]


def test_prob_trigger_is_seeded_and_replayable():
    def run():
        faults.install_plan({'seed': 123, 'rules': [
            {'point': 'jobs.monitor_probe', 'action': 'drop',
             'prob': 0.5}]})
        return [faults.point('jobs.monitor_probe') is faults.DROP
                for _ in range(64)]

    a, b = run(), run()
    assert a == b                      # same seed -> same firings
    assert any(a) and not all(a)       # actually probabilistic
    faults.install_plan({'seed': 124, 'rules': [
        {'point': 'jobs.monitor_probe', 'action': 'drop',
         'prob': 0.5}]})
    c = [faults.point('jobs.monitor_probe') is faults.DROP
         for _ in range(64)]
    assert c != a                      # different seed -> different


def test_plan_from_json_string_and_file(tmp_path):
    spec = {'rules': [{'point': 'checkpoint.save', 'action': 'raise',
                       'exc': 'OSError', 'message': 'disk gone'}]}
    faults.install_plan(json.dumps(spec))
    with pytest.raises(OSError, match='disk gone'):
        faults.point('checkpoint.save')
    path = tmp_path / 'plan.json'
    path.write_text(json.dumps(spec), encoding='utf-8')
    faults.install_plan(str(path))
    with pytest.raises(OSError, match='disk gone'):
        faults.point('checkpoint.save')
    faults.clear()
    assert faults.point('checkpoint.save') is None


def test_dotted_exception_path_and_default_type():
    faults.install_plan({'rules': [
        {'point': 'jobs.launch', 'action': 'raise',
         'exc': 'skypilot_tpu.robustness.errors.DeadlineExceededError',
         'times': 1},
        {'point': 'jobs.launch', 'action': 'raise', 'times': 1}]})
    with pytest.raises(DeadlineExceededError):
        faults.point('jobs.launch')
    with pytest.raises(faults.InjectedFault):
        faults.point('jobs.launch')


def test_delay_action_sleeps():
    faults.install_plan({'rules': [
        {'point': 'engine.device_get', 'action': 'delay',
         'delay_s': 0.03}]})
    t0 = time.monotonic()
    assert faults.point('engine.device_get') is None
    assert time.monotonic() - t0 >= 0.025


def test_scoped_rule_matches_only_its_context():
    """A scope is an eligibility filter BEFORE hit counting: calls
    outside the scope are invisible to the rule, so counters see
    only the matching stream."""
    faults.install_plan({'rules': [
        {'point': 'jobs.monitor_probe', 'action': 'drop',
         'scope': {'zone': 'us-east5-b'}, 'after': 1}]})
    # No context / wrong zone: never matches, never counts.
    assert faults.point('jobs.monitor_probe') is None
    assert faults.point('jobs.monitor_probe',
                        zone='us-west4-a') is None
    # First in-zone hit is eligible but after=1 defers it; second
    # fires — proving the wrong-zone calls above did not count.
    assert faults.point('jobs.monitor_probe',
                        zone='us-east5-b') is None
    assert faults.point('jobs.monitor_probe',
                        zone='us-east5-b') is faults.DROP
    assert faults.stats()['jobs.monitor_probe'] == {'hits': 2,
                                                    'fired': 1}


def test_scope_multi_key_and_validation():
    faults.install_plan({'rules': [
        {'point': 'jobs.monitor_probe', 'action': 'drop',
         'scope': {'zone': 'z1', 'job': '7'}}]})
    assert faults.point('jobs.monitor_probe', zone='z1') is None
    assert faults.point('jobs.monitor_probe', zone='z1',
                        job='8') is None
    assert faults.point('jobs.monitor_probe', zone='z1',
                        job='7') is faults.DROP
    with pytest.raises(ValueError, match='scope'):
        faults.install_plan({'rules': [
            {'point': 'jobs.monitor_probe',
             'scope': {'zone': 1}}]})


def test_windowed_rule_fires_only_inside_window():
    t = {'now': 0.0}
    faults.install_plan({'rules': [
        {'point': 'jobs.launch', 'action': 'raise',
         'exc': 'RuntimeError', 'start_s': 10.0,
         'duration_s': 5.0}]}, clock=lambda: t['now'])
    assert faults.point('jobs.launch') is None       # before
    t['now'] = 12.0
    with pytest.raises(RuntimeError):
        faults.point('jobs.launch')
    t['now'] = 15.0                                  # end exclusive
    assert faults.point('jobs.launch') is None
    with pytest.raises(ValueError, match='partial window'):
        faults.install_plan({'rules': [
            {'point': 'jobs.launch', 'start_s': 1.0}]})


def test_preempt_storm_drops_probes_for_scoped_jobs_in_window():
    """The derived point: one jobs.preempt_storm rule == a windowed,
    zone-scoped drop on jobs.monitor_probe, with a SEEDED start."""
    t = {'now': 0.0}
    plan = faults.install_plan({'seed': 11, 'rules': [
        {'point': 'jobs.preempt_storm',
         'scope': {'zone': 'us-east5-b'},
         'start_range': [20.0, 40.0], 'duration_s': 30.0}]},
        clock=lambda: t['now'])
    (window,) = plan.windows('jobs.monitor_probe')
    assert 20.0 <= window['start_s'] < 40.0
    assert window['end_s'] == pytest.approx(window['start_s'] + 30.0)
    assert window['scope'] == {'zone': 'us-east5-b'}
    # Same seed -> same storm start; different seed -> different.
    again = faults.FaultPlan(
        {'seed': 11, 'rules': [
            {'point': 'jobs.preempt_storm',
             'scope': {'zone': 'us-east5-b'},
             'start_range': [20.0, 40.0], 'duration_s': 30.0}]},
        clock=lambda: 0.0)
    assert again.windows('jobs.monitor_probe')[0]['start_s'] == \
        window['start_s']
    other = faults.FaultPlan(
        {'seed': 12, 'rules': [
            {'point': 'jobs.preempt_storm',
             'scope': {'zone': 'us-east5-b'},
             'start_range': [20.0, 40.0], 'duration_s': 30.0}]},
        clock=lambda: 0.0)
    assert other.windows('jobs.monitor_probe')[0]['start_s'] != \
        window['start_s']

    t['now'] = window['start_s'] + 1.0
    assert faults.point('jobs.monitor_probe',
                        zone='us-east5-b', job='1') is faults.DROP
    assert faults.point('jobs.monitor_probe',
                        zone='us-east5-b', job='2') is faults.DROP
    assert faults.point('jobs.monitor_probe',
                        zone='us-west4-a', job='3') is None
    t['now'] = window['end_s'] + 1.0
    assert faults.point('jobs.monitor_probe',
                        zone='us-east5-b', job='1') is None
    # Stats report under the derived point's own name.
    assert faults.stats()['jobs.preempt_storm']['fired'] == 2
    # A storm without a window fails at install, not silently.
    with pytest.raises(ValueError, match='requires a window'):
        faults.install_plan({'rules': [
            {'point': 'jobs.preempt_storm',
             'scope': {'zone': 'z'}}]})


def test_committed_example_storm_plan_installs():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        'examples', 'fault_plans', 'zone_storm.json')
    plan = faults.install_plan(path)
    assert plan.windows('jobs.monitor_probe')
    assert plan.windows('jobs.launch')


# ---------------------------------------------------------------------------
# Backoff jitter (satellite)
# ---------------------------------------------------------------------------
def test_backoff_decorrelated_jitter_bounds_and_determinism():
    import random
    mk = lambda: common_utils.Backoff(1.0, max_backoff=8.0,
                                      jitter=True,
                                      rng=random.Random(7))
    a = [mk().current_backoff() for _ in range(1)]  # seeded first draw
    b1, b2 = mk(), mk()
    seq1 = [b1.current_backoff() for _ in range(20)]
    seq2 = [b2.current_backoff() for _ in range(20)]
    assert seq1 == seq2                    # seeded -> reproducible
    assert all(1.0 <= s <= 8.0 for s in seq1)
    assert len(set(seq1)) > 5              # actually jittered
    assert a[0] == seq1[0]


def test_backoff_without_jitter_is_unchanged():
    b = common_utils.Backoff(2.0, max_backoff=10.0, multiplier=2.0)
    assert [b.current_backoff() for _ in range(4)] == \
        [2.0, 4.0, 8.0, 10.0]


# ---------------------------------------------------------------------------
# engine chaos (tiny llama)
# ---------------------------------------------------------------------------
@pytest.fixture(scope='module')
def tiny_model():
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import flax.linen as nn
    import jax.numpy as jnp

    from skypilot_tpu.models.llama import Llama, LlamaConfig
    model = Llama(LlamaConfig.tiny(kv_page_size=8, kv_total_pages=40))
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    return model, params


def _engine(tiny_model, **kw):
    from skypilot_tpu.models.batching import ContinuousBatchingEngine
    model, params = tiny_model
    kw.setdefault('num_slots', 2)
    kw.setdefault('max_total_len', 64)
    return ContinuousBatchingEngine(model, params, **kw)


def test_no_plan_greedy_output_byte_identical(tiny_model):
    """(d): an armed-but-never-firing plan and no plan at all produce
    byte-identical greedy output — the points really are no-ops."""
    eng = _engine(tiny_model)
    try:
        clean = eng.submit([5, 6, 7], max_new_tokens=8).result(
            timeout=120)
        faults.install_plan({'rules': [
            {'point': 'checkpoint.save', 'action': 'raise'}]})
        armed = eng.submit([5, 6, 7], max_new_tokens=8).result(
            timeout=120)
        assert armed == clean
    finally:
        eng.stop()


def test_poison_decode_step_outputs_bit_identical(tiny_model):
    """(a): one injected decode-step exception is contained — no
    request fails, no engine reset, and the output matches the clean
    run token for token (the fault fires before dispatch and before
    RNG is consumed)."""
    eng = _engine(tiny_model)
    try:
        clean = eng.submit([1, 2, 3, 4], max_new_tokens=10).result(
            timeout=120)
        faults.install_plan({'rules': [
            {'point': 'engine.decode_step', 'action': 'raise',
             'exc': 'RuntimeError', 'message': 'poison step',
             'after': 2, 'times': 1}]})
        poisoned = eng.submit([1, 2, 3, 4], max_new_tokens=10).result(
            timeout=120)
        assert poisoned == clean
        assert faults.stats()['engine.decode_step']['fired'] == 1
        assert eng.engine_restarts == 0
        assert eng.healthy()
    finally:
        faults.clear()
        eng.stop()


@pytest.mark.parametrize('temperature', [0.0, 0.8])
def test_poison_decode_step_right_after_a_prompt_finishes(
        tiny_model, temperature):
    """The first decode step of a request comes right after its
    prompt's last chunk, with the first token handed over on the
    device and unknown to the host. A fault raised there, before the
    dispatch, leaves the token in the engine's state: the retried
    round finds it and serves what a clean engine serves, greedy and
    sampled (the sampling's split was drawn once, before the fault)."""
    def run(poison):
        eng = _engine(tiny_model)
        try:
            if poison:
                faults.install_plan({'rules': [
                    {'point': 'engine.decode_step', 'action': 'raise',
                     'exc': 'RuntimeError',
                     'message': 'poison first step', 'times': 1}]})
            out = eng.submit([1, 2, 3, 4], max_new_tokens=10,
                             temperature=temperature).result(
                                 timeout=120)
            fired = faults.stats()['engine.decode_step']['fired'] \
                if poison else 0
            assert eng.engine_restarts == 0 and eng.healthy()
            assert (eng.first_tokens_deferred,
                    eng.first_tokens_synced) == (1, 0)
            return out, fired, eng.soft_errors_total
        finally:
            faults.clear()
            eng.stop()

    clean, _, _ = run(False)
    poisoned, fired, soft_errors = run(True)
    assert (fired, soft_errors) == (1, 1)
    assert poisoned == clean and len(clean) == 4 + 10


def test_poison_prefill_chunk_fails_only_that_slot(tiny_model):
    """(b): crash-only isolation — the poisoned request fails with
    the injected error; a sibling admitted alongside completes, and
    the engine serves bit-identically afterwards."""
    eng = _engine(tiny_model, prefill_chunk=8)
    try:
        clean = eng.submit(list(range(1, 20)),
                           max_new_tokens=5).result(timeout=120)
        faults.install_plan({'rules': [
            {'point': 'engine.prefill_chunk', 'action': 'raise',
             'exc': 'RuntimeError', 'message': 'poison prefill',
             'times': 1}]})
        victim = eng.submit(list(range(1, 20)), max_new_tokens=5)
        sibling = eng.submit([30, 31, 32], max_new_tokens=5)
        with pytest.raises(RuntimeError, match='poison prefill'):
            victim.result(timeout=120)
        assert len(sibling.result(timeout=120)) == 8
        faults.clear()
        again = eng.submit(list(range(1, 20)),
                           max_new_tokens=5).result(timeout=120)
        assert again == clean
        assert eng.healthy() and eng.engine_restarts == 0
    finally:
        faults.clear()
        eng.stop()


def test_deadline_reaps_mid_decode(tiny_model):
    eng = _engine(tiny_model)
    try:
        expired = eng.submit([1, 2, 3], max_new_tokens=4096,
                             deadline_s=0.02)
        healthy = eng.submit([4, 5, 6], max_new_tokens=5)
        with pytest.raises(DeadlineExceededError):
            expired.result(timeout=60)
        assert len(healthy.result(timeout=120)) == 8
        assert eng.deadline_exceeded == 1
        # The reaped slot's resources came back: a new request fits.
        assert len(eng.submit([7, 8], max_new_tokens=3).result(
            timeout=120)) == 5
    finally:
        eng.stop()


def test_deadline_reaps_queued_requests(tiny_model):
    eng = _engine(tiny_model, num_slots=1)
    try:
        hog = eng.submit([1, 2, 3], max_new_tokens=40)
        queued = eng.submit([4, 5, 6], max_new_tokens=40,
                            deadline_s=0.01)
        with pytest.raises(DeadlineExceededError):
            queued.result(timeout=60)
        hog.result(timeout=120)
        assert eng.queued_tokens() == 0
    finally:
        eng.stop()


def test_admission_control_sheds_by_request_count(tiny_model):
    eng = _engine(tiny_model, num_slots=1, max_queue_requests=2)
    try:
        futs, shed = [], 0
        for _ in range(10):
            try:
                futs.append(eng.submit([1, 2, 3], max_new_tokens=20))
            except QueueSaturatedError as e:
                assert e.retry_after_s > 0
                shed += 1
        assert shed > 0 and len(futs) >= 1
        assert eng.requests_shed == shed
        for f in futs:
            f.result(timeout=120)
        assert eng.queued_tokens() == 0
        assert not eng.saturated()
    finally:
        eng.stop()


def test_admission_control_sheds_by_token_budget(tiny_model):
    eng = _engine(tiny_model, num_slots=1, max_queue_tokens=16)
    try:
        hog = eng.submit(list(range(1, 9)), max_new_tokens=30)
        accepted, shed = [], 0
        for _ in range(6):
            try:
                accepted.append(eng.submit(list(range(1, 9)),
                                           max_new_tokens=2))
            except QueueSaturatedError:
                shed += 1
        assert shed > 0   # 8-token prompts trip a 16-token budget
        hog.result(timeout=120)
        for f in accepted:
            f.result(timeout=120)
        assert eng.queued_tokens() == 0
    finally:
        eng.stop()


@pytest.mark.filterwarnings(
    'ignore::pytest.PytestUnhandledThreadExceptionWarning')
def test_scheduler_death_fails_fast_not_hangs(tiny_model):
    """An injected SystemExit kills the scheduler thread (it is not
    an Exception, so the containment tiers can't catch it): pending
    futures fail with EngineDeadError, submit refuses new work, and
    healthy() flips — nobody hangs."""
    eng = _engine(tiny_model)
    try:
        faults.install_plan({'rules': [
            {'point': 'engine.decode_step', 'action': 'raise',
             'exc': 'SystemExit', 'times': 1}]})
        doomed = eng.submit([1, 2, 3], max_new_tokens=10)
        with pytest.raises(EngineDeadError):
            doomed.result(timeout=60)
        assert not eng.healthy()
        with pytest.raises(EngineDeadError):
            eng.submit([1], max_new_tokens=1)
    finally:
        faults.clear()
        eng.stop()


# ---------------------------------------------------------------------------
# HTTP plane: health probes, 429/504, metrics, drain
# ---------------------------------------------------------------------------
@pytest.fixture()
def robust_server(tiny_model):
    """A live inference HTTP server over a hardened engine: bounded
    queue, 30s deadline ceiling."""
    from skypilot_tpu.inference.http_server import make_server
    from skypilot_tpu.inference.runtime import InferenceRuntime
    model, params = tiny_model
    engine = _engine(tiny_model, num_slots=2, max_queue_requests=3)
    rt = InferenceRuntime(
        model=model, params=params,
        vocab_size=model.config.vocab_size, model_name='llama-tiny',
        max_total_len=64, spec_total=64, speculative=0, engine=engine,
        request_timeout=30.0, max_queue_requests=3)
    server = make_server(rt, 0)
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield f'http://127.0.0.1:{port}', server, rt, engine
    try:
        server.shutdown()
    except Exception:  # pylint: disable=broad-except
        pass
    engine.stop()


def _post(url, path, body, timeout=120):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={'Content-Type': 'application/json'})
    return urllib.request.urlopen(req, timeout=timeout)


def test_healthz_and_readyz(robust_server):
    url, server, _rt, engine = robust_server
    assert json.loads(urllib.request.urlopen(
        url + '/healthz', timeout=10).read()) == {'status': 'alive'}
    ready = json.loads(urllib.request.urlopen(
        url + '/readyz', timeout=10).read())
    assert ready == {'ready': True, 'reasons': []}

    # Draining: readiness flips (with the reason), liveness does not.
    server.draining.set()
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(url + '/readyz', timeout=10)
    assert exc.value.code == 503
    assert 'draining' in json.loads(exc.value.read())['reasons']
    assert urllib.request.urlopen(url + '/healthz',
                                  timeout=10).status == 200
    server.draining.clear()
    assert urllib.request.urlopen(url + '/readyz',
                                  timeout=10).status == 200
    assert engine.healthy()


def test_timeout_field_maps_to_504(robust_server):
    url, _server, rt, engine = robust_server
    before = engine.deadline_exceeded
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(url, '/generate', {'tokens': [[1, 2, 3]],
                                 'max_new_tokens': 4096,
                                 'timeout': 0.02})
    assert exc.value.code == 504
    assert 'DeadlineExceededError' in json.loads(
        exc.value.read())['error']
    assert engine.deadline_exceeded == before + 1
    stats = json.loads(urllib.request.urlopen(
        url + '/stats', timeout=30).read())
    assert stats['serving']['deadline_exceeded'] >= 1
    assert stats['deadline_exceeded'] >= 1
    assert rt.metrics.deadline_exceeded >= 1


def test_saturation_sheds_429_with_retry_after(robust_server):
    url, _server, _rt, engine = robust_server
    import concurrent.futures as cf

    def post_one(_):
        try:
            with _post(url, '/generate', {'tokens': [[1, 2, 3]],
                                          'max_new_tokens': 50}) as r:
                r.read()
            return 200, None
        except urllib.error.HTTPError as e:
            retry = e.headers.get('Retry-After')
            e.read()
            return e.code, retry

    with cf.ThreadPoolExecutor(10) as ex:
        results = list(ex.map(post_one, range(10)))
    codes = sorted(c for c, _ in results)
    assert codes.count(200) >= 2          # slots kept serving
    assert codes.count(429) >= 1          # overload was shed
    assert all(r is not None and int(r) >= 1
               for c, r in results if c == 429)
    assert engine.requests_shed >= codes.count(429)
    stats = json.loads(urllib.request.urlopen(
        url + '/stats', timeout=30).read())
    assert stats['serving']['requests_shed'] >= 1
    assert stats['max_queue_requests'] == 3


def test_metrics_expose_robustness_counters(robust_server):
    url, _server, _rt, _engine = robust_server
    text = urllib.request.urlopen(url + '/metrics',
                                  timeout=30).read().decode()
    for family in ('skypilot_serving_requests_shed_total',
                   'skypilot_serving_deadline_exceeded_total',
                   'skypilot_serving_engine_restarts_total'):
        assert f'# TYPE {family} counter' in text, family


def test_graceful_drain_completes_inflight_then_exits(robust_server):
    """Satellite: the SIGTERM drain contract — in-flight requests
    complete, new connections are refused after the accept loop
    stops, /readyz is 503 throughout, and the process 'exits' 0 (via
    the injectable exit_fn)."""
    from skypilot_tpu.inference.http_server import drain
    url, server, rt, _engine = robust_server

    results = []

    def inflight():
        with _post(url, '/generate', {'tokens': [[1, 2, 3]],
                                      'max_new_tokens': 120}) as r:
            results.append(json.loads(r.read()))

    t = threading.Thread(target=inflight)
    t.start()
    time.sleep(0.05)   # let the POST reach the handler

    exited = []
    drained = threading.Thread(
        target=lambda: drain(server, rt, drain_grace=60,
                             straggler_grace=0.5,
                             exit_fn=exited.append))
    drained.start()
    # Event-driven: the drain flips the flag BEFORE its straggler
    # window, so a probe issued right after the event lands inside it.
    assert server.draining.wait(timeout=10)
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(url + '/readyz', timeout=5)
    assert exc.value.code == 503
    assert 'draining' in json.loads(exc.value.read())['reasons']
    drained.join(timeout=60)
    t.join(timeout=60)
    assert exited == [0]
    # The in-flight request completed with its full generation
    # (capped at the engine's max_total_len=64).
    assert results and len(results[0]['tokens'][0]) == 64
    # New connections are refused (or never served) now.
    with pytest.raises(OSError):
        urllib.request.urlopen(url + '/healthz', timeout=2)


# ---------------------------------------------------------------------------
# jobs plane: launch retries, probe-drop recovery, recovery metric
# ---------------------------------------------------------------------------
def test_launch_retries_ride_out_injected_failures(monkeypatch):
    """Two injected ResourcesUnavailableErrors at jobs.launch are
    retried with (jittered) backoff; the third attempt lands."""
    from skypilot_tpu.jobs import recovery_strategy as rs

    launches = []
    monkeypatch.setattr(
        rs.execution, 'launch',
        lambda task, **kw: (launches.append(kw) or (7, object())))
    sleeps = []
    monkeypatch.setattr(rs.time, 'sleep', sleeps.append)

    class _Task:
        resources = ()

    ex = rs.StrategyExecutor('chaos-cluster', _Task())
    faults.install_plan({'rules': [
        {'point': 'jobs.launch', 'action': 'raise',
         'exc': 'skypilot_tpu.exceptions.ResourcesUnavailableError',
         'message': 'injected preemption', 'times': 2}]})
    assert ex._launch_with_retries(first_launch=False) == 7
    assert len(launches) == 1           # only the surviving attempt
    assert len(sleeps) == 2             # backoff between retries
    assert all(s > 0 for s in sleeps)
    assert faults.stats()['jobs.launch']['fired'] == 2


def test_launch_gives_up_after_max_attempts(monkeypatch):
    from skypilot_tpu import exceptions
    from skypilot_tpu.jobs import recovery_strategy as rs
    monkeypatch.setattr(rs.time, 'sleep', lambda s: None)
    monkeypatch.setattr(rs.execution, 'launch',
                        lambda task, **kw: (_ for _ in ()).throw(
                            AssertionError('must not launch')))

    class _Task:
        resources = ()

    ex = rs.StrategyExecutor('chaos-cluster', _Task())
    faults.install_plan({'rules': [
        {'point': 'jobs.launch', 'action': 'raise',
         'exc': 'skypilot_tpu.exceptions.ResourcesUnavailableError',
         'message': 'zone is gone'}]})
    with pytest.raises(exceptions.ResourcesUnavailableError):
        ex._launch_with_retries(first_launch=False, max_attempts=3)


def test_monitor_probe_drop_drives_recovery(monkeypatch):
    """A fault plan dropping agent probes is a synthetic preemption:
    the controller walks its real unreachable-grace machinery into
    _recover(), after which (probes restored) the job completes."""
    from skypilot_tpu.agent import job_lib as agent_job_lib
    from skypilot_tpu.jobs import controller as ctrl_mod
    from skypilot_tpu.jobs import failure_sources
    from skypilot_tpu.jobs import state

    monkeypatch.setattr(ctrl_mod, '_POLL_SECONDS', 0.005)
    monkeypatch.setattr(ctrl_mod, '_UNREACHABLE_GRACE_SECONDS', 0.02)
    monkeypatch.setattr(failure_sources, 'check_failed',
                        lambda name: None)
    status_log = []
    monkeypatch.setattr(state, 'set_status',
                        lambda jid, st, **kw: status_log.append(st))
    monkeypatch.setattr(state, 'bump_recovery', lambda jid: None)
    monkeypatch.setattr(state, 'set_stage', lambda jid, s: None)
    monkeypatch.setattr(state, 'set_agent_job_id', lambda jid, a: None)

    ctrl = ctrl_mod.JobController.__new__(ctrl_mod.JobController)
    ctrl.job_id = 1
    ctrl.cluster_name = 'chaos-managed'
    ctrl.group = None
    ctrl.pooled = False
    ctrl.stage = 0
    ctrl.stage_configs = [{}]
    ctrl.stage_max_restarts = 0
    ctrl._stage_restarts = 0
    ctrl._cancelled = False

    recovered = []

    class _Agent:
        def get_job(self, agent_job_id):
            st = (agent_job_lib.JobStatus.SUCCEEDED if recovered
                  else agent_job_lib.JobStatus.RUNNING)
            return {'status': st}

    ctrl._agent = lambda: _Agent()
    ctrl._cleanup = lambda cancel_job: None

    def _recover():
        recovered.append(True)
        faults.clear()   # the preempted zone "comes back"
        return 2

    ctrl._recover = _recover

    # Probes succeed twice, then every probe drops until recovery.
    faults.install_plan({'rules': [
        {'point': 'jobs.monitor_probe', 'action': 'drop',
         'after': 2, 'times': 100}]})
    final = ctrl._monitor_loop(agent_job_id=1)
    assert recovered == [True]
    assert final == state.ManagedJobStatus.SUCCEEDED
    assert state.ManagedJobStatus.RUNNING in status_log


def test_recovery_attempt_metric_labeled_by_strategy():
    from skypilot_tpu.jobs import recovery_strategy as rs
    from skypilot_tpu.observability import catalog
    child = catalog.counter(
        'skypilot_jobs_recovery_attempts_total').labels(
            strategy='failover')
    before = child.value
    rs._count_recovery_attempt(rs.FailoverStrategyExecutor.NAME)
    assert child.value == before + 1
    assert rs.FailoverStrategyExecutor.NAME == 'failover'
    assert rs.EagerNextRegionStrategyExecutor.NAME == \
        'eager_next_region'


# ---------------------------------------------------------------------------
# checkpoint.save point + hygiene
# ---------------------------------------------------------------------------
def test_checkpoint_save_point_fires(tmp_path):
    pytest.importorskip('orbax.checkpoint')
    from skypilot_tpu.parallel.checkpoints import CheckpointManager
    mgr = CheckpointManager(str(tmp_path / 'ckpt'))
    faults.install_plan({'rules': [
        {'point': 'checkpoint.save', 'action': 'raise',
         'exc': 'OSError', 'message': 'bucket unreachable',
         'times': 1}]})
    with pytest.raises(OSError, match='bucket unreachable'):
        mgr.save(0, {'x': 1})
    faults.clear()


def test_robustness_package_is_static_clean():
    """Satellite: `stpu check` has nothing to say about robustness/
    (no baseline rows, no suppressions needed)."""
    from skypilot_tpu import analysis
    pkg = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        'skypilot_tpu', 'robustness')
    assert analysis.run_paths([pkg]) == []
