"""CLI: `stpu` — thin wrappers that build Tasks, call the SDK, and
poll request ids.

Reference: sky/client/cli/command.py (8468 LoC, 105 commands). Core
command set here; jobs/serve groups register from their modules.
"""
from __future__ import annotations

import datetime
import os
import sys
from typing import Any, Dict, List, Optional

import click

from skypilot_tpu import exceptions
from skypilot_tpu.client import sdk
from skypilot_tpu.utils import common_utils


def _err(message: str) -> None:
    click.secho(f'Error: {message}', fg='red', err=True)
    sys.exit(1)


def _parse_env(env: List[str]) -> Dict[str, str]:
    out = {}
    for item in env:
        if '=' in item:
            k, v = item.split('=', 1)
            out[k] = v
        else:
            v = os.environ.get(item)
            if v is None:
                _err(f'--env {item}: not set in the caller environment')
            out[item] = v
    return out


def _parse_env_file(path: str) -> Dict[str, str]:
    """dotenv-style KEY=VAL lines; '#' comments and blanks skipped."""
    out: Dict[str, str] = {}
    try:
        with open(os.path.expanduser(path), 'r', encoding='utf-8') as f:
            for raw in f:
                line = raw.strip()
                if not line or line.startswith('#') or '=' not in line:
                    continue
                k, v = line.split('=', 1)
                out[k.strip()] = v.strip().strip('"').strip("'")
    except OSError as e:
        _err(f'--env-file {path}: {e}')
    return out


def _merged_env(env, env_file) -> Dict[str, str]:
    """--env-file entries with --env flags overriding on conflict."""
    out: Dict[str, str] = {}
    if env_file:
        out.update(_parse_env_file(env_file))
    out.update(_parse_env(list(env or [])))
    return out


def _build_task(entrypoint, name, workdir, infra, gpus, cpus, memory,
                num_nodes, use_spot, env, cmd=None, env_file=None):
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu import task as task_lib
    env_overrides = _merged_env(env, env_file)
    if entrypoint and entrypoint.endswith(('.yaml', '.yml')):
        config = common_utils.read_yaml(os.path.expanduser(entrypoint))
        task = task_lib.Task.from_yaml_config(config, env_overrides)
    else:
        run_cmd = cmd or entrypoint
        task = task_lib.Task(run=run_cmd, envs=env_overrides)
    if name:
        task.name = name
    if workdir:
        task.workdir = workdir
    if num_nodes:
        task.num_nodes = num_nodes
    overrides: Dict[str, Any] = {}
    if infra:
        overrides['infra'] = infra
    if gpus:
        overrides['accelerators'] = gpus
    if cpus:
        overrides['cpus'] = cpus
    if memory:
        overrides['memory'] = memory
    if use_spot is not None:
        overrides['use_spot'] = use_spot
    if overrides:
        task.set_resources({r.copy(**overrides) for r in task.resources})
    # A Local-cloud job shares this machine's filesystem but runs from
    # a synced copy whose path carries the cluster name, so the
    # in-checkout default compile cache (utils/compile_cache.py) would
    # move with it and never hit: hand the job the launcher's.
    cache_dir = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if cache_dir and 'JAX_COMPILATION_CACHE_DIR' not in task.envs and \
            all(str(r.cloud) == 'Local' for r in task.resources):
        task.update_envs({'JAX_COMPILATION_CACHE_DIR': cache_dir})
    return task


@click.group()
@click.version_option('0.1.0', prog_name='stpu')
def cli() -> None:
    """stpu: TPU-native sky orchestrator."""


# ---------------------------------------------------------------------------
# launch / exec
# ---------------------------------------------------------------------------
_task_options = [
    click.option('--name', '-n', default=None, help='Task name.'),
    click.option('--workdir', default=None,
                 help='Directory synced to ~/sky_workdir.'),
    click.option('--infra', default=None,
                 help='cloud[/region[/zone]], e.g. gcp/us-central2.'),
    click.option('--gpus', '--tpus', 'gpus', default=None,
                 help='Accelerator, e.g. tpu-v5p-128 or A100:8.'),
    click.option('--cpus', default=None),
    click.option('--memory', default=None),
    click.option('--num-nodes', type=int, default=None,
                 help='Number of nodes (TPU: slices).'),
    click.option('--use-spot/--no-use-spot', default=None),
    click.option('--env', multiple=True,
                 help='KEY=VAL or KEY (inherit).'),
    click.option('--env-file', default=None,
                 help='dotenv file; --env flags override its entries.'),
]


def _add_options(options):

    def wrap(f):
        for opt in reversed(options):
            f = opt(f)
        return f

    return wrap


@cli.command()
@click.argument('entrypoint', required=False)
@click.option('--cluster', '-c', default=None, help='Cluster name.')
@_add_options(_task_options)
@click.option('--idle-minutes-to-autostop', '-i', type=int, default=None)
@click.option('--down', is_flag=True, default=False,
              help='Autodown after the job finishes / on idle.')
@click.option('--retry-until-up', '-r', is_flag=True, default=False)
@click.option('--dryrun', is_flag=True, default=False)
@click.option('--detach-run', '-d', is_flag=True, default=False)
@click.option('--no-setup', is_flag=True, default=False)
@click.option('--optimize-target', type=click.Choice(['cost', 'time']),
              default='cost', help='Rank candidate hardware by $ or by '
                                   'estimated runtime.')
@click.option('--yes', '-y', is_flag=True, default=False)
def launch(entrypoint, cluster, name, workdir, infra, gpus, cpus, memory,
           num_nodes, use_spot, env, env_file, idle_minutes_to_autostop, down,
           retry_until_up, dryrun, detach_run, no_setup, optimize_target,
           yes) -> None:
    """Launch a task from YAML or a command (provisions a cluster)."""
    task = _build_task(entrypoint, name, workdir, infra, gpus, cpus, memory,
                       num_nodes, use_spot, env, env_file=env_file)
    if not yes and not dryrun:
        r = sorted(str(x) for x in task.resources)
        target = cluster or 'new cluster'
        click.echo(f'Launching {task.name or "task"} on {target}: {r}')
        click.confirm('Proceed?', default=True, abort=True)
    request_id = sdk.launch(
        task, cluster_name=cluster, dryrun=dryrun,
        detach_run=True,
        idle_minutes_to_autostop=idle_minutes_to_autostop, down=down,
        retry_until_up=retry_until_up, no_setup=no_setup,
        optimize_target=optimize_target)
    result = sdk.stream_and_get(request_id)
    if result and result.get('job_id') is not None and not detach_run:
        cname = (result.get('handle') or {}).get('cluster_name') or cluster
        sdk.tail_logs(cname, result['job_id'])


@cli.command(name='exec')
@click.argument('cluster')
@click.argument('entrypoint')
@_add_options(_task_options)
@click.option('--detach-run', '-d', is_flag=True, default=False)
def exec_cmd(cluster, entrypoint, name, workdir, infra, gpus, cpus, memory,
             num_nodes, use_spot, env, env_file, detach_run) -> None:
    """Run a task on an existing cluster (no provisioning)."""
    task = _build_task(entrypoint, name, workdir, infra, gpus, cpus, memory,
                       num_nodes, use_spot, env, env_file=env_file)
    request_id = sdk.exec(task, cluster, detach_run=True)
    result = sdk.stream_and_get(request_id)
    if result.get('job_id') is not None and not detach_run:
        sdk.tail_logs(cluster, result['job_id'])


# ---------------------------------------------------------------------------
# status & lifecycle
# ---------------------------------------------------------------------------
@cli.command()
@click.argument('clusters', nargs=-1)
@click.option('--refresh', '-r', is_flag=True, default=False)
@click.option('--endpoints', is_flag=True, default=False,
              help='Show head IP and opened-port URLs instead.')
@click.option('--kubernetes', '--k8s', 'kubernetes', is_flag=True,
              default=False,
              help='List ALL framework-managed pods in the current '
                   'kube context instead of this server\'s clusters.')
def status(clusters, refresh, endpoints, kubernetes) -> None:
    """Show clusters (or, with --kubernetes, every managed pod)."""
    if kubernetes:
        from rich.console import Console
        from rich.table import Table
        from skypilot_tpu.provision.kubernetes import instance as k8s_inst
        pods = k8s_inst.list_skypilot_pods()
        table = Table(box=None)
        for col in ('CLUSTER', 'POD', 'RANK', 'PHASE', 'NODE'):
            table.add_column(col)
        for pod in sorted(pods, key=lambda x: (x['cluster'],
                                               int(x['node_rank']))):
            table.add_row(pod['cluster'], pod['name'], pod['node_rank'],
                          pod['phase'], pod['node'])
        Console().print(table)
        return
    request_id = sdk.status(list(clusters) or None, refresh=refresh)
    records = sdk.get(request_id)
    if not records:
        click.echo('No existing clusters.')
        return
    if endpoints:
        for r in records:
            ip = r.get('head_ip')
            # A stopped cluster's handle keeps its last IPs — showing
            # them as live endpoints would point at released addresses.
            if not ip or r['status'] != 'UP':
                click.echo(f'{r["name"]}: (no endpoint — '
                           f'status {r["status"]})')
                continue
            ports = r.get('ports') or []
            if ports:
                for p in ports:
                    if '-' in str(p):
                        click.echo(f'{r["name"]}: {ip} ports {p}')
                    else:
                        click.echo(f'{r["name"]}: http://{ip}:{p}')
            else:
                click.echo(f'{r["name"]}: {ip} (no ports opened)')
        return
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('NAME', 'LAUNCHED', 'RESOURCES', 'STATUS', 'AUTOSTOP'):
        table.add_column(col)
    for r in records:
        launched = datetime.datetime.fromtimestamp(
            r['launched_at']).strftime('%Y-%m-%d %H:%M')
        autostop = (f'{r["autostop"]}m'
                    f'{" (down)" if r["autostop_down"] else ""}'
                    if r['autostop'] is not None and r['autostop'] >= 0
                    else '-')
        table.add_row(r['name'], launched, r['resources_str'] or '-',
                      r['status'], autostop)
    Console().print(table)


@cli.command()
@click.argument('clusters', nargs=-1)
@click.option('--all', '-a', 'all_clusters', is_flag=True, default=False,
              help='Start every STOPPED cluster.')
@click.option('--yes', '-y', is_flag=True, default=False)
def start(clusters, all_clusters, yes) -> None:
    """Restart stopped cluster(s)."""
    clusters = _resolve_cluster_args(clusters, all_clusters, 'start',
                                     status_filter='STOPPED')
    if all_clusters and not yes:
        click.confirm(f'Start {", ".join(clusters)}?', abort=True)
    for c in clusters:
        sdk.stream_and_get(sdk.start(c))
        click.echo(f'Cluster {c} started.')


def _resolve_cluster_args(clusters, all_clusters: bool, verb: str,
                          status_filter: Optional[str] = None
                          ) -> List[str]:
    if all_clusters:
        records = sdk.get(sdk.status())
        names = [r['name'] for r in records
                 if status_filter is None or r['status'] == status_filter]
        if not names:
            noun = (f'{status_filter} clusters'.lower()
                    if status_filter else 'existing clusters')
            click.echo(f'No {noun}.')
            sys.exit(0)
        return names
    if not clusters:
        raise click.UsageError(f'specify cluster name(s) or --all to '
                               f'{verb} every cluster')
    return list(clusters)


@cli.command()
@click.argument('clusters', nargs=-1)
@click.option('--all', '-a', 'all_clusters', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def stop(clusters, all_clusters, yes) -> None:
    """Stop cluster(s) (keep disks)."""
    clusters = _resolve_cluster_args(clusters, all_clusters, 'stop')
    if not yes:
        click.confirm(f'Stop {", ".join(clusters)}?', abort=True)
    for c in clusters:
        sdk.stream_and_get(sdk.stop(c))
        click.echo(f'Cluster {c} stopped.')


@cli.command()
@click.argument('clusters', nargs=-1)
@click.option('--all', '-a', 'all_clusters', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
@click.option('--purge', is_flag=True, default=False,
              help='Remove from state even if cloud cleanup fails.')
def down(clusters, all_clusters, yes, purge) -> None:
    """Terminate cluster(s)."""
    clusters = _resolve_cluster_args(clusters, all_clusters, 'terminate')
    if not yes:
        click.confirm(f'Terminate {", ".join(clusters)}?', abort=True)
    for c in clusters:
        sdk.stream_and_get(sdk.down(c, purge=purge))
        click.echo(f'Cluster {c} terminated.')


@cli.command()
@click.argument('cluster')
@click.option('--idle-minutes', '-i', type=int, required=True,
              help='-1 cancels autostop.')
@click.option('--down', is_flag=True, default=False)
def autostop(cluster, idle_minutes, down) -> None:
    """Set autostop/autodown on a cluster."""
    sdk.get(sdk.autostop(cluster, idle_minutes, down))
    click.echo(f'Autostop set on {cluster}: {idle_minutes}m '
               f'({"down" if down else "stop"}).')


# ---------------------------------------------------------------------------
# jobs on clusters
# ---------------------------------------------------------------------------
@cli.command()
@click.argument('cluster')
@click.option('--all-jobs', '-a', is_flag=True, default=False)
def queue(cluster, all_jobs) -> None:
    """Show a cluster's job queue."""
    jobs = sdk.get(sdk.queue(cluster, all_jobs))
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('ID', 'NAME', 'USER', 'SUBMITTED', 'STATUS'):
        table.add_column(col)
    for j in jobs:
        ts = datetime.datetime.fromtimestamp(
            j['submitted_at']).strftime('%H:%M:%S')
        table.add_row(str(j['job_id']), j.get('job_name') or '-',
                      j.get('username') or '-', ts, j['status'])
    Console().print(table)


@cli.command()
@click.argument('cluster')
@click.argument('job_ids', nargs=-1, type=int)
@click.option('--all', 'all_jobs', is_flag=True, default=False)
def cancel(cluster, job_ids, all_jobs) -> None:
    """Cancel job(s) on a cluster."""
    if not job_ids and not all_jobs:
        _err('specify job ids or --all')
    sdk.get(sdk.cancel(cluster, list(job_ids) or None, all_jobs))
    click.echo('Cancelled.')


@cli.command()
@click.argument('cluster')
@click.argument('job_id', required=False, type=int)
@click.option('--no-follow', is_flag=True, default=False)
@click.option('--tail', type=int, default=0)
@click.option('--sync-down', is_flag=True, default=False,
              help='Download the log to ~/sky_logs_download/ instead '
                   'of streaming it.')
def logs(cluster, job_id, no_follow, tail, sync_down) -> None:
    """Tail a job's logs (or download them with --sync-down)."""
    try:
        if sync_down:
            dst_dir = os.path.expanduser(
                os.path.join('~/sky_logs_download', cluster))
            os.makedirs(dst_dir, exist_ok=True)
            dst = os.path.join(dst_dir, f'job-{job_id or "latest"}.log')
            with open(dst, 'w', encoding='utf-8') as f:
                sdk.tail_logs(cluster, job_id, follow=False, tail=0,
                              output=f)
            click.echo(f'Log synced to {dst}')
            return
        sdk.tail_logs(cluster, job_id, follow=not no_follow, tail=tail)
    except exceptions.ClusterDoesNotExist as e:
        _err(str(e))


@cli.command()
@click.argument('cluster')
@click.option('--node', type=int, default=0,
              help='Host index to attach to (0 = head).')
def attach(cluster, node) -> None:
    """Interactive shell on a cluster host via the API server's
    websocket PTY bridge (reference: the server-side SSH tunnel —
    no direct network path to the cluster needed)."""
    from skypilot_tpu.server import attach as attach_mod
    token = None
    auth = sdk._headers().get('Authorization', '')  # pylint: disable=protected-access
    if auth.startswith('Bearer '):
        token = auth[len('Bearer '):]
    raise SystemExit(attach_mod.run_client(
        sdk.api_server_url(), cluster, node=node, token=token))


# ---------------------------------------------------------------------------
# info
# ---------------------------------------------------------------------------
@cli.command()
@click.argument('targets', nargs=-1)
@click.option('--format', 'fmt', type=click.Choice(['text', 'json']),
              default='text', help='Static-analysis report format.')
@click.option('--select', default=None, metavar='RULES',
              help='Comma-separated rules to run, e.g. SKY001,SKY003.')
@click.option('--baseline', 'baseline_path', default=None,
              metavar='PATH',
              help='Baseline JSON (default: the committed '
                   'analysis/baseline.json).')
@click.option('--no-baseline', is_flag=True, default=False,
              help='Report baselined findings too.')
@click.option('--write-baseline', is_flag=True, default=False,
              help='Rewrite the baseline file to grandfather every '
                   'current finding (requires --justification).')
@click.option('--justification', default=None,
              help='One-line reason recorded on entries written by '
                   '--write-baseline.')
@click.option('--changed', is_flag=True, default=False,
              help='Analyze only files changed vs --base (fast '
                   'pre-commit iteration; uses `git diff '
                   '--name-only`).')
@click.option('--base', default='HEAD', metavar='REF',
              help='Git ref --changed diffs against (default HEAD: '
                   'uncommitted work).')
@click.option('--migrate-baseline', 'migrate_baseline', is_flag=True,
              default=False,
              help='One-shot: rewrite a v1 (line-keyed) baseline as '
                   'v2 (symbol-keyed), preserving justifications; '
                   'stale rows are dropped.')
def check(targets, fmt, select, baseline_path, no_baseline,
          write_baseline, justification, changed, base,
          migrate_baseline) -> None:
    """Static analysis (`stpu check skypilot_tpu/`) or cloud probe.

    With PATH arguments — or any of --select/--format/--baseline/
    --changed — runs the SKY static-analysis suite (async-safety,
    jit-purity, lock discipline, metric hygiene, exception hygiene,
    pallas-interpret reachability, span discipline, thread
    ownership, donation discipline, fault-point drift; see
    docs/internals.md) and exits
    non-zero on any non-baselined finding. With cloud-name arguments (or none), probes cloud
    credentials and caches enabled clouds (the original behavior).
    """
    static_flags = (fmt != 'text' or select or baseline_path or
                    no_baseline or write_baseline or changed or
                    migrate_baseline)
    path_args = any(os.path.exists(t) or t.endswith('.py') or
                    os.sep in t for t in targets)
    if not static_flags and not path_args:
        enabled = sdk.get(sdk.check())
        if targets:
            for c in targets:
                mark = 'enabled' if c.lower() in enabled else 'disabled'
                click.echo(f'{c.lower()}: {mark}')
            return
        click.echo(f'Enabled clouds: {", ".join(enabled) or "none"}')
        return

    from skypilot_tpu import analysis
    from skypilot_tpu.analysis import core as analysis_core
    paths = list(targets)
    if not paths:
        # Default target: the installed package tree.
        paths = [analysis_core._PKG_DIR]
    if changed:
        paths = _changed_python_files(paths, base)
        if not paths:
            click.echo(f'no changed .py files vs {base}')
            sys.exit(0)
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        _err(f'no such path(s): {", ".join(missing)}')
    try:
        rules = analysis.resolve_select(select)
    except ValueError as e:
        _err(str(e))
    timings: dict = {}
    findings = analysis.run_paths(paths, rules, timings)
    if write_baseline:
        if not justification:
            _err('--write-baseline requires --justification '
                 '(the baseline is for triaged false positives, '
                 'each with a reason)')
        out = baseline_path or analysis_core.DEFAULT_BASELINE
        analysis_core.Baseline.from_findings(
            findings, justification).save(out)
        click.echo(f'Wrote {len(findings)} entr'
                   f'{"y" if len(findings) == 1 else "ies"} to {out}')
        return
    baseline = analysis_core.Baseline.load(
        baseline_path or analysis_core.DEFAULT_BASELINE)
    if migrate_baseline:
        out = baseline_path or analysis_core.DEFAULT_BASELINE
        migrated = baseline.migrated(findings)
        dropped = len(baseline.entries) - len(migrated.entries)
        migrated.save(out)
        click.echo(f'Migrated {out} to v2: {len(migrated.entries)} '
                   f'symbol-keyed entr'
                   f'{"y" if len(migrated.entries) == 1 else "ies"}'
                   f'{f", {dropped} stale dropped" if dropped else ""}')
        return
    if no_baseline:
        new, baselined = list(findings), []
    else:
        new, baselined = baseline.split(findings)
    if fmt == 'json':
        click.echo(analysis.render_json(new, baselined, timings))
    else:
        click.echo(analysis.render_text(new, baselined))
    sys.exit(1 if new else 0)


def _changed_python_files(scope_paths, base: str):
    """`.py` files changed vs git ref `base`, intersected with the
    requested scope — `stpu check --changed` pre-commit mode."""
    import subprocess
    from skypilot_tpu.analysis import core as analysis_core
    try:
        out = subprocess.run(
            ['git', 'diff', '--name-only', base, '--'],
            capture_output=True, text=True, check=True,
            cwd=analysis_core.REPO_ROOT)
    except (OSError, subprocess.CalledProcessError) as e:
        detail = getattr(e, 'stderr', '') or str(e)
        _err(f'--changed: git diff --name-only {base} failed: '
             f'{detail.strip()}')
    scope = [os.path.abspath(p) for p in scope_paths]
    files = []
    for rel in out.stdout.splitlines():
        if not rel.endswith('.py'):
            continue
        path = os.path.join(analysis_core.REPO_ROOT, rel)
        if not os.path.exists(path):
            continue  # deleted in the diff
        abs_path = os.path.abspath(path)
        if any(abs_path == s or abs_path.startswith(s + os.sep)
               for s in scope):
            files.append(path)
    return files


@cli.command(name='gpus')
@click.argument('accelerator', required=False)
@click.option('--region', default=None)
def gpus(accelerator, region) -> None:
    """List TPU/GPU offerings and prices (`stpu gpus tpu-v5p`)."""
    result = sdk.get(sdk.list_accelerators(accelerator, region))
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('ACCELERATOR', 'REGION', '$/hr', '$/hr (spot)', 'HOSTS',
                'TOPOLOGY'):
        table.add_column(col)
    from skypilot_tpu.utils import tpu_utils
    for acc in sorted(result):
        infos = result[acc]
        regions_seen = set()
        for info in infos:
            if info['region'] in regions_seen:
                continue
            regions_seen.add(info['region'])
            hosts = topo = '-'
            if tpu_utils.is_tpu(acc):
                spec = tpu_utils.get_slice_spec(acc)
                hosts, topo = str(spec.num_hosts), spec.topology_str
            table.add_row(acc, info['region'], f"{info['price']:.2f}",
                          f"{info['spot_price']:.2f}", hosts, topo)
    Console().print(table)


@cli.command(name='cost-report')
def cost_report() -> None:
    """Show cost of terminated clusters."""
    rows = sdk.get(sdk.cost_report())
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('NAME', 'RESOURCES', 'DURATION', 'COST ($)'):
        table.add_column(col)
    for r in rows:
        mins = (r['duration'] or 0) / 60
        table.add_row(r['name'], r['resources_str'] or '-',
                      f'{mins:.0f}m', f"{r['cost'] or 0:.2f}")
    Console().print(table)


@cli.command(name='metrics')
@click.option('--url', default=None, metavar='URL',
              help='Scrape this URL instead of the API server '
                   '(e.g. an inference replica: '
                   'http://HOST:PORT/metrics).')
@click.option('--stats', is_flag=True, default=False,
              help='Fetch the JSON /stats snapshot from an inference '
                   'server instead of Prometheus text (requires '
                   '--url or defaults to the replica root of URL).')
def metrics_cmd(url: Optional[str], stats: bool) -> None:
    """One metrics scrape: the API server's /api/metrics by default,
    or any replica's /metrics (--url) / JSON /stats (--stats).
    Prometheus text goes to stdout — pipe into grep/promtool."""
    import json as _json

    import requests as _requests
    if stats:
        if not url:
            _err('--stats needs --url http://HOST:PORT '
                 '(an inference replica)')
            return
        base = url.rstrip('/')
        if base.endswith('/metrics'):
            base = base[:-len('/metrics')]
        if not base.endswith('/stats'):
            base = base + '/stats'
        resp = _requests.get(base, timeout=15)
        resp.raise_for_status()
        click.echo(_json.dumps(resp.json(), indent=2))
        return
    if url:
        resp = _requests.get(url, timeout=15)
        resp.raise_for_status()
        click.echo(resp.text, nl=False)
        return
    click.echo(sdk.api_metrics(), nl=False)


# ---------------------------------------------------------------------------
# storage group
# ---------------------------------------------------------------------------
@cli.group()
def storage() -> None:
    """Manage storage objects."""


@storage.command(name='ls')
def storage_ls() -> None:
    names = sdk.get(sdk.storage_ls())
    for n in names:
        click.echo(n)


@storage.command(name='transfer')
@click.argument('src')
@click.argument('dst')
@click.option('--size-gb', type=float, default=None,
              help='estimated size; large S3->GCS jobs use the '
                   'server-side Storage Transfer Service')
@click.option('--dryrun', is_flag=True, default=False,
              help='print the transfer plan without executing')
def storage_transfer(src, dst, size_gb, dryrun) -> None:
    """Move bucket contents across clouds (gs:// <-> s3://)."""
    from skypilot_tpu import sky_config
    from skypilot_tpu.data import transfer as transfer_lib
    plan = transfer_lib.transfer(
        src, dst, size_gigabytes=size_gb,
        project_id=sky_config.get_nested(('gcp', 'project_id')),
        run=not dryrun)
    click.echo(f'method: {plan["method"]}')
    if 'command' in plan:
        click.echo(plan['command'])


@storage.command(name='delete')
@click.argument('name')
@click.option('--yes', '-y', is_flag=True, default=False)
def storage_delete(name, yes) -> None:
    if not yes:
        click.confirm(f'Delete storage {name}?', abort=True)
    sdk.get(sdk.storage_delete(name))


# ---------------------------------------------------------------------------
# api group
# ---------------------------------------------------------------------------
@cli.group()
def api() -> None:
    """Manage the API server."""


@api.command(name='start')
@click.option('--host', default='127.0.0.1')
@click.option('--port', type=int, default=None)
@click.option('--foreground', is_flag=True, default=False)
def api_start(host, port, foreground) -> None:
    url = sdk.api_start(host=host, port=port, foreground=foreground)
    click.echo(f'API server running at {url}')


@api.command(name='stop')
def api_stop() -> None:
    if sdk.api_stop():
        click.echo('API server stopped.')
    else:
        click.echo('No local API server found.')


@api.command(name='info')
def api_info_cmd() -> None:
    info = sdk.api_info()
    if info is None:
        click.echo(f'API server at {sdk.api_server_url()}: unreachable')
    else:
        click.echo(f'API server at {sdk.api_server_url()}: {info}')


@api.command(name='status')
@click.option('--limit', type=int, default=30)
def api_status(limit) -> None:
    rows = sdk.api_status(limit)
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('REQUEST', 'NAME', 'USER', 'STATUS'):
        table.add_column(col)
    for r in rows:
        table.add_row(r['request_id'], r['name'], r.get('user') or '-',
                      r['status'])
    Console().print(table)


@api.command(name='logs')
@click.argument('request_id')
def api_logs(request_id) -> None:
    try:
        sdk.stream_and_get(request_id)
    except exceptions.SkyError as e:
        _err(str(e))


@api.command(name='cancel')
@click.argument('request_id')
def api_cancel(request_id) -> None:
    if sdk.api_cancel(request_id):
        click.echo('Cancelled.')
    else:
        click.echo('Request already finished.')




# ---------------------------------------------------------------------------
# jobs group (managed jobs)
# ---------------------------------------------------------------------------
@cli.group()
def jobs() -> None:
    """Managed jobs: auto-recovering jobs on (preemptible) clusters."""


@jobs.command(name='launch')
@click.argument('entrypoint', required=False)
@_add_options(_task_options)
@click.option('--pool', default=None,
              help='Run on a pre-provisioned worker pool.')
@click.option('--detach-run', '-d', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_launch_cmd(entrypoint, name, workdir, infra, gpus, cpus, memory,
                    num_nodes, use_spot, env, env_file, pool, detach_run,
                    yes) -> None:
    """Launch a managed job (survives preemption via auto-recovery).

    A YAML with multiple documents is a PIPELINE: stages run
    sequentially, one cluster each, with per-stage recovery."""
    stages = None
    if entrypoint and (entrypoint.endswith(('.yaml', '.yml')) and
                       os.path.exists(os.path.expanduser(entrypoint))):
        docs = [c for c in common_utils.read_yaml_all(
            os.path.expanduser(entrypoint)) if c]
        if len(docs) > 1:
            # Per-stage resources come from the YAML; resource flags
            # would be ambiguous (which stage?) — reject instead of
            # silently ignoring them. --env applies to every stage.
            if any(v for v in (workdir, infra, gpus, cpus, memory,
                               num_nodes)) or use_spot is not None:
                raise click.UsageError(
                    'Pipelines take per-stage resources from the YAML; '
                    '--workdir/--infra/--gpus/--cpus/--memory/'
                    '--num-nodes/--use-spot do not apply.')
            env_overrides = _merged_env(env, env_file)
            from skypilot_tpu import task as task_lib
            stages = [task_lib.Task.from_yaml_config(d, env_overrides)
                      for d in docs]
    if stages is not None:
        if not yes:
            click.confirm(
                f'Launch {len(stages)}-stage pipeline '
                f'({", ".join(t.name or "?" for t in stages)})?',
                default=True, abort=True)
        result = sdk.get(sdk.jobs_launch(
            stages, name=name or stages[0].name, pool=pool))
        job_id = result['job_id']
        click.echo(f'Managed pipeline {job_id} submitted '
                   f'({len(stages)} stages).')
        if not detach_run:
            sdk.jobs_logs(job_id)
        return
    task = _build_task(entrypoint, name, workdir, infra, gpus, cpus, memory,
                       num_nodes, use_spot, env, env_file=env_file)
    if not yes:
        click.confirm(f'Launch managed job {task.name or "task"}?',
                      default=True, abort=True)
    result = sdk.get(sdk.jobs_launch(task, name=task.name, pool=pool))
    job_id = result['job_id']
    click.echo(f'Managed job {job_id} submitted.')
    if not detach_run:
        sdk.jobs_logs(job_id)


@jobs.group(name='pool')
def jobs_pool() -> None:
    """Worker pools that managed jobs reuse (skip provisioning)."""


@jobs_pool.command(name='apply')
@click.argument('entrypoint', required=False)
@click.option('--pool-name', '-p', 'pool_name', required=True)
@click.option('--workers', type=int, default=1)
@_add_options(_task_options)
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_pool_apply_cmd(entrypoint, pool_name, workers, name, workdir,
                        infra, gpus, cpus, memory, num_nodes, use_spot,
                        env, env_file, yes) -> None:
    """Provision a pool of worker clusters from a resources template."""
    task = _build_task(entrypoint, name, workdir, infra, gpus, cpus, memory,
                       num_nodes, use_spot, env, cmd='true',
                       env_file=env_file)
    task.run = None
    if not yes:
        click.confirm(f'Provision pool {pool_name} ({workers} workers)?',
                      default=True, abort=True)
    result = sdk.stream_and_get(sdk.jobs_pool_apply(task, pool_name,
                                                    workers))
    click.echo(f'Pool {pool_name} ready: {result["workers"]}')


@jobs_pool.command(name='ls')
def jobs_pool_ls_cmd() -> None:
    rows = sdk.get(sdk.jobs_pool_ls())
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('NAME', 'WORKERS', 'BUSY'):
        table.add_column(col)
    for r in rows:
        table.add_row(r['name'], str(r['num_workers']),
                      str(r['busy_workers']))
    Console().print(table)


@jobs_pool.command(name='down')
@click.argument('pool_name')
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_pool_down_cmd(pool_name, yes) -> None:
    if not yes:
        click.confirm(f'Tear down pool {pool_name}?', abort=True)
    sdk.stream_and_get(sdk.jobs_pool_down(pool_name))
    click.echo(f'Pool {pool_name} torn down.')


@jobs_pool.command(name='status')
@click.argument('pool_name')
def jobs_pool_status_cmd(pool_name) -> None:
    """Per-worker view: cluster status + the job each worker runs."""
    rows = sdk.get(sdk.jobs_pool_status(pool_name))
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('WORKER', 'STATUS', 'JOB'):
        table.add_column(col)
    for r in rows:
        table.add_row(r['worker'], r['status'],
                      str(r['job_id']) if r['job_id'] is not None else '-')
    Console().print(table)


@jobs.group(name='group')
def jobs_group() -> None:
    """Co-scheduled job groups (RL actor/learner, disaggregated serve)."""


@jobs_group.command(name='launch')
@click.argument('yaml_files', nargs=-1, required=True)
@click.option('--group-name', '-n', 'group_name', required=True)
def jobs_group_launch_cmd(yaml_files, group_name) -> None:
    """Launch one managed job per YAML, atomically, with each task's
    env carrying every peer's head address."""
    from skypilot_tpu import task as task_lib
    tasks = [task_lib.Task.from_yaml(f) for f in yaml_files]
    result = sdk.get(sdk.jobs_group_launch(tasks, group_name))
    click.echo(f'Group {group_name}: jobs {result["job_ids"]} submitted.')


@jobs_group.command(name='status')
@click.argument('group_name')
def jobs_group_status_cmd(group_name) -> None:
    rows = sdk.get(sdk.jobs_group_status(group_name))
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('ID', 'NAME', 'CLUSTER', 'ADDR', 'STATUS'):
        table.add_column(col)
    for r in rows:
        table.add_row(str(r['job_id']), r['name'] or '-',
                      r['cluster_name'] or '-', r['head_ip'] or '-',
                      r['status'])
    Console().print(table)


@jobs_group.command(name='cancel')
@click.argument('group_name')
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_group_cancel_cmd(group_name, yes) -> None:
    if not yes:
        click.confirm(f'Cancel all jobs in group {group_name}?', abort=True)
    cancelled = sdk.get(sdk.jobs_group_cancel(group_name))
    click.echo(f'Cancelled jobs: {cancelled}')


@jobs.command(name='queue')
@click.option('--refresh', '-r', is_flag=True, default=False)
@click.option('--skip-finished', '-s', is_flag=True, default=False)
def jobs_queue_cmd(refresh, skip_finished) -> None:
    """Show managed jobs."""
    rows = sdk.get(sdk.jobs_queue(refresh, skip_finished))
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('ID', 'NAME', 'CLUSTER', 'STAGE', 'STATUS', 'RECOVERIES',
                'ERROR'):
        table.add_column(col)
    for j in rows:
        table.add_row(str(j['job_id']), j.get('name') or '-',
                      j.get('cluster_name') or '-',
                      j.get('stage') or '-', j['status'],
                      str(j['recovery_count']),
                      (j.get('last_error') or '')[:40])
    Console().print(table)


@jobs.command(name='cancel')
@click.argument('job_ids', nargs=-1, type=int)
@click.option('--all', 'all_jobs', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_cancel_cmd(job_ids, all_jobs, yes) -> None:
    """Cancel managed job(s)."""
    if not job_ids and not all_jobs:
        _err('specify job ids or --all')
    if not yes:
        click.confirm('Cancel?', abort=True)
    cancelled = sdk.get(sdk.jobs_cancel(list(job_ids) or None, all_jobs))
    click.echo(f'Cancelled: {cancelled}')


@jobs.command(name='logs')
@click.argument('job_id', type=int)
@click.option('--no-follow', is_flag=True, default=False)
def jobs_logs_cmd(job_id, no_follow) -> None:
    """Stream a managed job's controller log."""
    sdk.jobs_logs(job_id, follow=not no_follow)


# ---------------------------------------------------------------------------
# serve group
# ---------------------------------------------------------------------------
@cli.group()
def serve() -> None:
    """Serving: replicated services with load balancing + autoscaling."""


@serve.command(name='up')
@click.argument('entrypoint')
@click.option('--service-name', '-s', default=None)
@_add_options(_task_options)
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_up_cmd(entrypoint, service_name, name, workdir, infra, gpus, cpus,
                 memory, num_nodes, use_spot, env, env_file, yes) -> None:
    """Bring up a service from a task YAML with a service: section."""
    task = _build_task(entrypoint, name, workdir, infra, gpus, cpus, memory,
                       num_nodes, use_spot, env, env_file=env_file)
    service_name = service_name or task.name or 'service'
    if not yes:
        click.confirm(f'Bring up service {service_name}?', default=True,
                      abort=True)
    result = sdk.get(sdk.serve_up(task, service_name))
    click.echo(f'Service {service_name} starting; endpoint: '
               f'{result["endpoint"]}')


@serve.command(name='status')
@click.argument('services', nargs=-1)
def serve_status_cmd(services) -> None:
    """Show services and their replicas."""
    rows = sdk.get(sdk.serve_status(list(services) or None))
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('NAME', 'VERSION', 'STATUS', 'ENDPOINT', 'REPLICAS'):
        table.add_column(col)
    for s in rows:
        ready = sum(1 for r in s['replicas'] if r['status'] == 'READY')
        table.add_row(s['name'], str(s['version']), s['status'],
                      s['endpoint'] or '-',
                      f"{ready}/{len(s['replicas'])}")
    Console().print(table)
    for s in rows:
        if s['replicas']:
            rep_table = Table(box=None, title=f"{s['name']} replicas")
            for col in ('ID', 'STATUS', 'ENDPOINT', 'CLUSTER'):
                rep_table.add_column(col)
            for r in s['replicas']:
                rep_table.add_row(str(r['replica_id']), r['status'],
                                  r.get('endpoint') or '-',
                                  r['cluster_name'])
            Console().print(rep_table)


@serve.command(name='update')
@click.argument('service_name')
@click.argument('entrypoint')
@_add_options(_task_options)
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_update_cmd(service_name, entrypoint, name, workdir, infra, gpus,
                     cpus, memory, num_nodes, use_spot, env, env_file,
                     yes) -> None:
    """Update a service to a new task version."""
    task = _build_task(entrypoint, name, workdir, infra, gpus, cpus, memory,
                       num_nodes, use_spot, env, env_file=env_file)
    if not yes:
        click.confirm(f'Update service {service_name}?', abort=True)
    result = sdk.get(sdk.serve_update(task, service_name))
    click.echo(f'Service {service_name} updated to v{result["version"]}.')


@serve.command(name='logs')
@click.argument('service_name')
@click.option('--no-follow', is_flag=True, default=False)
@click.option('--replica', type=int, default=None,
              help='Stream this replica\'s job log instead of the '
                   'controller log.')
def serve_logs_cmd(service_name, no_follow, replica) -> None:
    """Stream a service's controller log (or one replica's job log)."""
    if replica is not None:
        rows = sdk.get(sdk.serve_status([service_name]))
        if not rows:
            _err(f'service {service_name!r} not found')
        match = [r for r in rows[0]['replicas']
                 if r['replica_id'] == replica]
        if not match:
            known = sorted(r['replica_id'] for r in rows[0]['replicas'])
            _err(f'no replica {replica} (known: {known})')
        try:
            sdk.tail_logs(match[0]['cluster_name'], None,
                          follow=not no_follow)
        except exceptions.ClusterDoesNotExist:
            _err(f'replica {replica} has no live cluster '
                 f'({match[0]["status"]})')
        return
    sdk.serve_logs(service_name, follow=not no_follow,
                   output=sys.stdout)


@serve.command(name='down')
@click.argument('service_names', nargs=-1)
@click.option('--all', '-a', 'all_services', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
@click.option('--purge', is_flag=True, default=False)
def serve_down_cmd(service_names, all_services, yes, purge) -> None:
    """Tear down service(s)."""
    if all_services:
        service_names = [s['name'] for s in sdk.get(sdk.serve_status())]
        if not service_names:
            click.echo('No services.')
            return
    if not service_names:
        raise click.UsageError('specify service name(s) or --all')
    if not yes:
        click.confirm(f'Tear down {", ".join(service_names)}?', abort=True)
    for s in service_names:
        sdk.get(sdk.serve_down(s, purge=purge))
        click.echo(f'Service {s} torn down.')


@serve.command(name='sync-down-logs')
@click.argument('service_name')
def serve_sync_down_logs_cmd(service_name) -> None:
    """Download a service's controller log to ~/sky_logs_download/."""
    dst_dir = os.path.expanduser(
        os.path.join('~/sky_logs_download', 'serve'))
    os.makedirs(dst_dir, exist_ok=True)
    dst = os.path.join(dst_dir, f'{service_name}.log')
    with open(dst, 'w', encoding='utf-8') as f:
        sdk.serve_logs(service_name, follow=False, output=f)
    click.echo(f'Log synced to {dst}')


# ---------------------------------------------------------------------------
# recipes / volumes / debug
# ---------------------------------------------------------------------------
@cli.group()
def recipes() -> None:
    """Curated runnable recipes (bundled example YAMLs)."""


@recipes.command(name='list')
def recipes_list() -> None:
    from skypilot_tpu.recipes import core as recipes_core
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('NAME', 'ACCELERATOR', 'DESCRIPTION'):
        table.add_column(col)
    for r in recipes_core.list_recipes():
        table.add_row(r['name'], r['accelerator'], r['description'][:70])
    Console().print(table)


@recipes.command(name='show')
@click.argument('name')
def recipes_show(name) -> None:
    from skypilot_tpu.recipes import core as recipes_core
    try:
        path = recipes_core.get_recipe_path(name)
    except FileNotFoundError as e:
        _err(str(e))
    with open(path, 'r', encoding='utf-8') as f:
        click.echo(f.read())


@cli.group()
def volumes() -> None:
    """Persistent volumes."""


@volumes.command(name='apply')
@click.argument('name')
@click.option('--size', type=int, required=True, help='Size in GB.')
@click.option('--infra', default=None)
@click.option('--type', 'volume_type', default='pd-balanced')
def volumes_apply(name, size, infra, volume_type) -> None:
    from skypilot_tpu.volumes import core as volumes_core
    cfg = volumes_core.apply(name, size, infra, volume_type)
    click.echo(f'Volume {name} ({cfg["size_gb"]}GB {cfg["type"]}) ready.')


@volumes.command(name='ls')
def volumes_ls() -> None:
    from skypilot_tpu.volumes import core as volumes_core
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('NAME', 'SIZE', 'TYPE', 'INFRA', 'STATUS'):
        table.add_column(col)
    for v in volumes_core.ls():
        table.add_row(v['name'], f"{v['size_gb']}GB", v['type'],
                      v['infra'], v['status'])
    Console().print(table)


@volumes.command(name='delete')
@click.argument('name')
@click.option('--yes', '-y', is_flag=True, default=False)
def volumes_delete(name, yes) -> None:
    if not yes:
        click.confirm(f'Delete volume {name}?', abort=True)
    from skypilot_tpu.volumes import core as volumes_core
    volumes_core.delete(name)
    click.echo(f'Volume {name} deleted.')


@cli.command(name='debug-dump')
@click.option('--output', '-o', default='skypilot-debug.tar.gz')
def debug_dump(output) -> None:
    """Bundle local state + logs for a bug report (secrets redacted:
    the state DBs carry no credential material)."""
    import tarfile
    from skypilot_tpu import constants as const
    home = const.sky_home()
    if not os.path.isdir(home):
        _err(f'No state at {home}.')
    with tarfile.open(output, 'w:gz') as tar:
        for sub in ('state.db', 'managed_jobs.db', 'serve.db',
                    'api_server/requests.db', 'api_server/server.log',
                    'managed_jobs_logs', 'serve_logs', 'usage'):
            path = os.path.join(home, sub)
            if os.path.exists(path):
                tar.add(path, arcname=sub)
    click.echo(f'Wrote {output}.')


@cli.command(name='trace')
@click.argument('trace_id')
@click.option('--endpoint', '-e', 'endpoints', multiple=True,
              required=True, metavar='HOST:PORT',
              help='A serving process to query (repeat for each: '
                   'the LB, the prefill replica, the decode peer). '
                   'Each answers GET /debug/trace/<id> with its own '
                   'spans of the trace.')
@click.option('--output', '-o', default=None, metavar='FILE',
              help='Write the merged Chrome-trace JSON here '
                   '(default: stdout).')
@click.option('--timeout', type=float, default=5.0,
              help='Per-endpoint HTTP timeout, seconds.')
def trace_cmd(trace_id, endpoints, output, timeout) -> None:
    """Merge one request's spans across serving processes.

    A request traced at --trace-sample crosses up to three processes
    (LB route -> prefill replica -> decode peer), each recording its
    own spans under the shared trace id from the x-skypilot-trace
    header. This fetches every process's view, de-duplicates, sorts
    by wall clock, and emits ONE Chrome-trace JSON — load it in
    chrome://tracing or ui.perfetto.dev (`pid` rows = processes).
    """
    import json as json_lib

    import requests as requests_lib

    from skypilot_tpu.observability import tracing
    bodies = []
    misses = []
    for ep in endpoints:
        base = ep if '://' in ep else f'http://{ep}'
        url = f'{base.rstrip("/")}/debug/trace/{trace_id}'
        try:
            resp = requests_lib.get(url, timeout=timeout)
        except requests_lib.RequestException as e:
            misses.append(f'{ep}: {type(e).__name__}')
            continue
        if resp.status_code == 200:
            bodies.append(resp.json())
        else:
            # 404 is normal: a process the trace never crossed.
            misses.append(f'{ep}: HTTP {resp.status_code}')
    if not bodies:
        _err(f'trace {trace_id} not found on any endpoint'
             f'{" (" + "; ".join(misses) + ")" if misses else ""}')
    merged = tracing.merge_traces(bodies)
    text = json_lib.dumps(merged, indent=2)
    n = len(merged['traceEvents'])
    if output:
        with open(output, 'w', encoding='utf-8') as f:
            f.write(text)
        click.echo(f'Wrote {n} spans from {len(bodies)}/'
                   f'{len(endpoints)} endpoints to {output}.')
    else:
        click.echo(text)
    if misses:
        click.secho('; '.join(misses), fg='yellow', err=True)


@cli.group()
def batch() -> None:
    """Batch: map a task over dataset shards on a worker pool."""


@batch.command(name='launch')
@click.argument('entrypoint')
@click.option('--batch-name', '-b', 'batch_name', required=True)
@click.option('--input', 'input_path', required=True,
              help='JSONL input file.')
@click.option('--output-dir', required=True)
@click.option('--workers', type=int, default=2)
@click.option('--shards', type=int, default=None)
@_add_options(_task_options)
@click.option('--yes', '-y', is_flag=True, default=False)
def batch_launch_cmd(entrypoint, batch_name, input_path, output_dir,
                     workers, shards, name, workdir, infra, gpus, cpus,
                     memory, num_nodes, use_spot, env, env_file,
                     yes) -> None:
    """Launch a batch job over a JSONL dataset."""
    task = _build_task(entrypoint, name, workdir, infra, gpus, cpus, memory,
                       num_nodes, use_spot, env, env_file=env_file)
    if not yes:
        click.confirm(f'Launch batch {batch_name} ({workers} workers)?',
                      default=True, abort=True)
    result = sdk.get(sdk.batch_launch(task, batch_name, input_path,
                                      output_dir, workers, shards))
    click.echo(f'Batch {batch_name}: {result["num_shards"]} shards on '
               f'{result["num_workers"]} workers.')


@batch.command(name='ls')
def batch_ls_cmd() -> None:
    rows = sdk.get(sdk.batch_ls())
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('NAME', 'STATUS', 'SHARDS', 'FAILED', 'WORKERS'):
        table.add_column(col)
    for r in rows:
        table.add_row(r['name'], r['status'],
                      f"{r['shards_done']}/{r['num_shards']}",
                      str(r['shards_failed']), str(r['num_workers']))
    Console().print(table)


@batch.command(name='cancel')
@click.argument('batch_name')
@click.option('--yes', '-y', is_flag=True, default=False)
def batch_cancel_cmd(batch_name, yes) -> None:
    if not yes:
        click.confirm(f'Cancel batch {batch_name}?', abort=True)
    if sdk.get(sdk.batch_cancel(batch_name)):
        click.echo('Cancelled.')
    else:
        click.echo('Already finished or not found.')




@cli.group(name='users', invoke_without_command=True)
@click.pass_context
def users_cmd(ctx) -> None:
    """Users, roles, and service-account tokens (admin)."""
    if ctx.invoked_subcommand is not None:
        return
    rows = sdk.users_ls()
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('USER', 'ROLE', 'REQUESTS', 'LAST SEEN'):
        table.add_column(col)
    for r in rows:
        last = datetime.datetime.fromtimestamp(
            r['last_seen']).strftime('%m-%d %H:%M') if r['last_seen'] else '-'
        table.add_row(r['name'], r.get('role') or 'user',
                      str(r['request_count']), last)
    Console().print(table)


@users_cmd.command(name='role')
@click.argument('user')
@click.argument('role', type=click.Choice(['admin', 'user']))
def users_role_cmd(user: str, role: str) -> None:
    """Set USER's role (admin only)."""
    sdk.users_set_role(user, role)
    click.echo(f'{user}: role={role}')


@users_cmd.group(name='token')
def users_token_cmd() -> None:
    """Service-account tokens: server-derived identity for the API."""


@users_token_cmd.command(name='issue')
@click.argument('user')
@click.option('--role', default='user',
              type=click.Choice(['admin', 'user']))
def token_issue_cmd(user: str, role: str) -> None:
    """Mint a token for USER; the cleartext is printed ONCE."""
    out = sdk.token_issue(user, role)
    click.echo(f'token_id: {out["token_id"]}')
    click.echo(f'token:    {out["token"]}')
    click.echo('Store it now — it is not retrievable later. Clients '
               'present it via SKYPILOT_API_TOKEN or '
               'api_server.auth_token in config.')


@users_token_cmd.command(name='ls')
def token_ls_cmd() -> None:
    """List issued tokens (hashes only)."""
    from rich.console import Console
    from rich.table import Table
    table = Table(box=None)
    for col in ('TOKEN ID', 'USER', 'CREATED', 'LAST USED', 'REVOKED'):
        table.add_column(col)
    for t in sdk.token_ls():
        created = datetime.datetime.fromtimestamp(
            t['created_at']).strftime('%m-%d %H:%M')
        last = (datetime.datetime.fromtimestamp(
            t['last_used_at']).strftime('%m-%d %H:%M')
            if t['last_used_at'] else '-')
        table.add_row(t['token_id'], t['user_hash'], created, last,
                      'yes' if t['revoked'] else '')
    Console().print(table)


@users_token_cmd.command(name='revoke')
@click.argument('token_id')
def token_revoke_cmd(token_id: str) -> None:
    """Revoke a token by its id."""
    if sdk.token_revoke(token_id):
        click.echo('Revoked.')
    else:
        click.echo('No such token.', err=True)


# ---------------------------------------------------------------------------
# config / workspaces / ssh-node-pool / dashboard
# ---------------------------------------------------------------------------
@cli.group()
def config() -> None:
    """View and edit the layered config (server < user < project)."""


@config.command(name='list')
def config_list() -> None:
    """Dump the effective merged config as YAML."""
    import yaml as yaml_lib
    from skypilot_tpu import sky_config
    merged = sky_config.to_dict()
    if not merged:
        click.echo('# (empty config)')
        return
    click.echo(yaml_lib.safe_dump(merged, default_flow_style=False,
                                  sort_keys=False).rstrip())


@config.command(name='get')
@click.argument('key')
def config_get(key) -> None:
    """Read a dotted key, e.g. `stpu config get gcp.project_id`."""
    import yaml as yaml_lib
    from skypilot_tpu import sky_config
    sentinel = object()
    value = sky_config.get_nested(tuple(key.split('.')), sentinel)
    if value is sentinel:
        _err(f'{key}: not set')
    if isinstance(value, (dict, list)):
        click.echo(yaml_lib.safe_dump(value, default_flow_style=False,
                                      sort_keys=False).rstrip())
    else:
        click.echo(value)


@config.command(name='set')
@click.argument('key')
@click.argument('value')
def config_set(key, value) -> None:
    """Set a dotted key in the user config file (YAML-parsed value)."""
    import yaml as yaml_lib
    from skypilot_tpu import sky_config
    try:
        parsed = yaml_lib.safe_load(value)
    except yaml_lib.YAMLError:
        parsed = value
    try:
        path = sky_config.set_nested(tuple(key.split('.')), parsed)
    except Exception as e:  # pylint: disable=broad-except
        _err(f'rejected: {e}')
    click.echo(f'{key} = {parsed!r}  ({path})')


@config.command(name='unset')
@click.argument('key')
def config_unset(key) -> None:
    """Remove a dotted key from the user config file."""
    from skypilot_tpu import sky_config
    path = sky_config.set_nested(tuple(key.split('.')), None)
    click.echo(f'{key} removed  ({path})')


@cli.group()
def workspaces() -> None:
    """Multi-tenant namespaces with per-workspace cloud allow-lists."""


@workspaces.command(name='ls')
def workspaces_ls() -> None:
    from skypilot_tpu.workspaces import core as ws_core
    from rich.console import Console
    from rich.table import Table
    active = ws_core.active_workspace()
    table = Table(box=None)
    for col in ('NAME', 'ACTIVE', 'ALLOWED CLOUDS'):
        table.add_column(col)
    for name, ws in sorted(ws_core.get_workspaces().items()):
        allowed = (ws or {}).get('allowed_clouds')
        table.add_row(name, '*' if name == active else '',
                      ', '.join(allowed) if allowed else '(all)')
    Console().print(table)


@workspaces.command(name='show')
@click.argument('name', required=False)
def workspaces_show(name) -> None:
    import yaml as yaml_lib
    from skypilot_tpu.workspaces import core as ws_core
    try:
        ws = ws_core.get_workspace(name)
    except exceptions.SkyError as e:
        _err(str(e))
    click.echo(yaml_lib.safe_dump(
        {name or ws_core.active_workspace(): ws or {}},
        default_flow_style=False).rstrip())


@workspaces.command(name='switch')
@click.argument('name')
def workspaces_switch(name) -> None:
    """Make NAME the active workspace (persisted in user config)."""
    from skypilot_tpu import sky_config
    from skypilot_tpu.workspaces import core as ws_core
    try:
        ws_core.get_workspace(name)
    except exceptions.SkyError as e:
        _err(str(e))
    sky_config.set_nested(('active_workspace',), name)
    click.echo(f'Active workspace: {name}')


@cli.group(name='ssh-node-pool')
def ssh_node_pool() -> None:
    """Bring-your-own machines declared in ssh_node_pools.yaml."""


@ssh_node_pool.command(name='ls')
def ssh_node_pool_ls() -> None:
    from skypilot_tpu.clouds import ssh as ssh_cloud
    from rich.console import Console
    from rich.table import Table
    pools = ssh_cloud.load_pools()
    if not pools:
        click.echo(f'No pools declared ({ssh_cloud.POOLS_PATH}).')
        return
    table = Table(box=None)
    for col in ('POOL', 'HOSTS', 'USER', 'IDENTITY'):
        table.add_column(col)
    for name, pool in sorted(pools.items()):
        hosts = pool.get('hosts', [])
        users = {h.get('user') for h in hosts}
        keys = {h.get('identity_file') for h in hosts}
        table.add_row(
            name, str(len(hosts)),
            users.pop() if len(users) == 1 else '(mixed)',
            keys.pop() if len(keys) == 1 else '(mixed)')
    Console().print(table)


@ssh_node_pool.command(name='check')
@click.argument('pool', required=False)
@click.option('--timeout', type=float, default=10.0)
def ssh_node_pool_check(pool, timeout) -> None:
    """SSH-probe every host of a pool (`true` over the declared auth)."""
    from skypilot_tpu.clouds import ssh as ssh_cloud
    from skypilot_tpu.utils import command_runner
    from skypilot_tpu.utils import subprocess_utils
    pools = ssh_cloud.load_pools()
    if pool is not None:
        if pool not in pools:
            _err(f'pool {pool!r} not declared; known: '
                 + ', '.join(sorted(pools)))
        pools = {pool: pools[pool]}

    def _probe(host):
        runner = command_runner.SSHCommandRunner(
            (host['ip'], host.get('port', 22)), host.get('user', 'root'),
            host.get('identity_file', '~/.ssh/id_ed25519'))
        rc, _, err = runner.run('true', stream_logs=False,
                                require_outputs=True, timeout=timeout)
        return rc, (err or '').strip()

    for name, p in sorted(pools.items()):
        hosts = p.get('hosts', [])
        results = subprocess_utils.run_in_parallel(_probe, hosts)
        for host, (rc, err) in zip(hosts, results):
            ok = 'OK' if rc == 0 else f'FAIL ({err[:60]})'
            click.echo(f'{name}\t{host["ip"]}\t{ok}')


@cli.command()
@click.option('--no-open', is_flag=True, default=False,
              help='Print the URL instead of opening a browser.')
def dashboard(no_open) -> None:
    """Open the live web dashboard served by the API server."""
    url = sdk.api_server_url().rstrip('/') + '/dashboard'
    click.echo(url)
    if not no_open:
        import webbrowser
        webbrowser.open(url)


@api.command(name='login')
@click.option('--endpoint', '-e', default=None,
              help='API server URL, e.g. http://host:46580')
@click.option('--token', default=None,
              help='Service-account token (or set SKYPILOT_API_TOKEN).')
@click.option('--oauth', 'use_oauth', is_flag=True, default=False,
              help='Browser OIDC login (needs oauth.issuer/client_id).')
@click.option('--issuer', default=None, help='Override oauth.issuer.')
@click.option('--client-id', default=None,
              help='Override oauth.client_id.')
@click.option('--no-browser', is_flag=True, default=False,
              help='Print the authorize URL instead of opening it.')
def api_login(endpoint, token, use_oauth, issuer, client_id,
              no_browser) -> None:
    """Point this client at a remote API server (persisted in config)."""
    from skypilot_tpu import sky_config
    if not endpoint and not use_oauth:
        raise click.UsageError('pass --endpoint and/or --oauth')
    if endpoint:
        endpoint = endpoint.rstrip('/')
        sky_config.set_nested(('api_server', 'endpoint'), endpoint)
    if token:
        sky_config.set_nested(('api_server', 'auth_token'), token)
    if use_oauth:
        import requests as _requests
        from skypilot_tpu.client import oauth as oauth_lib
        try:
            oauth_lib.login(issuer=issuer, client_id=client_id,
                            open_browser=not no_browser)
        except (exceptions.SkyError, _requests.RequestException) as e:
            _err(f'OAuth login failed: {e}')
        click.echo('OAuth login complete; token cached.')
    if endpoint:
        info = sdk.api_info(endpoint)
        if info is None:
            click.secho(f'Warning: {endpoint} is not reachable right now.',
                        fg='yellow', err=True)
        click.echo(f'Logged in to {endpoint}.')


@api.command(name='logout')
def api_logout() -> None:
    """Drop the cached OAuth token."""
    from skypilot_tpu.client import oauth as oauth_lib
    click.echo('Logged out.' if oauth_lib.logout()
               else 'No cached OAuth token.')


@recipes.command(name='launch')
@click.argument('name')
@click.option('--cluster', '-c', default=None)
@click.option('--env', multiple=True, help='KEY=VAL or KEY (inherit).')
@click.option('--yes', '-y', is_flag=True, default=False)
def recipes_launch(name, cluster, env, yes) -> None:
    """Launch a bundled recipe by name (see `stpu recipes list`)."""
    from skypilot_tpu.recipes import core as recipes_core
    try:
        path = recipes_core.get_recipe_path(name)
    except FileNotFoundError as e:
        _err(str(e))
    from skypilot_tpu import task as task_lib
    task = task_lib.Task.from_yaml_config(
        common_utils.read_yaml(path), _parse_env(list(env or [])))
    if not yes:
        r = sorted(str(x) for x in task.resources)
        click.confirm(f'Launch recipe {name} on {r}?', default=True,
                      abort=True)
    request_id = sdk.launch(task, cluster_name=cluster, detach_run=True)
    result = sdk.stream_and_get(request_id)
    if result and result.get('job_id') is not None:
        cname = (result.get('handle') or {}).get('cluster_name') or cluster
        sdk.tail_logs(cname, result['job_id'])


# ---------------------------------------------------------------------------
# long-tail commands (reference: sky local up/down, sky ssh up/down,
# shell completion install, jobs pool logs)
# ---------------------------------------------------------------------------
_LOCAL_DEV_CLUSTER = 'stpu-local'


@cli.group()
def local() -> None:
    """Manage the local dev cluster (sandbox hosts, no cloud)."""


@local.command(name='up')
@click.option('--nodes', type=int, default=1,
              help='Number of sandbox hosts.')
def local_up(nodes) -> None:
    """Provision the local dev cluster (`stpu-local`) for fast
    iteration: later `stpu exec stpu-local ...` runs skip provisioning
    (reference: `sky local up` kind cluster)."""
    from skypilot_tpu import task as task_lib
    task = task_lib.Task(run='true', num_nodes=nodes)
    from skypilot_tpu import resources as resources_lib
    task.set_resources(resources_lib.Resources(infra='local'))
    request_id = sdk.launch(task, cluster_name=_LOCAL_DEV_CLUSTER,
                            detach_run=True)
    sdk.stream_and_get(request_id)
    click.echo(f'Local dev cluster {_LOCAL_DEV_CLUSTER!r} is up '
               f'({nodes} host(s)).')


@local.command(name='down')
def local_down() -> None:
    """Tear down the local dev cluster."""
    sdk.get(sdk.down(_LOCAL_DEV_CLUSTER))
    click.echo(f'Local dev cluster {_LOCAL_DEV_CLUSTER!r} removed.')


@ssh_node_pool.command(name='up')
@click.argument('pool')
def ssh_node_pool_up(pool) -> None:
    """Pre-deploy the runtime to every pool host (warms launches:
    the per-launch package rsync becomes a no-op delta)."""
    from skypilot_tpu.clouds import ssh as ssh_cloud
    from skypilot_tpu.provision import instance_setup
    from skypilot_tpu.utils import command_runner
    from skypilot_tpu.utils import subprocess_utils
    pools = ssh_cloud.load_pools()
    if pool not in pools:
        _err(f'pool {pool!r} not declared; known: '
             + ', '.join(sorted(pools)))
    hosts = pools[pool]['hosts']

    def deploy(host):
        runner = command_runner.SSHCommandRunner(
            (host['ip'], host['port']), host['user'],
            host['identity_file'])
        try:
            rc = runner.run('python3 --version', stream_logs=False)
            if rc != 0:
                return f'FAIL (no python3, rc={rc})'
            instance_setup.deploy_package(runner)
        except Exception as e:  # pylint: disable=broad-except
            # Per-host outcome rows: one bad host must not abort (or
            # hide) the rest of the fan-out.
            return f'FAIL ({str(e)[:80]})'
        return 'OK'

    results = subprocess_utils.run_in_parallel(deploy, hosts)
    for host, outcome in zip(hosts, results):
        click.echo(f'{pool}\t{host["ip"]}\t{outcome}')


@ssh_node_pool.command(name='down')
@click.argument('pool')
@click.option('--yes', '-y', is_flag=True, default=False)
def ssh_node_pool_down(pool, yes) -> None:
    """Stop agents and remove the deployed runtime from pool hosts."""
    from skypilot_tpu.clouds import ssh as ssh_cloud
    from skypilot_tpu.provision.ssh import instance as ssh_instance
    from skypilot_tpu.utils import command_runner
    from skypilot_tpu.utils import subprocess_utils
    pools = ssh_cloud.load_pools()
    if pool not in pools:
        _err(f'pool {pool!r} not declared; known: '
             + ', '.join(sorted(pools)))
    busy = [cluster for cluster, entry in
            ssh_instance.list_allocations().items()
            if entry.get('pool') == pool]
    if busy:
        _err(f'pool {pool!r} still hosts cluster(s) {sorted(busy)}; '
             'run `stpu down` on them first.')
    if not yes:
        click.confirm(f'Remove the runtime from all hosts of {pool!r}?',
                      default=True, abort=True)
    from skypilot_tpu.provision import instance_setup
    pkg_dir = instance_setup.remote_pkg_dir()

    def teardown(host):
        runner = command_runner.SSHCommandRunner(
            (host['ip'], host['port']), host['user'],
            host['identity_file'])
        try:
            rc = runner.run('pkill -f skypilot_tpu.agent.agent || true; '
                            f'rm -rf {pkg_dir}', stream_logs=False)
        except Exception as e:  # pylint: disable=broad-except
            return f'FAIL ({str(e)[:80]})'
        return 'OK' if rc == 0 else f'FAIL (rc={rc})'

    results = subprocess_utils.run_in_parallel(
        teardown, pools[pool]['hosts'])
    for host, outcome in zip(pools[pool]['hosts'], results):
        click.echo(f'{pool}\t{host["ip"]}\t{outcome}')


@jobs_pool.command(name='logs')
@click.argument('pool_name')
@click.option('--worker', '-w', type=int, default=0,
              help='Worker index within the pool.')
@click.option('--job-id', type=int, default=None,
              help='Job id on that worker (default: latest).')
def jobs_pool_logs_cmd(pool_name, worker, job_id) -> None:
    """Tail a pool worker's job log (workers are ordinary clusters
    named pool-<name>-w<i>)."""
    from skypilot_tpu.jobs import pools as pools_lib
    cluster = pools_lib.worker_cluster(pool_name, worker)
    sdk.tail_logs(cluster, job_id)


@cli.command()
@click.argument('shell', type=click.Choice(['bash', 'zsh', 'fish']))
def completion(shell) -> None:
    """Print the shell-completion script (add to your rc file):

    bash: eval "$(stpu completion bash)"
    """
    from click.shell_completion import get_completion_class
    comp_cls = get_completion_class(shell)
    if comp_cls is None:
        _err(f'No completion support for {shell!r}.')
    comp = comp_cls(cli, {}, 'stpu', '_STPU_COMPLETE')
    click.echo(comp.source())


def main() -> None:
    try:
        cli()
    except exceptions.SkyError as e:
        _err(str(e))


if __name__ == '__main__':
    main()
