"""CLI: the `sky`-equivalent command surface.

Parity: /root/reference/sky/cli.py (launch :1044, exec :1173,
status :1554, queue/logs/cancel/stop/autostop/start/down :1948-2581,
check :2948, show_gpus :3001, groups storage/jobs/serve :3416-4025).
Exposed as `python -m skypilot_tpu.cli` and the `skytpu` entry point.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import click

from skypilot_tpu import __version__
from skypilot_tpu import exceptions
from skypilot_tpu import sky_logging
from skypilot_tpu.utils import common_utils

logger = sky_logging.init_logger(__name__)


def _parse_env(env: Tuple[str, ...]) -> Dict[str, str]:
    out = {}
    for item in env:
        if '=' in item:
            key, value = item.split('=', 1)
        else:
            key, value = item, os.environ.get(item, '')
        out[key] = value
    return out


def _entrypoint_is_yaml(entrypoint: Optional[str]) -> bool:
    return bool(entrypoint and
                (entrypoint.endswith(('.yaml', '.yml')) or
                 os.path.isfile(os.path.expanduser(entrypoint))))


def _make_task(entrypoint: Optional[str], *, name: Optional[str],
               workdir: Optional[str], cloud: Optional[str],
               region: Optional[str], zone: Optional[str],
               accelerators: Optional[str], cpus: Optional[str],
               memory: Optional[str], instance_type: Optional[str],
               use_spot: Optional[bool], num_nodes: Optional[int],
               env: Tuple[str, ...], command: Optional[str] = None):
    """YAML (or inline command) → Task with CLI overrides applied.

    Parity: reference cli.py:702
    (_make_task_or_dag_from_entrypoint_with_overrides).
    """
    from skypilot_tpu import resources as resources_lib  # pylint: disable=import-outside-toplevel
    from skypilot_tpu import task as task_lib  # pylint: disable=import-outside-toplevel

    if _entrypoint_is_yaml(entrypoint):
        task = task_lib.Task.from_yaml(entrypoint)
    else:
        cmd = command if command is not None else entrypoint
        task = task_lib.Task(run=cmd)

    if name is not None:
        task.name = name
    if workdir is not None:
        task.workdir = workdir
    if num_nodes is not None:
        task.num_nodes = num_nodes
    if env:
        task.update_envs(_parse_env(env))

    override: Dict[str, Any] = {}
    if cloud is not None:
        override['cloud'] = cloud
    if region is not None:
        override['region'] = region
    if zone is not None:
        override['zone'] = zone
    if accelerators is not None:
        override['accelerators'] = accelerators
    if cpus is not None:
        override['cpus'] = cpus
    if memory is not None:
        override['memory'] = memory
    if instance_type is not None:
        override['instance_type'] = instance_type
    if use_spot is not None:
        override['use_spot'] = use_spot
    if override:
        if task.resources:
            task.set_resources(
                {r.copy(**override) for r in task.resources})
        else:
            task.set_resources(resources_lib.Resources(**override))
    return task


_TASK_OPTIONS = [
    click.option('--name', '-n', default=None, help='Task/cluster name.'),
    click.option('--workdir', default=None,
                 help='Directory synced to all hosts.'),
    click.option('--cloud', default=None,
                 help='Infra to use (gcp | local).'),
    click.option('--region', default=None),
    click.option('--zone', default=None),
    click.option('--gpus', '--accelerators', 'accelerators', default=None,
                 help="Accelerators, e.g. 'tpu-v5e-8' or 'A100:8'."),
    click.option('--cpus', default=None),
    click.option('--memory', default=None),
    click.option('--instance-type', '-t', default=None),
    click.option('--use-spot/--no-use-spot', 'use_spot', default=None),
    click.option('--num-nodes', type=int, default=None,
                 help='Number of slices/nodes.'),
    click.option('--env', multiple=True,
                 help='Env var KEY=VALUE (repeatable).'),
]


def _add_options(options):

    def deco(f):
        for option in reversed(options):
            f = option(f)
        return f

    return deco


# Shell completion (parity: reference cli.py:345
# --install-shell-completion).  Click emits the completion script
# itself (_SKYTPU_COMPLETE=<shell>_source skytpu); these options wire
# it into the user's rc file / completions dir.
_COMPLETION_SETUP = {
    'bash': ('~/.bashrc',
             'eval "$(_SKYTPU_COMPLETE=bash_source skytpu)"'),
    'zsh': ('~/.zshrc',
            'eval "$(_SKYTPU_COMPLETE=zsh_source skytpu)"'),
    'fish': ('~/.config/fish/completions/skytpu.fish',
             '_SKYTPU_COMPLETE=fish_source skytpu | source'),
}
_COMPLETION_MARK = '# skytpu shell completion'


def _install_completion(ctx, param, value):
    del param
    if not value or ctx.resilient_parsing:
        return
    rc_path, line = _COMPLETION_SETUP[value]
    path = os.path.expanduser(rc_path)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    content = ''
    if os.path.exists(path):
        with open(path, encoding='utf-8') as f:
            content = f.read()
    if _COMPLETION_MARK in content:
        click.echo(f'Shell completion already installed in {rc_path}.')
    else:
        with open(path, 'a', encoding='utf-8') as f:
            f.write(f'\n{_COMPLETION_MARK}\n{line}\n')
        click.echo(f'Installed {value} completion in {rc_path}; '
                   f'restart your shell to activate.')
    ctx.exit()


def _uninstall_completion(ctx, param, value):
    del param
    if not value or ctx.resilient_parsing:
        return
    rc_path, _ = _COMPLETION_SETUP[value]
    path = os.path.expanduser(rc_path)
    removed = False
    if os.path.exists(path):
        with open(path, encoding='utf-8') as f:
            lines = f.read().splitlines()
        kept, skip_next = [], False
        for line in lines:
            if skip_next:
                skip_next = False
                continue
            if line.strip() == _COMPLETION_MARK:
                removed = True
                skip_next = True  # the eval line that follows the mark
                # Also drop the blank separator install wrote, so
                # install/uninstall cycles don't accumulate blanks.
                if kept and not kept[-1].strip():
                    kept.pop()
                continue
            kept.append(line)
        if removed:
            with open(path, 'w', encoding='utf-8') as f:
                f.write('\n'.join(kept) + ('\n' if kept else ''))
    if removed:
        click.echo(f'Removed skytpu completion from {rc_path}.')
    else:
        click.echo(f'No skytpu completion found in {rc_path}; '
                   'nothing removed.')
    ctx.exit()


def _complete_cluster_name(ctx, param, incomplete):
    """Cluster-name completion for every cluster-taking command."""
    del ctx, param
    try:
        from skypilot_tpu import global_user_state  # pylint: disable=import-outside-toplevel
        return [r['name'] for r in global_user_state.get_clusters()
                if r['name'].startswith(incomplete)]
    except Exception:  # pylint: disable=broad-except
        return []  # completion must never crash the shell


@click.group()
# Explicit version: click's package introspection fails when running
# from a source tree (PYTHONPATH) rather than an installed wheel.
@click.version_option(version=__version__, message='%(version)s')
@click.option('--install-shell-completion',
              type=click.Choice(sorted(_COMPLETION_SETUP)),
              callback=_install_completion, expose_value=False,
              is_eager=True,
              help='Install shell tab-completion and exit.')
@click.option('--uninstall-shell-completion',
              type=click.Choice(sorted(_COMPLETION_SETUP)),
              callback=_uninstall_completion, expose_value=False,
              is_eager=True,
              help='Remove shell tab-completion and exit.')
def cli():
    """skypilot_tpu: run AI workloads on TPU slices, anywhere."""
    # Crash-safe orphan cleanup: kill daemons whose state dir vanished
    # (e.g. a kill -9'd run left skylets behind).  Cheap no-op normally.
    from skypilot_tpu.utils import daemon_registry  # pylint: disable=import-outside-toplevel
    daemon_registry.reap_stale()


# ------------------------------------------------------------------ launch


@cli.command()
@click.argument('entrypoint', required=False)
@click.option('--cluster', '-c', default=None, help='Cluster name.',
              shell_complete=_complete_cluster_name)
@click.option('--dryrun', is_flag=True, default=False)
@click.option('--detach-run', '-d', is_flag=True, default=False)
@click.option('--idle-minutes-to-autostop', '-i', type=int, default=None)
@click.option('--down', is_flag=True, default=False,
              help='Tear down the cluster when the job finishes.')
@click.option('--retry-until-up', '-r', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
@click.option('--no-setup', is_flag=True, default=False)
@_add_options(_TASK_OPTIONS)
def launch(entrypoint, cluster, dryrun, detach_run,
           idle_minutes_to_autostop, down, retry_until_up, yes, no_setup,
           **task_args):
    """Launch a task (YAML file or inline command) on a (new) cluster."""
    from skypilot_tpu import execution  # pylint: disable=import-outside-toplevel
    task = _make_task(entrypoint, **task_args)
    if not yes and not dryrun:
        click.confirm(f'Launching task on cluster '
                      f'{cluster or "(auto-named)"}. Proceed?',
                      default=True, abort=True)
    try:
        job_id = execution.launch(
            task, cluster_name=cluster, dryrun=dryrun,
            detach_run=detach_run, down=down,
            idle_minutes_to_autostop=idle_minutes_to_autostop,
            retry_until_up=retry_until_up, no_setup=no_setup)
    except exceptions.SkyTpuError as e:
        raise click.ClickException(common_utils.format_exception(e))
    if job_id is not None:
        click.echo(f'Job submitted with ID: {job_id}')


@cli.command(name='exec')
@click.argument('cluster', shell_complete=_complete_cluster_name)
@click.argument('entrypoint', required=False)
@click.option('--detach-run', '-d', is_flag=True, default=False)
@_add_options(_TASK_OPTIONS)
def exec_cmd(cluster, entrypoint, detach_run, **task_args):
    """Run a task on an existing cluster (skip provision/setup)."""
    from skypilot_tpu import execution  # pylint: disable=import-outside-toplevel
    task = _make_task(entrypoint, **task_args)
    try:
        job_id = execution.exec(task, cluster_name=cluster,
                                detach_run=detach_run)
    except exceptions.SkyTpuError as e:
        raise click.ClickException(common_utils.format_exception(e))
    if job_id is not None:
        click.echo(f'Job submitted with ID: {job_id}')


# ------------------------------------------------------------------ status


@cli.command()
@click.option('--refresh', '-r', is_flag=True, default=False,
              help='Re-query live cluster status from the provider.')
@click.option('--verbose', '-v', is_flag=True, default=False,
              help='Show the last launch stage-runtime decomposition.')
@click.option('--events', 'show_events', is_flag=True, default=False,
              help='Print the control-plane event timeline (flight '
                   'recorder) for the given cluster(s).')
@click.option('--export-trace', 'export_trace', default=None,
              help='With --events: also write the events as a '
                   'Chrome-trace JSON to this path.')
@click.argument('clusters', nargs=-1, shell_complete=_complete_cluster_name)
def status(refresh, verbose, show_events, export_trace, clusters):
    """Show clusters."""
    from skypilot_tpu import core  # pylint: disable=import-outside-toplevel
    from skypilot_tpu import usage_lib  # pylint: disable=import-outside-toplevel
    if show_events:
        if not clusters:
            raise click.UsageError(
                'status --events requires at least one cluster name.')
        _print_cluster_events(list(clusters), export_trace)
        return
    records = core.status(cluster_names=list(clusters) or None,
                          refresh=refresh)
    if not records:
        click.echo('No existing clusters.')
        return
    rows = []
    for r in records:
        handle = r.get('handle')
        resources_str = '-'
        if handle is not None and getattr(handle, 'launched_resources',
                                          None) is not None:
            resources_str = str(handle.launched_resources)
        launch_rec = r.get('last_launch')
        ttfs = (f'{launch_rec["time_to_first_step"]:.1f}s'
                if launch_rec else '-')
        rows.append((r['name'], resources_str, str(r['status'].value),
                     r.get('autostop', '-'), ttfs))
    _print_table(['NAME', 'RESOURCES', 'STATUS', 'AUTOSTOP',
                  'TIME-TO-FIRST-STEP'], rows)
    if verbose:
        for r in records:
            if r.get('last_launch'):
                click.echo(f'\n{r["name"]}: '
                           + usage_lib.format_decomposition(
                               r['last_launch']))


def _print_cluster_events(clusters: List[str],
                          export_trace: Optional[str]) -> None:
    """`status --events`: render each cluster's flight-recorder journal
    as a readable timeline (and optionally a Chrome trace)."""
    from skypilot_tpu.observability import events as events_lib  # pylint: disable=import-outside-toplevel
    all_events = []
    for name in clusters:
        events = events_lib.cluster_events(name)
        if not events:
            click.echo(f'{name}: no recorded events.')
            continue
        click.echo(f'Events for cluster {name} '
                   f'({len(events)} recorded):')
        for line in events_lib.format_timeline(events):
            click.echo(f'  {line}')
        all_events.extend(events)
    if export_trace and all_events:
        events_lib.export_chrome_trace(all_events, export_trace)
        click.echo(f'Chrome trace written to {export_trace} '
                   '(open in chrome://tracing or Perfetto).')


def _print_table(headers: List[str], rows: List[tuple]) -> None:
    widths = [len(h) for h in headers]
    for row in rows:
        for i, cell in enumerate(row):
            widths[i] = max(widths[i], len(str(cell)))
    fmt = '  '.join(f'{{:<{w}}}' for w in widths)
    click.echo(fmt.format(*headers))
    for row in rows:
        click.echo(fmt.format(*[str(c) for c in row]))


# ------------------------------------------------------- lifecycle verbs


@cli.command()
@click.argument('cluster', shell_complete=_complete_cluster_name)
@click.argument('port', required=False, type=int)
def endpoints(cluster, port):
    """Show a cluster's exposed port endpoints.

    Parity: reference `sky status --endpoints` / core.endpoints."""
    from skypilot_tpu import core  # pylint: disable=import-outside-toplevel
    try:
        eps = core.endpoints(cluster, port=port)
    except Exception as e:  # pylint: disable=broad-except
        raise click.ClickException(str(e)) from e
    for p, addr in sorted(eps.items()):
        click.echo(f'{p}: http://{addr}')


@cli.command()
@click.argument('clusters', nargs=-1, required=True,
                shell_complete=_complete_cluster_name)
@click.option('--yes', '-y', is_flag=True, default=False)
def stop(clusters, yes):
    """Stop cluster(s) (restartable with `start`)."""
    _lifecycle('stop', clusters, yes)


@cli.command()
@click.argument('clusters', nargs=-1, required=True,
                shell_complete=_complete_cluster_name)
@click.option('--yes', '-y', is_flag=True, default=False)
def start(clusters, yes):
    """Restart stopped cluster(s)."""
    _lifecycle('start', clusters, yes)


@cli.command()
@click.argument('clusters', nargs=-1, required=True,
                shell_complete=_complete_cluster_name)
@click.option('--yes', '-y', is_flag=True, default=False)
@click.option('--purge', is_flag=True, default=False)
def down(clusters, yes, purge):
    """Terminate cluster(s)."""
    _lifecycle('down', clusters, yes, purge=purge)


def _lifecycle(verb: str, clusters, yes: bool, **kwargs) -> None:
    from skypilot_tpu import core  # pylint: disable=import-outside-toplevel
    from skypilot_tpu import global_user_state  # pylint: disable=import-outside-toplevel
    names: List[str] = []
    for pattern in clusters:
        names.extend(global_user_state.get_glob_cluster_names(pattern))
    names = sorted(set(names))
    if not names:
        click.echo(f'No clusters match {clusters}.')
        return
    if not yes:
        click.confirm(f'{verb} cluster(s) {", ".join(names)}?',
                      default=True, abort=True)
    for name in names:
        try:
            getattr(core, verb)(name, **kwargs)
            click.echo(f'{verb}: {name} done.')
        except exceptions.SkyTpuError as e:
            click.echo(f'{verb}: {name} failed: '
                       f'{common_utils.format_exception(e)}', err=True)


@cli.command()
@click.argument('cluster', shell_complete=_complete_cluster_name)
@click.option('--idle-minutes', '-i', type=int, required=True)
@click.option('--down', is_flag=True, default=False)
@click.option('--cancel', is_flag=True, default=False)
def autostop(cluster, idle_minutes, down, cancel):
    """Schedule stop/down after idle minutes (-1 or --cancel clears)."""
    from skypilot_tpu import core  # pylint: disable=import-outside-toplevel
    if cancel:
        idle_minutes = -1
    core.autostop(cluster, idle_minutes, down=down)
    click.echo('Autostop updated.')


# ----------------------------------------------------------- job verbs


@cli.command()
@click.argument('cluster', shell_complete=_complete_cluster_name)
@click.option('--skip-finished', '-s', is_flag=True, default=False)
def queue(cluster, skip_finished):
    """Show the cluster's job queue."""
    from skypilot_tpu import core  # pylint: disable=import-outside-toplevel
    jobs = core.queue(cluster, all_jobs=not skip_finished)
    rows = [(j['job_id'], j['job_name'], j.get('username', '-'),
             j['status']) for j in jobs]
    _print_table(['ID', 'NAME', 'USER', 'STATUS'], rows)


@cli.command()
@click.argument('cluster', shell_complete=_complete_cluster_name)
@click.argument('job_id', required=False, type=int)
@click.option('--no-follow', is_flag=True, default=False)
def logs(cluster, job_id, no_follow):
    """Tail a job's logs."""
    from skypilot_tpu import core  # pylint: disable=import-outside-toplevel
    core.tail_logs(cluster, job_id, follow=not no_follow)


@cli.command()
@click.argument('cluster', shell_complete=_complete_cluster_name)
@click.argument('job_ids', nargs=-1, type=int)
@click.option('--all', '-a', 'all_jobs', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def cancel(cluster, job_ids, all_jobs, yes):
    """Cancel job(s) on a cluster."""
    from skypilot_tpu import core  # pylint: disable=import-outside-toplevel
    if not job_ids and not all_jobs:
        raise click.UsageError('Provide job ids or --all.')
    if not yes:
        what = 'all jobs' if all_jobs else f'jobs {list(job_ids)}'
        click.confirm(f'Cancel {what} on {cluster}?', default=True,
                      abort=True)
    core.cancel(cluster, job_ids=list(job_ids) or None,
                all_jobs=all_jobs)


# ------------------------------------------------------------ cost report


@cli.command(name='cost-report')
def cost_report():
    """Accumulated cost + launch-overhead per cluster (incl. history).

    Parity: reference `sky cost-report`; adds the time-to-first-step
    column (the north-star denominator, usage_lib).
    """
    from skypilot_tpu import core  # pylint: disable=import-outside-toplevel
    records = core.cost_report()
    if not records:
        click.echo('No clusters in history.')
        return
    rows = []
    for r in records:
        duration_h = (r.get('duration', 0) or 0) / 3600.0
        ttfs = (f'{r["time_to_first_step"]:.1f}s'
                if r.get('time_to_first_step') else '-')
        status = r.get('status')
        rows.append((r.get('name', '-'), f'{duration_h:.1f}h',
                     f'${r.get("total_cost", 0.0):.2f}', ttfs,
                     status.value if status else 'TERMINATED'))
    _print_table(['NAME', 'UPTIME', 'COST', 'TIME-TO-FIRST-STEP',
                  'STATUS'], rows)


# ------------------------------------------------------------------ check


@cli.command()
def check():
    """Verify credentials for each infra and enable the usable ones."""
    # NB: `skypilot_tpu.check` the *attribute* is the function (rebound
    # by the package __init__), so import it from the module directly.
    from skypilot_tpu.check import check as check_fn  # pylint: disable=import-outside-toplevel
    check_fn()


@cli.command(name='show-tpus')
@click.option('--all', '-a', 'show_all', is_flag=True, default=False)
def show_tpus(show_all):
    """List TPU (and GPU) offerings with pricing."""
    from skypilot_tpu import catalog  # pylint: disable=import-outside-toplevel
    entries = catalog.list_accelerators()
    rows = []
    for name, infos in sorted(entries.items()):
        for info in infos:
            if not show_all and not name.startswith('tpu'):
                continue
            rows.append((name, info.accelerator_count, info.cloud,
                         info.region or '-',
                         f'{info.price:.2f}' if info.price else '-',
                         f'{info.spot_price:.2f}'
                         if info.spot_price else '-'))
    _print_table(
        ['ACCELERATOR', 'COUNT', 'CLOUD', 'REGION', '$/HR', 'SPOT $/HR'],
        rows)


# ------------------------------------------------------------- jobs group


@cli.group(name='jobs')
def jobs_group():
    """Managed jobs with auto-recovery."""


@jobs_group.command(name='launch')
@click.argument('entrypoint', required=False)
@click.option('--detach-run', '-d', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
@_add_options(_TASK_OPTIONS)
def jobs_launch(entrypoint, detach_run, yes, **task_args):
    """Launch a managed job (supervised, auto-recovered).

    A multi-document YAML is a chain pipeline: each stage runs on its
    own cluster in order, supervised end-to-end (parity: reference
    managed-jobs pipelines)."""
    from skypilot_tpu import jobs  # pylint: disable=import-outside-toplevel
    entry = _load_chain_if_multidoc(entrypoint, task_args)
    if entry is None:
        entry = _make_task(entrypoint, **task_args)
    if not yes:
        click.confirm('Launch managed job?', default=True, abort=True)
    job_id = jobs.launch(entry, detach_run=detach_run)
    click.echo(f'Managed job ID: {job_id}')


def _load_chain_if_multidoc(entrypoint, task_args):
    """-> Dag when `entrypoint` is a multi-document YAML, else None."""
    if not _entrypoint_is_yaml(entrypoint):
        return None
    from skypilot_tpu.utils import common_utils  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.utils import dag_utils  # pylint: disable=import-outside-toplevel
    try:
        docs = [d for d in common_utils.read_yaml_all(
            os.path.expanduser(entrypoint)) if d]
    except OSError:
        return None
    if len(docs) <= 1:
        return None
    overrides = {k: v for k, v in task_args.items()
                 if v not in (None, ())}
    if overrides:
        raise click.UsageError(
            f'CLI task overrides {sorted(overrides)} cannot apply to a '
            'multi-stage pipeline YAML; set per-stage fields in the '
            'file instead.')
    return dag_utils.load_chain_dag_from_configs(docs)


@jobs_group.command(name='queue')
def jobs_queue():
    """List managed jobs."""
    from skypilot_tpu import jobs  # pylint: disable=import-outside-toplevel
    records = jobs.queue()
    rows = []
    for r in records:
        # WHY the job is (or last was) recovering, not just that it is.
        reason = r.get('last_recovery_reason') or r.get(
            'failure_reason') or '-'
        # Batch-infer drivers report shard-ledger progress through
        # jobs/state.py (same plumbing as the recovery reason).
        progress = r.get('batch_progress') or '-'
        rows.append((r['job_id'], r['task_id'], r['job_name'],
                     r['status'], r['recovery_count'], progress,
                     common_utils.truncate_long_string(str(reason), 48)))
    _print_table(['ID', 'TASK', 'NAME', 'STATUS', 'RECOVERIES',
                  'PROGRESS', 'REASON'], rows)


@jobs_group.command(name='events')
@click.argument('job_id', type=int)
@click.option('--export-trace', 'export_trace', default=None,
              help='Also write the events as a Chrome-trace JSON to '
                   'this path.')
def jobs_events(job_id, export_trace):
    """Show a managed job's control-plane event timeline.

    The flight recorder journals every launch attempt, preemption
    detection, and recovery span the controller performed for this job;
    this renders them as a timeline (post-mortemable after the
    controller exits)."""
    from skypilot_tpu.observability import events as events_lib  # pylint: disable=import-outside-toplevel
    events = events_lib.job_events(job_id)
    if not events:
        click.echo(f'Managed job {job_id}: no recorded events.')
        return
    click.echo(f'Events for managed job {job_id} '
               f'({len(events)} recorded):')
    for line in events_lib.format_timeline(events):
        click.echo(f'  {line}')
    if export_trace:
        events_lib.export_chrome_trace(events, export_trace)
        click.echo(f'Chrome trace written to {export_trace} '
                   '(open in chrome://tracing or Perfetto).')


@jobs_group.command(name='cancel')
@click.argument('job_ids', nargs=-1, type=int)
@click.option('--all', '-a', 'all_jobs', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_cancel(job_ids, all_jobs, yes):
    """Cancel managed job(s)."""
    from skypilot_tpu import jobs  # pylint: disable=import-outside-toplevel
    if not job_ids and not all_jobs:
        raise click.UsageError('Provide job ids or --all.')
    if not yes:
        click.confirm('Cancel managed job(s)?', default=True, abort=True)
    cancelled = jobs.cancel(list(job_ids) or None, all_jobs=all_jobs)
    click.echo(f'Cancellation requested for: {cancelled}')


@jobs_group.command(name='logs')
@click.argument('job_id', required=False, type=int)
@click.option('--no-follow', is_flag=True, default=False)
def jobs_logs(job_id, no_follow):
    """Tail a managed job's logs."""
    from skypilot_tpu import jobs  # pylint: disable=import-outside-toplevel
    jobs.tail_logs(job_id, follow=not no_follow)


@jobs_group.command(name='dashboard')
@click.option('--refresh', '-r', 'refresh_every', type=float, default=0,
              help='Redraw every N seconds (0 = print once and exit).')
def jobs_dashboard(refresh_every):
    """Live text dashboard of managed jobs.

    Parity: reference sky/jobs/dashboard (web) — rendered as a
    terminal table: status mix, per-job state, recoveries, age.
    """
    import collections  # pylint: disable=import-outside-toplevel
    import datetime  # pylint: disable=import-outside-toplevel
    import time as time_lib  # pylint: disable=import-outside-toplevel

    from skypilot_tpu import jobs  # pylint: disable=import-outside-toplevel

    def _render():
        records = jobs.queue()
        by_status = collections.Counter(r['status'] for r in records)
        summary = '  '.join(f'{s}: {n}'
                            for s, n in sorted(by_status.items()))
        now = time_lib.time()
        rows = []
        for r in records:
            age = '-'
            if r.get('submitted_at'):
                age = str(datetime.timedelta(
                    seconds=int(now - r['submitted_at'])))
            rows.append((r['job_id'], r['task_id'], r['job_name'],
                         r['status'], r['recovery_count'],
                         r.get('cluster_name') or '-', age))
        click.echo(f'Managed jobs — {len(records)} total'
                   + (f'  ({summary})' if summary else ''))
        _print_table(
            ['ID', 'TASK', 'NAME', 'STATUS', 'RECOVERIES', 'CLUSTER',
             'AGE'], rows)

    if refresh_every <= 0:
        _render()
        return
    try:
        while True:
            click.clear()
            _render()
            time_lib.sleep(refresh_every)
    except KeyboardInterrupt:
        pass


# ------------------------------------------------------ batch-infer group


@cli.group(name='batch-infer')
def batch_infer_group():
    """Offline bulk inference riding the serving QoS floor."""


@batch_infer_group.command(name='launch')
@click.option('--input', 'input_path', required=True,
              help='Source JSONL: one request object per line '
                   '("prompt" string or "prompt_ids" list, plus '
                   'optional per-row overrides).')
@click.option('--endpoint', required=True,
              help='Serving front door (LB or replica) URL.')
@click.option('--run-dir', default=None,
              help='Manifest/run directory '
                   '(default: <input>.batchrun).')
@click.option('--num-shards', type=int, default=8)
@click.option('--max-new-tokens', type=int, default=16)
@click.option('--inflight', type=int, default=None,
              help='Bounded in-flight rows '
                   '(default: SKYTPU_BATCH_INFLIGHT or 4).')
@click.option('--managed', is_flag=True, default=False,
              help='Submit the driver as a managed job (a dead driver '
                   'is relaunched and resumes off the ledger) instead '
                   'of running it inline.')
def batch_infer_launch(input_path, endpoint, run_dir, num_shards,
                       max_new_tokens, inflight, managed):
    """Shard INPUT into a run directory and drive it through ENDPOINT.

    Rows flow as QoS class `batch`: the router's weighted admission
    keeps interactive traffic at its floor and sheds batch overflow
    with 429 + Retry-After, which the driver honors.  The run
    directory's shard ledger makes any restart a resume — committed
    rows never re-run, and the final rewrite dedupes half-committed
    ones (exactly-once outputs)."""
    import json as json_lib  # pylint: disable=import-outside-toplevel

    from skypilot_tpu.batch import manifest as manifest_lib  # pylint: disable=import-outside-toplevel
    run_dir = run_dir or input_path + '.batchrun'
    manifest = manifest_lib.build_manifest(input_path, run_dir,
                                           num_shards=num_shards)
    click.echo(f'Manifest: {manifest.total_rows} rows in '
               f'{manifest.num_shards} shards under {run_dir}')
    if managed:
        import skypilot_tpu as sky  # pylint: disable=import-outside-toplevel
        from skypilot_tpu import jobs  # pylint: disable=import-outside-toplevel
        cmd = (f'python -m skypilot_tpu.batch.runner '
               f'--manifest-dir {run_dir} --endpoint {endpoint} '
               f'--max-new-tokens {max_new_tokens}')
        if inflight:
            cmd += f' --inflight {inflight}'
        task = sky.Task(name='batch-infer', run=cmd)
        job_id = jobs.launch(task)
        click.echo(f'Managed job ID: {job_id} (watch `sky jobs queue` '
                   f'PROGRESS, or `sky batch-infer status {run_dir}`)')
        return
    from skypilot_tpu.batch import runner as runner_lib  # pylint: disable=import-outside-toplevel
    job = runner_lib.BatchInferJob(run_dir, endpoint,
                                   max_new_tokens=max_new_tokens,
                                   inflight=inflight)
    click.echo(json_lib.dumps(job.run()))


@batch_infer_group.command(name='status')
@click.argument('run_dir')
def batch_infer_status(run_dir):
    """Show a run's shard-ledger progress."""
    from skypilot_tpu.batch import manifest as manifest_lib  # pylint: disable=import-outside-toplevel
    manifest = manifest_lib.Manifest(run_dir)
    progress = manifest_lib.ShardLedger(run_dir).progress(manifest)
    click.echo(
        f'{progress["shards_done"]}/{progress["shards_total"]} shards '
        f'({progress["rows_done"]}/{progress["rows_total"]} rows)')


@batch_infer_group.command(name='resume')
@click.argument('run_dir')
@click.option('--endpoint', required=True,
              help='Serving front door (LB or replica) URL.')
@click.option('--max-new-tokens', type=int, default=16)
@click.option('--inflight', type=int, default=None)
def batch_infer_resume(run_dir, endpoint, max_new_tokens, inflight):
    """Resume a dead run off its ledger.

    Committed rows never re-run; rows cut mid-commit re-run and dedupe
    on the final rewrite.  Resuming a finished run is an idempotent
    re-verification of the outputs."""
    import json as json_lib  # pylint: disable=import-outside-toplevel

    from skypilot_tpu.batch import runner as runner_lib  # pylint: disable=import-outside-toplevel
    job = runner_lib.BatchInferJob(run_dir, endpoint,
                                   max_new_tokens=max_new_tokens,
                                   inflight=inflight)
    click.echo(json_lib.dumps(job.run()))


# ------------------------------------------------------------ serve group


@cli.group(name='serve')
def serve_group():
    """Autoscaled serving."""


@serve_group.command(name='up')
@click.argument('entrypoint')
@click.option('--service-name', '-n', default=None)
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_up(entrypoint, service_name, yes):
    """Start a service from a task YAML with a `service:` section."""
    from skypilot_tpu import serve  # pylint: disable=import-outside-toplevel
    from skypilot_tpu import task as task_lib  # pylint: disable=import-outside-toplevel
    task = task_lib.Task.from_yaml(entrypoint)
    if not yes:
        click.confirm('Start service?', default=True, abort=True)
    name, endpoint = serve.up(task, service_name)
    click.echo(f'Service {name} starting; endpoint: {endpoint}')


@serve_group.command(name='update')
@click.argument('service_name')
@click.argument('entrypoint')
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_update(service_name, entrypoint, yes):
    """Roll the service over to a new task YAML."""
    from skypilot_tpu import serve  # pylint: disable=import-outside-toplevel
    from skypilot_tpu import task as task_lib  # pylint: disable=import-outside-toplevel
    task = task_lib.Task.from_yaml(entrypoint)
    if not yes:
        click.confirm(f'Update service {service_name}?', default=True,
                      abort=True)
    version = serve.update(task, service_name)
    click.echo(f'Service {service_name} updating to version {version}.')


@serve_group.command(name='status')
@click.argument('service_names', nargs=-1)
@click.option('--metrics', 'show_metrics', is_flag=True, default=False,
              help='Scrape /metrics from each READY replica and show '
                   'live engine telemetry (decode tokens/s, slots, '
                   'queue, TTFT/ITL p50/p99).')
def serve_status(service_names, show_metrics):
    """Show services and their replicas."""
    from skypilot_tpu import serve  # pylint: disable=import-outside-toplevel
    records = serve.status(list(service_names) or None)
    if not records:
        click.echo('No services.')
        return
    rows = []
    for r in records:
        ready = sum(1 for rep in r['replicas']
                    if rep['status'] == 'READY')
        # Multi-host slice replicas: surface the fleet's host footprint
        # (sum of per-replica num_hosts; '2x2' reads "2 replicas x 2
        # hosts" when uniform, else the plain total).
        host_counts = [rep.get('num_hosts') or 1 for rep in r['replicas']]
        if host_counts and len(set(host_counts)) == 1:
            hosts = (f'{len(host_counts)}x{host_counts[0]}'
                     if host_counts[0] > 1 else str(len(host_counts)))
        else:
            hosts = str(sum(host_counts)) if host_counts else '-'
        rows.append((r['name'], r['status'], r['version'],
                     f'{ready}/{len(r["replicas"])}', hosts,
                     r.get('load_balancer_port') or '-'))
    _print_table(['NAME', 'STATUS', 'VERSION', 'READY', 'HOSTS',
                  'LB PORT'], rows)
    if show_metrics:
        _serve_metrics_table(records)


def _hist_quantile(parsed, name: str, q: float):
    """Thin import: the real implementation (with linear interpolation
    inside the winning bucket) lives in observability/metrics.py as
    `histogram_quantile`, next to the exposition parser it consumes."""
    from skypilot_tpu.observability import metrics as metrics_lib  # pylint: disable=import-outside-toplevel
    return metrics_lib.histogram_quantile(parsed, name, q)


def _rank_lag(parsed) -> str:
    """Tick lag across a slice replica's ranks, from the
    skytpu_slice_rank_ticks_total{rank} counter: max - min ticks.  A
    growing lag names a degraded-but-alive rank (visible during drains
    and rolling updates, before the gang actually fails)."""
    ticks = parsed.get('skytpu_slice_rank_ticks_total') or {}
    per_rank = {}
    for labels, value in ticks.items():
        rank = dict(labels).get('rank')
        if rank is not None:
            per_rank[rank] = per_rank.get(rank, 0) + value
    if len(per_rank) < 2:
        return '-'
    return f'{int(max(per_rank.values()) - min(per_rank.values()))}'


def _serve_lb_table(records) -> None:
    """One row per service's load balancer, scraped from its
    /lb/metrics: controller-sync staleness (a dead controller shows up
    HERE, before replicas start flapping unseen)."""
    import requests  # pylint: disable=import-outside-toplevel

    from skypilot_tpu.observability import metrics as metrics_lib  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.serve import http_protocol  # pylint: disable=import-outside-toplevel
    rows = []
    for r in records:
        lb_port = r.get('load_balancer_port')
        if not lb_port:
            continue
        try:
            resp = requests.get(
                f'http://127.0.0.1:{lb_port}'
                f'{http_protocol.LB_METRICS}', timeout=5)
            resp.raise_for_status()
            parsed = metrics_lib.parse_exposition(resp.text)
            age = sum((parsed.get(
                'skytpu_lb_controller_sync_age_seconds') or {})
                .values())
            retries = sum((parsed.get('skytpu_lb_retries_total')
                           or {}).values())
            retired = sum((parsed.get('skytpu_lb_retired_total')
                           or {}).values())
            rows.append((r['name'], lb_port, f'{age:.0f}s',
                         int(retries), int(retired)))
        except (requests.RequestException, ValueError) as e:
            rows.append((r['name'], lb_port,
                         f'scrape failed: {e}', '-', '-'))
    if not rows:
        return
    click.echo('')
    _print_table(['SERVICE', 'LB PORT', 'SYNC AGE', 'RETRIES',
                  'RETIRED'], rows)


def _serve_router_table(records) -> None:
    """One row per router-tier instance, from the skytpu_router_*
    series on each registered router port's /lb/metrics.  In-process
    tiers share one metric registry (every port exposes every
    instance's series, distinguished by the `router` label), so rows
    are unioned across ports by that label."""
    import requests  # pylint: disable=import-outside-toplevel

    from skypilot_tpu.observability import metrics as metrics_lib  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.serve import http_protocol  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.serve import serve_state  # pylint: disable=import-outside-toplevel
    rows = []
    for r in records:
        ports = serve_state.get_router_ports(r)
        per_router = {}
        for port in ports:
            try:
                resp = requests.get(
                    f'http://127.0.0.1:{port}'
                    f'{http_protocol.LB_METRICS}', timeout=5)
                resp.raise_for_status()
                parsed = metrics_lib.parse_exposition(resp.text)
            except (requests.RequestException, ValueError):
                continue

            def by_router(name, parsed=parsed):
                out = {}
                for labels, value in (parsed.get(name) or {}).items():
                    rid = dict(labels).get('router')
                    if rid is not None:
                        out[rid] = value
                return out

            affinity = {}
            for labels, value in (parsed.get(
                    'skytpu_router_affinity_total') or {}).items():
                d = dict(labels)
                affinity.setdefault(d.get('router'), {})[
                    d.get('outcome')] = value
            for name, values in (
                    ('qps', by_router('skytpu_router_qps')),
                    ('inflight',
                     by_router('skytpu_router_inflight')),
                    ('sync_age',
                     by_router('skytpu_router_sync_age_seconds')),
                    ('requests',
                     by_router('skytpu_router_requests_total'))):
                for rid, value in values.items():
                    per_router.setdefault(rid, {})[name] = value
            for rid, outcomes in affinity.items():
                per_router.setdefault(rid, {})['affinity'] = outcomes
        for rid in sorted(per_router):
            stats = per_router[rid]
            outcomes = stats.get('affinity') or {}
            routed = sum(outcomes.values())
            share = (f'{outcomes.get("hit", 0) / routed:.0%}hit'
                     if routed else '-')
            age = stats.get('sync_age')
            rows.append((r['name'], rid,
                         f'{stats.get("qps", 0):g}',
                         int(stats.get('inflight', 0)),
                         share,
                         '-' if age is None else f'{age:.0f}s',
                         int(stats.get('requests', 0))))
    if not rows:
        return
    click.echo('')
    _print_table(['SERVICE', 'ROUTER', 'QPS', 'INFLIGHT', 'AFFINITY',
                  'SYNC AGE', 'REQUESTS'], rows)


def _serve_metrics_table(records) -> None:
    """One row per READY replica, scraped live from GET /metrics
    (observability/metrics.py exposition on the model server)."""
    import requests  # pylint: disable=import-outside-toplevel

    from skypilot_tpu.observability import metrics as metrics_lib  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.serve import http_protocol  # pylint: disable=import-outside-toplevel

    def fmt_ms(seconds):
        return '-' if seconds is None else (
            'inf' if seconds == float('inf')
            else f'{seconds * 1e3:g}ms')

    rows = []
    for r in records:
        for rep in r['replicas']:
            if rep['status'] != 'READY' or not rep.get('url'):
                continue
            url = rep['url']
            role = rep.get('role') or 'mixed'
            num_hosts = rep.get('num_hosts') or 1
            # LIVE role from the replica's health payload: a morphed
            # replica (dynamic co-location) must never render its
            # launch-time role; the serve_state record is the
            # fallback when the probe fails.
            try:
                health = requests.get(url + '/', timeout=5).json()
                role = health.get('role') or role
            except (requests.RequestException, ValueError):
                pass
            try:
                resp = requests.get(url + http_protocol.METRICS,
                                    timeout=5)
                resp.raise_for_status()
                parsed = metrics_lib.parse_exposition(resp.text)
            except (requests.RequestException, ValueError) as e:
                rows.append((r['name'], rep['replica_id'], url, role,
                             num_hosts, f'scrape failed: {e}', '-',
                             '-', '-', '-', '-', '-', '-'))
                continue

            def total(name, parsed=parsed):
                return sum((parsed.get(name) or {}).values())

            busy = int(total('skytpu_engine_busy_slots'))
            slots = int(total('skytpu_engine_slots'))
            # Paged-KV replicas: pages used/total plus the prefix-
            # cache hit share; dense replicas show '-'.
            pages_total = int(total('skytpu_engine_kv_pages_total'))
            if pages_total:
                hits = total('skytpu_engine_prefix_cache_hits_total')
                misses = total(
                    'skytpu_engine_prefix_cache_misses_total')
                share = (f' {hits / (hits + misses):.0%}hit'
                         if hits + misses else '')
                pages = (f'{int(total("skytpu_engine_kv_pages_used"))}'
                         f'/{pages_total}{share}')
            else:
                pages = '-'
            # Router view from the replica side: LB-routed requests
            # and the share whose prompt prefix hit a pinned replica
            # (the skytpu_engine_routed_total{role,affinity} counter).
            routed = parsed.get('skytpu_engine_routed_total') or {}
            routed_total = sum(routed.values())
            if routed_total:
                hits = sum(v for labels, v in routed.items()
                           if dict(labels).get('affinity') == 'hit')
                affinity = f'{hits / routed_total:.0%}hit'
            else:
                affinity = '-'
            rows.append((
                r['name'], rep['replica_id'], url, role, num_hosts,
                f'{total("skytpu_engine_decode_tokens_per_s"):g}',
                f'{busy}/{slots}',
                pages,
                affinity,
                int(total('skytpu_engine_queue_depth')),
                _rank_lag(parsed),
                f'{fmt_ms(_hist_quantile(parsed, "skytpu_engine_ttft_seconds", 0.5))}'
                f'/{fmt_ms(_hist_quantile(parsed, "skytpu_engine_ttft_seconds", 0.99))}',
                f'{fmt_ms(_hist_quantile(parsed, "skytpu_engine_itl_seconds", 0.5))}'
                f'/{fmt_ms(_hist_quantile(parsed, "skytpu_engine_itl_seconds", 0.99))}',
            ))
    if not rows:
        click.echo('No READY replicas to scrape.')
    else:
        click.echo('')
        _print_table(['SERVICE', 'REPLICA', 'URL', 'ROLE', 'HOSTS',
                      'TOK/S', 'SLOTS', 'KV PAGES', 'AFFINITY',
                      'QUEUE', 'RANK LAG', 'TTFT p50/p99',
                      'ITL p50/p99'], rows)
    _serve_lb_table(records)
    _serve_router_table(records)


@serve_group.command(name='down')
@click.argument('service_names', nargs=-1, required=True)
@click.option('--purge', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_down(service_names, purge, yes):
    """Stop service(s) and terminate replicas."""
    from skypilot_tpu import serve  # pylint: disable=import-outside-toplevel
    if not yes:
        click.confirm(f'Tear down {", ".join(service_names)}?',
                      default=True, abort=True)
    for name in service_names:
        serve.down(name, purge=purge)
        click.echo(f'Service {name} torn down.')


def _log_sources(record) -> List[Dict[str, Any]]:
    """Every structured-log endpoint of one service: each replica
    front's /logs, the LB's /lb/logs, the controller's
    /controller/logs."""
    from skypilot_tpu.serve import http_protocol  # pylint: disable=import-outside-toplevel
    targets, lb_url = _trace_targets(record)
    sources: List[Dict[str, Any]] = [
        {'kind': 'replica', 'url': t['url'],
         'path': http_protocol.LOGS,
         'replica_id': t['replica_id'], 'role': t['role']}
        for t in targets]
    if lb_url:
        sources.append({'kind': 'lb', 'url': lb_url,
                        'path': http_protocol.LB_LOGS})
    port = record.get('controller_port')
    if port:
        sources.append({'kind': 'controller',
                        'url': f'http://127.0.0.1:{port}',
                        'path': http_protocol.CONTROLLER_LOGS})
    return sources


def _merge_log_records(batches, seen=None) -> List[Dict[str, Any]]:
    """Merge per-endpoint record batches into one timestamp-ordered
    stream.  Dedup matters because in-process fleets (tests, single
    host) share one ring: every endpoint exports the same records."""
    seen = seen if seen is not None else set()
    out: List[Dict[str, Any]] = []
    for records in batches:
        for rec in records:
            key = (rec.get('seq'), rec.get('ts'), rec.get('logger'),
                   rec.get('msg'))
            if key in seen:
                continue
            seen.add(key)
            out.append(rec)
    out.sort(key=lambda r: (float(r.get('ts') or 0.0),
                            int(r.get('seq') or 0)))
    return out


def _log_record_matches(rec, replica_id, role) -> bool:
    """Client-side identity filter — per-record, not per-endpoint,
    because record identity is authoritative (a shared ring tags each
    record with the process that emitted it)."""
    if replica_id is not None and rec.get('replica_id') != replica_id:
        return False
    if role is not None and rec.get('role') != role:
        return False
    return True


def _fmt_log_record(rec) -> str:
    import datetime  # pylint: disable=import-outside-toplevel
    ts = float(rec.get('ts') or 0.0)
    stamp = datetime.datetime.fromtimestamp(ts).strftime(
        '%m-%d %H:%M:%S.%f')[:-3]
    proc = rec.get('process')
    if proc == 'lb':
        who = 'lb'
    elif proc not in (None, 'replica'):
        who = str(proc)
    else:
        rid = rec.get('replica_id')
        who = f'replica {rid}' if rid is not None else 'replica'
        if rec.get('role'):
            who += f' ({rec["role"]})'
    line = (f'{stamp} {str(rec.get("level") or "?")[:1]} [{who}] '
            f'{rec.get("logger", "?")}: {rec.get("msg", "")}')
    if rec.get('request_id'):
        line += f' (req {rec["request_id"]})'
    return line


@serve_group.command(name='logs')
@click.argument('service_name', required=False, default=None)
@click.option('--replica', '-R', 'replica_id', type=int, default=None,
              help='Only records emitted by this replica.')
@click.option('--role', default=None,
              help='Only records emitted by replicas of this role.')
@click.option('--follow', '-f', is_flag=True, default=False,
              help='Keep streaming new records (live fleet tail).')
@click.option('--level', '-l', default=None,
              help='Minimum level (DEBUG/INFO/WARNING/ERROR).')
@click.option('--grep', 'grep_pat', default=None,
              help='Only records whose message matches this pattern.')
@click.option('--request-id', 'request_id', default=None,
              help='Only records bound to this request id.')
@click.option('--target', default=None,
              type=click.Choice(['replica', 'controller']),
              help='Legacy raw file tail (pre-structured-ring path).')
def serve_logs(service_name, replica_id, role, follow, level,
               grep_pat, request_id, target):
    """Stream the fleet's structured logs, merged by timestamp.

    Fans in every process's bounded log ring — each replica front's
    `GET /logs`, the LB's `/lb/logs`, the controller's
    `/controller/logs` — and merges the records into one
    identity-prefixed stream, so one request's prefill, KV handoff and
    decode lines from three different processes read as one story.
    Server-side filters (--level/--grep/--request-id) keep the fan-in
    cheap; --follow pages each source by its sequence cursor."""
    import time as time_lib  # pylint: disable=import-outside-toplevel

    from skypilot_tpu import serve  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.observability import traces as traces_lib  # pylint: disable=import-outside-toplevel
    if target is not None:
        if service_name is None:
            raise click.ClickException('--target needs a service name.')
        serve.tail_logs(service_name, target=target,
                        replica_id=replica_id)
        return
    record = _pick_service(
        serve.status([service_name] if service_name else None),
        service_name)
    sources = _log_sources(record)
    if not sources:
        raise click.ClickException(
            f'Service {record["name"]} has no reachable processes.')
    cursors = {i: 0.0 for i in range(len(sources))}
    seen: set = set()

    def _poll() -> List[Dict[str, Any]]:
        batches = []
        for i, src in enumerate(sources):
            records = traces_lib.fetch_log_records(
                src['url'], src['path'], since=cursors[i] or None,
                level=level, grep=grep_pat, request_id=request_id)
            for rec in records:
                cursors[i] = max(cursors[i],
                                 float(rec.get('seq') or 0))
            batches.append(records)
        return [rec for rec in _merge_log_records(batches, seen)
                if _log_record_matches(rec, replica_id, role)]

    for rec in _poll():
        click.echo(_fmt_log_record(rec))
    if not follow:
        return
    try:
        while True:
            time_lib.sleep(1.0)
            for rec in _poll():
                click.echo(_fmt_log_record(rec))
    except KeyboardInterrupt:
        pass


def _trace_targets(record) -> Tuple[List[Dict[str, Any]],
                                    Optional[str]]:
    """(replica span targets, lb url) for one service record — every
    replica with a URL is queried (a DRAINING replica may still hold
    the span the user is after)."""
    targets = [{'url': rep['url'], 'replica_id': rep['replica_id'],
                'role': rep.get('role') or 'mixed'}
               for rep in record['replicas']
               if rep.get('url') and rep['status'] in
               ('READY', 'NOT_READY', 'DRAINING')]
    lb_port = record.get('load_balancer_port')
    lb_url = f'http://127.0.0.1:{lb_port}' if lb_port else None
    return targets, lb_url


def _pick_service(records, service_name: Optional[str]):
    if not records:
        raise click.ClickException('No services.')
    if service_name is None:
        if len(records) > 1:
            names = ', '.join(r['name'] for r in records)
            raise click.ClickException(
                f'Several services exist ({names}); pass --service.')
        return records[0]
    for record in records:
        if record['name'] == service_name:
            return record
    raise click.ClickException(f'Service {service_name!r} not found.')


@serve_group.command(name='trace')
@click.argument('request_id')
@click.option('--service', '-s', 'service_name', default=None,
              help='Service to query (default: the only one).')
@click.option('--export-trace', 'export_trace', default=None,
              help='Also write the stitched trace as a Chrome-trace '
                   'JSON to this path.')
def serve_trace(request_id, service_name, export_trace):
    """Stitch one request's spans across the fleet into a waterfall.

    Every process that touched the request exports its span segments
    (the LB's route/handoff/attempt phases via /lb/spans, each
    replica's engine + handoff-endpoint spans via /spans); this
    assembles them by request id into one end-to-end view — LB queue,
    route, KV handoff export/import, prefill, decode — with a failed
    attempt and its retry shown as distinct segments."""
    from skypilot_tpu import serve  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.observability import traces as traces_lib  # pylint: disable=import-outside-toplevel
    record = _pick_service(
        serve.status([service_name] if service_name else None),
        service_name)
    targets, lb_url = _trace_targets(record)
    if not targets and not lb_url:
        raise click.ClickException(
            f'Service {record["name"]} has no reachable processes.')
    segments = traces_lib.collect(request_id, targets, lb_url)
    if not segments:
        raise click.ClickException(
            f'No spans found for request {request_id!r} (finished '
            'long ago and aged out of the bounded span stores, or '
            'never reached this service).')
    # The request's log lines, interleaved into the waterfall by wall
    # time (same fan-in as `serve logs --request-id`).
    log_records = _merge_log_records([
        traces_lib.fetch_log_records(src['url'], src['path'],
                                     request_id=request_id)
        for src in _log_sources(record)])
    click.echo(f'Trace {request_id} — {len(segments)} segment(s) '
               f'across {len({(s.get("process"), s.get("replica_id")) for s in segments})} '
               f'process(es):')
    for line in traces_lib.interleave_logs(segments, log_records):
        click.echo(f'  {line}')
    if export_trace:
        traces_lib.export_chrome_trace(segments, export_trace)
        click.echo(f'Chrome trace written to {export_trace} '
                   '(open in chrome://tracing or Perfetto).')


@serve_group.command(name='profile')
@click.argument('service_name', required=False, default=None)
@click.option('--replica', '-R', 'replica_id', type=int, default=None,
              help='Only this replica (default: every reachable one).')
@click.option('--export-trace', 'export_trace', default=None,
              help='Write the tick-phase ring as Chrome-trace JSON '
                   'to this path (chrome://tracing / Perfetto).')
def serve_profile(service_name, replica_id, export_trace):
    """Tick-phase profile of a service's replicas.

    Pulls each replica's `GET /profile` payload — the engine's bounded
    ring of per-tick phase timings (admit / prefill-chunk / decode-step
    / spec-verify / device-wait / sample / page-scatter / handoff /
    slice-sync; the dispatch phases time the host's dispatch,
    device-wait the wait for the device), the recompile sentinel's
    per-jit-entry compile counts, and the device-memory watermark — and
    renders per-phase quantiles plus a
    collapsed-stack summary (pipe into a flamegraph tool)."""
    import json  # pylint: disable=import-outside-toplevel

    import requests  # pylint: disable=import-outside-toplevel

    from skypilot_tpu import serve  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.observability import profiling  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.serve import http_protocol  # pylint: disable=import-outside-toplevel
    record = _pick_service(
        serve.status([service_name] if service_name else None),
        service_name)
    targets, _ = _trace_targets(record)
    if replica_id is not None:
        targets = [t for t in targets
                   if t['replica_id'] == replica_id]
    if not targets:
        raise click.ClickException(
            f'Service {record["name"]} has no reachable replica'
            + (f' {replica_id}.' if replica_id is not None else 's.'))
    profiles = []
    for target in targets:
        try:
            resp = requests.get(
                target['url'].rstrip('/') + http_protocol.PROFILE,
                timeout=5)
            resp.raise_for_status()
            payload = resp.json()
        except (requests.RequestException, ValueError) as e:
            click.echo(f'replica {target["replica_id"]}: '
                       f'unreachable ({e})')
            continue
        if payload.get('profile'):
            profiles.append((target, payload['profile']))
    if not profiles:
        raise click.ClickException('No replica answered /profile with '
                                   'a profiling snapshot.')
    trace_events = []
    for target, snap in profiles:
        rid = target['replica_id']
        click.echo(f'Replica {rid} ({target.get("role") or "mixed"}) — '
                   f'{snap.get("ticks", 0)} profiled tick(s), ring '
                   f'{snap.get("ring_ticks")}:')
        rows = []
        for phase, agg in sorted((snap.get('phases') or {}).items()):
            def ms(v):
                return '-' if v is None else f'{v * 1e3:.3f}ms'
            rows.append((phase, agg.get('count', 0),
                         ms(agg.get('p50_s')), ms(agg.get('p99_s')),
                         ms(agg.get('max_s')),
                         f"{agg.get('total_s', 0.0) * 1e3:.1f}ms"))
        if rows:
            _print_table(['PHASE', 'COUNT', 'p50', 'p99', 'MAX',
                          'TOTAL'], rows)
        recomp = (snap.get('recompiles') or {})
        total_recompiles = recomp.get('steady_recompiles_total', 0)
        click.echo(f'  steady-state recompiles: {total_recompiles}')
        for fn, st in sorted((recomp.get('fns') or {}).items()):
            if st.get('steady_recompiles'):
                click.echo(f'    {fn}: {st["steady_recompiles"]} '
                           f'(compiles {st["compiles"]}, calls '
                           f'{st["calls"]})')
        mem = (snap.get('device_memory') or {}).get('watermark_bytes')
        if mem is not None:
            click.echo(f'  device memory watermark: {mem / 1e6:.1f} MB')
        click.echo('  collapsed stacks:')
        for line in profiling.collapsed_stacks(snap).splitlines():
            click.echo(f'    {line}')
        trace = profiling.chrome_trace(snap, pid=int(rid))
        trace_events.extend(trace['traceEvents'])
        click.echo('')
    if export_trace:
        with open(export_trace, 'w', encoding='utf-8') as f:
            json.dump({'traceEvents': trace_events,
                       'displayTimeUnit': 'ms'}, f)
        click.echo(f'Chrome trace written to {export_trace} '
                   '(open in chrome://tracing or Perfetto).')


def _sparkline(values, empty: str = '-') -> str:
    """Unicode sparkline of a binned series (None bins render as a
    space); scaled to the series max."""
    blocks = '▁▂▃▄▅▆▇█'
    present = [v for v in values or [] if v is not None]
    if not present:
        return empty
    hi = max(present)
    out = []
    for v in values:
        if v is None:
            out.append(' ')
        elif hi <= 0:
            out.append(blocks[0])
        else:
            out.append(blocks[min(len(blocks) - 1,
                                  int(v / hi * (len(blocks) - 1)
                                      + 0.5))])
    return ''.join(out)


def _fetch_telemetry(record) -> Optional[Dict[str, Any]]:
    """GET /controller/telemetry for one service (None when the
    controller is unreachable — `serve top` then shows fleet state
    only)."""
    import requests  # pylint: disable=import-outside-toplevel

    from skypilot_tpu.serve import http_protocol  # pylint: disable=import-outside-toplevel
    port = record.get('controller_port')
    if not port:
        return None
    try:
        resp = requests.get(
            f'http://127.0.0.1:{port}'
            f'{http_protocol.CONTROLLER_TELEMETRY}',
            timeout=5)
        resp.raise_for_status()
        return resp.json()
    except (requests.RequestException, ValueError):
        return None


def _fmt_tick_breakdown(phases: Optional[Dict[str, float]],
                        top: int = 2) -> str:
    """Compact `phase NN%` summary of a replica's tick-phase rates
    (the dominant `top` phases, shares of the recorded total)."""
    if not phases:
        return '-'
    total = sum(v for v in phases.values() if v) or 0.0
    if total <= 0:
        return '-'
    ranked = sorted(phases.items(), key=lambda kv: -(kv[1] or 0.0))
    return ' '.join(f'{name} {100.0 * (v or 0.0) / total:.0f}%'
                    for name, v in ranked[:top])


def _render_top(records, telemetry_by_service) -> None:
    """One `serve top` frame from already-fetched data (pure render —
    tests drive this directly)."""
    for r in records:
        telemetry = telemetry_by_service.get(r['name']) or {}
        mfu = telemetry.get('mfu') or {}
        breakdown = telemetry.get('tick_breakdown') or {}
        recompiles = telemetry.get('recompiles') or {}
        err_rates = telemetry.get('log_error_rates') or {}
        ready = sum(1 for rep in r['replicas']
                    if rep['status'] == 'READY')
        click.echo(f"{r['name']}  [{r['status']}]  v{r['version']}  "
                   f"{ready}/{len(r['replicas'])} ready  "
                   f"LB :{r.get('load_balancer_port') or '-'}")
        def fmt_mfu(v):
            if v is None:
                return '-'
            # Tiny models / emulated chips produce real-but-minuscule
            # MFU; scientific notation beats rendering 0.0000.
            return f'{v:.4f}' if v >= 5e-4 or v == 0 else f'{v:.1e}'

        rows = []
        for rep in r['replicas']:
            rid = str(rep['replica_id'])
            recomp = recompiles.get(rid)
            err = err_rates.get(rid)
            rows.append((rep['replica_id'],
                         rep.get('role') or 'mixed',
                         rep['status'], rep.get('url') or '-',
                         fmt_mfu(mfu.get(rid)),
                         _fmt_tick_breakdown(breakdown.get(rid)),
                         '-' if recomp is None else f'{recomp:g}',
                         '-' if err is None else f'{err:.3g}'))
        if rows:
            _print_table(['REPLICA', 'ROLE', 'STATUS', 'URL', 'MFU',
                          'TICK-BREAKDOWN', 'RECOMPILES', 'ERR/s'],
                         rows)
        roles = telemetry.get('roles') or {}
        if roles:
            click.echo('')
            rows = []
            for role, sig in sorted(roles.items()):
                def fmt(v, suffix=''):
                    return '-' if v is None else f'{v:g}{suffix}'
                rows.append((
                    role, fmt(sig.get('qps')),
                    _sparkline(sig.get('qps_spark')),
                    _sparkline(sig.get('tokens_per_s_spark')),
                    fmt(sig.get('ttft_p99_ms'), 'ms'),
                    fmt(sig.get('itl_p99_ms'), 'ms')))
            _print_table(['ROLE', 'QPS', 'QPS HISTORY',
                          'TOK/S HISTORY', 'TTFT p99', 'ITL p99'],
                         rows)
        batch = telemetry.get('batch') or None
        if batch:
            # Bulk-inference plane: only rendered while a batch driver
            # is actually pushing rows through the fleet.
            click.echo('')
            epochs = batch.get('weight_epochs') or {}
            epoch_str = ','.join(
                f'{rid}:{ep}' for rid, ep in sorted(epochs.items())
                if rid is not None) or '-'
            rps = batch.get('rows_per_s')
            _print_table(
                ['BATCH ROWS', 'ROWS/s', 'WEIGHT EPOCHS', 'SWAPS'],
                [(f"{batch.get('rows_total', 0):g}",
                  '-' if rps is None else f'{rps:.3g}',
                  epoch_str,
                  f"{batch.get('weight_swaps_total', 0):g}")])
        slos = telemetry.get('slos') or []
        if slos:
            click.echo('')
            rows = [(s['slo'], s.get('target', '-'),
                     f"{s.get('burn_fast', 0):g}",
                     f"{s.get('burn_slow', 0):g}",
                     'BREACH' if s.get('breaching') else 'ok')
                    for s in slos]
            _print_table(['SLO', 'TARGET', 'BURN fast', 'BURN slow',
                          'STATUS'], rows)
        spikes = telemetry.get('log_spikes') or []
        if spikes:
            click.echo('')
            rows = [(s.get('replica_id', '?'),
                     f"{s.get('rate_fast', 0):g}",
                     f"{s.get('rate_slow', 0):g}",
                     f"{s.get('threshold', 0):g}",
                     'SPIKE' if s.get('spiking') else 'ok')
                    for s in spikes]
            _print_table(['LOG ERRORS', 'ERR/s fast', 'ERR/s slow',
                          'THRESHOLD', 'STATUS'], rows)
        slow = telemetry.get('slow_traces') or []
        if slow:
            click.echo('')
            rows = [(s.get('request_id', '?'),
                     s.get('replica_id', '-'),
                     s.get('role') or '-',
                     f"{s.get('duration_ms', 0):.1f}ms",
                     f"{s['ttft_ms']:.1f}ms"
                     if s.get('ttft_ms') is not None else '-',
                     s.get('status', '-'))
                    for s in slow[:8]]
            _print_table(['SLOWEST TRACES', 'REPLICA', 'ROLE',
                          'TOTAL', 'TTFT', 'STATUS'], rows)
        click.echo('')


@serve_group.command(name='top')
@click.argument('service_names', nargs=-1)
@click.option('--refresh', '-r', 'refresh_every', type=float,
              default=2.0, help='Redraw every N seconds.')
@click.option('--once', is_flag=True, default=False,
              help='Print one frame and exit (scripting/CI).')
def serve_top(service_names, refresh_every, once):
    """Live fleet dashboard: replica table with per-replica MFU,
    per-role QPS/throughput sparklines and latency quantiles from the
    controller's telemetry ring buffers, SLO burn status, and the
    slowest recent traces."""
    import time as time_lib  # pylint: disable=import-outside-toplevel

    from skypilot_tpu import serve  # pylint: disable=import-outside-toplevel

    def _frame():
        records = serve.status(list(service_names) or None)
        if not records:
            click.echo('No services.')
            return
        telemetry = {r['name']: _fetch_telemetry(r) for r in records}
        _render_top(records, telemetry)

    if once or refresh_every <= 0:
        _frame()
        return
    try:
        while True:
            click.clear()
            _frame()
            time_lib.sleep(refresh_every)
    except KeyboardInterrupt:
        pass


# ------------------------------------------------------------ bench group


@cli.group(name='bench')
def bench_group():
    """Benchmark a task across candidate resources ($/step)."""


@bench_group.command(name='launch')
@click.argument('entrypoint')
@click.option('--benchmark', '-b', required=True, help='Benchmark name.')
@click.option('--gpus', '--accelerators', 'candidate_accels',
              multiple=True, required=True,
              help="Candidate accelerators (repeatable), e.g. "
                   "-A tpu-v5e-8 -A A100:8.")
@click.option('--cloud', default=None)
@click.option('--yes', '-y', is_flag=True, default=False)
def bench_launch(entrypoint, benchmark, candidate_accels, cloud, yes):
    """Launch ENTRYPOINT once per candidate accelerator."""
    from skypilot_tpu import benchmark as bench_lib  # pylint: disable=import-outside-toplevel
    from skypilot_tpu import resources as resources_lib  # pylint: disable=import-outside-toplevel
    from skypilot_tpu import task as task_lib  # pylint: disable=import-outside-toplevel
    task = task_lib.Task.from_yaml(entrypoint)
    candidates = [
        resources_lib.Resources(cloud=cloud, accelerators=accel)
        for accel in candidate_accels
    ]
    if not yes:
        click.confirm(
            f'Launch {len(candidates)} benchmark cluster(s)?',
            default=True, abort=True)
    clusters = bench_lib.launch_benchmark(task, benchmark, candidates)
    click.echo(f'Benchmark {benchmark} running on: {", ".join(clusters)}')


@bench_group.command(name='show')
@click.argument('benchmark')
def bench_show(benchmark):
    """Collect and show benchmark results."""
    from skypilot_tpu import benchmark as bench_lib  # pylint: disable=import-outside-toplevel
    results = bench_lib.get_benchmark_results(benchmark)
    rows = []
    for r in results:
        rows.append((r['cluster'], r['resources'] or '-',
                     r['num_steps'] or '-',
                     f"{r['seconds_per_step']:.3f}"
                     if r['seconds_per_step'] else '-',
                     f"{r['first_step_seconds']:.1f}"
                     if r['first_step_seconds'] else '-',
                     f"${r['cost_per_step']:.6f}"
                     if r['cost_per_step'] else '-'))
    _print_table(['CLUSTER', 'RESOURCES', 'STEPS', 'SEC/STEP',
                  'FIRST STEP (s)', '$/STEP'], rows)


@bench_group.command(name='ls')
def bench_ls():
    """List benchmarks."""
    from skypilot_tpu.benchmark import benchmark_state  # pylint: disable=import-outside-toplevel
    rows = [(b['name'],) for b in benchmark_state.get_benchmarks()]
    _print_table(['BENCHMARK'], rows)


@bench_group.command(name='down')
@click.argument('benchmark')
@click.option('--yes', '-y', is_flag=True, default=False)
def bench_down(benchmark, yes):
    """Terminate all clusters of a benchmark."""
    from skypilot_tpu import benchmark as bench_lib  # pylint: disable=import-outside-toplevel
    if not yes:
        click.confirm(f'Tear down benchmark {benchmark} clusters?',
                      default=True, abort=True)
    bench_lib.down_benchmark_clusters(benchmark)
    click.echo('Done.')


@bench_group.command(name='delete')
@click.argument('benchmark')
@click.option('--yes', '-y', is_flag=True, default=False)
def bench_delete(benchmark, yes):
    """Delete a benchmark's records."""
    from skypilot_tpu.benchmark import benchmark_state  # pylint: disable=import-outside-toplevel
    if not yes:
        click.confirm(f'Delete benchmark {benchmark}?', default=True,
                      abort=True)
    benchmark_state.remove_benchmark(benchmark)
    click.echo('Deleted.')


@bench_group.command(name='diff')
@click.option('--last', 'last_n', type=int, default=None,
              help='Baseline only the last N prior runs of each '
                   'group (default: all of them).')
@click.option('--history', 'history_file', default=None,
              help='History file (default: BENCH_history.jsonl at the '
                   'repo root, or SKYTPU_BENCH_HISTORY_PATH).')
@click.option('--min-rel', type=float,
              default=None, help='Minimum relative move that can '
              'count as a regression (default 0.10).')
def bench_diff(last_n, history_file, min_rel):
    """Diff the newest bench run of each (metric, config) group
    against its history with noise-aware thresholds.

    `bench.py` appends one record per run to BENCH_history.jsonl; this compares throughput, latency quantiles,
    and MFU against the baseline runs and **exits non-zero when any
    key moved past ``max(min_rel, 3 x cv)`` in the bad direction** —
    wire it after a bench run for a perf-regression gate."""
    from skypilot_tpu.observability import bench_history  # pylint: disable=import-outside-toplevel
    records = bench_history.load_records(history_file)
    if not records:
        raise click.ClickException(
            f'No bench history at '
            f'{bench_history.history_path(history_file)} — run '
            f'bench.py first.')
    kwargs = {}
    if min_rel is not None:
        kwargs['min_rel'] = min_rel
    findings = bench_history.diff_records(records, last=last_n,
                                          **kwargs)
    if not findings:
        click.echo(f'{len(records)} run(s), but no group has two '
                   'comparable runs yet — nothing to diff.')
        return
    for line in bench_history.format_findings(findings):
        click.echo(line)
    regressions = [f for f in findings if f['regression']]
    if regressions:
        raise SystemExit(
            f'{len(regressions)} perf regression(s) detected.')
    click.echo('No regressions.')


# ---------------------------------------------------------- storage group


@cli.group(name='storage')
def storage_group():
    """Bucket-backed storage objects."""


@storage_group.command(name='ls')
def storage_ls():
    """List storage objects."""
    from skypilot_tpu import core  # pylint: disable=import-outside-toplevel
    records = core.storage_ls()
    rows = [(r['name'], r['status'],
             ', '.join(r['handle'].get('store_types', []))
             if isinstance(r.get('handle'), dict) else '-')
            for r in records]
    _print_table(['NAME', 'STATUS', 'STORES'], rows)


@storage_group.command(name='delete')
@click.argument('names', nargs=-1, required=True)
@click.option('--yes', '-y', is_flag=True, default=False)
def storage_delete(names, yes):
    """Delete storage objects (and their buckets)."""
    from skypilot_tpu import core  # pylint: disable=import-outside-toplevel
    from skypilot_tpu import exceptions  # pylint: disable=import-outside-toplevel
    if not yes:
        click.confirm(f'Delete storage {", ".join(names)}?',
                      default=True, abort=True)
    for name in names:
        try:
            core.storage_delete(name)
        except exceptions.StorageError as e:
            click.echo(str(e), err=True)
            continue
        click.echo(f'Storage {name} deleted.')


# ---------------------------------------------------------- catalog group


@cli.group(name='catalog')
def catalog_group():
    """Price catalogs (list/refresh)."""


@catalog_group.command(name='refresh')
@click.option('--cloud', default='gcp', help='Cloud whose catalog to fetch.')
@click.option('--api-key', default=None,
              help='API key for the billing catalog API (optional).')
def catalog_refresh(cloud, api_key):
    """Re-fetch price catalogs from the cloud's SKU API."""
    from skypilot_tpu import catalog  # pylint: disable=import-outside-toplevel
    try:
        out = catalog.refresh(cloud, api_key=api_key)
    except Exception as e:  # pylint: disable=broad-except
        raise click.ClickException(
            f'Catalog refresh failed ({e}); the previous catalog remains '
            'in use.')
    for name, path in out.items():
        click.echo(f'{name}: {path}')


@catalog_group.command(name='status')
@click.option('--cloud', default='gcp')
def catalog_status(cloud):
    """Show catalog freshness."""
    from skypilot_tpu import catalog  # pylint: disable=import-outside-toplevel
    rows = []
    for name, age in catalog.catalog_age_hours(cloud).items():
        rows.append((name, 'embedded snapshot' if age is None
                     else f'fetched {age:.1f}h ago'))
    _print_table(['CATALOG', 'FRESHNESS'], rows)


# ------------------------------------------------------------ chaos group


@cli.group(name='chaos')
def chaos_group():
    """Deterministic fault injection with journal-verified recovery.

    Scenarios drive real launch->fault->recover flows on the local
    backend and replay the flight-recorder journal through liveness/
    safety invariants.  See docs/chaos.md for the fault-plan DSL
    (SKYTPU_CHAOS_PLAN) and the injection-site vocabulary.
    """


@chaos_group.command(name='list')
@click.option('--sites', 'show_sites', is_flag=True, default=False,
              help='Also list the registered injection sites.')
def chaos_list(show_sites):
    """List chaos scenarios (and optionally the site vocabulary)."""
    from skypilot_tpu.chaos import faults as faults_lib  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.chaos import scenarios as scenarios_lib  # pylint: disable=import-outside-toplevel
    rows = [(name, s.description)
            for name, s in sorted(scenarios_lib.SCENARIOS.items())]
    _print_table(['SCENARIO', 'DESCRIPTION'], rows)
    if show_sites:
        click.echo()
        _print_table(
            ['SITE', 'WHERE / EFFECT NOTES'],
            [(name, desc.replace('\n', ' '))
             for name, desc in sorted(faults_lib.SITES.items())])


@chaos_group.command(name='run')
@click.argument('scenario')
@click.option('--seed', type=int, default=0,
              help='Fault-plan seed; the same seed reproduces the '
                   'identical fault sequence.')
@click.option('--export-trace', 'export_trace', default=None,
              help='Write the scenario\'s merged journal as a '
                   'Chrome-trace JSON to this path.')
def chaos_run(scenario, seed, export_trace):
    """Run one chaos scenario and verify its journal invariants."""
    from skypilot_tpu.chaos import scenarios as scenarios_lib  # pylint: disable=import-outside-toplevel
    try:
        result = scenarios_lib.run_scenario(scenario, seed=seed,
                                            export_trace=export_trace)
    except ValueError as e:
        raise click.ClickException(str(e))
    click.echo(result.summary())
    if result.fault_sequence:
        click.echo('Fault sequence:')
        for fault in result.fault_sequence:
            click.echo(f'  #{fault["call"]:<3d} {fault["site"]:<24s} '
                       f'{fault["effect"]}')
    for key, value in sorted(result.details.items()):
        click.echo(f'  {key}: {value}')
    if export_trace:
        click.echo(f'Chrome trace written to {export_trace} '
                   '(open in chrome://tracing or Perfetto).')
    if not result.ok:
        for violation in result.violations:
            click.echo(f'  VIOLATION: {violation}')
        raise click.ClickException(
            f'{len(result.violations)} invariant violation(s).')


def _changed_package_files(pkg_root) -> Optional[set]:
    """Package-relative paths of files touched vs git HEAD (staged,
    unstaged, and untracked); None when git is unavailable — the
    caller then falls back to the full-tree report."""
    import pathlib  # pylint: disable=import-outside-toplevel
    import subprocess  # pylint: disable=import-outside-toplevel
    repo_root = pathlib.Path(pkg_root).parent
    try:
        out = subprocess.run(
            ['git', 'status', '--porcelain', '--untracked-files=all'],
            cwd=repo_root, capture_output=True, text=True, timeout=10,
            check=True).stdout
    except (OSError, subprocess.SubprocessError):
        return None
    changed = set()
    prefix = pathlib.Path(pkg_root).name + '/'
    for line in out.splitlines():
        # XY <path> (or `XY <old> -> <new>` for renames: take the new).
        path = line[3:].split(' -> ')[-1].strip().strip('"')
        if path.startswith(prefix):
            changed.add(path[len(prefix):])
    return changed


@cli.command()
@click.option('--rule', 'rules', multiple=True,
              help='Run only the passes owning these rule ids '
                   '(repeatable); framework rules always run.')
@click.option('--json', 'as_json', is_flag=True, default=False,
              help='Deterministic JSON report (diffable; byte-'
                   'identical across runs on one tree).')
@click.option('--list-rules', is_flag=True, default=False,
              help='Print the rule catalog and exit.')
@click.option('--changed', 'changed_only', is_flag=True, default=False,
              help='Report only findings in files changed vs git HEAD '
                   '(staged/unstaged/untracked).  The FULL package is '
                   'still indexed and every pass still runs — cross-'
                   'module contracts need the whole tree — only the '
                   'report is filtered, for fast fix iteration.')
@click.option('--update-baseline', is_flag=True, default=False,
              help='Grandfather every current unsuppressed finding '
                   'into lint-baseline.json (the file only shrinks '
                   'after that: stale entries fail lint).')
def lint(rules, as_json, list_rules, changed_only, update_baseline):
    """Static analysis over the whole package (AST-only, no imports).

    Exit 1 on unsuppressed findings.  Rule catalog, suppression
    syntax, and the baseline workflow: docs/static-analysis.md.
    """
    import pathlib  # pylint: disable=import-outside-toplevel

    from skypilot_tpu import analysis  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.analysis import core as lint_core  # pylint: disable=import-outside-toplevel
    if list_rules:
        for rule, owner in sorted(lint_core.rule_catalog().items()):
            click.echo(f'{rule:24s} {owner}')
        return
    if changed_only and update_baseline:
        raise click.ClickException(
            '--changed filters the report; the baseline must be '
            'written from a full run.')
    pkg_root = pathlib.Path(__file__).resolve().parent
    baseline = pkg_root.parent / lint_core.BASELINE_FILENAME
    idx = analysis.PackageIndex(pkg_root)
    try:
        result = lint_core.run_lint(
            idx, rules=list(rules) or None,
            baseline_path=baseline if baseline.is_file() else None)
    except ValueError as e:   # unknown --rule
        raise click.ClickException(str(e))
    if changed_only:
        changed = _changed_package_files(pkg_root)
        if changed is None:
            click.echo('git unavailable; reporting the full tree.',
                       err=True)
        else:
            result.findings = [f for f in result.findings
                               if f.file in changed]
            result.suppressed = [f for f in result.suppressed
                                 if f.file in changed]
            result.baselined = [f for f in result.baselined
                                if f.file in changed]
    if update_baseline:
        # Keep still-reproducing grandfathered findings, add the new
        # ones; never baseline the framework's own meta-findings.
        keep = [f for f in result.findings + result.baselined
                if f.rule not in (lint_core.RULE_BASELINE_STALE,
                                  'suppression-invalid')]
        lint_core.write_baseline(baseline, keep)
        click.echo(f'Baselined {len(keep)} finding(s) into '
                   f'{baseline}.')
        return
    if as_json:
        click.echo(result.to_json())
    else:
        for f in result.findings:
            click.echo(f'skypilot_tpu/{f.render()}')
        click.echo(f'{len(result.findings)} finding(s), '
                   f'{len(result.suppressed)} suppressed, '
                   f'{len(result.baselined)} baselined '
                   f'({len(idx.modules)} modules, '
                   f'{result.duration_s:.1f}s).')
    if not result.ok:
        raise SystemExit(1)


def main() -> None:
    # Pin the completion trigger var: click otherwise derives it from
    # the program name, which breaks completion when invoked as
    # `python -m skypilot_tpu.cli` instead of the `skytpu` script.
    cli(complete_var='_SKYTPU_COMPLETE')


if __name__ == '__main__':
    main()
