"""Multi-host launcher. Reference: python/paddle/distributed/launch.py
(paddle.distributed.launch CLI spawning one proc per device + elastic).

TPU-native: one process per HOST (JAX single-controller per host drives all
local chips; a chip belongs to one process at a time). The launcher itself
never initialises a jax backend, so it holds no chip. It execs the script once
per host via the same env-var contract as the reference (PADDLE_TRAINER_ID
/ TRAINERS_NUM / MASTER). ``--nproc_per_node N`` with N > 1 is the
multi-process EMULATION layout and needs ``JAX_PLATFORMS=cpu``: on a host
with TPU chips every child would open all of them and all but one would
fail or hang, so the launcher refuses instead. Plus an elastic watchdog
with TWO failure detectors:
 - exit watch: restart on nonzero child exit (up to --max_restarts);
 - liveness watch: the framework touches a heartbeat file every train step
   (hapi.Model train steps call ``touch_heartbeat``; custom loops may call
   it directly). If the file goes stale for longer than
   --heartbeat_timeout the child is presumed hung (e.g. blocked inside a
   collective whose peer died — exit codes never fire for those),
   SIGTERM'd, then SIGKILL'd, and restarted. Resume comes from the latest
   checkpoint the script wrote (orbax/hapi save).
On a pod slice, run this on every host (GKE/xmanager provide the env).
"""
import argparse
import os
import signal
import subprocess
import sys
import time

HEARTBEAT_ENV = 'PADDLE_HEARTBEAT_FILE'


def touch_heartbeat():
    """Signal liveness to the launcher (no-op when not launched by it)."""
    path = os.environ.get(HEARTBEAT_ENV)
    if not path:
        return
    try:
        with open(path, 'a'):
            os.utime(path, None)
    except OSError:
        pass


def _parse(argv=None):
    p = argparse.ArgumentParser('paddle_tpu.distributed.launch')
    p.add_argument('--nnodes', type=int,
                   default=int(os.environ.get('PADDLE_TRAINERS_NUM', '1')))
    p.add_argument('--node_rank', type=int,
                   default=int(os.environ.get('PADDLE_TRAINER_ID', '0')))
    # reference CLI compat: --nproc_per_node spawns that many local
    # jax.distributed processes (on TPU the normal layout is ONE process
    # per host driving all local chips). --gpus/--devices take the
    # reference's comma-separated device-id list; here the LIST LENGTH is
    # the local process count (the ids themselves are meaningless for a
    # TPU mesh).
    p.add_argument('--nproc_per_node', dest='nproc', type=int, default=None)
    p.add_argument('--gpus', '--devices', dest='device_list', default=None)
    p.add_argument('--master', default=os.environ.get('PADDLE_MASTER', ''))
    p.add_argument('--max_restarts', type=int, default=0)
    p.add_argument('--heartbeat_timeout', type=float, default=0.0,
                   help='seconds of heartbeat-file staleness before the '
                        'child is declared hung and restarted; 0 disables')
    # elastic membership (reference fleet/elastic --np + etcd; here a
    # shared membership directory — see fleet/elastic.py)
    p.add_argument('--elastic_dir', default=None,
                   help='shared membership directory enabling elastic '
                        'scale up/down across launchers')
    p.add_argument('--np', dest='np_spec', default=None,
                   help='MIN[:MAX] node count for elastic mode')
    p.add_argument('--elastic_poll', type=float, default=1.0)
    p.add_argument('--ckpt_dir', default=None,
                   help='checkpoint directory (utils.checkpoint layout): '
                        'before each lifetime the launcher finds the latest '
                        'VERIFIED step, advertises it through the elastic '
                        'KVStore, and exports the membership-agreed restore '
                        'point as PADDLE_RESUME_STEP to the children')
    p.add_argument('--log_dir', default=None)
    p.add_argument('training_script')
    p.add_argument('training_script_args', nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _kill(proc):
    proc.terminate()
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


_shutdown_requested = False


def _agree_resume_step(ckpt_dir, mgr):
    """Latest locally-verified checkpoint step, reconciled with elastic
    peers (min over live members' advertisements) so every re-ranked worker
    restores the same state. Returns None when no verified step exists."""
    from ..utils.checkpoint import latest_verified_step
    step = latest_verified_step(ckpt_dir)
    if mgr is None:
        return step
    if step is not None:
        mgr.advertise_step(step)
    agreed = mgr.agreed_step()
    if agreed is not None and agreed != step:
        print(f'[launch] resume point: local verified step {step}, '
              f'membership agreed {agreed}', file=sys.stderr)
    return agreed if agreed is not None else step


def _run_group(cmd, envs, hb_paths, hb_timeout, stop_check=None):
    """One lifetime of the local process group. All-or-nothing (elastic
    restarts are whole-group, like the reference): first nonzero exit or
    stale heartbeat kills the rest. Returns (exit_code | None, hung,
    stop_reason). ``stop_check()`` (elastic membership poll) may return a
    reason string to gracefully stop the group for a rescale."""
    procs = []
    for env, hb in zip(envs, hb_paths):
        if hb:
            env = dict(env, **{HEARTBEAT_ENV: hb})
            with open(hb, 'a'):
                os.utime(hb, None)        # fresh epoch for this lifetime
        procs.append(subprocess.Popen(cmd, env=env))

    def _fwd(sig, frame):
        # record the external shutdown so main() EXITS instead of treating
        # the children's 143s as a crash and resurrecting the job
        global _shutdown_requested
        _shutdown_requested = True
        for p in procs:
            p.send_signal(sig)
    signal.signal(signal.SIGTERM, _fwd)

    live = set(range(len(procs)))
    poll_s = min(hb_timeout / 4.0, 5.0) if hb_timeout > 0 else 1.0
    if stop_check is not None:
        poll_s = min(poll_s, 0.5)
    while live:
        time.sleep(poll_s if len(live) < len(procs) or hb_timeout > 0
                   or stop_check is not None else 0.2)
        for i in sorted(live):
            code = procs[i].poll()
            if code is not None:
                live.discard(i)
                if code != 0:
                    for j in live:
                        _kill(procs[j])
                    return code, False, None
        if stop_check is not None:
            reason = stop_check()
            if reason:
                print(f'[launch] elastic: {reason} — stopping group for '
                      'rescale', file=sys.stderr)
                for j in live:
                    _kill(procs[j])
                return None, False, reason
        if hb_timeout > 0:
            for i in sorted(live):
                hb = hb_paths[i]
                try:
                    stale = time.time() - os.path.getmtime(hb)
                except OSError:
                    stale = 0.0
                if stale > hb_timeout:
                    print(f'[launch] rank {i} heartbeat stale {stale:.0f}s '
                          f'(> {hb_timeout:.0f}s): group presumed hung, '
                          'killing', file=sys.stderr)
                    for j in live:
                        _kill(procs[j])
                    return None, True, None
    return 0, False, None


def _local_tpu_chips():
    """TPU chips this host exposes, counted from their device nodes (the
    launcher must not ask jax: that would take the chips from the
    children)."""
    import glob
    return len(glob.glob('/dev/accel[0-9]*')
               + glob.glob('/dev/vfio/[0-9]*'))


def _refuse_shared_chips(nproc):
    """N > 1 local processes are fine on the CPU platform; on a TPU host
    they would all open the same chips."""
    if nproc <= 1 or os.environ.get('JAX_PLATFORMS', '').lower() == 'cpu':
        return
    chips = _local_tpu_chips()
    if chips:
        sys.exit(
            f'[launch] refusing --nproc_per_node {nproc}: this host has '
            f'{chips} TPU chip(s) and a chip belongs to one process — '
            f'every child would open all of them and hang. On a TPU host '
            f'run ONE process (the default) that drives all local chips; '
            f'for multi-process emulation set JAX_PLATFORMS=cpu.')


def _build_envs(args, nproc, nnodes, node_rank):
    total = nnodes * nproc
    master = args.master
    if not master and nnodes == 1 and nproc > 1:
        # single-node multi-process: localhost coordinator is correct.
        # Multi-NODE without --master stays unset so init_parallel_env
        # skips jax.distributed (a loud fast misconfig, not a silent hang
        # against the wrong host's localhost).
        master = '127.0.0.1'
    envs = []
    for local_rank in range(nproc):
        env = dict(os.environ)
        env['PADDLE_TRAINERS_NUM'] = str(total)
        env['PADDLE_TRAINER_ID'] = str(node_rank * nproc + local_rank)
        env['PADDLE_LOCAL_RANK'] = str(local_rank)
        if master:
            host, _, port = master.partition(':')
            env['PADDLE_MASTER'] = host
            env['MASTER_PORT'] = port or '8476'
        envs.append(env)
    return envs


def main(argv=None):
    args = _parse(argv)
    if args.nproc is not None:
        nproc = max(1, args.nproc)
    elif args.device_list:
        nproc = len([d for d in args.device_list.split(',') if d != ''])
    else:
        nproc = 1
    _refuse_shared_chips(nproc)
    hb_paths = [None] * nproc
    if args.heartbeat_timeout > 0:
        base = args.log_dir or '/tmp'
        os.makedirs(base, exist_ok=True)
        hb_paths = [os.path.join(base, f'paddle_hb_{os.getpid()}_{r}')
                    for r in range(nproc)]

    mgr = None
    if args.elastic_dir:
        from .fleet.elastic import ElasticManager, parse_np
        np_min, np_max = parse_np(args.np_spec)
        mgr = ElasticManager(args.elastic_dir,
                             heartbeat_interval=args.elastic_poll,
                             min_nodes=np_min or 1, max_nodes=np_max)
        mgr.register()

    restarts = 0
    try:
        while True:
            if mgr is not None:
                members = mgr.wait_for_quorum()
                eff = mgr.effective(members)
                rank = mgr.rank_of(members)
                if rank is None:          # hot spare beyond max_nodes
                    time.sleep(args.elastic_poll)
                    continue
                nnodes, node_rank = len(eff), rank
                print(f'[launch] elastic lifetime: {nnodes} node(s), '
                      f'this is rank {node_rank}', file=sys.stderr)
                stop_check = lambda: mgr.poll(members)   # noqa: E731
            else:
                nnodes, node_rank = args.nnodes, args.node_rank
                stop_check = None
            envs = _build_envs(args, nproc, nnodes, node_rank)
            if args.ckpt_dir:
                agreed = _agree_resume_step(args.ckpt_dir, mgr)
                if agreed is not None:
                    for env in envs:
                        env['PADDLE_RESUME_STEP'] = str(agreed)
            cmd = ([sys.executable, args.training_script]
                   + args.training_script_args)
            start = time.time()
            code, hung, rescale = _run_group(cmd, envs, hb_paths,
                                             args.heartbeat_timeout,
                                             stop_check=stop_check)
            if code == 0:
                if mgr is not None:
                    # clean completion: tell peers this is NOT a node loss
                    mgr.mark_done()
                return 0
            if _shutdown_requested:
                sys.exit(code if code is not None else 1)
            if rescale:
                # membership changed: relaunch with re-ranked world —
                # does NOT consume a crash-restart budget slot
                print(f'[launch] rescale ({rescale}) after '
                      f'{time.time() - start:.0f}s; relaunching',
                      file=sys.stderr)
                continue
            if restarts >= args.max_restarts:
                sys.exit(code if code is not None else 1)
            restarts += 1
            why = 'hung (heartbeat stale)' if hung else f'exited {code}'
            print(f'[launch] group {why} after {time.time()-start:.0f}s; '
                  f'restart {restarts}/{args.max_restarts}', file=sys.stderr)
    finally:
        if mgr is not None:
            mgr.deregister()


if __name__ == '__main__':
    main()
