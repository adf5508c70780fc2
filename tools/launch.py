#!/usr/bin/env python
"""Multi-host job launcher (rebuild of tools/launch.py + the dmlc-core
ssh tracker).

The reference starts a scheduler plus N servers/workers and wires them
through ``DMLC_*`` env rendezvous.  The TPU-native control plane is
``jax.distributed``: one coordinator address, ``num_processes`` and a
``process_id`` per host — the launcher's job is only to spawn the
program everywhere with those env vars set (`MXTPU_COORDINATOR`,
`MXTPU_NUM_PROCS`, `MXTPU_PROC_ID`, consumed by
mxnet_tpu.kvstore.create("dist_sync")).

Modes:
  local: spawn -n processes on this machine, each pinned to the CPU
         backend (CPU mesh testing: N processes cannot share one
         machine's chip, which belongs to a single process)
  ssh:   spawn one process per host in -H hostfile via ssh, rsyncing
         the working dir first (reference ssh tracker behavior)
"""

import argparse
import os
import signal
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _with_repo_path(env):
    """Children must import mxnet_tpu regardless of the caller's cwd
    (the launcher is invoked from anywhere; the package is not
    pip-installed)."""
    pp = env.get("PYTHONPATH", "")
    if _REPO not in pp.split(os.pathsep):
        env["PYTHONPATH"] = _REPO + (os.pathsep + pp if pp else "")
    return env


def _child_env(coordinator, n, rank, extra=None):
    """Environment of one LOCAL rank: the rendezvous variables, and the
    CPU pin — a chip belongs to one process, so n local ranks that all
    opened it would fail or hang."""
    env = dict(os.environ)
    env.update({
        "MXTPU_COORDINATOR": coordinator,
        "MXTPU_NUM_PROCS": str(n),
        "MXTPU_PROC_ID": str(rank),
        "JAX_PLATFORMS": "cpu",
    })
    if extra:
        env.update(extra)
    return _with_repo_path(env)


def _drain(stream):
    """Discard a child's stdout after the handshake so later prints (e.g.
    logging from an unpickled server-side optimizer) cannot fill the pipe
    and block the server mid-request."""
    import threading

    def run():
        for _ in stream:
            pass

    threading.Thread(target=run, daemon=True).start()


def _spawn_servers(num_servers, num_workers):
    """Start parameter-server shard processes (reference tracker starting
    server nodes); returns (procs, comma-joined addr list)."""
    procs, addrs = [], []
    try:
        for _ in range(num_servers):
            proc = subprocess.Popen(
                [sys.executable, "-m", "mxnet_tpu.ps",
                 "--workers", str(num_workers)],
                stdout=subprocess.PIPE, text=True,
                env=_with_repo_path(dict(os.environ)))
            procs.append(proc)
            line = proc.stdout.readline().strip()
            if not line.startswith("PS_ADDR "):
                raise RuntimeError(
                    f"parameter server failed to start: {line!r}")
            addrs.append(line.split(" ", 1)[1])
            _drain(proc.stdout)
        return procs, ",".join(addrs)
    except Exception:
        for p in procs:
            p.kill()
        raise


def launch_local(n, command, extra_env=None, num_servers=0, max_restarts=0):
    """Spawn n local processes with distinct ranks; returns exit code.

    With ``max_restarts`` > 0 a worker that exits nonzero is respawned
    under the same rank (elastic recovery: PS servers keep state and
    treat the restarted worker's re-init as a no-op, the reference's
    ps-lite is_recovery contract).  Only meaningful with ``-s`` servers;
    collectives-backed jobs cannot absorb a member restart.
    """
    import time

    if max_restarts and not num_servers:
        raise ValueError(
            "--max-restarts requires -s servers: a collectives-backed job "
            "cannot absorb a member restart (the jax.distributed world is "
            "already formed); it would hang instead of failing fast")
    coordinator = f"127.0.0.1:{_free_port()}"
    procs = {}
    restarts = {rank: 0 for rank in range(n)}
    server_procs = []
    extra = dict(extra_env or {})
    try:
        if num_servers:
            server_procs, addrs = _spawn_servers(num_servers, n)
            extra["MXTPU_PS_ADDRS"] = addrs
        for rank in range(n):
            procs[rank] = subprocess.Popen(
                command, env=_child_env(coordinator, n, rank, extra))
        code = 0
        pending = set(procs)
        while pending:
            for rank in sorted(pending):
                rc = procs[rank].poll()
                if rc is None:
                    continue
                if rc != 0 and restarts[rank] < max_restarts:
                    restarts[rank] += 1
                    sys.stderr.write(
                        f"worker rank {rank} exited rc={rc}; restart "
                        f"{restarts[rank]}/{max_restarts}\n")
                    # reference is_recovery contract: the restarted node
                    # knows to skip startup barriers
                    renv = dict(extra)
                    renv["MXTPU_IS_RECOVERY"] = "1"
                    procs[rank] = subprocess.Popen(
                        command, env=_child_env(coordinator, n, rank, renv))
                else:
                    code = rc or code
                    pending.discard(rank)
            time.sleep(0.1)
        return code
    finally:
        for p in list(procs.values()) + server_procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)


def launch_gang(n, command, extra_env=None, gang_restarts=0):
    """Spawn n ranks as ONE gang: if any member dies, kill the rest and
    respawn the whole job (fresh coordinator port) up to
    ``gang_restarts`` times, with ``MXTPU_RESTART_COUNT`` incremented
    and ``MXTPU_IS_RECOVERY=1`` set for every rank of the new life.

    This is the collectives-backed (SPMD) elastic contract — the
    jax.distributed world cannot absorb a single-member restart the way
    the PS mode can (--max-restarts), so recovery is gang-level:
    workers are expected to resume from their latest complete sharded
    checkpoint (parallel/checkpoint.py), the pod-scale analog of the
    reference's tracker restarting a dead job from model.save files."""
    import time

    life = 0
    while True:
        coordinator = f"127.0.0.1:{_free_port()}"
        extra = dict(extra_env or {})
        extra["MXTPU_RESTART_COUNT"] = str(life)
        if life:
            extra["MXTPU_IS_RECOVERY"] = "1"
        procs = {rank: subprocess.Popen(
            command, env=_child_env(coordinator, n, rank, extra))
            for rank in range(n)}
        failed = None
        pending = set(procs)
        try:
            while pending and failed is None:
                for rank in sorted(pending):
                    rc = procs[rank].poll()
                    if rc is None:
                        continue
                    if rc != 0:
                        failed = (rank, rc)
                        break
                    pending.discard(rank)
                time.sleep(0.1)
        finally:
            if failed is not None or pending:
                # one death hangs peers in collectives: kill the gang
                for p in procs.values():
                    if p.poll() is None:
                        p.kill()
                for p in procs.values():
                    p.wait()
        if failed is None:
            return 0
        if life >= gang_restarts:
            sys.stderr.write(
                f"gang member rank {failed[0]} exited rc={failed[1]}; "
                "restart budget exhausted\n")
            return failed[1]
        life += 1
        sys.stderr.write(
            f"gang member rank {failed[0]} exited rc={failed[1]}; "
            f"gang restart {life}/{gang_restarts}\n")


def launch_ssh(hostfile, command, sync_dir=None, username=None):
    with open(hostfile) as f:
        hosts = [h.strip() for h in f if h.strip() and not h.startswith("#")]
    n = len(hosts)
    coordinator = f"{hosts[0]}:{_free_port()}"
    cwd = sync_dir or os.getcwd()
    procs = []
    for rank, host in enumerate(hosts):
        target = f"{username}@{host}" if username else host
        if sync_dir:
            subprocess.check_call(
                ["rsync", "-az", "--delete", cwd + "/", f"{target}:{cwd}/"])
        env_prefix = (f"MXTPU_COORDINATOR={coordinator} "
                      f"MXTPU_NUM_PROCS={n} MXTPU_PROC_ID={rank} "
                      # same contract as _with_repo_path: remote ranks
                      # must import mxnet_tpu from the synced tree no
                      # matter what cwd the job uses
                      f"PYTHONPATH={_REPO}${{PYTHONPATH:+:$PYTHONPATH}}")
        remote = f"cd {cwd} && {env_prefix} {' '.join(command)}"
        procs.append(subprocess.Popen(["ssh", "-o", "BatchMode=yes",
                                       target, remote]))
    code = 0
    for p in procs:
        code = p.wait() or code
    return code


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-n", "--num-workers", type=int, default=1)
    p.add_argument("-s", "--num-servers", type=int, default=0,
                   help="parameter-server shards for dist_async/dist_sync "
                        "PS mode (reference dmlc tracker -s)")
    p.add_argument("--max-restarts", type=int, default=0,
                   help="respawn a crashed worker under the same rank up "
                        "to N times (PS mode keeps state; is_recovery "
                        "analog)")
    p.add_argument("--gang-restarts", type=int, default=0,
                   help="collectives-mode elastic: if any rank dies, "
                        "restart the WHOLE job up to N times (workers "
                        "resume from their latest sharded checkpoint)")
    p.add_argument("-H", "--hostfile", default=None,
                   help="one host per line; enables ssh mode")
    p.add_argument("--launcher", choices=["local", "ssh"], default=None)
    p.add_argument("--sync-dir", default=None,
                   help="rsync this dir to all hosts before launch")
    p.add_argument("--username", default=None)
    p.add_argument("command", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if not args.command:
        p.error("no command given")
    command = args.command[1:] if args.command[0] == "--" else args.command
    mode = args.launcher or ("ssh" if args.hostfile else "local")
    if mode == "ssh":
        if not args.hostfile:
            p.error("ssh mode needs -H hostfile")
        return launch_ssh(args.hostfile, command, args.sync_dir, args.username)
    if args.gang_restarts:
        if args.num_servers or args.max_restarts:
            p.error("--gang-restarts is the collectives-mode elastic "
                    "path; it does not compose with -s/--max-restarts")
        return launch_gang(args.num_workers, command,
                           gang_restarts=args.gang_restarts)
    return launch_local(args.num_workers, command,
                        num_servers=args.num_servers,
                        max_restarts=args.max_restarts)


if __name__ == "__main__":
    sys.exit(main())
