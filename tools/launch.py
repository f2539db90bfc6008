#!/usr/bin/env python
"""Multi-process job launcher (reference: tools/launch.py + the dmlc
trackers, 3rdparty/dmlc-core/tracker/dmlc_tracker/{local,ssh,mpi}.py).

TPU-native re-design: the reference starts 1 scheduler + S servers + N
workers talking ps-lite over ZMQ.  Here there are no servers — SPMD
collectives replace the parameter server — so the launcher starts N worker
processes wired to one jax.distributed coordinator via the SAME DMLC_*
environment variables the reference uses, so reference launch scripts keep
working:

    # single machine (the reference's no-cluster test mode; CPU hosts)
    python tools/launch.py -n 2 python train.py --kv-store dist_sync

On a TPU host a training job is ONE process over all the local chips
(``parallel.make_mesh`` + ``SPMDTrainer``): a chip belongs to one process,
every worker inherits the same environment and so claims every local chip,
and the second worker of ``-n N`` on one chip host fails at backend
start-up.  Use ``-n`` greater than one per host only on CPU hosts, or one
process per HOST across TPU hosts with ``--launcher ssh``.

    # multi-machine over ssh (reference: dmlc_tracker/ssh.py)
    python tools/launch.py -n 8 -H hostfile --launcher ssh \
        python train.py --kv-store dist_sync

Env handed to each worker (consumed by parallel.distributed.initialize):
    DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT  -> coordinator address
    DMLC_NUM_WORKER                       -> process count
    DMLC_WORKER_ID                       -> process rank

ssh launcher contract (mirrors dmlc_tracker/ssh.py behavior):
  * hostfile = one host per line ('#' comments and blanks skipped); ranks
    are assigned round-robin over the hosts;
  * each remote command re-exports the DMLC_* contract plus a passthrough
    set (PYTHONPATH, JAX_*, MXNET_*/MXTPU_*) and cd's into the launch
    cwd — the code tree must exist at the same path on every host (the
    reference's --sync-dst-dir rsync convenience is not implemented);
  * rank 0 — and the jax.distributed coordinator — runs on the FIRST
    host; workers dial it at --host (default: the first hostfile entry,
    which must therefore be a name the OTHER hosts can resolve);
  * --ssh-cmd overrides the ssh binary/options (e.g. 'ssh -p 2222').
"""
import argparse
import os
import shlex
import shutil
import socket
import subprocess
import sys
import time


def _worker_env(host, port, num_workers, rank):
    """The DMLC env contract one worker sees (reference: dmlc_tracker)."""
    env = dict(os.environ)
    env["DMLC_PS_ROOT_URI"] = host
    env["DMLC_PS_ROOT_PORT"] = str(port)
    env["DMLC_NUM_WORKER"] = str(num_workers)
    env["DMLC_WORKER_ID"] = str(rank)
    env["DMLC_ROLE"] = "worker"
    return env


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _read_hosts(args, ap):
    hosts = []
    if args.hostfile:
        try:
            with open(args.hostfile) as f:
                for line in f:
                    line = line.split("#", 1)[0].strip()
                    if line:
                        hosts.append(line)
        except OSError as e:
            ap.error(f"cannot read hostfile {args.hostfile!r}: {e}")
    if args.hosts:
        hosts.extend(h.strip() for h in args.hosts.split(",") if h.strip())
    if not hosts:
        ap.error("--launcher ssh needs hosts: -H/--hostfile or --hosts")
    return hosts


_PASSTHROUGH_PREFIXES = ("DMLC_", "MXNET_", "MXTPU_", "JAX_", "XLA_")
_PASSTHROUGH_NAMES = ("PYTHONPATH",)


def _remote_command(env, command, cwd):
    """One shell string that recreates the env contract remotely,
    matching how dmlc_tracker/ssh.py prefixes 'export k=v;' pairs."""
    exports = [f"export {k}={shlex.quote(v)}"
               for k, v in sorted(env.items())
               if k.startswith(_PASSTHROUGH_PREFIXES)
               or k in _PASSTHROUGH_NAMES]
    cmd = " ".join(shlex.quote(c) for c in command)
    return "; ".join(exports + [f"cd {shlex.quote(cwd)}", f"exec {cmd}"])


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True,
                    help="number of worker processes")
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for reference CLI parity; SPMD has no "
                         "parameter servers, so this is ignored")
    ap.add_argument("--launcher", default="local",
                    choices=["local", "ssh", "mpi"],
                    help="'local' (single machine) or 'ssh' (hostfile); "
                         "'mpi' is accepted for reference CLI parity but "
                         "errors with guidance (not available here)")
    ap.add_argument("-H", "--hostfile", default=None,
                    help="ssh: file with one host per line")
    ap.add_argument("--hosts", default=None,
                    help="ssh: comma-separated host list (alternative or "
                         "additional to -H)")
    ap.add_argument("--ssh-cmd", default="ssh",
                    help="ssh: remote-shell command, e.g. 'ssh -p 2222' "
                         "(options are split shell-style)")
    ap.add_argument("--host", default=None,
                    help="coordinator address workers dial; defaults to "
                         "127.0.0.1 (local) or the FIRST hostfile entry "
                         "(ssh — rank 0 runs there)")
    ap.add_argument("--port", type=int, default=0,
                    help="coordinator port (0 = pick a free one)")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="worker command, e.g. python train.py")
    args = ap.parse_args()
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        ap.error("missing worker command")
    if args.num_servers:
        print(f"[launch] note: -s {args.num_servers} ignored — SPMD "
              "collectives replace parameter servers", file=sys.stderr)

    port = args.port or _free_port()
    procs = []

    if args.launcher == "mpi":
        ap.error("--launcher mpi is not implemented in this build; use "
                 "--launcher ssh (same DMLC env contract — mpi only "
                 "differs in who spawns the processes) or "
                 "--launcher local")

    if args.launcher == "ssh":
        hosts = _read_hosts(args, ap)
        ssh_argv = shlex.split(args.ssh_cmd)
        if not ssh_argv or shutil.which(ssh_argv[0]) is None:
            ap.error(
                f"--launcher ssh: remote-shell command {args.ssh_cmd!r} "
                "not found on PATH. Install an ssh client, or point "
                "--ssh-cmd at one; on a machine without ssh, use "
                "--launcher local")
        # rank 0 — and with it the jax.distributed coordinator — runs on
        # hosts[0], so that is the address every worker must dial.  (The
        # port is probed on the launcher, a best-effort the reference
        # tracker shares: it may race a binding on hosts[0]; pass --port
        # to pin a known-free one.)
        host = args.host or hosts[0]
        if host in ("localhost", "127.0.0.1") and any(
                h not in ("localhost", "127.0.0.1") for h in hosts):
            print(f"[launch] warning: coordinator address {host} is "
                  "loopback but the hostfile names remote hosts — they "
                  "will not reach it; pass --host", file=sys.stderr)
        cwd = os.getcwd()
        for rank in range(args.num_workers):
            env = _worker_env(host, port, args.num_workers, rank)
            target = hosts[rank % len(hosts)]
            remote = _remote_command(env, args.command, cwd)
            procs.append(subprocess.Popen(ssh_argv + [target, remote]))
    else:   # local
        host = args.host or "127.0.0.1"
        for rank in range(args.num_workers):
            env = _worker_env(host, port, args.num_workers, rank)
            procs.append(subprocess.Popen(args.command, env=env))

    # supervise ALL workers at once: a crash in any rank while the others
    # block in collectives must tear the job down, not hang the launcher
    # behind an in-order wait
    rc = 0
    live = dict(enumerate(procs))
    while live:
        for rank in list(live):
            r = live[rank].poll()
            if r is None:
                continue
            del live[rank]
            if r != 0:
                print(f"[launch] worker {rank} exited rc={r}",
                      file=sys.stderr)
                rc = rc or r
        if rc:   # one failed: kill the rest
            for p in live.values():
                if p.poll() is None:
                    p.terminate()
            for p in live.values():
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
            break
        if live:
            time.sleep(0.2)
    sys.exit(rc)


if __name__ == "__main__":
    main()
