#!/usr/bin/env python
"""Distributed job launcher (reference: tools/launch.py → dmlc tracker).

The reference spawns N workers + N servers through the dmlc-core tracker
(local/ssh/mpi/...).  Multi-host jax needs one *worker* process per host
pointed at a coordinator — no servers (the PS collapses into mesh
collectives).  This launcher reproduces the reference CLI:

- ``launch.py -n 4 --launcher local python train.py`` spawns 4 local
  processes with JAX distributed env wired, each seeing a slice of a CPU
  device mesh (the dist_sync_kvstore-test pattern, SURVEY.md §4).  A
  CPU-mesh tool: run it with ``JAX_PLATFORMS=cpu``.  On a TPU host every
  one of the N children would claim every chip, and a chip belongs to
  one process at a time — there, ONE process drives all the chips
  through the mesh (``DataParallelTrainer(mesh=...)``, docs/distributed.md)
  and this launcher starts one rank per *host* (``ssh``/``echo``).
- ``launch.py -n 4 --launcher ssh -H hostfile python train.py`` drives
  the same env handshake over ssh, one rank per hostfile line
  (round-robin), mirroring the dmlc ssh tracker the reference CI
  exercises (reference ci/docker/runtime_functions.sh:732-735,
  dmlc-core tracker/dmlc_tracker/ssh.py): env exported on the remote
  command line, cwd preserved, same coordinator address everywhere.
- ``--launcher echo`` only prints the per-rank environment (real pods:
  GKE/metadata provides the same variables).
- ``--restart-failed N`` makes the launch *elastic*: a rank that exits
  non-zero is relaunched (same rank id, same env — the worker redials
  the coordinator/PS and rejoins) up to N times, with delays from the
  shared ``resilience.backoff`` policy so a correlated crash doesn't
  thundering-herd the coordinator.
- ``-s/--num-servers 1`` spawns a dedicated ``DMLC_ROLE=server`` rank
  hosting the elastic PS (the reference CLI's ``-s``), with snapshot+WAL
  recovery armed through ``--ps-state-dir`` (``MXTPU_PS_STATE_DIR``) —
  so ``--restart-failed`` respawns of a SIGKILLed *server* recover the
  exact pre-crash weights/updater state instead of wiping the fleet.
  Once every worker exits, the server rank is drained with SIGTERM
  (which flushes a final snapshot) rather than left running.
"""
from __future__ import annotations

import argparse
import importlib.util
import os
import shlex
import socket
import subprocess
import sys
import time


def _load_by_path(name, *rel):
    """Load a module by file path so the launcher (which must stay
    jax-free — it forks workers) never imports the mxnet_tpu package."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), *rel)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_backoff():
    """The shared BackoffPolicy (resilience/backoff.py, stdlib-only)."""
    return _load_by_path("_mxtpu_backoff", "mxnet_tpu", "resilience",
                         "backoff.py")


def _load_metrics():
    """The telemetry metrics registry (telemetry/metrics.py,
    stdlib-only) — the launcher dumps its fleet-supervision numbers in
    the same versioned JSON schema the trainer does, so one
    ``tools/parse_log.py`` reads both."""
    return _load_by_path("_mxtpu_metrics", "mxnet_tpu", "telemetry",
                         "metrics.py")


def free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_LOCAL_HOSTS = {"localhost", "127.0.0.1", "::1"}


def read_hostfile(path):
    hosts = []
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if line:
                hosts.append(line.split()[0])  # "host [slots]" — host only
    if not hosts:
        raise SystemExit("hostfile %r lists no hosts" % path)
    return hosts


def routable_ip(remote_hosts=()):
    """An IP of this machine that other hosts can dial, found with the
    UDP-connect trick: ``connect()`` on a datagram socket sends nothing,
    but ``getsockname()`` reveals the source address the kernel routes
    through toward the peer (the dmlc ssh tracker advertises the
    tracker's routable IP the same way).  Returns None when no
    non-loopback route exists (air-gapped/misconfigured host)."""
    probes = [h for h in remote_hosts if h not in _LOCAL_HOSTS]
    probes.append("8.8.8.8")  # any public IP routes; no packet is sent
    for host in probes:
        try:
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            try:
                s.connect((host, 53))
                ip = s.getsockname()[0]
            finally:
                s.close()
        except OSError:
            continue
        if not ip.startswith("127."):
            return ip
    return None


def coordinator_address(hosts):
    """host:port for the JAX coordinator (and rank-0 PS).

    Rank 0 — the process that BINDS the coordinator — runs on hosts[0],
    so that is the address every rank must dial, not the launcher's.
    Three cases:

    - all hosts local: 127.0.0.1 with a locally probed free port;
    - hosts[0] local but the hostfile mixes in remote hosts: 127.0.0.1
      would make every remote rank dial ITSELF, so a routable address of
      this machine is advertised (UDP-connect trick); if none can be
      determined the launch errors out rather than silently wedging —
      pass --coordinator explicitly then;
    - hosts[0] remote: no local probe is possible, so a high random port
      on hosts[0] is used (collisions are rare; pin with --coordinator)."""
    remote = [h for h in hosts if h not in _LOCAL_HOSTS]
    if hosts[0] in _LOCAL_HOSTS:
        if not remote:
            return "127.0.0.1:%d" % free_port()
        ip = routable_ip(remote)
        if ip is None:
            raise SystemExit(
                "hostfile mixes localhost with remote hosts but no "
                "routable address for this machine could be determined; "
                "pass --coordinator HOST:PORT explicitly")
        return "%s:%d" % (ip, free_port())
    import random
    return "%s:%d" % (hosts[0], random.randint(20000, 59999))


def worker_env(coordinator, n, rank, ps_port, num_servers=0):
    """The per-rank env handshake (shared by every launcher)."""
    return {
        # jax.distributed.initialize() reads these
        "JAX_COORDINATOR_ADDRESS": coordinator,
        "JAX_NUM_PROCESSES": str(n),
        "JAX_PROCESS_ID": str(rank),
        # reference-compatible names (kvstore scripts read these)
        "DMLC_ROLE": "worker",
        "DMLC_NUM_WORKER": str(n),
        "DMLC_WORKER_ID": str(rank),
        # DMLC_NUM_SERVER > 0 tells workers a dedicated PS rank exists,
        # so rank 0 must NOT also bind the port with an embedded server
        "DMLC_NUM_SERVER": str(num_servers),
        # async parameter server address (kvstore dist_async)
        "MXTPU_PS_PORT": str(ps_port),
    }


def server_env(n, ps_port, state_dir):
    """The dedicated PS rank's env: the same command is spawned with
    DMLC_ROLE=server (the reference tracker's convention) and the
    program's `_init_kvstore_server_module()` hosts the elastic PS.
    The state dir arms snapshot+WAL crash recovery, which is what makes
    `--restart-failed` respawns of this rank a *recovery*, not a wipe."""
    env = {
        "DMLC_ROLE": "server",
        "DMLC_NUM_WORKER": str(n),
        "DMLC_NUM_SERVER": "1",
        "MXTPU_PS_PORT": str(ps_port),
    }
    if state_dir:
        env["MXTPU_PS_STATE_DIR"] = state_dir
    return env


def ssh_command(host, env, command, cwd):
    """One rank's ssh invocation: env exported on the remote command line
    (a remote shell inherits nothing), cwd preserved, command exec'd —
    the dmlc ssh tracker's contract (dmlc_tracker/ssh.py)."""
    exports = "".join("export %s=%s; " % (k, shlex.quote(str(v)))
                      for k, v in sorted(env.items()))
    # `cd || exit`: a missing remote cwd must kill the rank, not silently
    # run the worker from $HOME with wrong relative paths
    remote = "cd %s || exit 1; %sexec %s" % (
        shlex.quote(cwd), exports,
        " ".join(shlex.quote(c) for c in command))
    return ["ssh", "-o", "StrictHostKeyChecking=no",
            "-o", "PasswordAuthentication=no", host, remote]


def main():
    parser = argparse.ArgumentParser(
        description="Launch a distributed training job")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        choices=[0, 1],
                        help="spawn a dedicated DMLC_ROLE=server rank "
                             "hosting the elastic PS (one host server; "
                             "the reference CLI's -s).  0 = rank 0 "
                             "embeds the PS (default)")
    parser.add_argument("--ps-state-dir", default=None,
                        help="server snapshot+WAL directory "
                             "(MXTPU_PS_STATE_DIR); with --num-servers "
                             "and --restart-failed a respawned server "
                             "RECOVERS from it.  Default: a fresh "
                             "mxtpu_ps_state tmpdir when a server rank "
                             "is spawned")
    parser.add_argument("--launcher", default="local",
                        choices=["local", "ssh", "echo"])
    parser.add_argument("-H", "--hostfile", default=None,
                        help="one host per line (ssh launcher); every "
                             "rank runs on localhost when omitted")
    parser.add_argument("--coordinator", default=None,
                        help="override the coordinator host:port all "
                             "ranks connect to")
    parser.add_argument("--ps-port", type=int, default=None,
                        help="pin the rank-0 parameter-server port "
                             "(dist_async); by default a free port is "
                             "probed locally, or a high random port is "
                             "picked when rank 0 runs on a remote host "
                             "(where no probe is possible)")
    parser.add_argument("--restart-failed", type=int, default=0,
                        help="elastic restarts: relaunch a rank that "
                             "exits non-zero up to N times (same rank "
                             "id/env, exponential backoff with jitter); "
                             "0 = fail fast (default)")
    parser.add_argument("--env", action="append", default=[],
                        help="extra K=V forwarded to every worker "
                             "(reference launch.py --env)")
    parser.add_argument("--metrics-json", default=None,
                        help="write the launcher's fleet-supervision "
                             "metrics (per-rank restarts/exit codes, "
                             "wall time) as versioned telemetry JSON "
                             "on exit — the schema tools/parse_log.py "
                             "reads")
    parser.add_argument("--telemetry-dir", default=None,
                        help="arm fleet telemetry: exported as "
                             "MXTPU_TELEMETRY_DIR to every rank "
                             "(flight rings + metrics dumps land "
                             "there; see docs/observability.md)")
    parser.add_argument("--env-server", default=None,
                        help="unused; kept for reference CLI parity")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")

    hosts = (read_hostfile(args.hostfile) if args.hostfile
             else ["localhost"] * args.num_workers)
    if args.coordinator:
        coordinator = args.coordinator
    elif args.launcher == "ssh":
        coordinator = coordinator_address(hosts)
    else:
        coordinator = "127.0.0.1:%d" % free_port()
    # the PS binds on rank 0's host (the coordinator host, kvstore.py):
    # a port probed free HERE proves nothing about a remote rank 0, so
    # mirror coordinator_address — probe locally, random remotely,
    # --ps-port to pin (ADVICE r5 item 2)
    if args.ps_port is not None:
        ps_port = args.ps_port
    elif hosts[0] in _LOCAL_HOSTS:
        ps_port = free_port()
    else:
        import random
        ps_port = random.randint(20000, 59999)
    for kv in args.env:
        if "=" not in kv:
            parser.error("--env expects K=V, got %r" % kv)
    extra = dict(kv.split("=", 1) for kv in args.env)
    if args.telemetry_dir:
        os.makedirs(args.telemetry_dir, exist_ok=True)
        extra.setdefault("MXTPU_TELEMETRY_DIR",
                         os.path.abspath(args.telemetry_dir))
    if args.num_servers and not args.ps_state_dir:
        # recovery must be armed by default: a respawned server with no
        # state dir would come back EMPTY and wedge every worker
        import tempfile
        args.ps_state_dir = tempfile.mkdtemp(prefix="mxtpu_ps_state_")
        print("launch: server state dir %s (pass --ps-state-dir to pin)"
              % args.ps_state_dir, file=sys.stderr)

    def rank_env(rank):
        """rank is an int worker id or the string 'server'."""
        if rank == "server":
            renv = server_env(args.num_workers, ps_port, args.ps_state_dir)
        else:
            renv = worker_env(coordinator, args.num_workers, rank, ps_port,
                              args.num_servers)
        renv.update(extra)
        return renv

    all_ranks = (["server"] if args.num_servers else []) \
        + list(range(args.num_workers))

    if args.launcher == "echo":
        for rank in all_ranks:
            env = rank_env(rank)
            print("%s %s" % (" ".join("%s=%s" % kv
                                      for kv in sorted(env.items())),
                             " ".join(args.command)))
        return

    def spawn(rank):
        renv = rank_env(rank)
        if args.launcher == "ssh":
            # remote shells inherit nothing: forward the runtime-relevant
            # locals alongside the handshake (the dmlc tracker forwards
            # its env lists the same way).  The server rank runs on the
            # PS host — hosts[0], where the port was probed.
            for k in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH"):
                if k in os.environ and k not in renv:
                    renv[k] = os.environ[k]
            host = hosts[0] if rank == "server" else hosts[rank % len(hosts)]
            cmd = ssh_command(host, renv, args.command, os.getcwd())
            return subprocess.Popen(cmd)
        env = dict(os.environ)
        env.update(renv)
        return subprocess.Popen(args.command, env=env)

    t_launch = time.monotonic()
    running = {rank: spawn(rank) for rank in all_ranks}
    budgets = {rank: args.restart_failed for rank in all_ranks}
    attempts = {rank: 0 for rank in all_ranks}
    exit_codes = {}                    # rank -> last observed exit code
    policy = _load_backoff().BackoffPolicy(
        base_s=1.0, factor=2.0, max_delay_s=30.0,
        max_retries=max(args.restart_failed, 1), jitter=0.25)
    rc = 0
    # bounded poll loop (not a bare wait): crashed ranks are noticed and
    # — with --restart-failed — relaunched while the rest keep running,
    # which is what lets the elastic PS tier exercise worker rejoin.
    # Backoff is a per-rank respawn DEADLINE, not an inline sleep: a
    # correlated multi-rank crash must not serialize restarts or stall
    # polling of the ranks still running.
    respawn_at = {}                    # rank -> monotonic deadline
    server_draining = False
    while running or respawn_at:
        time.sleep(0.2)
        now = time.monotonic()
        for rank in [r for r, t in respawn_at.items() if now >= t]:
            del respawn_at[rank]
            running[rank] = spawn(rank)
        # all workers done -> drain the server rank (SIGTERM flushes its
        # final snapshot); a post-drain exit is a shutdown, not a crash
        workers_left = any(r != "server"
                           for r in list(running) + list(respawn_at))
        if not workers_left and "server" in running and not server_draining:
            server_draining = True
            budgets["server"] = 0
            running["server"].terminate()
        for rank, p in list(running.items()):
            r = p.poll()
            if r is None:
                continue
            del running[rank]
            exit_codes[rank] = r
            if r != 0 and budgets[rank] > 0:
                budgets[rank] -= 1
                delay = policy.delay(attempts[rank])
                attempts[rank] += 1
                print("launch: rank %s exited rc=%d; restarting in %.1fs "
                      "(%d restarts left)" % (rank, r, delay,
                                              budgets[rank]),
                      file=sys.stderr)
                respawn_at[rank] = now + delay
            else:
                rc = rc or r
    if args.metrics_json:
        _dump_launch_metrics(args, attempts, exit_codes,
                             time.monotonic() - t_launch, rc)
    sys.exit(rc)


def _dump_launch_metrics(args, attempts, exit_codes, wall_s, rc):
    """The launcher's half of the one-pane contract: per-rank restart
    counts and exit codes plus fleet wall time, in the same versioned
    metrics JSON schema ``DataParallelTrainer.fit`` dumps."""
    metrics = _load_metrics()
    reg = metrics.MetricsRegistry()
    g = reg.gauge("mxtpu_launch_rank_restarts_total",
                  "elastic restarts consumed per rank")
    for rank, n in attempts.items():
        g.set(n, rank=rank)
    g = reg.gauge("mxtpu_launch_rank_exit_code",
                  "last observed exit code per rank")
    for rank, code in exit_codes.items():
        g.set(code, rank=rank)
    reg.gauge("mxtpu_launch_wall_seconds", "fleet wall time").set(wall_s)
    reg.gauge("mxtpu_launch_num_workers", "").set(args.num_workers)
    reg.gauge("mxtpu_launch_num_servers", "").set(args.num_servers)
    reg.gauge("mxtpu_launch_exit_code", "the launcher's own rc").set(rc)
    try:
        reg.dump_json(args.metrics_json, source="tools/launch.py")
    except OSError as e:
        print("launch: metrics dump failed: %s" % e, file=sys.stderr)


if __name__ == "__main__":
    main()
