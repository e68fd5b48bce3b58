"""Pod-scale launcher CLI (``dstpu``).

Capability analogue of the reference's ``deepspeed`` CLI
(``launcher/runner.py:436 main`` — hostfile parsing:230, include/exclude
filters:310, world-info encoding; ``launcher/launch.py`` per-node spawner;
``multinode_runner.py`` PDSH/MPI/Slurm backends).

TPU model differences: one *process per host* controls all local chips (not
one per device), and rendezvous is JAX's coordinator service instead of
MASTER_ADDR/NCCL.  So the launcher's job is: resolve the host list (hostfile
/ GCE TPU-pod metadata / --hosts), pick the coordinator, and start the
training script on every host over ssh with COORDINATOR_ADDRESS /
NUM_PROCESSES / PROCESS_ID exported — plus a single-host fast path that just
execs the script.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import shlex
import signal
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

from ..utils.logging import logger

DEFAULT_COORDINATOR_PORT = 8476


def parse_hostfile(path: str) -> Dict[str, int]:
    """``host slots=N`` lines → {host: slots}. Reference: runner.py:230."""
    hosts: Dict[str, int] = {}
    with open(path) as f:
        for line in f:
            line = line.split("#")[0].strip()
            if not line:
                continue
            parts = line.split()
            host = parts[0]
            slots = 1
            for p in parts[1:]:
                if p.startswith("slots="):
                    slots = int(p.split("=")[1])
            if host in hosts:
                raise ValueError(f"duplicate host {host!r} in hostfile")
            hosts[host] = slots
    if not hosts:
        raise ValueError(f"no hosts found in {path}")
    return hosts


def filter_hosts(hosts: Dict[str, int], include: str = "", exclude: str = ""
                 ) -> Dict[str, int]:
    """--include/--exclude 'host1,host2' filters. Reference: runner.py:310
    (device-level @-syntax does not apply: processes are per-host on TPU)."""
    result = dict(hosts)
    if include:
        wanted = set(h.strip() for h in include.split(",") if h.strip())
        unknown = wanted - set(result)
        if unknown:
            raise ValueError(f"--include hosts not in hostfile: {sorted(unknown)}")
        result = {h: s for h, s in result.items() if h in wanted}
    if exclude:
        banned = set(h.strip() for h in exclude.split(",") if h.strip())
        unknown = banned - set(hosts)
        if unknown:
            raise ValueError(f"--exclude hosts not in hostfile: {sorted(unknown)}")
        result = {h: s for h, s in result.items() if h not in banned}
    if not result:
        raise ValueError("host filters removed every host")
    return result


def encode_world_info(hosts: Dict[str, int]) -> str:
    """base64 world info passed to remote processes (reference runner.py:401)."""
    return base64.urlsafe_b64encode(json.dumps(hosts).encode()).decode()


def decode_world_info(blob: str) -> Dict[str, int]:
    return json.loads(base64.urlsafe_b64decode(blob.encode()).decode())


def build_env(coordinator: str, port: int, num_processes: int, process_id: int,
              extra_env: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    env = {
        "COORDINATOR_ADDRESS": f"{coordinator}:{port}",
        "NUM_PROCESSES": str(num_processes),
        "PROCESS_ID": str(process_id),
        "DSTPU_MULTIPROCESS": "1",
    }
    if extra_env:
        env.update(extra_env)
    return env


def launch(args: argparse.Namespace) -> int:
    from .multinode_runner import discover_slurm_hosts, get_runner

    # -- resolve hosts -------------------------------------------------
    if args.hostfile and os.path.exists(args.hostfile):
        hosts = parse_hostfile(args.hostfile)
    elif args.hosts:
        hosts = {h: 1 for h in args.hosts.split(",")}
    elif (slurm_hosts := discover_slurm_hosts()) is not None:
        # running inside a Slurm allocation: use it (reference runner.py
        # Slurm resource detection); only auto-pick srun when the user did
        # not explicitly request a launcher
        hosts = slurm_hosts
        if args.launcher is None:
            args.launcher = "slurm"
    else:
        hosts = {"localhost": 1}
    if args.launcher is None:
        args.launcher = "ssh"
    hosts = filter_hosts(hosts, args.include, args.exclude)
    host_list = list(hosts)
    n = len(host_list)

    extra_env = {}
    for kv in args.env or []:
        k, _, v = kv.partition("=")
        extra_env[k] = v

    script_cmd = [sys.executable, args.script, *args.script_args] \
        if args.script.endswith(".py") else [args.script, *args.script_args]

    # -- single host: exec in place (reference: runner.py single-node path)
    if n == 1 and host_list[0] in ("localhost", "127.0.0.1"):
        env = dict(os.environ)
        env.update(extra_env)
        if args.force_multiprocess:
            env.update(build_env("127.0.0.1", args.coordinator_port, 1, 0))
        logger.info(f"launching locally: {' '.join(script_cmd)}")
        proc = subprocess.Popen(script_cmd, env=env)
        try:
            return proc.wait()
        except KeyboardInterrupt:
            proc.send_signal(signal.SIGTERM)
            return proc.wait()

    # -- multi host through the selected backend -----------------------
    backend_args = args.launcher_args
    if args.launcher == "ssh" and not backend_args:
        backend_args = args.ssh_args  # --ssh_args only feeds the ssh backend
    runner = get_runner(args.launcher, backend_args)
    if not runner.backend_exists():
        raise RuntimeError(
            f"launcher backend {runner.name!r} not available on this host")
    if args.launcher == "slurm":
        # srun assigns SLURM_PROCID in nodelist (natural-sorted) order, not
        # in -w order — align our host order so rank 0 == the coordinator
        from .multinode_runner import natural_sorted

        host_list = natural_sorted(host_list)
        hosts = {h: hosts[h] for h in host_list}
    coordinator = host_list[0]
    world_blob = encode_world_info(hosts)

    if runner.single_command:
        # rank comes from the fabric (SLURM_PROCID / OMPI rank / pdsh
        # host-index); PROCESS_ID deliberately unset
        env = build_env(coordinator, args.coordinator_port, n, 0, extra_env)
        env.pop("PROCESS_ID")
        env["DSTPU_WORLD_INFO"] = world_blob
        cmd = runner.get_cmd(env, hosts, script_cmd)
        logger.info(f"[{runner.name}] {' '.join(cmd)}")
        proc = subprocess.Popen(
            cmd, env={**os.environ, **runner.local_env()})
        try:
            return proc.wait()
        except KeyboardInterrupt:
            proc.send_signal(signal.SIGTERM)
            return proc.wait()

    procs: List[subprocess.Popen] = []
    for pid, host in enumerate(host_list):
        env = build_env(coordinator, args.coordinator_port, n, pid, extra_env)
        env["DSTPU_WORLD_INFO"] = world_blob
        cmd = runner.get_per_host_cmd(host, env, script_cmd)
        logger.info(f"[{host}] {' '.join(cmd[-1:])}")
        procs.append(subprocess.Popen(cmd))

    rc = 0
    try:
        for p in procs:
            rc = p.wait() or rc
    except KeyboardInterrupt:  # propagate ctrl-c to every node
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            p.wait()
    return rc


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="dstpu", description="deepspeed_tpu pod launcher")
    p.add_argument("--hostfile", default="/job/hostfile",
                   help="'host slots=N' lines (reference hostfile format)")
    p.add_argument("--hosts", default="",
                   help="comma-separated host list (alternative to hostfile)")
    p.add_argument("--include", default="", help="comma-separated host allowlist")
    p.add_argument("--exclude", default="", help="comma-separated host denylist")
    p.add_argument("--coordinator_port", type=int, default=DEFAULT_COORDINATOR_PORT)
    p.add_argument("--launcher", default=None,
                   choices=["ssh", "pdsh", "openmpi", "mpich", "impi",
                            "slurm"],
                   help="multi-node backend (reference --launcher flag); "
                        "default: slurm inside a Slurm allocation, else ssh")
    p.add_argument("--launcher_args", default="",
                   help="extra flags for the backend command")
    p.add_argument("--ssh_args", default="", help="extra ssh flags")
    p.add_argument("--env", action="append", metavar="K=V",
                   help="extra environment for every process")
    p.add_argument("--force_multiprocess", action="store_true",
                   help="set coordinator env even for a single local host")
    p.add_argument("script", help="training script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> int:
    return launch(parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
