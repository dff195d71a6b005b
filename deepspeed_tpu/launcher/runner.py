"""The `dstpu` CLI launcher.

Reference: ``deepspeed/launcher/runner.py:364`` (hostfile parse, include/
exclude filters, single-node subprocess, PDSH/MPI/SLURM multinode runners,
env propagation) and ``launcher/launch.py:117`` (per-node spawn).

TPU-native differences: one process drives all local chips (no proc-per-GPU
fan-out), and multi-host rendezvous is `jax.distributed.initialize` via
COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID. The launcher therefore:
  single host  -> exec the script with the env set;
  multi host   -> build per-host ssh commands from a hostfile (pdsh-style),
                  or emit the `gcloud compute tpus tpu-vm ssh --worker=all`
                  command for TPU pods.
"""

import argparse
import os
import shlex
import subprocess
import sys
from typing import Dict, List, Tuple

from deepspeed_tpu.utils.logging import logger

DEFAULT_COORD_PORT = 8476


def fetch_hostfile(path: str) -> Dict[str, int]:
    """Parse 'hostname slots=N' lines (reference: fetch_hostfile:176)."""
    hosts: Dict[str, int] = {}
    if not path or not os.path.isfile(path):
        return hosts
    with open(path) as f:
        for line in f:
            line = line.split("#")[0].strip()
            if not line:
                continue
            parts = line.split()
            name = parts[0]
            slots = 1
            for p in parts[1:]:
                if p.startswith("slots="):
                    slots = int(p.split("=")[1])
            if name in hosts:
                raise ValueError(f"duplicate host {name} in hostfile")
            hosts[name] = slots
    return hosts


def parse_inclusion_exclusion(hosts: Dict[str, int], include: str,
                              exclude: str) -> Dict[str, int]:
    """--include/--exclude 'host1,host2' filters (reference: :231; slot-level
    selection has no TPU meaning, host-level only)."""
    out = dict(hosts)
    if include:
        names = [h.split(":")[0] for h in include.split(",")]
        out = {h: s for h, s in out.items() if h in names}
    if exclude:
        names = [h.split(":")[0] for h in exclude.split(",")]
        out = {h: s for h, s in out.items() if h not in names}
    if not out:
        raise ValueError("no hosts remain after include/exclude filtering")
    return out


def build_ssh_commands(hosts: Dict[str, int], script_cmd: List[str],
                       master_addr: str = None,
                       port: int = DEFAULT_COORD_PORT,
                       export_envs: Dict[str, str] = None,
                       use_agent: bool = True) -> List[List[str]]:
    """One ssh command per host. With use_agent (default), each host runs
    the per-node launch agent (launcher/launch.py — jax.distributed env
    wiring + signal handling + process-tree kill); the raw env-prefix form
    remains for minimal targets without the package installed."""
    hostnames = list(hosts)
    master = master_addr or hostnames[0]
    cmds = []
    for pid, host in enumerate(hostnames):
        envs = {
            # the single source of truth: the agent and comm both read these
            "COORDINATOR_ADDRESS": f"{master}:{port}",
            "NUM_PROCESSES": str(len(hostnames)),
            "PROCESS_ID": str(pid),
        }
        envs.update(export_envs or {})
        env_str = " ".join(f"{k}={shlex.quote(v)}" for k, v in envs.items())
        if use_agent:
            agent = (f"{sys.executable} -m deepspeed_tpu.launcher.launch "
                     f"-- {' '.join(map(shlex.quote, script_cmd))}")
            remote = f"cd {shlex.quote(os.getcwd())} && {env_str} {agent}"
        else:
            remote = (f"cd {shlex.quote(os.getcwd())} && {env_str} "
                      f"{' '.join(map(shlex.quote, script_cmd))}")
        cmds.append(["ssh", "-o", "StrictHostKeyChecking=no", host, remote])
    return cmds


def gcloud_tpu_command(tpu_name: str, zone: str, script_cmd: List[str]) -> List[str]:
    """TPU-pod equivalent of the pdsh runner: one gcloud ssh to all workers."""
    return ["gcloud", "compute", "tpus", "tpu-vm", "ssh", tpu_name,
            f"--zone={zone}", "--worker=all",
            f"--command={' '.join(map(shlex.quote, script_cmd))}"]


def _read_ds_env(path: str = ".deepspeed_env") -> Dict[str, str]:
    """Env propagation file (reference: runner.py:506-517)."""
    out = {}
    if os.path.isfile(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line and "=" in line and not line.startswith("#"):
                    k, v = line.split("=", 1)
                    out[k] = v
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="dstpu", description="deepspeed_tpu launcher")
    parser.add_argument("--hostfile", default="/job/hostfile")
    parser.add_argument("--include", default="")
    parser.add_argument("--exclude", default="")
    parser.add_argument("--master_addr", default=None)
    parser.add_argument("--master_port", type=int, default=DEFAULT_COORD_PORT)
    parser.add_argument("--tpu", default=None, help="TPU pod name (gcloud mode)")
    parser.add_argument("--zone", default=None, help="gcloud zone")
    parser.add_argument("--launcher", default="ssh",
                        choices=["ssh", "pdsh", "openmpi", "mpich",
                                 "mvapich", "slurm"],
                        help="multinode backend (reference: "
                             "multinode_runner.py); ssh = built-in agent")
    parser.add_argument("--dry_run", action="store_true",
                        help="print the launch commands without executing")
    parser.add_argument("--no_agent", action="store_true",
                        help="skip the per-node launch agent (raw env-prefix "
                             "ssh — for hosts without deepspeed_tpu "
                             "installed)")
    parser.add_argument("script", help="training script")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    script_cmd = [sys.executable, args.script] + list(args.script_args)

    if args.tpu:
        cmd = gcloud_tpu_command(args.tpu, args.zone or "", script_cmd)
        if args.dry_run:
            print(" ".join(map(shlex.quote, cmd)))
            return 0
        return subprocess.call(cmd)

    hosts = fetch_hostfile(args.hostfile)
    hosts = parse_inclusion_exclusion(hosts, args.include, args.exclude) if hosts else hosts

    if len(hosts) <= 1:
        # single host: exec in place (reference: runner.py:462-480 subprocess)
        logger.info(f"launching single-host: {' '.join(script_cmd)}")
        if args.dry_run:
            print(" ".join(map(shlex.quote, script_cmd)))
            return 0
        # the child owns the chip: safe only while this launcher process
        # never initializes a JAX backend itself
        return subprocess.call(script_cmd)

    if args.launcher != "ssh":
        from deepspeed_tpu.launcher.multinode_runner import get_runner
        import os as _os
        # .deepspeed_env entries bypass the export whitelist (same contract
        # as the ssh path, which propagates all of them)
        runner = get_runner(args.launcher, hosts, script_cmd,
                            master_addr=args.master_addr,
                            master_port=args.master_port,
                            env=dict(_os.environ),
                            extra_env=_read_ds_env())
        if not runner.backend_exists():
            logger.warning(f"{args.launcher} binary not found on PATH")
        cmd = runner.get_cmd()
        if args.dry_run:
            print(" ".join(map(shlex.quote, cmd)))
            return 0
        return subprocess.call(cmd)

    cmds = build_ssh_commands(hosts, script_cmd, args.master_addr,
                              args.master_port, _read_ds_env(),
                              use_agent=not args.no_agent)
    if args.dry_run:
        for c in cmds:
            print(" ".join(map(shlex.quote, c)))
        return 0
    procs = [subprocess.Popen(c) for c in cmds]
    rc = 0
    try:
        for p in procs:
            rc |= p.wait()
    except KeyboardInterrupt:
        # kill the whole tree (reference: launch.py:103 signal handling)
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait()
        rc = 130
    return rc


if __name__ == "__main__":
    sys.exit(main())


def ssh_main(argv=None):
    """``dstpu_ssh``: run a command on every hostfile host (reference:
    ``bin/ds_ssh`` — pdsh convenience wrapper)."""
    parser = argparse.ArgumentParser(
        prog="dstpu_ssh", description="run a command on all hostfile hosts")
    parser.add_argument("--hostfile", default="/job/hostfile")
    parser.add_argument("--dry_run", action="store_true")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    if not args.command:
        parser.error("no command given")
    hosts = fetch_hostfile(args.hostfile) or {"localhost": 1}
    remote = " ".join(map(shlex.quote, args.command))
    rc = 0
    for host in hosts:
        cmd = ["ssh", "-o", "StrictHostKeyChecking=no", host, remote]
        if args.dry_run:
            print(" ".join(map(shlex.quote, cmd)))
            continue
        print(f"----- {host} -----", flush=True)
        rc |= subprocess.call(cmd)
    return rc
