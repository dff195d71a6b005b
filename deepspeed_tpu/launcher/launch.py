"""Per-node launch agent: env setup, process spawn, signal handling.

Reference: ``deepspeed/launcher/launch.py:117`` — decodes the world info,
sets MASTER_ADDR/RANK per local GPU, spawns one process per device, and
kills the whole process tree on signals (``:103``).

TPU-native re-design: a TPU host runs ONE process for all its local chips
(jax addresses them as a single client), so the agent spawns one user
process per host, wiring the rendezvous env ``comm.init_distributed``
reads (COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID, plus the
torch-style RANK / WORLD_SIZE / MASTER_ADDR / MASTER_PORT aliases). The
runner's ssh env prefix is the normal source of these values — the agent
passes them through and only needs ``--world_info`` when run standalone.

Signal handling matches the reference: SIGINT/SIGTERM forward to the child
process GROUP (the user script may spawn data workers), and the agent waits
with a kill escalation so no orphans survive a cancelled job.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

from deepspeed_tpu.utils.logging import logger


def build_child_env(world: Optional[Dict] = None,
                    node_rank: Optional[int] = None,
                    base_env: Optional[Dict[str, str]] = None
                    ) -> Dict[str, str]:
    """Env for the user process. With world=None the runner's exported
    COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID pass through untouched;
    an explicit `world` ({"coordinator": "host:port", "num_nodes": N}) +
    node_rank overrides them (standalone use)."""
    env = dict(base_env if base_env is not None else os.environ)
    if world is not None:
        env["COORDINATOR_ADDRESS"] = world["coordinator"]
        env["NUM_PROCESSES"] = str(world["num_nodes"])
        env["PROCESS_ID"] = str(node_rank)
    # torch-style aliases for scripts that read them (and comm's fallback)
    if "COORDINATOR_ADDRESS" in env:
        host, _, port = env["COORDINATOR_ADDRESS"].rpartition(":")
        env.setdefault("MASTER_ADDR", host)
        env.setdefault("MASTER_PORT", port)
    if "NUM_PROCESSES" in env:
        env.setdefault("WORLD_SIZE", env["NUM_PROCESSES"])
    if "PROCESS_ID" in env:
        env.setdefault("RANK", env["PROCESS_ID"])
    return env


class LaunchAgent:
    """Spawns and supervises the user process on one node."""

    def __init__(self, cmd: List[str], world: Optional[Dict] = None,
                 node_rank: Optional[int] = None,
                 kill_grace_s: float = 5.0):
        self.cmd = cmd
        self.env = build_child_env(world, node_rank)
        self.grace = kill_grace_s
        self.proc: Optional[subprocess.Popen] = None
        self._signaled = False

    def _forward_signal(self, signum, _frame):
        # reference launch.py:103 — kill the whole tree, not just the child
        self._signaled = True
        if self.proc is not None and self.proc.poll() is None:
            try:
                os.killpg(os.getpgid(self.proc.pid), signum)
            except ProcessLookupError:
                pass

    def run(self) -> int:
        # handlers BEFORE the spawn: a signal landing in the gap would kill
        # the agent while the child (own session) survived orphaned —
        # _forward_signal tolerates proc=None
        prev_int = signal.signal(signal.SIGINT, self._forward_signal)
        prev_term = signal.signal(signal.SIGTERM, self._forward_signal)
        try:
            if self._signaled:
                return 128 + signal.SIGTERM
            # the child owns the chip: safe only while this agent process
            # never initializes a JAX backend itself
            self.proc = subprocess.Popen(
                self.cmd, env=self.env, start_new_session=True)
            while True:
                rc = self.proc.poll()
                if rc is not None:
                    return rc
                time.sleep(0.1)
                if self._signaled:
                    # grace period, then escalate to SIGKILL on the group
                    deadline = time.time() + self.grace
                    while time.time() < deadline:
                        if self.proc.poll() is not None:
                            return self.proc.returncode
                        time.sleep(0.1)
                    try:
                        os.killpg(os.getpgid(self.proc.pid), signal.SIGKILL)
                    except ProcessLookupError:
                        pass
                    self.proc.wait()
                    return self.proc.returncode
        finally:
            signal.signal(signal.SIGINT, prev_int)
            signal.signal(signal.SIGTERM, prev_term)


def _parse_world_info(raw: str):
    try:
        return json.loads(raw)
    except json.JSONDecodeError:
        pass
    import base64
    import binascii
    try:
        return json.loads(base64.urlsafe_b64decode(raw.encode()))
    except (binascii.Error, ValueError, json.JSONDecodeError):
        raise argparse.ArgumentTypeError(
            "world_info must be JSON like "
            '{"coordinator": "host:port", "num_nodes": N} '
            "(or base64 of it)")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="per-node launch agent (reference: launcher/launch.py)")
    p.add_argument("--world_info", type=_parse_world_info, default=None,
                   help="optional standalone rendezvous override; normally "
                        "the runner exports COORDINATOR_ADDRESS/"
                        "NUM_PROCESSES/PROCESS_ID and this is omitted")
    p.add_argument("--node_rank", type=int, default=None)
    p.add_argument("--node_host", default=None,
                   help="this node's hostname; its index in "
                        "world_info['hosts'] becomes the node rank (the "
                        "pdsh %%h path, where every node gets the SAME "
                        "command line)")
    p.add_argument("cmd", nargs=argparse.REMAINDER,
                   help="user script command (after --)")
    a = p.parse_args(argv)
    cmd = a.cmd[1:] if a.cmd and a.cmd[0] == "--" else a.cmd
    if not cmd:
        p.error("no user command given (append: -- python train.py ...)")
    node_rank = a.node_rank
    if node_rank is None and a.node_host is not None:
        hosts = (a.world_info or {}).get("hosts")
        if not hosts:
            p.error("--node_host needs world_info with a 'hosts' list")
        short = a.node_host.split(".")[0]
        if a.node_host in hosts:
            node_rank = hosts.index(a.node_host)
        elif short in hosts:
            node_rank = hosts.index(short)
        else:
            p.error(f"host {a.node_host!r} not in world_info hosts {hosts}")
    if node_rank is None:
        node_rank = int(os.environ.get(
            "PROCESS_ID", os.environ.get("NODE_RANK", 0)))
    # the SIGTERM->SIGKILL grace window is the user process' preemption
    # budget: a PreemptionHandler-driven training loop has exactly this
    # long to checkpoint-and-exit (README "Fault tolerance")
    grace = float(os.environ.get("DSTPU_KILL_GRACE_S", 5.0))
    agent = LaunchAgent(cmd, a.world_info, node_rank, kill_grace_s=grace)
    logger.info(f"launch agent: node {agent.env.get('PROCESS_ID', '?')}/"
                f"{agent.env.get('NUM_PROCESSES', '?')} coordinator="
                f"{agent.env.get('COORDINATOR_ADDRESS', '?')} "
                f"cmd={' '.join(cmd)}")
    return agent.run()


if __name__ == "__main__":
    sys.exit(main())
