"""Multi-host experiment scheduler for the autotuner.

Reference: ``deepspeed/autotuning/scheduler.py`` (ResourceManager — an
experiment queue with per-node slot accounting: each experiment is launched
as subprocesses on a reserved node subset via the multinode runner, results
are parsed from the experiment directory, nodes are released on completion).

TPU-native re-design: an experiment is a JSON engine config measured by
``python -m deepspeed_tpu.autotuning.experiment <cfg.json> <out.json>`` —
one process per host (a TPU host's chips share one jax client, so hostfile
slots document chip counts, they don't multiply processes). The manager
partitions the host pool greedily: candidates whose mesh fits a SUBSET of
hosts run concurrently on disjoint subsets (the reference's node
reservation), full-pool candidates run alone. Launching rides the
``launcher.multinode_runner`` backends; single-host pools degrade to a
plain local subprocess, which is also how the unit tests execute a real
experiment end-to-end.
"""

import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import Any, Callable, Dict, List, Optional

from deepspeed_tpu.utils.logging import logger


@dataclasses.dataclass
class Experiment:
    exp_id: int
    config: Dict[str, Any]
    num_hosts: int = 1                       # hosts this candidate needs
    hosts: List[str] = dataclasses.field(default_factory=list)
    status: str = "pending"                  # pending|running|done|failed
    result: Optional[Dict[str, Any]] = None
    error: Optional[str] = None

    @property
    def metric(self) -> float:
        if self.result and "samples_per_sec" in self.result:
            return float(self.result["samples_per_sec"])
        return float("-inf")


def hosts_needed(config: Dict[str, Any], chips_per_host: int) -> int:
    """Host count a candidate's mesh needs: ceil(world / chips_per_host)."""
    axes = (config.get("mesh") or {}).get("axes") or {}
    world = 1
    for v in axes.values():
        world *= int(v)
    return max(1, -(-world // max(1, chips_per_host)))


class ResourceManager:
    """Greedy host-pool partitioner + experiment launcher/collector.

    ``launch`` is injectable (tests; custom transports). The default
    launches the experiment module locally when the group is this host,
    or via the pdsh multinode runner otherwise, writing the result JSON
    into ``results_dir/exp_<id>/result.json`` exactly like the reference's
    per-experiment directories.

    **Shared-filesystem requirement**: remotely-launched experiments write
    ``result.json`` under ``results_dir`` *on the remote host*, and
    ``_collect`` reads that same path *on this host* — so for multi-host
    pools ``results_dir`` must live on storage every host mounts (NFS /
    gcsfuse; TPU pods already mount one for checkpoints). With a local-only
    results_dir every remote experiment reports "no result file". Pass a
    custom ``launch`` that fetches results over its own transport to lift
    the requirement.
    """

    def __init__(self, hosts: List[str], chips_per_host: int = 4,
                 results_dir: str = "autotuning_exps",
                 launch: Optional[Callable[[Experiment], None]] = None,
                 poll_s: float = 1.0, timeout_s: float = 3600.0):
        # every alias of THIS machine is one host slot: its chips belong to
        # one process at a time, so two local experiment children must never
        # run at once (the second would fail or hang waiting for the chip)
        hosts = list(hosts) or ["localhost"]
        local = [h for h in hosts if self._is_local([h])]
        self.hosts = [h for h in hosts if h not in local[1:]]
        self.chips_per_host = chips_per_host
        self.results_dir = results_dir
        self._launch = launch or self._launch_default
        self.poll_s = poll_s
        self.timeout_s = timeout_s
        self._procs: Dict[int, Any] = {}

    # ------------------------------------------------------------------
    def _exp_dir(self, exp: Experiment) -> str:
        d = os.path.join(self.results_dir, f"exp_{exp.exp_id}")
        os.makedirs(d, exist_ok=True)
        return d

    @staticmethod
    def _is_local(hosts: List[str]) -> bool:
        return set(hosts) <= {"localhost", "127.0.0.1", os.uname().nodename}

    def _launch_default(self, exp: Experiment):
        d = self._exp_dir(exp)
        cfg_path = os.path.join(d, "config.json")
        out_path = os.path.join(d, "result.json")
        with open(cfg_path, "w") as f:
            json.dump(exp.config, f)
        script = [sys.executable, "-m", "deepspeed_tpu.autotuning.experiment",
                  cfg_path, out_path]
        local = self._is_local(exp.hosts)
        if local:
            # NOT chip-safe from a parent that has touched JAX: the child
            # needs the local chip, and a parent that initialized a backend
            # (e.g. the in-process Autotuner) already holds it
            self._procs[exp.exp_id] = subprocess.Popen(
                script, stdout=subprocess.DEVNULL,
                stderr=subprocess.STDOUT)
        else:
            from deepspeed_tpu.launcher.multinode_runner import PDSHRunner
            runner = PDSHRunner({h: self.chips_per_host for h in exp.hosts},
                                script, env=dict(os.environ))
            self._procs[exp.exp_id] = subprocess.Popen(
                runner.get_cmd(), stdout=subprocess.DEVNULL,
                stderr=subprocess.STDOUT)

    def _collect(self, exp: Experiment):
        out_path = os.path.join(self._exp_dir(exp), "result.json")
        proc = self._procs.pop(exp.exp_id, None)
        rc = proc.wait() if proc is not None else 0
        if os.path.exists(out_path):
            with open(out_path) as f:
                exp.result = json.load(f)
            exp.status = "failed" if exp.result.get("error") else "done"
            exp.error = exp.result.get("error")
        else:
            exp.status = "failed"
            if exp.hosts and not self._is_local(exp.hosts):
                # the most common cause is NOT the experiment failing but
                # results_dir living on host-local storage (see class doc)
                exp.error = (
                    f"no result file at {out_path} (rc={rc}) — experiment "
                    f"ran remotely on {exp.hosts}; results_dir "
                    f"'{self.results_dir}' must be on a filesystem shared "
                    "by every host (NFS/gcsfuse), or pass a custom launch "
                    "that fetches results back")
                logger.error(f"autotuning exp {exp.exp_id}: {exp.error}")
            else:
                exp.error = f"no result file (rc={rc})"

    def _done(self, exp: Experiment) -> bool:
        proc = self._procs.get(exp.exp_id)
        return proc is None or proc.poll() is not None

    # ------------------------------------------------------------------
    def schedule(self, configs: List[Dict[str, Any]]) -> List[Experiment]:
        """Run every candidate; disjoint host groups run CONCURRENTLY.
        Returns the experiments sorted most-throughput-first."""
        exps = [Experiment(exp_id=i, config=c,
                           num_hosts=min(len(self.hosts),
                                         hosts_needed(c, self.chips_per_host)))
                for i, c in enumerate(configs)]
        pending = list(exps)
        running: List[Experiment] = []
        free = list(self.hosts)
        t0 = time.time()
        while pending or running:
            # reap finished
            for exp in list(running):
                if self._done(exp):
                    self._collect(exp)
                    running.remove(exp)
                    free.extend(exp.hosts)
                    logger.info(
                        f"autotuning exp {exp.exp_id}: {exp.status}"
                        + (f" {exp.metric:.1f} samples/s"
                           if exp.status == "done" else f" ({exp.error})"))
            # greedy assignment onto free hosts
            for exp in list(pending):
                if exp.num_hosts <= len(free):
                    exp.hosts = [free.pop(0) for _ in range(exp.num_hosts)]
                    exp.status = "running"
                    pending.remove(exp)
                    running.append(exp)
                    self._launch(exp)
            if time.time() - t0 > self.timeout_s:
                for exp in running:
                    proc = self._procs.pop(exp.exp_id, None)
                    if proc is not None and proc.poll() is None:
                        proc.kill()
                    exp.status = "failed"
                    exp.error = "timeout"
                break
            if running:
                time.sleep(self.poll_s)
        return sorted(exps, key=lambda e: e.metric, reverse=True)


def schedule_experiments(configs: List[Dict[str, Any]],
                         hosts: Optional[List[str]] = None,
                         chips_per_host: int = 4,
                         results_dir: str = "autotuning_exps",
                         **kw) -> List[Experiment]:
    """Convenience entry: partition `hosts` and measure every candidate."""
    rm = ResourceManager(hosts or ["localhost"],
                         chips_per_host=chips_per_host,
                         results_dir=results_dir, **kw)
    return rm.schedule(configs)
