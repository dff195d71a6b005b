"""Autotuner: search {mesh shape, ZeRO stage, microbatching, remat policy}.

Reference: ``deepspeed/autotuning/autotuner.py:39`` (Autotuner — builds an
experiment space from the DS config, launches each candidate as a subprocess
via the scheduler, ranks by throughput/latency, writes results dirs) plus its
``tuner/{GridSearchTuner,RandomTuner,ModelBasedTuner}``.

TPU-native re-design: no subprocess launcher — XLA compiles + runs each
candidate in-process (a failed/OOM candidate just scores -inf), and mesh
shape × remat policy matter MORE than on GPU (the SPMD partitioner realizes
a different program per mesh). The search space is the cross product of
  - mesh factorizations of the device count over (data, fsdp, tensor),
  - ZeRO stage (0/1 for replicated-param meshes, 3 for fsdp meshes),
  - gradient-accumulation depth (microbatch sizes),
  - remat policy (transformer models),
pruned to `tuner_num_trials`, each measured for a few real steps.
"""

import dataclasses
import gc
import itertools
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from deepspeed_tpu.utils.logging import logger


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


@dataclasses.dataclass
class Trial:
    overrides: Dict[str, Any]
    samples_per_sec: float = float("-inf")
    step_ms: float = float("inf")
    error: Optional[str] = None

    def describe(self) -> str:
        mesh = self.overrides.get("mesh", {}).get("axes", {})
        z = self.overrides.get("zero_optimization", {}).get("stage", "-")
        gas = self.overrides.get("gradient_accumulation_steps", "-")
        remat = self.overrides.get("_remat_policy", "-")
        return (f"mesh={mesh} zero={z} gas={gas} remat={remat}: "
                + (f"{self.samples_per_sec:.1f} samples/s "
                   f"({self.step_ms:.1f} ms/step)"
                   if self.error is None else f"FAILED ({self.error})"))


class Autotuner:
    """In-process grid/random search over engine configurations."""

    def __init__(self, model, base_config: Dict[str, Any], devices=None):
        # in-process by design: this process initializes the backend and
        # holds the chip(s) for every trial — never pair it with
        # scheduler.ResourceManager's local children in the same process
        import jax
        self.model = model
        self.base = dict(base_config)
        self.at_cfg = self.base.get("autotuning", {})
        self.devices = devices
        self.n_devices = len(devices) if devices else jax.device_count()
        self.trials: List[Trial] = []

    # ------------------------------------------------------------------
    def candidates(self) -> List[Dict[str, Any]]:
        n = self.n_devices
        model_cfg = getattr(self.model, "config", None)
        heads = getattr(model_cfg, "num_heads", None)
        layers = getattr(model_cfg, "num_layers", None)
        batch = int(self.base.get("train_batch_size", 8))

        meshes: List[Tuple[Dict[str, int], int]] = []  # (axes, zero stage)
        experts = getattr(model_cfg, "num_experts", 1) or 1
        for tp in _divisors(n):
            if tp > 8 or (heads and heads % tp):
                continue
            rest = n // tp
            # pure-DP variants (stage 0/1/2 equivalent sharding: 0 and 1)
            for stage in (0, 1):
                meshes.append(({"data": rest, "tensor": tp}, stage))
            # fully-sharded variant
            if rest > 1:
                meshes.append(({"fsdp": rest, "tensor": tp}, 3))
            # pipeline variants: stages must divide the layer stack AND the
            # remaining devices (the 1F1B schedule needs gas microbatches,
            # handled by the gas loop below)
            if layers:
                for pp in _divisors(rest):
                    if pp > 1 and pp <= 8 and layers % pp == 0 \
                            and rest // pp >= 1:
                        meshes.append(
                            ({"pipe": pp, "data": rest // pp,
                              "tensor": tp}, 1))
        # expert axis: MoE models shard the expert stack
        if experts > 1:
            for ep in _divisors(min(n, experts)):
                if ep > 1 and experts % ep == 0 and n % ep == 0:
                    meshes.append(({"expert": ep, "data": n // ep}, 1))

        # gas candidates follow the batch's actual divisor structure instead
        # of a hardcoded [1, 2, 4]
        gas_opts = [g for g in _divisors(batch) if g <= 16]
        gas_opts = gas_opts[:max(1, int(
            self.at_cfg.get("num_tuning_micro_batch_sizes", 3)))]

        remat_opts: List[Optional[str]] = [None]
        if model_cfg is not None and hasattr(model_cfg, "remat_policy"):
            remat_opts = [None, "dots_saveable", "save_nothing"]

        out = []
        for (axes, stage), gas, remat in itertools.product(
                meshes, gas_opts, remat_opts):
            dp_like = axes.get("data", 1) * axes.get("fsdp", 1)
            micro = batch // (gas * dp_like) if dp_like else 0
            if micro < 1:
                continue
            ov: Dict[str, Any] = {
                "mesh": {"axes": axes},
                "zero_optimization": {"stage": stage},
                "gradient_accumulation_steps": gas,
            }
            if remat is not None:
                ov["_remat_policy"] = remat
            out.append(ov)
        seed = 0
        if str(self.at_cfg.get("tuner_type", "gridsearch")) == "random":
            rng = np.random.default_rng(seed)
            rng.shuffle(out)
        limit = int(self.at_cfg.get("tuner_num_trials", 50))
        return out[:limit]

    # ------------------------------------------------------------------
    def _build_model(self, overrides):
        remat = overrides.get("_remat_policy")
        cfg = getattr(self.model, "config", None)
        if remat is None or cfg is None:
            return self.model
        from deepspeed_tpu.models import make_model
        return make_model(dataclasses.replace(
            cfg, remat=remat != "none", remat_policy=remat),
            name=self.model.name)

    def _sample_batch(self, batch_size: int):
        cfg = getattr(self.model, "config", None)
        S = min(getattr(cfg, "max_seq_len", 512) or 512, 2048)
        V = getattr(cfg, "vocab_size", 1000)
        r = np.random.default_rng(0)
        return {"input_ids": r.integers(0, V, size=(batch_size, S),
                                        dtype=np.int32)}

    def measure(self, overrides: Dict[str, Any], steps: int = 3) -> Trial:
        import jax
        import deepspeed_tpu
        trial = Trial(overrides=overrides)
        cfg = json.loads(json.dumps(self.base))  # deep copy
        for k, v in overrides.items():
            if k.startswith("_"):
                continue
            if isinstance(v, dict):
                cfg.setdefault(k, {}).update(v)
            else:
                cfg[k] = v
        cfg["autotuning"] = {"enabled": False}
        cfg.setdefault("steps_per_print", 10 ** 9)
        engine = None
        try:
            model = self._build_model(overrides)
            engine, *_ = deepspeed_tpu.initialize(
                model=model, config=cfg, devices=self.devices)
            # the batch must match THIS candidate's resolved global batch, or
            # the samples/sec ranking is fabricated
            batch = self._sample_batch(engine.config.train_batch_size)
            engine.train_batch(batch)          # compile + warmup
            t0 = time.perf_counter()
            for _ in range(steps):
                engine.train_batch(batch)
            jax.block_until_ready(engine.state["step"])
            dt = (time.perf_counter() - t0) / steps
            trial.step_ms = dt * 1e3
            # engine.config solves the batch triad even when the user gave
            # only micro+gas; never index the raw dict for it
            trial.samples_per_sec = engine.config.train_batch_size / dt
        except Exception as e:  # noqa: BLE001 — OOM/compile failures score -inf
            trial.error = f"{type(e).__name__}: {e}"[:200]
        finally:
            del engine
            gc.collect()
        return trial

    # ------------------------------------------------------------------
    def run(self, steps: int = 3) -> Tuple[Dict[str, Any], List[Trial]]:
        cands = self.candidates()
        early_stop = int(self.at_cfg.get("tuner_early_stopping", 5))
        logger.info(f"autotuning: {len(cands)} candidates on "
                    f"{self.n_devices} devices")
        best: Optional[Trial] = None
        since_best = 0
        for ov in cands:
            t = self.measure(ov, steps=steps)
            self.trials.append(t)
            logger.info("autotuning trial: " + t.describe())
            if best is None or t.samples_per_sec > best.samples_per_sec:
                best, since_best = t, 0
            else:
                since_best += 1
                if early_stop and since_best >= early_stop:
                    logger.info("autotuning: early stop "
                                f"({early_stop} trials without improvement)")
                    break
        results_dir = self.at_cfg.get("results_dir", "autotuning_results")
        try:
            os.makedirs(results_dir, exist_ok=True)
            with open(os.path.join(results_dir, "results.json"), "w") as f:
                json.dump([dataclasses.asdict(t) for t in self.trials], f,
                          indent=2, default=str)
        except OSError as e:
            logger.warning(f"autotuning: could not write results: {e}")
        if best is None or best.error is not None:
            raise RuntimeError("autotuning: every candidate failed; last "
                               f"error: {self.trials[-1].error}")
        logger.info("autotuning BEST: " + best.describe())
        return best.overrides, self.trials


def autotune_config(model, config: Dict[str, Any], devices=None,
                    steps: int = 3):
    """Run the search; returns (merged_config, model) — the base config with
    the winning overrides merged in (autotuning disabled so the resulting
    engine builds directly) and the model, rebuilt if the winning trial chose
    a different remat policy."""
    tuner = Autotuner(model, config, devices=devices)
    best, _ = tuner.run(steps=steps)
    merged = json.loads(json.dumps(config))
    for k, v in best.items():
        if k.startswith("_"):
            continue
        if isinstance(v, dict):
            merged.setdefault(k, {}).update(v)
        else:
            merged[k] = v
    merged["autotuning"] = {"enabled": False}
    return merged, tuner._build_model(best)
