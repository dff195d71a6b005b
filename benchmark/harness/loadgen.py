"""Traffic: find a mix's generator by its ``kind`` and run it.

A mix is a data file ``benchmark/traffic/<name>.json`` = ``{"kind": ...,
...parameters}``; a kind is ``benchmark/traffic_kinds/<kind>.py`` (hyphens
in the kind name are underscores in the file name) with

    JOB = "train" | "serve"
    def generate(seed: int, params: dict, ctx: dict) -> schedule

a pure function of its arguments. ``ctx`` carries what only the cell knows:
``vocab_size``, ``seconds`` (the window), ``max_model_len``. Helpers shared
by the kinds live here so that a new kind stays a few lines.

``load_module`` is also how every other file of its own is found by name: a
per-layer reader (``layer_metrics/``), a model family (``families/``).
"""
import importlib.util
import json
import os
from statistics import NormalDist

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# what a family file provides (benchmark/README.md, "A family")
FAMILY_PROTOCOL = ("Reference", "train_flops_per_token", "flash_flops",
                   "decode_step_bytes", "TOY")


def load_module(directory: str, name: str):
    path = os.path.join(BENCH_DIR, directory, name.replace("-", "_") + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"{directory} has no {os.path.basename(path)} "
                                f"(looked for {path})")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{directory}.{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(hf: dict):
    """The model family of a configuration: ``families/<model_type>.py``,
    found by the published ``model_type`` the configuration file carries at
    its top level. There is no default family: a ``model_type`` without a
    file, or a file without the whole protocol, raises."""
    if not hf.get("model_type"):
        raise KeyError("the configuration has no `model_type`: its family file "
                       "(benchmark/families/<model_type>.py) cannot be found")
    family = load_module("families", hf["model_type"])
    missing = [n for n in FAMILY_PROTOCOL if not hasattr(family, n)]
    if missing:
        raise AttributeError(f"{family.__file__} lacks {missing} of the family "
                             f"protocol {list(FAMILY_PROTOCOL)}")
    return family


def load_traffic(name: str) -> dict:
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def generate(traffic: dict, seed: int, ctx: dict):
    return load_module("traffic_kinds", traffic["kind"]).generate(seed, traffic, ctx)


def lognormal_lengths(rng, n: int, median: float, sigma: float, lo: int,
                      hi: int) -> np.ndarray:
    """n lengths from a clipped lognormal, STRATIFIED: one draw from each
    of n equal-probability slices of the distribution, then shuffled. Every
    seed sees the same distribution of lengths (and so nearly the same
    total work); which request gets which length, and when, is the seed's."""
    q = (np.arange(n) + rng.random(n)) / n
    z = np.array([NormalDist().inv_cdf(min(max(x, 1e-9), 1 - 1e-9)) for x in q])
    vals = np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(np.int64)
    rng.shuffle(vals)
    return vals


def random_prompt(rng, length: int, vocab_size: int) -> np.ndarray:
    return rng.integers(0, vocab_size, size=int(length), dtype=np.int32)
