"""What both jobs share: the compile counter, device readings, the model
config, the profiler session, the rehearsal's shrink of the traffic."""
import glob
import json
import os
import shutil

from . import loadgen

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(BENCH_DIR, "out")

# keys of a configuration file that are not the published config
_NOT_HF = ("source", "reduced", "assumed", "deployment", "run", "correct")

# --rehearsal: the configuration's family gives the toy widths (its `TOY`);
# lengths of the traffic are divided by REHEARSAL_SHRINK
REHEARSAL_SHRINK = 8


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_config(name: str) -> dict:
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def hf_of(cfg: dict, rehearsal: bool = False) -> dict:
    """The published config dict as it is run (depth cut included); for the
    rehearsal, at its family's toy widths and depth."""
    hf = {k: v for k, v in cfg.items() if k not in _NOT_HF}
    if rehearsal:
        hf.update(loadgen.load_family(hf).TOY)
    return hf


def model_config(cfg: dict, hf: dict, max_seq_len: int):
    """The program's model config, by the program's own reading of the
    published keys (depth included, whatever the family calls it)."""
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    return hf_config_to_transformer(
        hf, max_seq_len=max_seq_len, **cfg["run"].get("overrides", {}))


class CompileCounter:
    """Programs lowered or handed to the backend compiler (a persistent-
    cache load counts: the program was not warm in THIS process)."""
    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name in self.EVENTS:
            self.n += 1
            self.seconds += secs
            self.names.append(str(kw.get("fun_name", "?")))


def memory(devices) -> dict:
    """Fullest chip: resident bytes now and the process peak. The backend's
    peak does NOT include a program's temporaries (PERF.md, PR 21)."""
    use = peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        use = max(use, int(st.get("bytes_in_use", 0)))
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"bytes_in_use": use, "peak_bytes_in_use": peak}


class TraceSession:
    """One profiler session; fails loudly when the profiler cannot start.
    The python tracer is off (it adds ~100k events a second of serving) and
    HLO protos are not embedded; ``bench:`` TraceAnnotations still land on
    the host plane."""

    def __init__(self, tag: str):
        self.dir = os.path.join(OUT_DIR, "trace", tag)
        self.reduced = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        opts.raise_error_on_start_failure = True
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def reduce(self):
        from . import trace_reduce
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one xplane.pb under {self.dir}, "
                               f"found {files}")
        self.reduced = trace_reduce.reduce(trace_reduce.read_xplane(files[0]))
        return self.reduced
