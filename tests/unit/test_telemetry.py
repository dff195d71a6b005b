"""Unified telemetry (PR 3 tentpole): in-graph accumulators, step tracing,
anomaly detection, sinks — without re-serializing the async pipeline.

Pins the acceptance contracts:
  * bit-for-bit training parity with telemetry on vs off over 20 fp16 steps
    including a forced overflow (the accumulators observe, never perturb);
  * ZERO added steady-state blocking fetches: between steps_per_print
    boundaries the hot loop performs no device_get at all, and each boundary
    performs exactly ONE batched device_get (telemetry leaf included);
  * CSV/JSONL sink round-trip, CSV handle caching, wandb per-step batching
    (via a stub module);
  * a captured step trace loads as valid Chrome-trace JSON;
  * the telemetry-leak graft-lint corpus entry is flagged by BOTH the
    donation and collective-audit analyzers.
"""

import json
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import telemetry as tel


# --------------------------------------------------------------------------
# shared toy model / config / batches (mirrors test_dataloader_prefetch)
# --------------------------------------------------------------------------

class ToyLinear:
    """Minimal ModelSpec whose loss can be pushed to an fp16 grad overflow
    on demand through the input magnitude."""

    name = "toy-linear"

    def __init__(self, d=8):
        self.d = d

    def init(self, rng):
        return {"w": jax.random.normal(rng, (self.d, self.d),
                                       jnp.float32) * 0.1}

    @property
    def logical_axes(self):
        return {"w": None}

    def loss_fn(self, params, batch, rng, deterministic):
        y = batch["x"] @ params["w"].astype(batch["x"].dtype)
        return jnp.mean(jnp.square(y).astype(jnp.float32))


def fp16_cfg(**overrides):
    cfg = {"train_batch_size": 16,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
           "fp16": {"enabled": True, "initial_scale_power": 8},
           "bf16": {"enabled": False},
           "steps_per_print": 100}
    cfg.update(overrides)
    return cfg


def tel_cfg(**tel_overrides):
    t = {"enabled": True}
    t.update(tel_overrides)
    return t


def overflow_batches(n=20, boost_at=7):
    rng = np.random.default_rng(0)
    batches = [{"x": rng.normal(size=(16, 8)).astype(np.float32)}
               for _ in range(n)]
    batches[boost_at] = {"x": (batches[boost_at]["x"] * 1e8
                               ).astype(np.float32)}
    return batches


def params_bits(engine):
    w = np.asarray(jax.device_get(engine.state["params"]["w"]))
    return w.view(np.uint16)


# --------------------------------------------------------------------------
# (a) bit-for-bit parity: telemetry must observe, never perturb
# --------------------------------------------------------------------------

class TestTelemetryParity:
    def test_on_vs_off_bit_for_bit_with_overflow(self):
        batches = overflow_batches()
        off, *_ = deepspeed_tpu.initialize(model=ToyLinear(),
                                           config=fp16_cfg())
        for b in batches:
            off.train_batch(b)

        on, *_ = deepspeed_tpu.initialize(
            model=ToyLinear(), config=fp16_cfg(telemetry=tel_cfg()))
        on.train_batches(iter(batches), 20)

        assert off.global_steps == on.global_steps == 20
        assert off.skipped_steps == on.skipped_steps == 1
        assert off.get_loss_scale() == on.get_loss_scale()
        np.testing.assert_array_equal(params_bits(off), params_bits(on))

    def test_fused_k_steps_accumulate_and_match(self):
        """pipeline.fuse_steps=4 threads the accumulator leaf through the
        unrolled program: same bits, and the window stats count all 20 steps
        + the one overflow."""
        batches = overflow_batches()
        ref, *_ = deepspeed_tpu.initialize(model=ToyLinear(),
                                           config=fp16_cfg())
        for b in batches:
            ref.train_batch(b)
        fused, *_ = deepspeed_tpu.initialize(
            model=ToyLinear(),
            config=fp16_cfg(telemetry=tel_cfg(),
                            pipeline={"fuse_steps": 4, "in_flight": 2}))
        fused.train_batches(iter(batches), 20)
        np.testing.assert_array_equal(params_bits(ref), params_bits(fused))
        win = fused.drain_telemetry()
        assert win["steps"] == 20
        assert win["overflows"] == 1
        assert win["overflow_rate"] == pytest.approx(1 / 20)

    def test_window_stats_content(self):
        e, *_ = deepspeed_tpu.initialize(
            model=ToyLinear(), config=fp16_cfg(telemetry=tel_cfg()))
        e.train_batches(iter(overflow_batches()), 20)
        win = e.drain_telemetry()
        assert win["steps"] == 20 and win["applied"] == 19
        assert math.isfinite(win["loss_mean"]) and win["loss_mean"] > 0
        assert win["gnorm_max"] >= win["gnorm_mean"] > 0
        # histogram counts every applied (non-overflow) step exactly once
        assert sum(win["gnorm_hist"]) == 19
        assert win["update_ratio_mean"] > 0
        # a second drain sees an EMPTY window (cumulative diff semantics)
        win2 = e.drain_telemetry()
        assert win2["steps"] == 0 and win2["overflows"] == 0

    def test_checkpoint_roundtrips_telemetry_leaf(self, tmp_path):
        e, *_ = deepspeed_tpu.initialize(
            model=ToyLinear(), config=fp16_cfg(telemetry=tel_cfg()))
        e.train_batches(iter(overflow_batches(n=10)), 10)
        e.save_checkpoint(str(tmp_path), tag="ck")
        e2, *_ = deepspeed_tpu.initialize(
            model=ToyLinear(), config=fp16_cfg(telemetry=tel_cfg()))
        e2.load_checkpoint(str(tmp_path), tag="ck")
        assert e2.skipped_steps == 1
        # cumulative counters restored; the window baseline restarts so the
        # first post-restore drain covers exactly the restored totals
        win = e2.drain_telemetry()
        assert win["steps"] == 10 and win["overflows"] == 1

    def test_loads_checkpoint_without_telemetry_leaf(self, tmp_path):
        """A telemetry-off checkpoint loads into a telemetry-on engine: the
        leaf is rebuilt fresh and keeps counting."""
        plain, *_ = deepspeed_tpu.initialize(model=ToyLinear(),
                                             config=fp16_cfg())
        for b in overflow_batches(n=5, boost_at=2):
            plain.train_batch(b)
        plain.save_checkpoint(str(tmp_path), tag="legacy")
        e2, *_ = deepspeed_tpu.initialize(
            model=ToyLinear(), config=fp16_cfg(telemetry=tel_cfg()))
        e2.load_checkpoint(str(tmp_path), tag="legacy")
        assert e2.global_steps == 5 and e2.skipped_steps == 1
        assert "telemetry" in e2.state
        e2.train_batches(iter(overflow_batches(n=5, boost_at=3)), 5)
        win = e2.drain_telemetry()
        assert win["steps"] == 5 and win["overflows"] == 1


# --------------------------------------------------------------------------
# (b) zero added steady-state blocking fetches
# --------------------------------------------------------------------------

class TestSingleBatchedFetch:
    def test_one_device_get_per_print_window(self, monkeypatch):
        # the LR schedule needs the device skip counter at boundaries — it
        # must ride the SAME batched fetch, not a second round trip
        e, *_ = deepspeed_tpu.initialize(
            model=ToyLinear(),
            config=fp16_cfg(steps_per_print=10, telemetry=tel_cfg(),
                            scheduler={"type": "WarmupLR",
                                       "params": {"warmup_max_lr": 1e-2,
                                                  "warmup_num_steps": 5}}))
        batches = overflow_batches()

        calls = []
        real = jax.device_get

        def counting(x):
            calls.append(x)
            return real(x)

        monkeypatch.setattr(jax, "device_get", counting)
        e.train_batches(iter(batches), 20)
        # 20 steps / steps_per_print=10 -> exactly 2 boundary crossings,
        # each ONE batched device_get — telemetry adds ZERO fetches
        assert len(calls) == 2, f"expected 2 batched fetches, saw {len(calls)}"
        # and each fetch carried the telemetry leaf AND the skip counter
        # (for the LR schedule) alongside the metrics
        for c in calls:
            assert "_telemetry" in c and "loss" in c and "_skipped" in c

    def test_returned_metrics_stay_device_resident(self):
        e, *_ = deepspeed_tpu.initialize(
            model=ToyLinear(),
            config=fp16_cfg(steps_per_print=100, telemetry=tel_cfg()))
        m = e.train_batch(overflow_batches(n=1, boost_at=0)[0])
        assert isinstance(m["loss"], jax.Array)  # not float()ed per step


# --------------------------------------------------------------------------
# accumulator / host-window math
# --------------------------------------------------------------------------

class TestAccumulators:
    def test_accumulate_and_window_diff(self):
        leaf = tel.init_leaf(8)
        step = jax.jit(lambda t, loss, g, ov, r: tel.accumulate(
            t, loss=loss, gnorm=g, overflow=ov, update_ratio=r))
        f = jnp.float32
        ov = jnp.asarray(False)
        leaf = step(leaf, f(1.0), f(2.0), ov, f(0.1))
        snap1 = jax.device_get(leaf)
        leaf = step(leaf, f(3.0), f(0.5), ov, f(0.3))
        leaf = step(leaf, f(999.0), f(1e9), jnp.asarray(True), f(0.0))
        snap2 = jax.device_get(leaf)
        win = tel.window_stats(snap2, snap1)
        assert win["steps"] == 2 and win["overflows"] == 1
        assert win["applied"] == 1
        assert win["loss_mean"] == pytest.approx(3.0)
        assert win["gnorm_mean"] == pytest.approx(0.5)
        assert win["update_ratio_mean"] == pytest.approx(0.3, rel=1e-5)
        # the overflow step contributed nothing to the value stats
        assert win["loss_max"] == pytest.approx(3.0)
        assert sum(win["gnorm_hist"]) == 1
        full = tel.window_stats(snap2, None)
        assert full["steps"] == 3 and full["applied"] == 2

    def test_hist_bucket_positions(self):
        leaf = tel.init_leaf(16)
        ov = jnp.asarray(False)
        for g in (2.0 ** -20, 1.0, 2.0 ** 20):  # below, mid, above range
            leaf = tel.accumulate(leaf, loss=jnp.float32(0), gnorm=jnp.float32(g),
                                  overflow=ov)
        hist = np.asarray(jax.device_get(leaf["gnorm_hist"]))
        assert hist[0] == 1 and hist[-1] == 1
        # gnorm=1 (log2=0): bucket 0 is the underflow bucket, bucket k>=1
        # covers [2^(HIST_LOG2_MIN+k-1), 2^(HIST_LOG2_MIN+k))
        assert hist[-tel.HIST_LOG2_MIN + 1] == 1
        assert hist.sum() == 3

    def test_all_overflow_window_has_no_loss_max(self):
        """A window with zero applied steps has no loss data: loss_max must
        come out None (not the -inf seed) so scalar sinks skip it."""
        leaf = tel.init_leaf(8)
        leaf = tel.accumulate(leaf, loss=jnp.float32(999.0),
                              gnorm=jnp.float32(1e9),
                              overflow=jnp.asarray(True))
        win = tel.window_stats(jax.device_get(leaf), None)
        assert win["steps"] == 1 and win["applied"] == 0
        assert win["loss_max"] is None

    def test_host_window_mirrors_device_semantics(self):
        hw = tel.HostWindow(8)
        hw.add({"loss": 1.0, "grad_norm": 2.0, "overflow": False})
        hw.add({"loss": np.float32(3.0), "grad_norm": np.float32(4.0),
                "overflow": np.asarray(True)})
        # drain consumes what the engine's batched device_get fetched
        snap = hw.drain(jax.device_get(hw.pending()))
        win = tel.window_stats(snap, None)
        assert win["steps"] == 2 and win["overflows"] == 1
        assert win["loss_mean"] == pytest.approx(1.0)
        assert win["gnorm_mean"] == pytest.approx(2.0)
        assert hw.pending() == []  # queue cleared


# --------------------------------------------------------------------------
# anomaly detection
# --------------------------------------------------------------------------

def _anomaly_cfg(**over):
    from deepspeed_tpu.config import AnomalyConfig
    return AnomalyConfig.from_dict(over)


def _win(**over):
    base = {"steps": 10, "applied": 10, "overflows": 0, "overflow_rate": 0.0,
            "loss_mean": 1.0, "loss_max": 1.0, "gnorm_mean": 1.0,
            "gnorm_max": 1.0, "update_ratio_mean": 0.01, "gnorm_hist": []}
    base.update(over)
    return base


class TestAnomalyDetector:
    def test_loss_spike_fires_after_warmup(self):
        det = tel.AnomalyDetector(_anomaly_cfg(warmup_windows=1,
                                               loss_spike_factor=2.0))
        assert det.observe(_win(), step=10) == []     # warmup: seeds only
        events = det.observe(_win(loss_mean=10.0), step=20)
        rules = {e["rule"] for e in events}
        assert "loss_spike" in rules
        spike = next(e for e in events if e["rule"] == "loss_spike")
        assert spike["severity"] == "critical"        # >2x factor x baseline
        assert spike["step"] == 20 and spike["baseline"] is not None

    def test_nonfinite_loss_is_always_critical(self):
        det = tel.AnomalyDetector(_anomaly_cfg())
        events = det.observe(_win(loss_mean=float("nan")), step=5)
        assert any(e["rule"] == "loss_spike" and e["severity"] == "critical"
                   for e in events)

    def test_overflow_burst_no_warmup(self):
        det = tel.AnomalyDetector(_anomaly_cfg(overflow_burst_rate=0.25))
        events = det.observe(
            _win(overflows=5, overflow_rate=0.5, applied=5), step=10)
        assert any(e["rule"] == "overflow_burst"
                   and e["severity"] == "critical" for e in events)

    def test_stall_regression(self):
        det = tel.AnomalyDetector(_anomaly_cfg(warmup_windows=1,
                                               stall_regression_factor=3.0))
        det.observe(_win(stall_ms_per_step=1.0), step=10)
        events = det.observe(_win(stall_ms_per_step=10.0), step=20)
        assert any(e["rule"] == "dispatch_stall" for e in events)

    def test_steady_state_stays_quiet(self):
        det = tel.AnomalyDetector(_anomaly_cfg())
        for i in range(5):
            assert det.observe(_win(), step=10 * (i + 1)) == []


# --------------------------------------------------------------------------
# step tracing / chrome trace export (acceptance: valid Chrome-trace JSON)
# --------------------------------------------------------------------------

class TestStepTracer:
    def test_span_window_and_chrome_export(self, tmp_path):
        tr = tel.StepTracer()
        with tr.span("dispatch"):
            pass
        with tr.span("block"):
            pass
        with tr.span("dispatch"):
            pass
        win = tr.drain_window()
        assert win["dispatch_count"] == 2 and win["block_count"] == 1
        assert win["dispatch_ms"] >= 0
        assert tr.drain_window() == {}  # window reset
        path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
        with open(path) as f:
            data = json.load(f)
        assert isinstance(data["traceEvents"], list) and data["traceEvents"]
        for ev in data["traceEvents"]:
            assert ev["ph"] in ("X", "i")
            assert "name" in ev and "ts" in ev and "pid" in ev

    def test_engine_trace_covers_pipeline_phases(self, tmp_path):
        e, *_ = deepspeed_tpu.initialize(
            model=ToyLinear(), config=fp16_cfg(telemetry=tel_cfg()))
        e.train_batches(iter(overflow_batches(n=8)), 8)
        path = e.export_trace(str(tmp_path / "step_trace.json"))
        with open(path) as f:
            data = json.load(f)
        names = {ev["name"] for ev in data["traceEvents"]}
        # dispatch + prefetch + data_wait + block phases all recorded
        assert {"dispatch", "prefetch", "data_wait", "block"} <= names

    def test_profiler_window_survives_fused_stride(self, tmp_path):
        """A fused K-step stride that jumps over [start, start+num) must
        start a shifted capture, not silently lose it; a run RESUMED past
        the window must not capture at all."""
        cfg = types.SimpleNamespace(enabled=True, start_step=10, num_steps=2,
                                    output_dir=str(tmp_path / "p"))
        tr = tel.StepTracer(trace_cfg=cfg)
        for step in (0, 4, 8, 12):   # stride 4 jumps over [10, 12)
            tr.maybe_profile(step)
        assert tr._profiling          # shifted capture opened at step 12
        tr.maybe_profile(16)
        assert not tr._profiling and tr._profile_done
        assert any(os.scandir(str(tmp_path / "p")))
        resumed = tel.StepTracer(trace_cfg=types.SimpleNamespace(
            enabled=True, start_step=10, num_steps=2,
            output_dir=str(tmp_path / "q")))
        resumed.maybe_profile(100000)  # checkpoint resume past the window
        assert resumed._profile_done and not resumed._profiling

    def test_export_requires_telemetry(self):
        e, *_ = deepspeed_tpu.initialize(model=ToyLinear(),
                                         config=fp16_cfg())
        with pytest.raises(RuntimeError):
            e.export_trace("/tmp/never.json")


# --------------------------------------------------------------------------
# (c) sinks: CSV caching round-trip, JSONL round-trip, wandb batching
# --------------------------------------------------------------------------

def _sink_cfg(tmp_path, **over):
    d = {"enabled": True, "output_path": str(tmp_path), "job_name": "t",
         "team": None, "group": None, "project": None}
    d.update(over)
    return types.SimpleNamespace(**d)


class TestSinks:
    def test_csv_caches_handles_and_roundtrips(self, tmp_path):
        from deepspeed_tpu.monitor import CSVMonitor
        mon = CSVMonitor(_sink_cfg(tmp_path))
        mon.write_events([("Train/loss", 1.0, 1), ("Train/lr", 0.1, 1)])
        mon.write_events([("Train/loss", 2.0, 2), ("Train/lr", 0.2, 2)])
        # the satellite fix: handles are cached per metric, not reopened
        assert set(mon._files) == {"Train/loss", "Train/lr"}
        mon.flush()
        assert mon._files == {}  # flush closed them
        loss_csv = os.path.join(mon.dir, "Train_loss.csv")
        with open(loss_csv) as f:
            rows = list(f.read().strip().splitlines())
        assert rows[0].startswith("step,")        # header once
        assert len(rows) == 3
        assert rows[1].startswith("1,1.0") and rows[2].startswith("2,2.0")
        # writes after flush reopen and append without a second header
        mon.write_events([("Train/loss", 3.0, 3)])
        mon.flush()
        with open(loss_csv) as f:
            assert len(f.read().strip().splitlines()) == 4

    def test_jsonl_roundtrip_events_and_records(self, tmp_path):
        from deepspeed_tpu.monitor import JSONLMonitor
        path = str(tmp_path / "events.jsonl")
        mon = JSONLMonitor(path)
        mon.write_events([("telemetry/loss_mean", 1.5, 10)])
        mon.write_records([{"type": "anomaly", "rule": "loss_spike",
                            "severity": "critical", "step": 10,
                            "value": 9.0}])
        mon.flush()
        lines = [json.loads(l) for l in open(path)]
        assert lines[0] == {"type": "scalar", "name": "telemetry/loss_mean",
                            "value": 1.5, "step": 10,
                            "time": lines[0]["time"]}
        assert lines[1]["type"] == "anomaly"
        assert lines[1]["rule"] == "loss_spike"
        assert lines[1]["severity"] == "critical" and "time" in lines[1]

    def test_wandb_batches_one_log_per_step(self, tmp_path, monkeypatch):
        calls = []
        stub = types.ModuleType("wandb")
        stub.init = lambda **kw: None
        stub.log = lambda data, step=None: calls.append((dict(data), step))
        monkeypatch.setitem(sys.modules, "wandb", stub)
        from deepspeed_tpu.monitor import WandbMonitor
        mon = WandbMonitor(_sink_cfg(tmp_path))
        assert mon.enabled
        mon.write_events([("a", 1.0, 1), ("b", 2.0, 1),
                          ("a", 3.0, 2), ("b", 4.0, 2)])
        # the satellite fix: 4 events across 2 steps -> exactly 2 log calls
        assert calls == [({"a": 1.0, "b": 2.0}, 1), ({"a": 3.0, "b": 4.0}, 2)]

    def test_scalar_sinks_project_anomaly_records(self, tmp_path):
        from deepspeed_tpu.monitor import CSVMonitor
        mon = CSVMonitor(_sink_cfg(tmp_path))
        mon.write_records([{"type": "anomaly", "rule": "gnorm_drift",
                            "severity": "warning", "step": 7},
                           {"type": "telemetry_window", "step": 7}])
        mon.flush()
        files = os.listdir(mon.dir)
        assert "anomaly_gnorm_drift.csv" in files
        # the window record (no scalar projection) produced no file
        assert len(files) == 1


# --------------------------------------------------------------------------
# engine end-to-end: events fan out, anomalies fire, static join reports
# --------------------------------------------------------------------------

class TestEngineTelemetryEndToEnd:
    def test_jsonl_and_csv_fanout_with_anomaly(self, tmp_path):
        jsonl = str(tmp_path / "tel.jsonl")
        cfg = fp16_cfg(
            steps_per_print=10,
            csv_monitor={"enabled": True, "output_path": str(tmp_path),
                         "job_name": "job"},
            telemetry=tel_cfg(jsonl_path=jsonl,
                              anomaly={"enabled": True,
                                       "overflow_burst_rate": 0.05}))
        e, *_ = deepspeed_tpu.initialize(model=ToyLinear(), config=cfg)
        # window 1 contains the forced overflow -> overflow_burst fires
        e.train_batches(iter(overflow_batches(n=20, boost_at=3)), 20)
        e.monitor.flush()
        recs = [json.loads(l) for l in open(jsonl)]
        types_seen = {r["type"] for r in recs}
        assert {"scalar", "telemetry_window", "anomaly"} <= types_seen
        windows = [r for r in recs if r["type"] == "telemetry_window"]
        assert windows[0]["overflows"] == 1 and windows[0]["steps"] == 10
        assert windows[1]["steps"] == 10 and windows[1]["overflows"] == 0
        anomalies = [r for r in recs if r["type"] == "anomaly"]
        assert any(a["rule"] == "overflow_burst" for a in anomalies)
        csv_dir = os.path.join(str(tmp_path), "job")
        files = set(os.listdir(csv_dir))
        assert "telemetry_loss_mean.csv" in files
        assert "anomaly_overflow_burst.csv" in files
        # exactly ONE scalar row per fired anomaly (regression: the engine
        # events list + the write_records projection double-wrote these)
        bursts = [a for a in anomalies if a["rule"] == "overflow_burst"]
        with open(os.path.join(csv_dir, "anomaly_overflow_burst.csv")) as f:
            rows = f.read().strip().splitlines()
        assert len(rows) - 1 == len(bursts)  # header + one row per event

    def test_static_join_reports_mfu_and_comm_rate(self):
        e, *_ = deepspeed_tpu.initialize(
            model=ToyLinear(),
            config=fp16_cfg(zero_optimization={"stage": 2},
                            telemetry=tel_cfg()))
        e.train_batches(iter(overflow_batches(n=10)), 10)
        win = e.drain_telemetry()
        assert win["steps_per_sec"] > 0
        # ZeRO-2 on an 8-way mesh moves real collective bytes every step
        assert win["modeled_comm_bytes_per_sec"] > 0
        assert 0 <= win.get("window_mfu", 0.0) < 1.0
        # the memory-lint join rides the same static cost: modeled peak
        # next to the allocator's measured high-water (when the transport
        # exposes memory_stats — CPU does)
        assert win["modeled_peak_hbm"] > 0

    def test_comms_logger_events_reach_monitor(self, tmp_path):
        jsonl = str(tmp_path / "comm.jsonl")
        from deepspeed_tpu.comm import comms_logger
        comms_logger.reset()
        cfg = fp16_cfg(steps_per_print=10,
                       comms_logger={"enabled": True},
                       telemetry=tel_cfg(jsonl_path=jsonl))
        e, *_ = deepspeed_tpu.initialize(model=ToyLinear(), config=cfg)
        try:
            from deepspeed_tpu import comm
            # trace-time + host-blocking records the engine should fan out
            comms_logger.record("all_reduce", "data", 4096)
            comms_logger.record_host("init_distributed", 1.5)
            e.train_batches(iter(overflow_batches(n=10)), 10)
            e.monitor.flush()
            recs = [json.loads(l) for l in open(jsonl)]
            names = {r["name"] for r in recs if r["type"] == "scalar"}
            assert any(n.startswith("comm/") and n.endswith("/count")
                       for n in names)
            assert any(n.startswith("comm/host_ms/") for n in names)
            # log_summary fans out through a monitor as well
            comm.log_summary(monitor=e.monitor, step=e.global_steps)
        finally:
            comms_logger.configure(enabled=False)

    def test_host_window_engine_plumbing(self):
        """Host-driven optimizer paths (NVMe swapper, layer-streamed
        executor) have no jitted optimizer apply, so the engine mirrors the
        accumulator host-side. Those executors need pinned_host memory this
        CPU backend lacks (pre-existing test_offload/test_infinity skips),
        so the host mirror is wired in directly: per-step metric scalars
        queue UN-fetched and drain at the boundary's one batched fetch."""
        from deepspeed_tpu.telemetry import HostWindow
        e, *_ = deepspeed_tpu.initialize(
            model=ToyLinear(),
            config=fp16_cfg(steps_per_print=5,
                            telemetry=tel_cfg(static_join=False)))
        e._tel_in_graph = False          # what a host-driven init would set
        e._tel_host = HostWindow(16)
        for b in overflow_batches(n=5, boost_at=1):
            e.train_batch(b)
        win = e.telemetry_window()       # drained at the step-5 boundary
        assert win is not None
        assert win["steps"] == 5 and win["overflows"] == 1
        assert math.isfinite(win["loss_mean"]) and win["loss_mean"] > 0
        assert sum(win["gnorm_hist"]) == 4
        assert e._tel_host.pending() == []


# --------------------------------------------------------------------------
# graft-lint: the telemetry-leak corpus entry (CI tooling satellite)
# --------------------------------------------------------------------------

class TestTelemetryLeakCorpus:
    def test_both_analyzers_flag_the_leak(self, devices8):
        from deepspeed_tpu.analysis.corpus import run_corpus
        report = run_corpus("telemetry-leak", devices=devices8[:2])
        assert not report.ok
        rules = {f.rule for f in report.findings}
        assert "donation-missing" in rules          # un-donated stats leaf
        # the per-step collective, seen by its bytes (the defect-free twin:
        # test_analysis.py::TestSeededCorpus::test_defect_free_twin_is_ok)
        assert "collective-census-drift" in rules
        leak = next(f for f in report.findings
                    if f.rule == "donation-missing")
        assert "telemetry" in leak.ident


# --------------------------------------------------------------------------
# config surface
# --------------------------------------------------------------------------

class TestTelemetryConfig:
    def test_defaults_off_and_validation(self):
        from deepspeed_tpu.config import Config, ConfigError
        cfg = Config.load({})
        assert not cfg.telemetry.enabled
        assert cfg.telemetry.anomaly.enabled
        with pytest.raises(ConfigError):
            Config.load({"telemetry": {"gnorm_hist_buckets": 1}})
        with pytest.raises(ConfigError):
            Config.load({"telemetry": {"trace": {"num_steps": 0}}})

    def test_sections_parse(self):
        from deepspeed_tpu.config import Config
        cfg = Config.load({"telemetry": {
            "enabled": True, "jsonl_path": "/tmp/x.jsonl",
            "trace": {"enabled": True, "start_step": 5, "num_steps": 3},
            "anomaly": {"loss_spike_factor": 4.0}}})
        assert cfg.telemetry.trace.start_step == 5
        assert cfg.telemetry.anomaly.loss_spike_factor == 4.0
        assert cfg.telemetry.jsonl_path == "/tmp/x.jsonl"
