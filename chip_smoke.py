#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, end to end, through the entry points a user calls
(``deepspeed_tpu.initialize`` -> ``train_batch``/``train_batches``;
``deepspeed_tpu.init_serving`` -> ``run``), in ONE process, on Mistral-7B at
its published widths (hidden 4096, 32 query / 8 KV heads x 128, FFN 14336,
vocab 32000, untied, rope theta 10000). No width is cut; only depth, so the
train state / the served weights fit the chip. Weights are random, from a
seed. Nothing here is a benchmark: any time printed is information labelled
with the device it ran on, not a metric.

    python chip_smoke.py                 one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4       one four-chip host: ZeRO-3 fsdp 2 x
                                         tensor 2 trains, tensor_parallel=4
                                         serves all 32 layers
    python chip_smoke.py --rehearsal     the same legs at toy widths on the
                                         CPU, Pallas in interpret mode — to
                                         catch a NameError before chip time
                                         is spent; JSON says "rehearsal": true

Fails loudly: any platform other than ``tpu`` (outside --rehearsal) exits
non-zero naming what it found, and no leg is wrapped in a try/except — a leg
that raises ends the run with its traceback. A passing run prints what it
found as one ``SUMMARY {json}`` line (depth cuts, per-leg pass, decode
backends, parity numbers; ``"rehearsal": true`` under --rehearsal) and then,
on the chip only, the verdict as the LAST line of stdout:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` —
exactly those keys. A failing run prints neither.
"""

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import os
import re
import sys
import time

import numpy as np

MISTRAL_7B = {
    "model_type": "mistral", "vocab_size": 32000, "hidden_size": 4096,
    "intermediate_size": 14336, "num_hidden_layers": 32,
    "num_attention_heads": 32, "num_key_value_heads": 8,
    "max_position_embeddings": 32768, "sliding_window": 4096,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
}
# --rehearsal: the same family and the same head grouping (4 query heads
# per KV head, head_dim >= 64 so the paged kernel gate admits it) at toy
# widths
TOY = dict(MISTRAL_7B, vocab_size=512, hidden_size=1024,
           intermediate_size=1024, num_hidden_layers=4,
           num_attention_heads=16, num_key_value_heads=4)


@dataclasses.dataclass(frozen=True)
class Sizes:
    hf: dict
    seq: int                 # train sequence length
    train_layers: int
    train_batch: int
    serve_layers: int
    max_seqs: int            # serving slots (fewer than the requests)
    max_model_len: int
    # (prompt length, new tokens). With prompt_bucket = block_size = 64:
    # 100/120/128 pad to 128 (a flash bucket, 100 and 120 through the
    # in-kernel padding mask), 33/40/60 pad to 64 (an XLA bucket); 60 + 24
    # grows across the 64-token block boundary mid-decode (a second KV block
    # is allocated); every request decodes for 2-3 quanta of 8 steps
    requests: tuple = ((40, 20), (60, 24), (100, 17), (120, 20), (128, 9),
                       (90, 24), (33, 18))
    why: str = ""


# 16 B/param of ZeRO-1 state (bf16 params + grads, fp32 master/m/v): one
# layer is 218 M params, embeddings + head 262 M -> 2 layers = 698 M =
# 10.4 GiB, the deepest that fits 16 GB beside activations and the fp32
# logits chunk. Serving holds bf16 weights only: 8 layers = 3.7 GiB.
ONE_CHIP = Sizes(MISTRAL_7B, seq=2048, train_layers=2, train_batch=1,
                 serve_layers=8, max_seqs=4, max_model_len=1024,
                 why="train: 2 of 32 layers = 698M params x 16 B/param of "
                     "ZeRO-1 state = 10.4 GiB of one 16 GB chip; serve: 8 "
                     "of 32 layers = 3.7 GiB of bf16 weights + the KV pool")
# ZeRO-3 over fsdp 2 x tensor 2 shards all 16 B/param four ways: 8 layers =
# 2.0 B params = 29.9 GiB = 7.5 GiB per chip. tensor_parallel=4 holds the
# WHOLE model: 32 layers = 13.5 GiB bf16 = 3.4 GiB per chip.
FOUR_CHIPS = Sizes(MISTRAL_7B, seq=2048, train_layers=8, train_batch=2,
                   serve_layers=32, max_seqs=4, max_model_len=1024,
                   why="train: 8 of 32 layers = 2.0B params x 16 B/param "
                       "of ZeRO-3 state = 7.5 GiB per chip; serve: all 32 "
                       "layers, no cut")
REHEARSAL = Sizes(TOY, seq=256, train_layers=2, train_batch=2,
                  serve_layers=2, max_seqs=2, max_model_len=256,
                  requests=((40, 10), (60, 12), (100, 9), (128, 9)),
                  why="toy widths on the CPU: a rehearsal, not a result")

DEGRADE_EVENTS = ("backend_degraded", "aio_fallback", "serving_recovered")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def device_report(devs) -> dict:
    """The device as JAX reports it."""
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def verdict_line(device: dict) -> str:
    """The last line of stdout of a pass on the chip: exactly these keys."""
    return json.dumps({"ok": True, "device": device})


def rel_l2(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def hbm():
    """(bytes in use, process peak) per device, or None where the backend
    reports no memory stats (the CPU)."""
    import jax
    out = []
    for d in jax.devices():
        st = d.memory_stats()
        if not st:
            return None
        out.append((int(st["bytes_in_use"]), int(st["peak_bytes_in_use"])))
    return out


def fmt_hbm(stats) -> str:
    if stats is None:
        return "n/a (backend reports no memory stats)"
    return " ".join(f"dev{i}: {u / 2**30:.2f} GiB in use, peak "
                    f"{p / 2**30:.2f}" for i, (u, p) in enumerate(stats))


def per_device_bytes(tree) -> list:
    """Bytes each device actually holds of a pytree of committed arrays."""
    import jax
    held = {d.id: 0 for d in jax.devices()}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            held[sh.device.id] += sh.data.nbytes
    return [held[d.id] for d in jax.devices()]


def assert_balanced(name: str, per_dev: list, band: float) -> None:
    """Not everything on the first chip: max/min within `band`."""
    lo, hi = min(per_dev), max(per_dev)
    log(f"  per-device {name}: "
        + " ".join(f"{b / 2**30:.3f}" for b in per_dev) + " GiB")
    assert lo > 0 and hi / lo <= band, (
        f"{name} unbalanced across chips: {per_dev} (band {band})")


_SHAPE = re.compile(r"=\s*\(?\s*[a-z0-9]+\[([0-9,]*)\]")


def mosaic_result_dims(hlo: str) -> list:
    """First result shape of every Mosaic (tpu_custom_call) instruction in
    an optimized, PER-DEVICE HLO text."""
    dims = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _SHAPE.search(line)
            assert m, f"unparsed Mosaic call: {line[:200]}"
            dims.append(tuple(int(x) for x in m.group(1).split(",") if x))
    return dims


def model_config(sz: Sizes, layers: int, max_seq_len: int, rehearsal: bool,
                 **overrides):
    """The smoke's model, offline from the config dict. Off the TPU the
    Pallas kernels are opt-in (interpret mode); on it "auto" selects them."""
    from deepspeed_tpu.models.hf_import import hf_config_to_transformer
    if rehearsal:
        overrides["attention_impl"] = "pallas"
    return hf_config_to_transformer(sz.hf, num_layers=layers,
                                    max_seq_len=max_seq_len, **overrides)


# --------------------------------------------------------------------------
# legs
# --------------------------------------------------------------------------

def leg_kernels(sz: Sizes, pool_shape, rehearsal: bool) -> dict:
    """The chip computed the right thing: the Pallas kernels against the
    plain XLA paths at the smoke's own shapes."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.transformer import _paged_attention, attention

    cfg = model_config(sz, 1, sz.seq, rehearsal)
    xla = dataclasses.replace(cfg, attention_impl="xla")
    B, S, Nq, Nkv, D = (1, sz.seq, cfg.num_heads, cfg.kv_heads,
                        cfg.dim_per_head)
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q = jax.random.normal(ks[0], (B, S, Nq, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Nkv, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Nkv, D), jnp.bfloat16)
    w = jax.random.normal(ks[3], (B, S, Nq, D), jnp.bfloat16)

    def value_and_grads(c):
        f = jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(
                (attention(q, k, v, cfg=c) * w).astype(jnp.float32)),
            argnums=(0, 1, 2)))
        return f(q, k, v)

    # Tolerance 2e-2 relative L2: inputs, probabilities (XLA path) and
    # outputs are bf16 (8 mantissa bits, 2^-8 = 3.9e-3 per rounding); the
    # kernel keeps probabilities in fp32 and the XLA path rounds them to
    # bf16 before P@V, so the two programs differ by a few bf16 roundings
    # accumulated over S keys. Bit-equality between two compiled programs is
    # never asserted.
    tol = 2e-2
    out = {}
    o_ref = jax.jit(lambda q, k, v: attention(q, k, v, cfg=xla))(q, k, v)
    o_got = jax.jit(lambda q, k, v: attention(q, k, v, cfg=cfg))(q, k, v)
    out["flash_fwd"] = rel_l2(o_got, o_ref)
    _, g_ref = value_and_grads(xla)
    for fused in (False, True):
        _, g = value_and_grads(dataclasses.replace(cfg, fused_backward=fused))
        for name, a, b in zip("qkv", g, g_ref):
            out[f"flash_bwd_{'fused' if fused else 'unfused'}_d{name}"] = \
                rel_l2(a, b)
    # the smallest flash bucket of the prefill (P = 128) through the
    # in-kernel key-padding mask
    P = 128
    mask = jnp.arange(P)[None, :] < 100
    qs, ks_, vs = q[:, :P], k[:, :P], v[:, :P]
    m_ref = jax.jit(lambda q, k, v: attention(q, k, v, mask, cfg=xla))(
        qs, ks_, vs)
    m_got = jax.jit(lambda q, k, v: attention(q, k, v, mask, cfg=cfg))(
        qs, ks_, vs)
    out["flash_fwd_masked_P128"] = rel_l2(m_got[:, :100], m_ref[:, :100])

    # paged decode at the pool shape the server built: scattered block
    # tables, slots at different lengths (one inside its first block, one
    # exactly on a block boundary, one deep into the table)
    NB, bs, _, _ = pool_shape            # token-major [NB, bs, Nkv, D]
    S_slots, MB = sz.max_seqs, sz.max_model_len // bs
    kp = jax.random.normal(ks[4], pool_shape, jnp.bfloat16)
    vp = jax.random.normal(ks[5], pool_shape, jnp.bfloat16)
    qd = jax.random.normal(ks[6], (S_slots, 1, Nq, D), jnp.bfloat16)
    kr = jax.random.normal(ks[7], (S_slots, Nkv, 1, D), jnp.bfloat16)
    vr = kr * 0.5
    rng = np.random.default_rng(0)
    ids = rng.permutation(np.arange(1, NB))[:S_slots * MB].reshape(
        S_slots, MB).astype(np.int32)
    lens = np.array([bs // 2, bs, MB * bs - 3, 3 * bs + 1][:S_slots],
                    np.int32)
    lens = np.minimum(lens, MB * bs - 1)
    tables, lens = jnp.asarray(ids), jnp.asarray(lens)

    def decode(backend):
        return jax.jit(lambda q, kp, vp: _paged_attention(
            q, kp, vp, tables, lens, cfg, kv_row=(kr, vr),
            backend=backend))(qd, kp, vp)

    out["paged_decode"] = rel_l2(decode("pallas"), decode("xla"))
    for name, err in out.items():
        log(f"  {name}: rel L2 {err:.2e} (tolerance {tol:.0e})")
        assert np.isfinite(err) and err < tol, (name, err)
    return out


def leg_train(sz: Sizes, chips: int, rehearsal: bool) -> dict:
    """A few steps on a repeated seeded batch, `transformer.fused_backward`
    off and on: loss finite and falling, the
    timed steps end in block_until_ready, the step lowers with the Mosaic
    kernels, and on four chips nothing is replicated or lopsided."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.analysis.lint import lower_engine_programs
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.utils.hlo_check import assert_no_spmd_replication

    # the train cells' settings (benchmark/configs/mistral-7b-train.json)
    cfg = model_config(sz, sz.train_layers, sz.seq, rehearsal, remat=True,
                       remat_policy="dots_saveable", loss_chunk=sz.seq)
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(sz.train_batch, sz.seq), dtype=np.int32)}
    layout = ({"zero_optimization": {"stage": 3},
               "mesh": {"axes": {"fsdp": 2, "tensor": 2}}} if chips == 4
              else {"zero_optimization": {"stage": 1}})
    report = {}
    for fused in (False, True):
        tag = "fused_backward" if fused else "unfused_backward"
        engine, *_ = deepspeed_tpu.initialize(
            model=make_model(cfg, name="mistral-7b-widths"), config={
                "train_batch_size": sz.train_batch,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                "pipeline": {"in_flight": 4, "prefetch": True},
                "transformer": {"fused_backward": fused},
                "steps_per_print": 1000000, **layout})
        t0 = time.perf_counter()
        # the first step compiles; on a mesh the compile must not log an
        # involuntary full rematerialization (a tensor replicated in the
        # hot loop)
        first = assert_no_spmd_replication(engine.train_batch, batch)
        losses = [float(first["loss"])]
        compile_s = time.perf_counter() - t0
        losses += [float(engine.train_batch(batch)["loss"])
                   for _ in range(3)]
        # async path, timed window ends in block_until_ready
        n = 4
        t0 = time.perf_counter()
        last = engine.train_batches((batch for _ in range(n)), n)
        dispatched_s = time.perf_counter() - t0
        jax.block_until_ready(engine.state)
        blocked_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        losses.append(float(last["loss"]))
        fetch_ms = (time.perf_counter() - t1) * 1e3
        step_ms = blocked_s / n * 1e3
        log(f"  {tag}: losses {[round(x, 4) for x in losses]}")
        log(f"  {tag}: first step (compile included) {compile_s:.1f}s; "
            f"{n} async steps: returned after {dispatched_s * 1e3:.0f} ms, "
            f"block_until_ready after {blocked_s * 1e3:.0f} ms "
            f"({step_ms:.0f} ms/step on this device — information, not a "
            f"metric); loss fetch after the block {fetch_ms:.2f} ms")
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], f"loss not falling: {losses}"
        # block_until_ready really blocked: nothing was left to wait for
        # when the loss was fetched afterwards
        assert fetch_ms < max(20.0, step_ms / 2), (fetch_ms, step_ms)
        arts = lower_engine_programs(engine, batch)
        n_mosaic = sum(a.stablehlo.count("tpu_custom_call") for a in arts)
        log(f"  {tag}: train step lowers with {n_mosaic} Mosaic custom "
            "call(s)")
        # on the chip: forward (+ its remat replay), dQ and dK/dV grids;
        # interpret mode lowers to plain HLO
        assert n_mosaic == 0 if rehearsal else n_mosaic >= 3, n_mosaic
        if chips > 1:
            if not rehearsal:
                dims = mosaic_result_dims(arts[0].optimized_hlo)
                b_loc, kv_loc = sz.train_batch // 2, cfg.kv_heads // 2
                log(f"  {tag}: per-device Mosaic result shapes {dims}")
                assert dims and all(d[:2] == (b_loc, kv_loc) for d in dims), (
                    "a flash kernel runs replicated, not on its "
                    f"[batch/fsdp={b_loc}, kv_heads/tensor={kv_loc}] shard")
            assert_balanced("params", per_device_bytes(
                engine.state["params"]), band=1.02)
            assert_balanced("optimizer state", per_device_bytes(
                engine.state["opt"]), band=1.02)
        stats = hbm()
        log(f"  {tag}: HBM {fmt_hbm(stats)}")
        if stats is not None and chips > 1:
            assert_balanced("HBM in use", [u for u, _ in stats], band=1.10)
        report[tag] = losses
        assert engine.close()
        del engine, first, last, arts
        gc.collect()
    a, b = report["unfused_backward"], report["fused_backward"]
    # same seed, same batch: the two backward variants differ only in where
    # rowsum(dO*O) is computed (fp32 both ways), so the trajectories agree
    # to bf16 step noise. Measured against the initial loss: the repeated
    # batch is memorized within a few steps and a gap relative to a loss
    # of 1e-5 would compare rounding noise.
    drift = max(abs(x - y) for x, y in zip(a, b)) / a[0]
    log(f"  fused vs unfused loss trajectories: max gap {drift:.2e} of the "
        "initial loss")
    assert drift < 1e-2, (a, b)
    return report


def leg_serve(sz: Sizes, chips: int, rehearsal: bool, forced_pallas: bool
              ) -> dict:
    """More requests than slots, prompts in flash and XLA buckets, several
    decode quanta each. `forced_pallas=False` is the engine as a user gets it
    by default; True pins a float KV pool and the paged Pallas kernel so
    Mosaic compiles ops/decode_attention.py at the real pool shape."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import make_model
    from deepspeed_tpu.robustness import events

    cfg = model_config(sz, sz.serve_layers, sz.max_model_len, rehearsal)
    model = make_model(cfg, name="mistral-7b-widths")
    serving = dict(max_seqs=sz.max_seqs, max_model_len=sz.max_model_len)
    kwargs = {}
    if forced_pallas:
        serving["decode_backend"] = "pallas"
        kwargs["kv_cache_bits"] = 0
    if chips > 1:
        kwargs["tensor_parallel"] = chips
    events.clear()
    srv = deepspeed_tpu.init_serving(model, serving=serving, **kwargs)
    log(f"  decode_backend={srv.decode_backend} backend_bench="
        f"{srv.backend_bench} mesh={srv.mesh_desc} "
        f"kv_cache_bits={srv.model.config.kv_cache_bits} "
        f"pool {tuple(srv.pools['k'].shape)} {srv.pools['k'].dtype}")
    if forced_pallas:
        assert srv.decode_backend == "pallas", srv.backend_bench
    if chips > 1:
        assert srv.pools["k"].sharding.spec[3] == "tensor"
        assert_balanced("weights", per_device_bytes(srv.engine.params),
                        band=1.02)
        assert_balanced("KV pools", per_device_bytes(srv.pools),
                        band=1.02)
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, cfg.vocab_size, size=p, dtype=np.int32), n)
            for p, n in sz.requests]
    assert len(reqs) > sz.max_seqs
    t0 = time.perf_counter()
    outs = srv.run(reqs)
    wall = time.perf_counter() - t0
    assert len(outs) == len(reqs), (len(outs), len(reqs))
    for (prompt, n), rid in zip(reqs, sorted(outs)):
        o = outs[rid]
        assert o.shape == (prompt.size + n,), (o.shape, prompt.size, n)
        assert np.array_equal(o[:prompt.size], prompt)
        assert ((0 <= o) & (o < cfg.vocab_size)).all()
    assert srv.allocator.used_blocks == 0, srv.allocator.used_blocks
    if chips > 1:
        assert srv.pools["k"].sharding.spec[3] == "tensor"
    st = srv.stats()
    log(f"  {len(outs)} requests on {sz.max_seqs} slots completed in "
        f"{wall:.1f}s (compiles included); generated "
        f"{int(st['generated_tokens'])} tokens, preemptions "
        f"{int(st['preemptions'])}, recoveries {int(st['recoveries'])}")

    # what ran: the decode step and the flash-bucket prefill, lowered with
    # the server's own arrays (tracing only, nothing executes)
    S, MB = sz.max_seqs, srv.MB
    tok = jnp.zeros((S,), jnp.int32)
    tab = jnp.zeros((S, MB), jnp.int32)
    act = jnp.ones((S,), bool)
    with srv.engine.mesh:
        dec = jax.jit(lambda p, pools: srv.model.decode_step_paged(
            p, tok, pools, tab, tok, active=act,
            backend=srv.decode_backend)).lower(srv.engine.params, srv.pools)
        pre = jax.jit(lambda p, pools: srv.model.prefill_paged(
            p, jnp.zeros((1, 128), jnp.int32), pools,
            jnp.zeros((128 // srv.config.block_size,), jnp.int32),
            length=jnp.int32(100))).lower(srv.engine.params, srv.pools)
        n_dec = dec.as_text().count("tpu_custom_call")
        n_pre = pre.as_text().count("tpu_custom_call")
        log(f"  Mosaic custom calls in the lowered decode step: {n_dec}; "
            f"in the lowered P=128 prefill: {n_pre}")
        on_chip = not rehearsal
        assert (n_dec > 0) == (on_chip and srv.decode_backend == "pallas")
        assert (n_pre > 0) == on_chip
        if chips > 1 and n_dec:
            dims = mosaic_result_dims(dec.compile().as_text())
            log(f"  per-device Mosaic result shapes (decode): {dims}")
            assert dims and all(d[:2] == (S, cfg.kv_heads // chips)
                                for d in dims), (
                "the paged kernel runs replicated, not on its kv-head slice")
    counts = {e: len(events.history(e))
              for e in ("decode_backend_selected",) + DEGRADE_EVENTS}
    log(f"  events: {counts}")
    assert all(counts[e] == 0 for e in DEGRADE_EVENTS), counts
    stats = hbm()
    log(f"  HBM {fmt_hbm(stats)}")
    if stats is not None and chips > 1:
        assert_balanced("HBM in use", [u for u, _ in stats], band=1.10)
    pool_shape = tuple(srv.pools["k"].shape[1:])
    result = {"decode_backend": srv.decode_backend,
              "backend_bench": srv.backend_bench, "events": counts,
              "pool_shape": pool_shape}
    assert srv.close()
    del srv, outs, dec, pre
    gc.collect()
    return result


def leg_backend_bench(sz: Sizes, chips: int, rehearsal: bool) -> dict:
    """The init micro-bench a float-pool engine runs by default
    (`decode_backend="auto"`): both backends compile and are timed on the
    real pool shape, and a Mosaic refusal would raise here, not degrade.
    The times are printed as information; which backend should be the
    default is ROADMAP S3's question, answered by the ledger."""
    import deepspeed_tpu
    from deepspeed_tpu.models import make_model

    cfg = model_config(sz, sz.serve_layers, sz.max_model_len, rehearsal)
    srv = deepspeed_tpu.init_serving(
        make_model(cfg, name="mistral-7b-widths"),
        serving=dict(max_seqs=sz.max_seqs, max_model_len=sz.max_model_len),
        kv_cache_bits=0,
        **({"tensor_parallel": chips} if chips > 1 else {}))
    bench = srv.backend_bench
    log(f"  decode_backend={srv.decode_backend} backend_bench={bench} (on "
        "this device — information, not a metric)")
    if rehearsal:
        assert bench["reason"] == "non-TPU backend", bench
    else:
        assert bench["xla_ms"] > 0 and bench["pallas_ms"] > 0, bench
    assert srv.close()
    del srv
    gc.collect()
    return bench


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy widths on the CPU, Pallas in interpret mode")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}")

    import jax
    import jaxlib
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # what the persistent cache did for this run: programs loaded from it,
    # programs compiled and written to it, seconds inside the compiler
    cache = {"hits": 0, "written": 0, "backend_compile_s": 0.0}

    def on_event(name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache["written"] += 1

    def on_duration(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            cache["backend_compile_s"] += secs

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    device = device_report(jax.devices())
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu} "
        f"python {sys.version.split()[0]}")
    log(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"devices={device['count']} compile_cache={cache_dir}")
    if device["platform"] != "tpu" and not args.rehearsal:
        print(f"chip_smoke: needs a TPU; JAX found platform="
              f"{device['platform']!r} ({device['kind']}, {device['count']} "
              "device(s)). Use --rehearsal for the CPU dry run.",
              file=sys.stderr)
        return 1
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{device['count']} device(s)", file=sys.stderr)
        return 1
    accel = get_accelerator()
    log(f"accelerator table: peak {accel.peak_flops_per_device() / 1e12:.1f} "
        f"TFLOP/s bf16, HBM {accel.hbm_bytes() / 2**30:.2f} GiB")
    sz = (REHEARSAL if args.rehearsal
          else FOUR_CHIPS if args.chips == 4 else ONE_CHIP)
    log(f"model: Mistral-7B widths {'(TOY: rehearsal)' if args.rehearsal else ''}"
        f" hidden {sz.hf['hidden_size']} heads "
        f"{sz.hf['num_attention_heads']}/{sz.hf['num_key_value_heads']} "
        f"ffn {sz.hf['intermediate_size']} vocab {sz.hf['vocab_size']}; "
        f"depth cut — {sz.why}")

    legs = {}

    def run_leg(name, fn, *a):
        log(f"LEG {name} ...")
        t0 = time.perf_counter()
        out = fn(*a)
        log(f"LEG {name}: PASS ({time.perf_counter() - t0:.1f}s)")
        legs[name] = "pass"
        return out

    from deepspeed_tpu.inference.serving import ServingConfig
    bs = ServingConfig().block_size
    pool_shape = (sz.max_seqs * (sz.max_model_len // bs) + 1,
                  bs, sz.hf["num_key_value_heads"],
                  sz.hf["hidden_size"] // sz.hf["num_attention_heads"])
    parity = run_leg("kernels", leg_kernels, sz, pool_shape, args.rehearsal)
    train = run_leg("train", leg_train, sz, args.chips, args.rehearsal)
    default = run_leg("serve_default", leg_serve, sz, args.chips,
                      args.rehearsal, False)
    forced = run_leg("serve_pallas", leg_serve, sz, args.chips,
                     args.rehearsal, True)
    bench = run_leg("backend_bench", leg_backend_bench, sz, args.chips,
                    args.rehearsal)
    # the kernel parity leg ran at the pool shape the server really built
    assert forced["pool_shape"] == pool_shape, (forced["pool_shape"],
                                                pool_shape)

    stats = hbm()
    summary = {
        "ok": True, "device": device, "jax": jax.__version__,
        "jaxlib": jaxlib.__version__, "libtpu": libtpu,
        "rehearsal": args.rehearsal, "chips": args.chips,
        "depth": {"train_layers": sz.train_layers,
                  "serve_layers": sz.serve_layers,
                  "of": MISTRAL_7B["num_hidden_layers"], "why": sz.why},
        "legs": legs,
        "decode_backend": {"default": default["decode_backend"],
                           "default_reason": default["backend_bench"],
                           "forced": forced["decode_backend"],
                           "auto_float_pool": bench},
        "events": {e: default["events"][e] + forced["events"][e]
                   for e in default["events"]},
        "kernel_parity_rel_l2": {k: float(f"{v:.3g}")
                                 for k, v in parity.items()},
        "train_losses": {k: [round(x, 4) for x in v]
                         for k, v in train.items()},
        "peak_hbm_bytes": (None if stats is None
                           else max(p for _, p in stats)),
        "compile_cache": {"dir": cache_dir, "hits": cache["hits"],
                          "written": cache["written"],
                          "backend_compile_s":
                              round(cache["backend_compile_s"], 1)},
        "elapsed_s": round(time.perf_counter() - t_start, 1),
        "claim": None,
    }
    log(f"compile cache: {cache['hits']} program(s) loaded, "
        f"{cache['written']} compiled and written, "
        f"{cache['backend_compile_s']:.1f}s in the backend compiler")
    print("SUMMARY " + json.dumps(summary), flush=True)
    if not args.rehearsal:
        # the verdict, last and alone. A rehearsal is not a result and
        # prints none.
        print(verdict_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
