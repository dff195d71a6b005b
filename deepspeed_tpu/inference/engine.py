"""Inference engine.

Reference: ``deepspeed/inference/engine.py:35`` (InferenceEngine: dtype
conversion, TP group creation, injection policies, CUDA-graph capture,
generate wrapper) + ``deepspeed/__init__.py:214`` (init_inference).

TPU-native: "kernel injection" is the XLA compiler (+ Pallas attention);
"CUDA graph capture/replay" is jit compilation-caching by construction. What
remains real: automatic tensor-parallel sharding of the params (AutoTP
equivalent via logical axes), the KV cache, and a compiled decode loop.
"""

import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.config import Config
from deepspeed_tpu.parallel import (
    MeshPlan, build_mesh, make_rules, spec_tree)
from deepspeed_tpu.telemetry.tracing import build_clock, span


def init_inference(model, config=None, mesh=None, dtype=None, params=None,
                   rng=None, **kwargs):
    """Reference: ``deepspeed/__init__.py:214``. `model` is a ModelSpec with a
    decode-capable apply (models/transformer.py provides one). Dict configs
    accept InferenceConfig field names directly (quantize_bits, max_tokens,
    fuse_gemms, ...) alongside the training-config surface. params: a
    pre-built tree (e.g. load_hf_params output) instead of random init."""
    if isinstance(config, InferenceConfig):
        return InferenceEngine(model, config, mesh=mesh, params=params,
                               rng=rng)
    fields = {f.name for f in dataclasses.fields(InferenceConfig)}
    raw = dict(config) if isinstance(config, dict) else {}
    raw.update(kwargs)
    icfg_kwargs = {k: v for k, v in raw.items() if k in fields}
    rest = {k: v for k, v in raw.items() if k not in fields and k != "mp_size"}
    # training-config spelling: "tensor_parallel": {"tp_size": N}
    tp_val = icfg_kwargs.get("tensor_parallel")
    if isinstance(tp_val, dict):
        rest["tensor_parallel"] = icfg_kwargs.pop("tensor_parallel")
    cfg = Config.load(rest if isinstance(config, dict) else config)
    icfg_kwargs.setdefault(
        "tensor_parallel",
        raw.get("mp_size", getattr(cfg.tensor_parallel, "tp_size", 1)
                if cfg else 1))
    if dtype is not None:
        icfg_kwargs["dtype"] = dtype
    return InferenceEngine(model, InferenceConfig(**icfg_kwargs), mesh=mesh,
                           params=params, rng=rng)


@dataclasses.dataclass
class InferenceConfig:
    """Reference: ``deepspeed/inference/config.py:125``."""
    tensor_parallel: int = 1
    # expert parallelism for MoE serving (ISSUE 15): the stacked expert dim
    # of the MoE FFN weights shards over the `expert` mesh axis (the
    # reference's expert-parallel groups, utils/groups.py); GSPMD inserts
    # the dispatch/combine all-to-alls at the token<->expert resharding.
    # Needs a MoE model whose num_experts divides by the degree.
    expert_parallel: int = 1
    dtype: Any = None
    max_tokens: int = 1024
    max_batch_size: int = 8
    replace_with_kernel_inject: bool = True   # = use Pallas attention path
    enable_cuda_graph: bool = False           # no-op: jit caches by design
    # int8 weight-only quantization (reference: inference int8 kernel path,
    # csrc/transformer/inference): layer weights stored int8 in HBM,
    # dequantized one layer at a time inside the scan
    quantize_bits: Optional[int] = None
    # qkv + up/gate GEMV fusion for the decode path (reference: qkv_gemm /
    # fused_gemm_gelu); tp=1 only. None -> on for float weights, off for
    # int8 (measured: fusion hurts the dequant-in-scan path ~20% on v5e)
    fuse_gemms: Optional[bool] = None
    # weight-ONLY int8 decode matmuls (ISSUE 17): weights stay int8 in
    # HBM — a ~2x bigger model fits per replica — and the dequant fuses
    # into the matmul EPILOGUE (per-out-channel scales factor out of the
    # contraction; see ops/quantizer.weight_matmul), instead of the
    # quantize_bits dequant-in-scan path that materializes a float copy
    # of each layer. Scales shard with their out columns under TP
    # (quantized_logical_axes), so this composes with tensor parallelism
    # and the paged/spec/chunked serving paths. Mutually exclusive with
    # quantize_bits. 8 is the only supported value.
    weight_bits: Optional[int] = None
    # int8 KV cache for decode: at long context the cache read is the
    # decode bound, and int8 halves it (per-position scales keep the
    # softmax exact to ~1e-2 rel). None -> context-aware default: ON when
    # max_tokens >= 1024, OFF below it. At short context decode is
    # op-latency bound and the per-step quantize overhead can never pay
    # for the halved read — the r5 blanket-int8 default cost the ctx-256
    # rung 2.6% (2853 -> 2779 tok/s) before this threshold existed.
    kv_cache_bits: Optional[int] = None


class InferenceEngine:
    def __init__(self, model, config: InferenceConfig, mesh: Optional[Mesh] = None,
                 params=None, rng=None):
        # what the constructor cost (``setup``, at its end): a serving
        # engine over this one reports it as its own set-up's first part
        builds = build_clock()
        t0, build_s = time.perf_counter(), builds.seconds
        self.model = model
        self.config = config
        tp = max(1, config.tensor_parallel)
        ep = max(1, getattr(config, "expert_parallel", 1) or 1)
        n_dev = jax.device_count()
        if mesh is None:
            if n_dev % (tp * ep) != 0:
                raise ValueError(f"tp={tp} x ep={ep} does not divide "
                                 f"device count {n_dev}")
            plan = MeshPlan(data=n_dev // (tp * ep), expert=ep, tensor=tp)
            mesh = build_mesh(plan)
        else:
            # mesh-native: an explicit mesh is authoritative for the
            # parallel degrees — a config degree that CONTRADICTS it is a
            # caller bug (sharding rules built from the config degree would
            # silently replicate what the mesh was built to shard)
            mesh_tp = mesh.shape.get("tensor", 1)
            mesh_ep = mesh.shape.get("expert", 1)
            if config.tensor_parallel > 1 and mesh_tp != tp:
                raise ValueError(f"tensor_parallel={tp} but the mesh's "
                                 f"tensor axis has size {mesh_tp}")
            if ep > 1 and mesh_ep != ep:
                raise ValueError(f"expert_parallel={ep} but the mesh's "
                                 f"expert axis has size {mesh_ep}")
            tp, ep = mesh_tp, mesh_ep
        self.mesh = mesh
        self.tp = tp
        self.ep = ep
        from deepspeed_tpu.parallel.context import set_parallel_context
        from deepspeed_tpu.parallel import MeshPlan as _MP
        self._plan = _MP(data=mesh.shape.get("data", 1),
                         expert=mesh.shape.get("expert", 1),
                         tensor=mesh.shape.get("tensor", 1))
        set_parallel_context(mesh, self._plan)
        self.dtype = config.dtype or jnp.bfloat16

        # int8 weight-only quantization: rebuild the model with the
        # dequant-in-scan forward and the {"q","scale"} param structure.
        # weight_bits=8 shares the storage layout but keeps the weights
        # int8 through the matmul (epilogue dequant) — the serving path.
        self._quantized = bool(config.quantize_bits)
        self._weight_only = bool(getattr(config, "weight_bits", None))
        if self._weight_only:
            if int(config.weight_bits) != 8:
                raise ValueError(f"weight_bits={config.weight_bits} "
                                 "unsupported (8 = int8 is the only value)")
            if self._quantized:
                raise ValueError(
                    "weight_bits and quantize_bits are mutually exclusive: "
                    "both store int8 weights — weight_bits fuses the "
                    "dequant into the matmul epilogue instead of "
                    "materializing a float copy per layer")
        from deepspeed_tpu.models.transformer import TransformerConfig
        is_tf = isinstance(getattr(model, "config", None), TransformerConfig)
        if ep > 1:
            n_exp = getattr(getattr(model, "config", None),
                            "num_experts", 1) or 1
            if n_exp <= 1:
                if config.expert_parallel > 1:
                    raise ValueError(
                        f"expert_parallel={ep} needs a MoE model "
                        "(num_experts > 1) — the expert axis shards the "
                        "stacked expert dim of the MoE FFN weights")
                # the expert axis came from a SHARED mesh, not a request:
                # a dense model simply has no "expert" logical axis, so
                # nothing shards over it — same as before the axis was
                # adopted (a training mesh reused for dense inference
                # must not crash)
                ep = 1
                self.ep = 1
            elif n_exp % ep:
                raise ValueError(
                    f"expert_parallel={ep} does not divide "
                    f"num_experts={n_exp}: each chip must hold a whole "
                    "expert slice")

        # int8 KV cache: the ModelSpec closures capture the config, so flip
        # the flag by REBUILDING the spec before the quantize/fuse branches
        # below read model.config. The default keys off the engine's
        # declared context budget (max_tokens): the int8 read only pays
        # where the cache read dominates the step, i.e. long context —
        # measured crossover ~1k positions on v5e (see InferenceConfig).
        if is_tf:
            kvb = config.kv_cache_bits
            # the long-context default is a rule about per-head K/V planes;
            # a model whose planes are LATENT rows (a normalised latent
            # beside a rotary key, one row for every head) keeps them in
            # the float dtype: no int8 recipe for such a row exists here
            latent = bool(model.config.latent_planes)
            if kvb is None:
                kvb = 8 if (int(config.max_tokens or 0) >= 1024
                            and not latent) else 0
            kvb = int(kvb)
            if kvb not in (0, 8):
                raise ValueError(f"kv_cache_bits={kvb} unsupported "
                                 "(0 = float cache, 8 = int8)")
            if kvb and latent:
                raise ValueError(
                    "kv_cache_bits=8 on a model with latent attention: its "
                    "cache is one row a token of a normalised latent and a "
                    "rotary key, shared by every head, and it is kept in the "
                    "pool's float dtype (leave kv_cache_bits unset, or 0)")
            if model.config.kv_cache_bits != kvb:
                import dataclasses as _dc
                from deepspeed_tpu.models import make_model as _mk
                model = _mk(_dc.replace(model.config, kv_cache_bits=kvb),
                            name=model.name)
                self.model = model
        # decode GEMV fusion (wqkv, w_in_gate): tp=1 only — the concat dim
        # would interleave head shards under tensor parallelism
        fuse = (config.fuse_gemms if config.fuse_gemms is not None
                else not (self._quantized or self._weight_only))
        self._fused = (fuse and is_tf and tp == 1
                       and model.config.num_experts == 1)
        if self._quantized or self._weight_only:
            import dataclasses as _dc
            from deepspeed_tpu.models.transformer import (
                fused_logical_axes, quantized_logical_axes)
            from deepspeed_tpu.models import make_model as _mk
            if not is_tf:
                raise ValueError("quantize_bits/weight_bits require a "
                                 "transformer ModelSpec")
            qcfg = _dc.replace(
                model.config, quantized_weights=True,
                weight_only_bits=8 if self._weight_only else 0)
            base_axes = fused_logical_axes(qcfg) if self._fused else None
            model = _dc.replace(_mk(qcfg, name=model.name),
                                logical_axes=quantized_logical_axes(
                                    qcfg, base_axes=base_axes))
            self.model = model
        elif self._fused:
            import dataclasses as _dc
            from deepspeed_tpu.models.transformer import fused_logical_axes
            model = _dc.replace(model,
                                logical_axes=fused_logical_axes(model.config))
            self.model = model

        # AutoTP equivalent: logical axes -> tensor-axis sharding
        rules = make_rules(zero_stage=0, tp=tp > 1)
        self.param_specs = spec_tree(model.logical_axes, rules)
        self.param_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), self.param_specs,
            is_leaf=lambda x: isinstance(x, P))

        def _fuse(p):
            if not is_tf:
                return p
            from deepspeed_tpu.models.transformer import (fuse_layer_stack,
                                                          unfuse_layer_stack)
            lay = p.get("layers", {}) if isinstance(p, dict) else {}
            fused_in = isinstance(lay, dict) and ("wqkv" in lay
                                                  or "w_in_gate" in lay)
            if self._fused and not fused_in:
                return fuse_layer_stack(p, model.config)
            if not self._fused and fused_in:
                return unfuse_layer_stack(p, model.config)
            return p

        # the parameters: initialised or handed in, cast, fused, quantised
        # and placed — the host's part of it; the device runs the program
        # behind whatever the caller does next
        with span("ds:setup.weights") as sp_weights:
            if self._quantized or self._weight_only:
                from deepspeed_tpu.models.transformer import \
                    quantize_layer_stack
                if params is None:
                    rng = rng if rng is not None else jax.random.PRNGKey(0)
                    params = model.init(rng)
                quant_fn = jax.jit(
                    lambda p: quantize_layer_stack(_fuse(jax.tree.map(
                        lambda x: x.astype(self.dtype)
                        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
                        else x, p)), bits=int(config.quantize_bits
                                              or config.weight_bits)),
                    out_shardings=self.param_shardings)
                with mesh:
                    params = quant_fn(jax.tree.map(jnp.asarray, params))
            elif params is None:
                rng = rng if rng is not None else jax.random.PRNGKey(0)
                init_fn = jax.jit(
                    lambda k: _fuse(jax.tree.map(
                        lambda p: p.astype(self.dtype), model.init(k))),
                    out_shardings=self.param_shardings)
                with mesh:
                    params = init_fn(rng)
            else:
                cast_fn = jax.jit(
                    lambda p: _fuse(jax.tree.map(
                        lambda x: jnp.asarray(x, self.dtype), p)),
                    out_shardings=self.param_shardings)
                with mesh:
                    params = cast_fn(jax.tree.map(jnp.asarray, params))
        self.params = params

        self._forward = jax.jit(lambda p, ids: model.apply(p, ids))
        self._rules = rules
        self._encode_fn = None     # encoder-model hidden-state path
        self._forward_kw = None    # kwarg-carrying forward (UNet context)
        self._vae_encode_fn = None
        self._vae_decode_fn = None
        self._prefill_cache = {}   # (B, pad_prompt, max_len); prompt_len
        # is a traced argument, NOT part of the compile key
        self._decode_loop_cache = {}  # (B, pad_prompt, max_len, n_steps, temp)
        self._init_cache_cache = {}   # (B, max_len)
        # ``t0``: when the constructor began; ``init_s``: its seconds, of
        # which ``weights_s`` under ds:setup.weights and ``build_s`` building
        # programs (the init program, mostly: the build clock's word)
        self.setup = {"t0": t0, "init_s": time.perf_counter() - t0,
                      "weights_s": sp_weights.seconds,
                      "build_s": builds.seconds - build_s}

    def _batch_spec(self, batch_size: int) -> P:
        """Shard batch over `data` only when it divides evenly (small ad-hoc
        batches replicate instead of erroring)."""
        dp = self.mesh.shape.get("data", 1)
        return P("data") if dp > 1 and batch_size % dp == 0 else P()

    def _cache_shardings(self, batch_size: int):
        """KV cache shardings: batch over data (when divisible), kv heads over
        tensor — the cache shards exactly like the attention weights do."""
        if self.model.cache_axes is None:
            return None
        batch_axis = self._batch_spec(batch_size)
        rules = type(self._rules)(
            self._rules.rules
            + (("batch", "data" if batch_axis else None),))
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            spec_tree(self.model.cache_axes(), rules),
            is_leaf=lambda x: isinstance(x, P))

    def _init_cache(self, batch_size: int, max_len: int):
        key = (batch_size, max_len)
        init = self._init_cache_cache.get(key)
        if init is None:
            init = jax.jit(
                lambda: self.model.init_cache(batch_size, max_len,
                                              dtype=self.dtype),
                out_shardings=self._cache_shardings(batch_size))
            self._init_cache_cache[key] = init
        with self.mesh:
            return init()

    def _cached_decode_fns(self, B, pad_prompt, prompt_len, max_len, n_steps,
                           temperature):
        """Two jitted programs, memoized per shape bucket (the reference gets
        the same effect from CUDA-graph capture; here it is jit caching by
        construction). The decode scan is keyed on (B, pad_prompt, max_len,
        n_steps, temperature) — pad_prompt is part of the key because the
        windowed read lengths are derived from it; prefill on (B, pad_prompt,
        max_len) with the true prompt length as a traced argument — a new
        prompt length inside the same buckets compiles nothing."""
        pkey = (B, pad_prompt, max_len)
        prefill_raw = self._prefill_cache.get(pkey)
        if prefill_raw is None:
            data_sh = NamedSharding(self.mesh, self._batch_spec(B))
            repl = NamedSharding(self.mesh, P())
            prefill_raw = jax.jit(
                lambda p, ids, cache, length: self.model.prefill(
                    p, ids, cache, length=length),
                in_shardings=(self.param_shardings, data_sh,
                              self._cache_shardings(B), repl),
                donate_argnums=(2,))
            self._prefill_cache[pkey] = prefill_raw
        prefill_fn = lambda p, ids, cache: prefill_raw(  # noqa: E731
            p, ids, cache, jnp.int32(prompt_len))
        dkey = (B, pad_prompt, max_len, n_steps, temperature)
        decode_fn = self._decode_loop_cache.get(dkey)
        if decode_fn is None:
            from deepspeed_tpu.inference.generation import make_decode_loop
            loop = make_decode_loop(self.model, n_steps, temperature,
                                    start_len=pad_prompt, max_len=max_len)
            decode_fn = jax.jit(loop, donate_argnums=(2,))
            self._decode_loop_cache[dkey] = decode_fn
        return prefill_fn, decode_fn

    def forward(self, input_ids, **kwargs):
        """Full-sequence logits (prefill path). Extra array kwargs (e.g.
        the conditioned UNet's ``t``/``context``) pass through to the
        spec's apply inside the jit."""
        from deepspeed_tpu.parallel.context import set_parallel_context
        set_parallel_context(self.mesh, self._plan)
        input_ids = jnp.asarray(input_ids)
        input_ids = jax.device_put(
            input_ids,
            NamedSharding(self.mesh, self._batch_spec(input_ids.shape[0])))
        with self.mesh:
            if kwargs:
                if self._forward_kw is None:
                    self._forward_kw = jax.jit(
                        lambda p, ids, kw: self.model.apply(p, ids, **kw))
                return self._forward_kw(
                    self.params, input_ids,
                    {k: jnp.asarray(v) for k, v in kwargs.items()})
            return self._forward(self.params, input_ids)

    __call__ = forward

    def vae_encode(self, x, sample: bool = False, rng=None):
        """DSVAE.encode (reference: diffusers/vae.py:96): latent mean (or
        a reparameterized sample) for image batch x [B, H, W, C]."""
        from deepspeed_tpu.models.vae import VAEConfig, vae_encode as _enc
        cfg = getattr(self.model, "config", None)
        if not isinstance(cfg, VAEConfig):
            raise ValueError("vae_encode() requires a VAE ModelSpec")
        if self._vae_encode_fn is None:
            self._vae_encode_fn = jax.jit(
                lambda p, x: _enc(p, x, cfg))
        with self.mesh:
            mean, logvar = self._vae_encode_fn(self.params,
                                               jnp.asarray(x))
        if sample:
            rng = rng if rng is not None else jax.random.PRNGKey(0)
            return mean + jnp.exp(0.5 * logvar) * jax.random.normal(
                rng, mean.shape)
        return mean

    def vae_decode(self, z):
        """DSVAE.decode: latent [B, h, w, latent] -> image."""
        from deepspeed_tpu.models.vae import VAEConfig, vae_decode as _dec
        cfg = getattr(self.model, "config", None)
        if not isinstance(cfg, VAEConfig):
            raise ValueError("vae_decode() requires a VAE ModelSpec")
        if self._vae_decode_fn is None:
            self._vae_decode_fn = jax.jit(lambda p, z: _dec(p, z, cfg))
        with self.mesh:
            return self._vae_decode_fn(self.params, jnp.asarray(z))

    def encode(self, input_ids, attention_mask=None, token_type_ids=None):
        """Encoder-model hidden states [B, S, H] (BERT/RoBERTa; reference:
        the encoder task pipelines init_inference serves in
        tests/unit/inference/test_inference.py — fill-mask / classification
        heads consume these)."""
        from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                      forward as _fwd)
        cfg = getattr(self.model, "config", None)
        if not isinstance(cfg, TransformerConfig):
            raise ValueError("encode() requires a transformer ModelSpec")
        from deepspeed_tpu.parallel.context import set_parallel_context
        set_parallel_context(self.mesh, self._plan)
        if self._encode_fn is None:
            self._encode_fn = jax.jit(
                lambda p, ids, mask, tt: _fwd(
                    p, ids, cfg, attention_mask=mask, token_type_ids=tt,
                    return_hidden=True)[0])
        B = jnp.asarray(input_ids).shape[0]
        sh = NamedSharding(self.mesh, self._batch_spec(B))
        put = lambda x: (jax.device_put(jnp.asarray(x), sh)  # noqa: E731
                         if x is not None else None)
        with self.mesh:
            return self._encode_fn(self.params, put(input_ids),
                                   put(attention_mask), put(token_type_ids))

    def generate(self, input_ids, max_new_tokens: int = 32, temperature: float = 0.0,
                 rng=None):
        """Greedy/temperature sampling decode. Uses the model's KV-cache decode
        path when available (models with init_cache/decode_step), else
        recomputes the prefix each step (correct but O(n^2) — small-model
        fallback)."""
        from deepspeed_tpu.inference.generation import generate as _gen
        return _gen(self, input_ids, max_new_tokens=max_new_tokens,
                    temperature=temperature, rng=rng)
