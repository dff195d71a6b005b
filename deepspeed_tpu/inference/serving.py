"""Serving engine: continuous batching over a paged KV cache.

Replaces the one-shot ``generate()`` loop as the multi-tenant serving path
(ROADMAP item 2, SURVEY §6 capability bar). Three pieces:

  1. **Paged KV cache** — fixed-size blocks in preallocated pools, per-
     sequence block tables, gather-based reads (models/transformer
     ``decode_step_paged``). The decode step compiles ONCE for the pool
     shape; admitting/evicting sequences changes table CONTENTS only.
  2. **Continuous batching** — a RequestScheduler admits/evicts/preempts at
     step boundaries. The host loop reuses the PR-2 bounded-dispatch-window
     idea: prefills of admitted requests and the quantum's decode steps all
     dispatch WITHOUT a host sync between them (the device queue overlaps
     prefill of new requests with decode of running ones); the only sync is
     ONE fetch of the round's sampled tokens at the scheduling boundary.
  3. **Quantized decode** — int8 KV blocks (dequant fused into the
     attention read via score scaling, ops/quantizer) and int8 weights via
     the InferenceEngine's existing ``quantize_bits`` path.

The decode-attention backend (a paged Pallas kernel vs the XLA gather) is
picked at engine init from what the engine can see — an int8 pool's read is
PRICED from its shapes (``ops/decode_attention.paged_read_price``), a float
pool's two reads are timed on the real pool — never by a flag the default
leaves set, and the choice is logged with what decided it as a structured
telemetry event (``decode_backend_selected``).

Token/row bookkeeping (the invariant every path maintains):
``req.cached_rows`` = KV rows actually in the pool for this request. A
(re-)prefill sets it to ``len(context)`` and leaves the NEXT sampled token
pending in the device token vector; each decode step writes the pending
token's row (cached_rows + 1) and samples a new pending token. Host-side
``generated`` absorbs the pending chain at the round boundary from the one
token fetch.

Reliability tier (ISSUE 10 — see README "Serving reliability"): per-request
TTFT/total **deadlines** with mid-decode cancellation, **admission
watermarks** that shed load with a typed ``AdmissionRejected``,
**anti-starvation aging** in the scheduler, **fault-tolerant rounds** — the
quantum dispatch runs under an optional watchdog, and any round failure
(failed/hung dispatch, injected fault, kernel failure) recovers by
preempting every running request back to the queue, rebuilding the device
pool, and re-prefilling from host-side cursors (bit-exact by the same
recompute math preemption resume uses). A Pallas ``backend_fault`` degrades
the decode backend to the XLA gather mid-serve (``backend_degraded``
event). SIGTERM **drains**: in-flight requests checkpoint through the
integrity chain (manifest + COMMITTED marker) and a restarted engine
``resume()``s them with byte-identical continuations. Every
shed/deadline/degrade/recovery decision is a structured robustness event,
drained into the telemetry JSONL at round boundaries.

Latency frontier (ISSUE 12 — see README "Latency frontier"): a
**copy-on-write prefix cache** (``enable_prefix_cache``) maps cached
prompt blocks into new requests' tables by reference and forks the
partially-filled boundary block on first write; **token-budget chunked
prefill** (``prefill_token_budget``) slices long-prompt admissions
across rounds so running requests' inter-token latency stays flat; and
**speculative decoding** (``spec_tokens``) verifies K drafted tokens in
one ``decode_span_paged`` pass with greedy output parity. All three are
default-off and compose with the reliability tier: recoveries clear the
cache with the pool they rebuild, drains serialize mid-chunk prefills,
and resume/migration re-prefills THROUGH the cache.

Pod-scale serving (ISSUE 15 — see README "Pod-scale serving"): the engine
is mesh-native. Under **tensor parallelism** the paged block pools
``[L, NB, block_size, nkv, hd]`` shard on the kv-head dim over the
``tensor`` mesh axis via the same Megatron col/row rules the weights use
(``paged_cache_logical_axes``), every decode/prefill/span program pins its
pool output to that sharding, and the per-layer out-projection reductions
are the only cross-chip collectives (census-pinned by graft-lint; the
``tp-serving-replicated-pool`` corpus entry plants the drift defect).
**Expert parallelism** shards the MoE FFN expert stacks over the
``expert`` axis (``InferenceConfig.expert_parallel``) with the existing
``moe/`` dispatch inserting the all-to-alls. The host side — allocator,
scheduler, prefix cache, block ids — stays UNSHARDED replicated metadata,
so CoW/chunked-prefill/spec-decode compose unchanged (parity-pinned).
Drains record the mesh topology and resume/migration refuse a
mesh-incompatible placement with the typed ``ResumeIncompatible``.
"""

import collections
import contextlib
import dataclasses
import functools
import gc
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import numpy as np

from deepspeed_tpu.inference.kv_cache import (BlockAllocator, blocks_for,
                                              cache_bytes, kv_payload_nbytes,
                                              ring_leaves)
from deepspeed_tpu.inference.schemas import (DRAIN_STATE_VERSION,
                                             KV_PAYLOAD_SCHEMA)
from deepspeed_tpu.inference.scheduler import (AdmissionRejected, Request,
                                               RequestScheduler)
from deepspeed_tpu.robustness import events as rb_events
from deepspeed_tpu.robustness import faults as rb_faults
from deepspeed_tpu.robustness.preemption import Preempted
from deepspeed_tpu.telemetry.tracing import (BuildLog, build_clock,
                                             build_log, span)


@dataclasses.dataclass(eq=False)
class _DispatchedRound:
    """A decode round on the device's queue: what its commits need, so they
    can come after slots have changed hands. ``entries``: (request, its
    slot, its ``preemptions``) per decoding slot — a request that has since
    finished, been cancelled, handed off or preempted is no longer the one
    dispatched, and its tokens are dropped. The round's steps are fetched
    in two parts, each ``(tokens [steps, max_seqs], [per step (expert load,
    exit distribution)])`` stacked on the device right behind its last step
    (a stack at fetch time would queue behind whatever was dispatched
    since): ``head``, fetched by the call that dispatched the round — for a
    verify step its (argmaxes, accepted lengths), ``spec`` —, and ``tail``,
    the last steps, which stay in flight while the host commits the head
    and schedules and dispatches the next round, and are fetched by the
    next call. Either is None when it holds no step. ``t0``: when the
    dispatch began (request traces). ``covered``: what the probe read just
    before the round's first step was issued (``_dispatch_round``).
    ``shape``: the round's key of ``_step_shapes``; ``held``: the blocks
    the running requests held when it was built (``_tables_device``)."""
    entries: list
    shape: tuple
    held: int
    head: Any
    tail: Any
    spec: bool
    t0: float
    covered: Optional[bool] = None

    def live(self):
        """The entries still owed tokens."""
        return [(req, slot) for req, slot, life in self.entries
                if req.preemptions == life
                and req.state in ("running", "ending")]


def _ahead_steps(quantum: int) -> int:
    """How many of a plain round's last steps stay unfetched while the next
    round is scheduled and dispatched: a quarter of the quantum. They have
    to outlast the host's work between two rounds (commit, schedule,
    tables, keys, the first dispatch: 9-20 ms in the benchmark's cells,
    whose steps take 13-53 ms), and every step more is a step by which a
    request's tokens reach the host later and its slot is given away
    later."""
    return max(1, quantum // 4)


def _in_one_chunk(fn, *args):
    """``fn(*args)`` with every frame below in ONE chunk of the interpreter's
    frame stack. CPython 3.12 keeps a thread's frames in 16 KiB chunks and
    maps a new chunk at every call that crosses a chunk's end, unmapping it
    at the return. JAX's tracing and lowering recursion is deeper than a
    chunk, so some call in it straddles a boundary, and WHICH one — a rare
    one or one made a thousand times a program — moves with the size of
    every frame above: a local variable more or less in ``_round``, in
    ``step`` or in the caller's loop. That is the seconds of ``setup_s`` that
    came and went with frame sizes since PR 23 (a chat cell's 16 prefill
    programs: 2.6 or 4.7 s of lowering, and no better on a fresh thread,
    whose tracing then straddled instead; PERF.md section 6, PR 36). A chunk
    is as large as the frame that opens it needs, so this frame declares an
    evaluation stack of 64 Ki words it never uses: it opens a chunk of
    1 MiB, and the ~0.5 MiB behind it hold whatever ``fn`` calls."""
    return fn(*args)


_in_one_chunk.__code__ = _in_one_chunk.__code__.replace(co_stacksize=1 << 16)


class _GcClock:
    """Seconds this process has spent in garbage collections of any
    generation: ONE ``gc.callbacks`` hook, hung up by the first serving
    engine and shared by all of them (the list is the process's, and so is
    a collection), two ``perf_counter`` stamps a collection. A round reads
    ``seconds`` before and after itself (``ServingEngine.step``): its
    ``gc_ms``."""

    def __init__(self):
        self.seconds = 0.0
        self._t0 = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None

    def install(self) -> "_GcClock":
        if self not in gc.callbacks:
            gc.callbacks.append(self)
        return self


_GC_CLOCK = _GcClock()


class DecodeDispatchHang(RuntimeError):
    """The watchdog timed out a decode round: the dispatch (or its token
    fetch) never came back within ``dispatch_timeout_s``."""


class SlotStateUnsupported(ValueError):
    """What a model that keeps something PER SERVING SLOT beside its K/V
    blocks (``ModelSpec.slot_leaves``: a recurrent state — ``nemotron_h``,
    ``qwen3_next`` —, a window ring — ``afmoe``) cannot be served with,
    refused at ``init_serving`` or at the call
    (``ServingEngine._by_blocks_alone``): a request's
    state there is its K/V blocks AND its slot's state, and everything that
    shares, rolls back, resumes or ships a request's state by its blocks
    alone — the prefix cache and its copy-on-write fork, chunked prefill,
    speculation, K/V export / import, LoRA on the projections, a pool split
    over ``tensor`` — would need a snapshot of that state, which nothing
    keeps yet."""

    def __init__(self, what: str):
        super().__init__(
            f"{what} is not supported on a model with recurrent or window "
            "blocks: a request's state is its K/V blocks and a per-slot "
            "state (recurrent state, window ring), and no snapshot of the "
            "latter is kept")
        self.what = what


class ResumeIncompatible(ValueError):
    """A drained request (or a whole foreign drain) cannot be restored on
    THIS engine: the local block-table width / ``max_model_len`` is smaller
    than the work needs. Typed so the router's migration path can try the
    next survivor instead of corrupting — past the table width the growth
    clamp would silently overwrite the last block (the PR-10 context-cap
    analysis), which is exactly the corruption this refusal prevents.
    Subclasses ``ValueError`` for the PR-10 same-engine resume contract."""


def load_drain_state(save_dir: str, tag: Optional[str] = None
                     ) -> Dict[str, Any]:
    """Read a serving drain snapshot through the integrity chain.
    ``tag=None`` resolves the newest tag under ``save_dir`` that passes
    integrity validation — a torn drain is skipped, not loaded; an explicit
    tag is validated and refused loudly when torn. Returns the state dict
    with ``"tag"`` added. Shared by ``ServingEngine.resume`` (whole-drain
    restore) and the router's failover path (which splits the requests
    across survivors via ``accept_migration``)."""
    import json
    import os
    from deepspeed_tpu.robustness import integrity

    if tag is None:
        tag = integrity.newest_valid_tag(save_dir)
        if tag is None:
            raise FileNotFoundError(
                f"no integrity-valid serving drain tag under {save_dir}")
    tag_dir = os.path.join(save_dir, tag)
    ok, reason = integrity.validate_tag(tag_dir)
    if not ok:
        raise ValueError(
            f"serving drain tag '{tag}' failed integrity: {reason}")
    with open(os.path.join(tag_dir, "state.json")) as f:
        state = json.load(f)
    state["tag"] = tag
    return state


def measure_paged_backends(mcfg, k_pool, v_pool, *, max_seqs: int, MB: int,
                           block_size: int, num_blocks: int, dtype,
                           iters: int = 10, mesh=None):
    """Time the paged Pallas kernel vs the XLA gather over the given
    single-layer pools ([NB, block_size, n_kv, head_dim], the layout of
    ``init_paged_cache``) on a representative load: every slot half-to-full,
    blocks scattered through the pool (a fresh pool's identity layout
    would flatter the gather). Returns (xla_ms, pallas_ms).

    One caller: ServingEngine._select_backend, on the engine's real pools
    at init (``decode_backend="auto"`` on a float pool)."""
    import contextlib
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.transformer import _paged_attention

    nkv, hd, nq = mcfg.kv_heads, mcfg.dim_per_head, mcfg.num_heads
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (max_seqs, 1, nq, hd), dtype)
    kr = jax.random.normal(ks[1], (max_seqs, nkv, 1, hd), dtype)
    vr = jax.random.normal(ks[2], (max_seqs, nkv, 1, hd), dtype)
    rng = np.random.default_rng(0)
    ids = np.zeros((max_seqs, MB), np.int32)
    perm = rng.permutation(np.arange(1, num_blocks))
    n_per = max(1, min(MB, (num_blocks - 1) // max(1, max_seqs)))
    for s in range(max_seqs):
        row = perm[(s * n_per) % len(perm):][:n_per]
        ids[s, :len(row)] = row
    tables = jnp.asarray(ids)
    lens = jnp.asarray(rng.integers(max(1, block_size * n_per // 2),
                                    block_size * n_per + 1,
                                    size=(max_seqs,)), jnp.int32)

    def timed(backend):
        f = jax.jit(lambda q, kp, vp: _paged_attention(
            q, kp, vp, tables, lens, mcfg, kv_row=(kr, vr),
            backend=backend))
        np.asarray(jax.device_get(f(q, k_pool, v_pool)))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            o = f(q, k_pool, v_pool)
        np.asarray(jax.device_get(o))
        return (time.perf_counter() - t0) / iters * 1e3

    with (mesh if mesh is not None else contextlib.nullcontext()):
        return timed("xla"), timed("pallas")


def kv_payload_crc(data: Dict[str, Any]) -> int:
    """Checksum of an exported KV payload's buffers (key-sorted, so the
    number is layout-stable): a torn/corrupt handoff must be DETECTED at
    import and fall back to re-prefill — decoding garbage KV would emit
    wrong tokens silently. crc32 is plenty: this guards torn transport,
    not adversaries."""
    import zlib
    crc = 0
    for name in sorted(data):
        crc = zlib.crc32(np.ascontiguousarray(data[name]).tobytes(), crc)
    return crc


@dataclasses.dataclass
class ServingConfig:
    """Knobs of the serving tier (see README "Serving" for the memory
    math). Pool sizing: ``num_blocks`` defaults to full residency —
    every slot can hold ``max_model_len`` tokens — plus the trash block;
    shrink it to oversubscribe (the scheduler queues/preempts instead of
    OOMing)."""
    max_seqs: int = 8                  # concurrent sequences (slots)
    block_size: int = 64               # tokens per KV block
    num_blocks: Optional[int] = None   # pool blocks incl. trash block 0
    max_model_len: Optional[int] = None  # per-request context cap
    decode_quantum: int = 8            # decode steps per scheduling round
    temperature: float = 0.0
    eos_token_id: Optional[int] = None
    decode_backend: str = "auto"       # auto | xla | pallas
    prompt_bucket: int = 64            # prompt pad granularity (compile reuse)
    # --- reliability tier (all default off = pre-reliability behavior) ---
    # default per-request deadlines (ms from submit; add_request overrides
    # per request; None = unbounded). Enforced at round boundaries:
    # missed requests are CANCELLED — slot and blocks return to the pool
    # mid-decode — and counted in stats()["deadline_misses"].
    ttft_deadline_ms: Optional[float] = None
    deadline_ms: Optional[float] = None
    # admission watermarks: queue-length cap / held-pool-fraction cap
    # beyond which add_request sheds with a typed AdmissionRejected
    # (never silent queue growth — `serving-unbounded-queue` corpus)
    max_queue: Optional[int] = None
    pool_watermark: Optional[float] = None
    # dispatch watchdog: a round whose quantum dispatch, or whose token
    # fetch, exceeds this raises DecodeDispatchHang and recovers by
    # rebuilding the batch from host-side cursors. None = no watchdog.
    dispatch_timeout_s: Optional[float] = None
    # round recovery attempts before the failure propagates (a transient
    # fault heals on the first retry; a deterministic bug still raises)
    round_retries: int = 2
    # robustness/telemetry events drain into this JSONL at round
    # boundaries (same record schema as the training engine's sink)
    telemetry_jsonl: Optional[str] = None
    # --- latency frontier (ISSUE 12; all default off = PR-10 behavior) ---
    # copy-on-write prefix cache: finished prefills publish their blocks
    # under chained content hashes, admissions map matching prefix blocks
    # by REFERENCE (BlockAllocator refcounts) and fork the partially-
    # filled boundary block on first write. Cached blocks evict LRU under
    # pool pressure — a hit is a latency win, a miss never an admission
    # loss.
    enable_prefix_cache: bool = False
    # chunked prefill: per-round token budget SHARED between prefill
    # chunks and the decode quantum's `decode_quantum * n_decoding`
    # reservation — long prompts slice across rounds instead of stalling
    # running requests' inter-token latency. None = whole-prompt prefill
    # at admission (the PR-9 behavior).
    prefill_token_budget: Optional[int] = None
    # speculative decoding: K proposed tokens verified per round in one
    # decode_span_paged pass (0 = off). Greedy-only (temperature 0.0):
    # the accept rule keeps output token-identical to K=0. Proposer
    # defaults to self-drafting n-gram lookup (n = ``_SPEC_NGRAM``);
    # spec_proposer is the draft hook — any (context ids, k) -> <= k
    # proposed ids callable.
    spec_tokens: int = 0
    spec_proposer: Optional[Any] = None
    # --- multi-tenant LoRA serving (ISSUE 17; 0 = off) ----------------
    # device adapter slot pool size INCLUDING the reserved all-zero null
    # slot 0 (base-model requests index it). Adapter A/B tables live in a
    # host-side AdapterStore and page into slots like KV blocks: refcount
    # while requests are in flight, LRU-evicted under slot pressure,
    # re-paged on demand. A decode quantum batches requests with
    # DIFFERENT adapters in one dispatch via a per-slot gathered einsum —
    # one compile per pool shape, never per adapter set.
    adapter_slots: int = 0
    lora_rank: int = 0                 # shared by all adapters (one shape)
    lora_targets: tuple = ("q", "k", "v", "o")
    # --- fleet observability (ISSUE 18; default off = PR-17 behavior) ---
    # per-request distributed tracing: host-wall-clock spans only (one
    # telemetry.tracing.span + a deque append per span, ZERO added device
    # syncs — tracing on/off is bit-identical, pinned by test_fleet_obs).
    # Arm at runtime with enable_request_trace() to A/B a warm engine.
    request_trace: bool = False
    trace_replica: str = "r0"          # process row in the merged trace
    # --- disaggregated serving (ISSUE 19; "both" = colocated behavior) ---
    # fleet tier this engine serves: a "prefill" engine runs prompt
    # prefills and emits each request's FIRST token but never a decode
    # quantum — requests then sit prefill_done until the router hands
    # them (with their KV bytes) to a "decode"/"both" replica. The role
    # also rides the replica heartbeat meta so the router's admission
    # targets prefill-capable replicas first. "both" is the pre-ISSUE-19
    # colocated engine, and what role-less heartbeats interop as.
    role: str = "both"                 # prefill | decode | both


# the n of the self-drafting proposer's n-gram lookup, and the bound of the
# request tracer's ring of spans: one value each in every use, so constants
_SPEC_NGRAM = 3
_TRACE_EVENTS = 65536


# blocks of one entry of a decode round's block list: a RUN of two columns of
# one slot, 128 positions at the cells' 64-token blocks — what the per-slot
# view of the scores is tiled in, so an entry lands in it as whole tiles (one
# block an entry: every pass between list and view relayouts 64-wide rows,
# the read of 64 blocks in the chat cell 1.58 ms a step against 1.20; four:
# up to three padded blocks a slot, PERF.md section 6, PR 38). A slot's blocks
# are padded to whole runs with the trash block.
_RUN = 2


def _list_ladder(MB: int, shares: tuple) -> tuple:
    """The lengths a decode round's block list may have, as columns a slot
    (the list holds slots x columns blocks): ``1 / share`` of the full
    table width ``MB`` for each of ``shares``, rounded up to whole runs
    (32, (4, 2, 1) -> 8, 16, 32; 20 -> 6, 10, 20; 16, (8, 4) -> 2, 4). A
    constant of the engine's shape, not a setting: one step program is
    built per entry, and each costs a serve cell 0.45-0.7 s of set-up — its
    tracing and lowering, which no cache keeps (PERF.md section 6, PR 33).
    The ladder is over the SUM of the blocks the slots hold, which moves
    far less from round to round and from seed to seed than the longest
    slot's, so it is geometric: halves."""
    return tuple(sorted({-(-MB // (share * _RUN)) * _RUN
                         for share in shares}))


def _slot_ladder(max_seqs: int) -> tuple:
    """The slot counts a decode round may be dispatched at: a quarter of
    ``max_seqs`` in whole tiles of 16 rows (what a bf16 activation's rows
    come in on the TPU), and the whole — 48 -> 16, 48; 128 -> 32, 128. An
    engine whose quarter is half of its slots or more has the whole alone
    (32 -> 32): the narrow step would save no matmul and under half of what
    in the read follows the slots (the per-slot view of the scores and its
    softmax), and costs what every step program costs, half a second of
    every start. The scheduler hands out the lowest free slot, so the
    running requests sit in the first rows and a round whose highest
    running slot lies below the quarter runs as one of the quarter's
    programs (``_tables_device``). A constant of the engine's shape like
    ``_list_ladder``; ``ServingEngine._step_shapes`` says how the programs
    are spent on the two."""
    few = -(-max_seqs // 64) * 16
    return (few, max_seqs) if 2 * few < max_seqs else (max_seqs,)


# Prompts one prefill program takes in its row (``_get_prefill_fn``): the
# static length of its ``starts`` / ``lengths``. A constant of the program's
# shape, not a setting: a round admits two or three prompts where slots are
# kept full, and the row is no longer than the longest bucket built.
_SEGMENTS = 4


# expert-routing counters of a stats window (ServingEngine._note_counters)
_MOE_COUNTERS = {"kept": 0, "asked": 0, "max_over_mean": 0.0, "rounds": 0,
                 "touched": 0.0, "steps": 0, "prefill_touched": 0.0,
                 "prefills": 0}


# latency-tier and round-order counters of a stats window
_LAT_COUNTERS = {"spec_steps": 0, "spec_proposed": 0, "spec_accepted": 0,
                 "prefill_chunks": 0, "prefill_chunk_tokens": 0,
                 # whole prompts prefilled, the programs that took them and
                 # the prompts that shared a program's row (_pack_prefills)
                 "prefill_prompts": 0, "prefill_programs": 0,
                 "prefill_packed_prompts": 0,
                 # key tiles of the rows the packed flash forward took, one
                 # kv head's pass of one layer (ops/flash_attention
                 # .packed_walk): what its ONE call walks, and a causal
                 # pass a prompt in the same tiles
                 "prefill_attn_tiles_walked": 0,
                 "prefill_attn_tiles_looped": 0,
                 "cow_forks": 0,
                 # plain decode rounds dispatched while the round before was
                 # still unfetched (of the rounds step_shape_rounds counts),
                 # and slot-rounds whose tokens nobody was left to take
                 "rounds_ahead": 0, "dropped_slot_rounds": 0,
                 # of the rounds ahead: the chip still had work when the
                 # next round's first step was issued / had run dry
                 "ahead_covered_rounds": 0, "ahead_dry_rounds": 0,
                 # over the plain rounds: blocks the step gathered a plane
                 # (the length of the list it was handed) and blocks the
                 # running requests held
                 "kv_blocks_gathered": 0, "kv_blocks_held": 0}


class ServingEngine:
    """Continuous-batching server over an InferenceEngine's params/mesh.

    >>> eng = init_inference(model, config={...})
    >>> srv = ServingEngine(eng, ServingConfig(max_seqs=32))
    >>> outs = srv.run([(prompt_ids, 64), ...])   # {rid: output ids}
    >>> srv.stats()                               # TTFT p50/p99, tok/s
    """

    def __init__(self, engine, config: Optional[ServingConfig] = None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from deepspeed_tpu.parallel import spec_tree

        # --- set-up (ISSUE 55): what this engine built, and when, counted
        # from where its InferenceEngine's construction began; the process's
        # one build listener, whose running total a round diffs (build_ms)
        self._clock = build_clock()
        t_init, build_s = time.perf_counter(), self._clock.seconds
        self._builds = BuildLog(
            t0=getattr(engine, "setup", {}).get("t0", t_init))
        # programs built by the first reset_stats() (None: not reset yet)
        self._built_at_reset: Optional[int] = None
        self._build_overlap_s = 0.0        # _get_quantum_step
        self.engine = engine
        self.config = config or ServingConfig()
        c = self.config
        model = engine.model
        if model.decode_step_paged is None or model.prefill_paged is None:
            raise ValueError("ServingEngine needs the paged decode "
                             "protocol (models/transformer make_model)")
        self.model = model
        mcfg = model.config
        # --- mesh geometry (ISSUE 15: pod-scale serving) ---------------
        # the engine's mesh is authoritative: tensor parallelism shards
        # the KV block pools on the kv-head dim (paged_cache_logical_axes
        # "heads" -> the Megatron col/row rules), expert parallelism
        # shards the MoE FFN stacks. Both recorded here so drains,
        # heartbeats and migrations can carry the topology.
        # read the ENGINE's resolved degrees, not the raw mesh shape: a
        # dense model on a shared mesh that happens to carry an expert
        # axis has ep degraded to 1 (nothing shards over it), and the
        # drain/heartbeat topology must say so — advertising the unused
        # axis would spuriously refuse migrations to dense survivors
        self.tp = int(getattr(engine, "tp",
                              engine.mesh.shape.get("tensor", 1)))
        self.ep = int(getattr(engine, "ep",
                              engine.mesh.shape.get("expert", 1)))
        nkv = getattr(mcfg, "kv_heads", None)
        if self.tp > 1 and nkv is not None and nkv % self.tp:
            raise ValueError(
                f"tensor parallel degree {self.tp} does not divide "
                f"kv_heads={nkv}: the paged block pools shard on the "
                "kv-head dim, so each chip must hold a whole head slice")
        if c.block_size < 8 or c.block_size % 8:
            raise ValueError(f"block_size={c.block_size}: TPU tiling needs "
                             "a multiple of 8")
        if c.decode_backend not in ("auto", "xla", "pallas"):
            # a typo'd backend would be recorded in telemetry while the
            # attention dispatch silently ran XLA
            raise ValueError(f"decode_backend={c.decode_backend!r}: one of "
                             "auto | xla | pallas")
        if c.role not in ("prefill", "decode", "both"):
            raise ValueError(f"role={c.role!r}: one of prefill | decode | "
                             "both (the disaggregated-fleet tier label)")
        model_cap = getattr(mcfg, "max_seq_len", None)
        want = int(c.max_model_len or model_cap or 2048)
        want = -(-want // c.block_size) * c.block_size
        if model_cap:
            # never admit positions the model can't represent (learned
            # position tables / rotary training range): clamp DOWN to the
            # model cap, block-aligned
            want = min(want, (model_cap // c.block_size) * c.block_size)
        if want < c.block_size:
            raise ValueError(
                f"max_model_len/model max_seq_len ({c.max_model_len} / "
                f"{model_cap}) leaves no room for one "
                f"{c.block_size}-token block")
        # what the model keeps per serving slot beside its K/V blocks, by its
        # own statement: the names of those leaves of the pool (none: a
        # request's state is its blocks alone)
        self._slot_state = tuple(model.slot_leaves)
        for armed, what in (
                (c.enable_prefix_cache, "the prefix cache"),
                (c.prefill_token_budget is not None, "chunked prefill"),
                (c.spec_tokens > 0, "speculative decoding"),
                (c.adapter_slots > 0, "LoRA adapter serving"),
                (self.tp > 1, "a tensor-parallel pool")):
            if armed:
                self._by_blocks_alone(what)
        if self.tp > 1 and getattr(mcfg, "latent_planes", 0):
            raise ValueError(
                f"tensor parallel degree {self.tp} over latent attention: "
                "every head reads the one latent row a token keeps, so "
                "neither the pool nor the absorbed read splits over heads "
                "here yet")
        # what the plain rounds read of the model's window rings
        # (``model.ring_rows`` rows each; reset_stats windows)
        self._win = {"slot_rounds": 0, "rows_in_window": 0}
        self.max_model_len = want
        self.MB = self.max_model_len // c.block_size     # table width
        # a per-slot state pool is updated whole and in place (a Pallas
        # operand is a whole buffer: a narrower step would copy
        # ``state[:, :slots]``), so its engine keeps every round at max_seqs
        self._slot_counts = ((c.max_seqs,) if self._slot_state
                             else _slot_ladder(c.max_seqs))
        num_blocks = c.num_blocks or (c.max_seqs * self.MB + 1)
        if num_blocks - 1 < self.MB:
            raise ValueError(
                f"num_blocks={num_blocks}: one sequence at "
                f"max_model_len={self.max_model_len} needs {self.MB} "
                "blocks + the trash block")
        self.num_blocks = num_blocks
        # prompt buckets are block-aligned (prefill scatters whole blocks)
        # and coarse (compiles are reused across nearby prompt lengths)
        self._bucket = max(c.prompt_bucket, c.block_size)
        if self._bucket % c.block_size:
            self._bucket = -(-self._bucket // c.block_size) * c.block_size

        if c.pool_watermark is not None and not 0 < c.pool_watermark <= 1:
            raise ValueError(f"pool_watermark={c.pool_watermark}: a held-"
                             "pool fraction in (0, 1]")
        # --- latency-frontier validation (ISSUE 12) --------------------
        if c.spec_tokens < 0:
            raise ValueError(f"spec_tokens={c.spec_tokens}: >= 0 "
                             "(0 disables speculation)")
        if c.prefill_token_budget is not None and c.prefill_token_budget < 1:
            raise ValueError(
                f"prefill_token_budget={c.prefill_token_budget}: a "
                "positive per-round token budget (None disables chunking)")
        # --- multi-tenant LoRA validation (ISSUE 17) -------------------
        self._lora = c.adapter_slots > 0
        if self._lora:
            if c.adapter_slots < 2:
                raise ValueError(
                    f"adapter_slots={c.adapter_slots}: need >= 2 (slot 0 "
                    "is the reserved all-zero null adapter)")
            if c.lora_rank < 1:
                raise ValueError(
                    f"lora_rank={c.lora_rank}: adapter serving needs a "
                    "positive shared rank (one device pool shape)")
        latency_armed = (c.enable_prefix_cache or c.spec_tokens > 0
                         or c.prefill_token_budget is not None
                         or self._lora)
        if latency_armed and model.decode_span_paged is None:
            raise ValueError(
                "prefix cache / chunked prefill / speculative decoding / "
                "LoRA serving need the span protocol (models/transformer "
                "make_model decode_span_paged) — this model doesn't "
                "provide it")
        if c.spec_tokens > 0 and c.temperature:
            raise ValueError(
                f"spec_tokens={c.spec_tokens} with temperature="
                f"{c.temperature}: speculation is greedy-only (the accept "
                "rule's output-parity argument needs argmax sampling; the "
                "stochastic accept/reject rule is future work)")
        self.allocator = BlockAllocator(num_blocks)
        self._prefix_cache = None
        if c.enable_prefix_cache:
            from deepspeed_tpu.inference.prefix_cache import PrefixCache
            # no cap of its own on the blocks it holds: the pool's pressure
            # evicts them (the scheduler asks before it queues or preempts)
            self._prefix_cache = PrefixCache(self.allocator, c.block_size)
        # the scheduler's per-round row guarantee must cover a verify
        # step's K+1 writes as well as the plain quantum's
        self._sched_quantum = max(c.decode_quantum,
                                  c.spec_tokens + 1 if c.spec_tokens else 1)
        self.scheduler = RequestScheduler(
            self.allocator, c.max_seqs, c.block_size, self._sched_quantum,
            prompt_blocks=lambda n: self._pad_prompt(n) // c.block_size,
            max_blocks_per_seq=self.MB, max_queue=c.max_queue,
            pool_watermark=c.pool_watermark,
            prefix_cache=self._prefix_cache)
        self._proposer = None
        if c.spec_tokens > 0:
            from deepspeed_tpu.inference.spec_decode import NgramProposer
            self._proposer = (c.spec_proposer
                              or NgramProposer(_SPEC_NGRAM).propose)

        # device state -------------------------------------------------
        # Pool shardings come from the SAME col/row rules the weights use:
        # paged_cache_logical_axes maps the kv-head dim to "heads", which
        # the engine's rules put on the `tensor` mesh axis — each chip
        # holds its head-slice of EVERY block, block ids stay replicated
        # host metadata. Every jitted serving program below pins its pool
        # output to these shardings (out_shardings), so the pool layout
        # can never silently drift to replicated mid-serve (the
        # `tp-serving-replicated-pool` corpus defect).
        axes = (model.paged_cache_axes()
                if model.paged_cache_axes is not None else None)
        if axes is not None:
            specs = spec_tree(axes, engine._rules)
            self._pool_shardings = jax.tree.map(
                lambda s: NamedSharding(engine.mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))
        else:
            self._pool_shardings = None
        self._repl_sharding = NamedSharding(engine.mesh, P())
        # fresh-pool program cached: fault recovery rebuilds the pool with
        # the same jitted init the constructor uses
        self._init_pools_fn = jax.jit(
            lambda: model.init_paged_cache(
                num_blocks, c.block_size, dtype=engine.dtype,
                max_seqs=c.max_seqs),
            out_shardings=self._pool_shardings)
        with span("ds:setup.pools") as sp, engine.mesh:
            self.pools = self._init_pools_fn()
        self._pools_s = sp.seconds         # and every recovery's (_recover)
        # the per-slot recurrent state's dtype: that of the first per-slot
        # leaf that is no ring (None for a model without one)
        rings = ring_leaves(model, self.pools)
        # the block pool's dtype: that of its first leaf that is no slot's
        leaf = self._block_leaf()
        self.kv_pool_dtype = None if leaf is None else str(leaf.dtype)
        self.state_pool_dtype = next(
            (str(self.pools[k].dtype) for k in self._slot_state
             if k not in rings), None)
        # logical pool size (the README memory math, mesh-independent: the
        # model's own tree summed, ``kv_cache.cache_bytes``) vs
        # the PER-DEVICE shard each chip actually holds: on a tp-sharded
        # engine the resident HBM is logical / tp (the kv-head slice), and
        # pool_bytes — what stats()/bench report — must price THAT, not
        # the logical array (ISSUE 15: the old single number overstated
        # HBM by the tp degree on sharded engines)
        self._cache_bytes = cache_bytes(model, self.pools)
        self.pool_bytes_logical = sum(self._cache_bytes.values())
        from deepspeed_tpu.parallel.partitioning import sharded_bytes
        self.pool_bytes = sharded_bytes(self.pools)
        # --- adapter slot pool (ISSUE 17: paged multi-LoRA) ------------
        # the KV block-pool discipline applied to read-only weights: a
        # fixed device slot pool (all-zero = the null adapter), host-side
        # refcount/LRU accounting (kv_cache.AdapterSlotPool), a host RAM
        # store of every registered adapter's A/B stacks, and ONE jitted
        # page-in program writing a slot's tables in place. The A/B slot
        # tables shard under the SAME col/row rules as their projections
        # (adapter_pool_logical_axes), so the gathered LoRA delta is
        # computed shard-local.
        self.adapter_store = None
        self.adapter_slots = None
        self.adapter_pool = None
        self._apool_shardings = None
        if self._lora:
            from deepspeed_tpu.inference.kv_cache import AdapterSlotPool
            from deepspeed_tpu.inference.lora import (
                AdapterStore, adapter_pool_logical_axes, init_adapter_pool)
            self.adapter_store = AdapterStore(mcfg, c.lora_rank,
                                              c.lora_targets)
            self.adapter_slots = AdapterSlotPool(c.adapter_slots)
            aspecs = spec_tree(adapter_pool_logical_axes(c.lora_targets),
                               engine._rules)
            self._apool_shardings = jax.tree.map(
                lambda s: NamedSharding(engine.mesh, s), aspecs,
                is_leaf=lambda x: isinstance(x, P))
            self._init_apool_fn = jax.jit(
                lambda: init_adapter_pool(mcfg, c.adapter_slots,
                                          c.lora_rank, c.lora_targets,
                                          dtype=engine.dtype),
                out_shardings=self._apool_shardings)
            with engine.mesh:
                self.adapter_pool = self._init_apool_fn()
            # page-in: one slot's tables written in place (donated pool —
            # read-only BETWEEN page-ins, never inside a decode round)
            self._page_in_fn = jax.jit(
                lambda pool, tabs, slot: jax.tree.map(
                    lambda p, t: p.at[:, slot].set(t), pool, tabs),
                donate_argnums=(0,), out_shardings=self._apool_shardings)
            self.pool_bytes += sharded_bytes(self.adapter_pool)
            self.pool_bytes_logical += sum(
                int(np.prod(a.shape)) * a.dtype.itemsize
                for a in jax.tree.leaves(self.adapter_pool))
        self._tokens = jnp.zeros((c.max_seqs,), jnp.int32)
        self._requests: Dict[int, Request] = {}
        self._finished: List[Request] = []
        self._cancelled: List[Request] = []
        self._prefill_fns: Dict[int, Any] = {}
        # {"step" | "prefill_<bucket>": the expert layers' dispatch form},
        # written when a program is traced (stats()["moe_dispatch"])
        self._moe_forms: Dict[str, Optional[str]] = {}
        self._chunk_fns: Dict[int, Any] = {}
        self._quantum_step = None
        self._spec_step = None
        # one tiny program copies a block in place for the CoW fork — its
        # shape is the pool's, so it compiles once (per-shard copy: the
        # block index walks the unsharded NB dim, no collective)
        self._copy_block_fn = jax.jit(
            lambda pools, src, dst: jax.tree.map(
                lambda a: a.at[:, dst].set(a[:, src]), pools),
            donate_argnums=(0,), out_shardings=self._pool_shardings)
        # disaggregated KV handoff programs (ISSUE 19): ONE compile each,
        # the _copy_block_fn idiom widened to a block-id VECTOR padded to
        # the table width MB. Export gathers a request's blocks (pads
        # index trash block 0 — discarded on the host slice); import
        # scatters a padded payload back in (pad writes land in trash
        # block 0, which is never read). The gather must NOT donate the
        # pools — the source keeps serving its other requests; a
        # head-sharded engine's device_get assembles the full logical
        # array, so payloads are mesh-independent. The PAYLOAD keeps the
        # head-major order [L, n, nkv, bs, hd] (+ scales [L, n, nkv, bs])
        # whatever the pool stores: the blocks are turned at this edge.
        from deepspeed_tpu.models.transformer import (
            paged_blocks_from_logical, paged_blocks_to_logical)
        self._gather_blocks_fn = jax.jit(
            lambda pools, ids: paged_blocks_to_logical(
                jax.tree.map(lambda a: a[:, ids], pools)))
        self._scatter_blocks_fn = jax.jit(
            lambda pools, ids, data: jax.tree.map(
                lambda a, d: a.at[:, ids].set(d), pools,
                paged_blocks_from_logical(data)),
            donate_argnums=(0,), out_shardings=self._pool_shardings)
        # in-flight handoff staging: host bytes of exported payloads not
        # yet released + imported payloads not yet scattered. Real memory
        # — stats()["pool_bytes"] prices it alongside the device pool.
        self._kv_staging: Dict[int, int] = {}
        self._rng_counter = 0
        self._base_key = None              # _next_key
        self._stats_t0: Optional[float] = None
        # latency-frontier counters (reset_stats windows)
        self._itl_ms: List[float] = []
        # expert-routing counters (reset_stats windows; _note_counters)
        self._moe = dict(_MOE_COUNTERS)
        # a looped model's exit distribution, summed over every sampled
        # position of the stats window, then their count (_note_counters)
        self._ut_steps = int(getattr(mcfg, "ut_steps", 1))
        self._exit = np.zeros((self._ut_steps + 1,), np.float64)
        self._lat = dict(_LAT_COUNTERS)
        # reliability bookkeeping ---------------------------------------
        self._counters = {"shed": 0, "deadline_misses": 0, "degraded": 0,
                          "recoveries": 0, "recovery_ms": 0.0,
                          "handoffs": 0, "handoff_bytes": 0,
                          "handoff_fallbacks": 0}
        # recovery epoch: a watchdog-abandoned round thread re-checks this
        # after its (injected) stall and bails out WITHOUT dispatching —
        # stale work never races the recovered engine
        self._epoch = 0
        # latest watchdog round thread — close() joins it bounded so an
        # abandoned round can't outlive the engine that spawned it
        self._round_thread: Optional[threading.Thread] = None
        # the watchdog arms only once the quantum step has run once: the
        # first round's jit compile is legitimate wall time, not a hang
        self._quantum_warm = False
        # the plain decode round whose last steps the last step() left
        # unfetched: the next step() dispatches its own round first and
        # only then fetches and commits them, so the chip never waits for
        # the host's commit / schedule / tables / first dispatch
        self._inflight: Optional[_DispatchedRound] = None
        self._draining = False
        self._preemption = None            # attach_preemption()
        self._drain_dir: Optional[str] = None
        # --- fleet observability (ISSUE 18) ----------------------------
        # round-phase decomposition: every _round() times its phases
        # (schedule / housekeeping / prefill dispatch / decode dispatch /
        # token fetch / commit) through telemetry.tracing.span — always on,
        # seven inactive TraceAnnotations a round. A round leaves ONE record
        # (_round's docstring); what the stats window keeps of them is in
        # _reset_round_records.
        self._gc = _GC_CLOCK.install()
        self._reset_round_records()
        # when the engine last came to hold no request (None: it holds
        # one), and what a round's record says of the interval it ended
        self._empty_since: Optional[float] = None
        self._empty_before_s = 0.0
        self._tracer = None                # RequestTracer when armed
        if c.request_trace:
            self.enable_request_trace(replica=c.trace_replica)
        self._jsonl = None
        if c.telemetry_jsonl:
            from deepspeed_tpu.monitor.monitor import JSONLMonitor
            self._jsonl = JSONLMonitor(c.telemetry_jsonl)

        # which read of the pool the decode step takes (priced or timed) --
        self.decode_backend, self.backend_bench = self._select_backend()
        # plain decode rounds dispatched per (slot count, columns a slot)
        # (reset_stats windows; _tables_device)
        self._table_rounds = self._step_shapes()
        # the constructor's seconds, and of them those spent building
        # programs (the pool's init, the backend's micro-bench)
        self._init_s = time.perf_counter() - t_init
        self._init_build_s = self._clock.seconds - build_s

    # ---- mesh geometry -----------------------------------------------

    @property
    def mesh_desc(self) -> str:
        """Human/JSON mesh label, e.g. "tensor=2" / "expert=4" / "single"
        — what the bench records next to the SLO numbers."""
        axes = {k: int(v) for k, v in self.engine.mesh.shape.items()
                if int(v) > 1}
        return "x".join(f"{k}={v}" for k, v in axes.items()) or "single"

    def _check_geometry(self, eng: Optional[Dict[str, Any]],
                        source: Optional[str] = None) -> None:
        """Refuse restoring work drained on a DIFFERENT mesh geometry.
        The byte-identical-continuation contract is per-geometry: the
        drained request's already-emitted tokens were argmaxes of the
        drained mesh's float program, and a different tp/ep degree
        regroups the out-projection reductions (different float
        reordering) — a continuation there is best-effort, not the
        guarantee resume()/accept_migration promise. Records that predate
        the geometry fields (pre-ISSUE-15 drains) pass: their engines
        were single-chip and so is the ambiguity."""
        if eng is None:
            return
        want_tp, want_ep = eng.get("tp"), eng.get("ep")
        src = f" (drained by {source})" if source else ""
        if want_tp is not None and int(want_tp) != self.tp or \
                want_ep is not None and int(want_ep) != self.ep:
            raise ResumeIncompatible(
                f"drained state{src} came from a tp={want_tp} ep={want_ep} "
                f"engine; this engine is tp={self.tp} ep={self.ep} — "
                "byte-identical continuation is only guaranteed on a "
                "matching mesh geometry (place it on a survivor with the "
                "same tp/ep degrees)")

    @property
    def max_seqs(self) -> int:
        return self.config.max_seqs

    def _block_leaf(self):
        """The first leaf of the pool that belongs to no slot: a plane of
        the block pool (None for a model that keeps no blocks)."""
        return next((a for k, a in self.pools.items()
                     if k not in self._slot_state), None)

    def _by_blocks_alone(self, what: str) -> None:
        """THE rule for what this engine refuses (``SlotStateUnsupported``):
        ``what`` takes a request's K/V blocks for the whole of its state."""
        if self._slot_state:
            raise SlotStateUnsupported(what)

    # ---- fleet observability (ISSUE 18) ------------------------------

    def enable_request_trace(self, replica: Optional[str] = None,
                             on_span=None):
        """Arm per-request tracing on a (possibly warm) engine. Spans are
        host-wall-clock only — no device syncs, bit-identical outputs —
        so the bench A/Bs the SAME engine traced vs untraced. Returns the
        tracer (``on_span`` is the per-span hook; see RequestTracer for
        the sync-leak contract)."""
        from deepspeed_tpu.telemetry.request_trace import RequestTracer
        self._tracer = RequestTracer(
            replica=replica or self.config.trace_replica,
            max_events=_TRACE_EVENTS, on_span=on_span)
        return self._tracer

    def disable_request_trace(self) -> None:
        self._tracer = None

    @property
    def tracer(self):
        return self._tracer

    def _rspan(self, rid: int, name: str, **args):
        """Span context for request ``rid`` — a no-op nullcontext when
        tracing is off, so hook sites stay one-liners on the hot path."""
        if self._tracer is None:
            return contextlib.nullcontext()
        return self._tracer.span(rid, name, **args)

    def export_trace(self, path: Optional[str] = None):
        """This replica's trace stream (``RequestTracer.export`` dict);
        with ``path``, write it merged as Chrome-trace JSON. Multi-replica
        merges go through ``telemetry.merge_chrome_trace`` with every
        replica's stream."""
        if self._tracer is None:
            return None
        from deepspeed_tpu.telemetry.request_trace import merge_chrome_trace
        stream = self._tracer.export()
        if path:
            merge_chrome_trace([stream], path=path)
        return stream

    # a round's phase entry (the ring's keys) -> phase_decomposition()'s
    _PHASE_OUT = {"schedule_ms": "serve_schedule_ms",
                  "housekeeping_ms": "serve_housekeeping_ms",
                  "prefill_ms": "serve_prefill_dispatch_ms",
                  "decode_ms": "serve_decode_dispatch_ms",
                  "fetch_ms": "serve_fetch_ms",
                  "commit_ms": "serve_commit_ms",
                  "round_ms": "serve_round_ms", "tokens": "serve_tokens"}

    def phase_decomposition(self) -> Dict[str, float]:
        """The decomposition the serving doctor prices
        (``profiling.doctor.diagnose_serving``): total host ms per phase
        over the WHOLE stats window (since ``reset_stats()``) plus round /
        token counts and the tracing-overhead evidence (device_syncs
        self-report)."""
        out: Dict[str, float] = {
            "serve_rounds": float(self._rounds),
            "serve_phase_stall_events": float(self._phase_stall_events),
            "trace_armed": float(self._tracer is not None),
            "trace_device_syncs": float(self._tracer.device_syncs
                                        if self._tracer else 0),
        }
        for key, total in self._phase_totals.items():
            out[self._PHASE_OUT[key]] = total
        return {k: (round(v, 3) if k.endswith("_ms") else v)
                for k, v in out.items()}

    # thresholds for the blind-stall event: only a WARM engine's rounds
    # count (the first rounds' jit compiles are legitimate wall time), and
    # a phase must be both absolutely slow and dominant before the event
    # fires — CPU-test rounds stay quiet
    _STALL_MIN_ROUND_MS = 50.0
    _STALL_FRACTION = 0.6
    # the ring of round records: a 45 s window of the fastest cell is ~450
    # rounds; and how many of the slowest rounds a window keeps whole
    _RING_ROUNDS = 512
    _SLOW_ROUNDS = 8
    _PHASES = ("schedule", "housekeeping", "prefill", "decode", "fetch",
               "commit")

    def _reset_round_records(self) -> None:
        """What a stats window keeps of its rounds' records: the ring of
        the newest (the ONE bounded store: the stall rule's baseline and
        the median), the totals over every round, and over the
        decode-dominated rounds (``_decode_dominated``) the maximum of each
        phase and of the round and the slowest few, each with its
        follower."""
        self._phases: "collections.deque[Dict[str, Any]]" = \
            collections.deque(maxlen=self._RING_ROUNDS)
        self._phase_totals = dict.fromkeys(self._PHASE_OUT, 0.0)
        self._phase_max = dict.fromkeys(
            [f"{p}_ms" for p in self._PHASES] + ["round_ms"], 0.0)
        self._slow: List[list] = []        # [record, follower], slowest first
        self._slow_open: Optional[list] = None   # the pair owed a follower
        self._gc_ms_total = 0.0
        self._build_ms_total = 0.0
        self._empty_s = 0.0                # closed empty intervals
        self._rounds = 0                   # rounds in this stats window
        self._round_tokens = 0             # tokens committed this round
        self._phase_stall_events = 0       # serving_phase_stall emissions

    @staticmethod
    def _decode_dominated(entry: Dict[str, Any]) -> bool:
        """A round whose length is its decode steps': fewer prompts
        admitted than requests were decoding when it began. That leaves out
        the round that fills every empty slot at the start of a saturating
        window and the first round after an empty engine, whose length is
        their prefills', and a round that ran nothing."""
        return entry["prefills"] < entry["running_before"]

    def _note_phases(self, entry: Dict[str, Any]) -> None:
        """Take one round's record (``_round``) into the stats window: the
        ring, the totals over every round, the maxima and the slowest
        rounds over the decode-dominated ones — a slow round is kept with
        the record of the round that FOLLOWED it, whose probe tells a slow
        device (``ahead_covered`` True, as in any sound round) from a host
        that was away while the device went on (False, and a short fetch).
        Then the stall rule: at most one ``serving_phase_stall`` event per
        stats window, when a phase dominates a round that regressed against
        the window's own steady state (3x the prior-round median, with >= 8
        warm rounds of baseline — jit-compile rounds never have one, so
        short CPU runs stay quiet). The fetch counts like any phase: a
        round's device time is constant to four digits, so a fetch three
        times the median is the device, its runtime or a descheduled host,
        and the record — the event's payload — says which. (That the
        window's TOTALS are fetch-bound is still health: the doctor's
        reading, ``profiling.doctor.diagnose_serving``.)"""
        self._phases.append(entry)
        self._rounds += 1
        for key in self._phase_totals:
            self._phase_totals[key] += entry[key]
        self._gc_ms_total += entry["gc_ms"]
        self._build_ms_total += entry["build_ms"]
        if self._slow_open is not None:
            self._slow_open[1] = entry
            self._slow_open = None
        if self._decode_dominated(entry):
            for key, top in self._phase_max.items():
                if entry[key] > top:
                    self._phase_max[key] = entry[key]
            slow = self._slow
            if len(slow) < self._SLOW_ROUNDS \
                    or entry["round_ms"] > slow[-1][0]["round_ms"]:
                self._slow_open = [entry, None]
                slow.append(self._slow_open)
                slow.sort(key=lambda pair: -pair[0]["round_ms"])
                del slow[self._SLOW_ROUNDS:]
        if (not self._quantum_warm or self._phase_stall_events
                or len(self._phases) < 9
                or entry["round_ms"] < self._STALL_MIN_ROUND_MS):
            return
        prior = sorted(e["round_ms"] for e in list(self._phases)[:-1])
        if entry["round_ms"] < 3.0 * max(prior[len(prior) // 2], 1e-9):
            return
        for phase in self._PHASES:
            ms = entry[f"{phase}_ms"]
            if ms > self._STALL_FRACTION * entry["round_ms"]:
                self._phase_stall_events += 1
                rb_events.emit("serving_phase_stall", phase=phase,
                               phase_ms=round(ms, 2),
                               round_ms=round(entry["round_ms"], 2),
                               record=dict(entry))
                break

    def _open_empty_s(self, now: float) -> float:
        """Seconds of the stats window for which the engine has held no
        request up to ``now``, if it holds none; else 0."""
        if self._empty_since is None or self._stats_t0 is None:
            return 0.0
        return max(0.0, now - max(self._empty_since, self._stats_t0))

    def _occupied(self, now: float) -> None:
        """The engine holds a request again: close the empty interval, if
        one is open. Only what lies inside the stats window counts, in
        ``engine_empty_s`` and in the next round's ``empty_before_ms``."""
        gone = self._open_empty_s(now)
        self._empty_s += gone
        self._empty_before_s += gone
        self._empty_since = None

    def obs_meta(self) -> Dict[str, Any]:
        """Compact rollup payload for the router's fleet aggregation:
        mergeable fixed-edge histograms (TTFT / ITL over THIS stats
        window) plus occupancy gauges. Rides every heartbeat ``meta`` —
        a dead replica's last-seen payload IS its drained stats, so the
        fleet rollup keeps its history without a side channel."""
        from deepspeed_tpu.telemetry.exposition import (DEFAULT_EDGES_MS,
                                                        Histogram)
        ttft = Histogram(DEFAULT_EDGES_MS)
        ttft.observe_many((r.first_token_t - r.submit_t) * 1e3
                          for r in self._finished
                          if r.first_token_t is not None)
        itl = Histogram(DEFAULT_EDGES_MS)
        itl.observe_many(self._itl_ms)
        pool_occ = float(self.allocator.used_fraction)
        meta: Dict[str, Any] = {
            "ttft_ms_hist": ttft.to_dict(),
            "itl_ms_hist": itl.to_dict(),
            "pool_occupancy": round(pool_occ, 4),
            "completed": len(self._finished),
            "cancelled": len(self._cancelled),
            "generated_tokens": sum(len(r.generated)
                                    for r in self._finished),
        }
        if self._lora:
            usable = max(1, self.adapter_slots.num_slots - 1)
            meta["adapter_occupancy"] = round(
                self.adapter_slots.resident / usable, 4)
            meta["adapter_page_ins"] = self.adapter_slots.page_ins
        return meta

    # ---- shape bucketing ---------------------------------------------

    def _pad_prompt(self, n: int) -> int:
        return max(self._bucket,
                   min(-(-n // self._bucket) * self._bucket,
                       self.max_model_len))

    # ---- backend selection (a price or a measurement, not a flag) -----

    def _select_backend(self):
        """Which read of the paged pool the decode step takes, logged as a
        telemetry event (``decode_backend_selected``) with what decided it.

        An int8 pool is PRICED: ``ops/decode_attention.paged_read_price`` at
        this engine's slots, table width, block size and heads — the bytes
        the XLA read moves against the bytes ``paged_decode_int8`` moves and
        its fixed costs, the constants fitted on the chip — and the kernel
        takes the step where it is the cheaper read by more than the tie
        band. Nothing is timed. A pool of LATENT rows (one row a token for
        every head) is priced the same way, by ``ops/latent_decode.
        latent_read_price``, its kernel ``latent_decode``. A float pool of
        per-head K/V keeps the other kernel
        (``paged_decode_attention``) and the micro-bench that times it
        against the XLA gather on this engine's pool shapes. Non-TPU
        backends keep XLA (interpret-mode Pallas is not a serving path), and
        so does every engine the kernels do not cover: a ``tensor`` mesh over
        an int8 pool, ALiBi, a custom ``attn_scale``, per-layer windows,
        float16."""
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.ops.decode_attention import (int8_kernel_fits,
                                                        paged_read_price)
        from deepspeed_tpu.ops.latent_decode import (
            kernel_fits as latent_kernel_fits, latent_read_price)
        from deepspeed_tpu.robustness.events import emit

        c = self.config
        mcfg = self.model.config
        forced = c.decode_backend if c.decode_backend != "auto" else None
        on_tpu = jax.default_backend() == "tpu"
        int8_pool = getattr(mcfg, "kv_cache_bits", 0) == 8
        price = None
        # capability gate FIRST — _paged_attention would silently fall back
        # to the XLA gather for these, so selecting (or honoring a forced)
        # "pallas" here would make the telemetry event and the bench's
        # serve_decode_backend misreport what actually runs
        unavailable = None
        if self.engine.dtype == jnp.float16:
            unavailable = "f16 compute dtype (Mosaic has no f16)"
        elif (getattr(mcfg, "position_type", None) == "alibi"
              # a stated softmax scale: the per-head kernels take 1/sqrt(D);
              # the latent read is handed its scale (``latent_attention.
              # mixer_step``: YaRN's ``mscale^2`` rides on it)
              or (getattr(mcfg, "attn_scale", None) is not None
                  and not getattr(mcfg, "latent_planes", 0))
              or (getattr(mcfg, "attn_windows", None)
                  and not getattr(mcfg, "block_pattern", None))):
            # attn_windows: decode_step_paged passes a TRACED per-layer
            # window (even all-global entries), which the kernel gate
            # rejects. Under a block pattern the windows are the ring
            # blocks' (models/hybrid.py): no paged plane has one
            unavailable = "kernel-unsupported attention variant"
        elif getattr(mcfg, "latent_planes", 0):
            # one row a token for every head, V a slice of K: neither of the
            # per-head kernels' layout, and the float micro-bench below
            # builds per-head rows. A latent plane's read is PRICED, like an
            # int8 pool's: the XLA list read against ``ops/latent_decode``
            leaf = self._block_leaf()
            shapes = dict(block_size=c.block_size, lanes=leaf.shape[-1],
                          rank=mcfg.kv_lora_rank, itemsize=leaf.dtype.itemsize)
            if latent_kernel_fits(**shapes):
                price = latent_read_price(slots=c.max_seqs, MB=self.MB,
                                          heads=mcfg.num_heads, **shapes)
            else:
                unavailable = "latent kernel cannot be built at these shapes"
        elif mcfg.dim_per_head < 64:
            # the deleted contiguous kernel carried the same hardware
            # gate: sub-64 lanes don't lower well through Mosaic
            unavailable = f"head_dim {mcfg.dim_per_head} < 64"
        elif int8_pool and self.tp > 1:
            unavailable = "int8 KV pool under a tensor mesh"
        elif int8_pool:
            shapes = dict(MB=self.MB, block_size=c.block_size,
                          n_kv=mcfg.kv_heads, head_dim=mcfg.dim_per_head,
                          rep=mcfg.num_heads // mcfg.kv_heads)
            if int8_kernel_fits(**shapes):
                price = paged_read_price(slots=c.max_seqs,
                                         num_blocks=self.num_blocks, **shapes)
            else:
                unavailable = "int8 kernel cannot be built at these shapes"
        backend = reason = None
        if unavailable is not None:
            backend = "xla"
            reason = (f"pallas unavailable ({unavailable})"
                      if forced == "pallas" else unavailable)
        elif forced:
            backend, reason = forced, "forced by config"
        elif not on_tpu:
            backend, reason = "xla", "non-TPU backend"
        elif price is not None:
            backend, reason = price["choice"], price["why"]
        if reason is not None:
            bench = {"backend": backend, "reason": reason}
            if price is not None:
                bench.update(xla_bytes=price["xla_bytes"],
                             kernel_bytes=price["kernel_bytes"],
                             priced=price["choice"])
            emit("decode_backend_selected", **bench)
            return backend, bench

        # a Mosaic refusal of the paged kernel at these pool shapes
        # propagates: an engine that quietly serves on the XLA gather hides
        # exactly the compiler finding the micro-bench exists to surface
        # (the RUN-TIME backend_fault ladder is a different thing and stays)
        xla_ms, pallas_ms = measure_paged_backends(
            mcfg, self.pools["k"][0], self.pools["v"][0],
            max_seqs=c.max_seqs, MB=self.MB, block_size=c.block_size,
            num_blocks=self.num_blocks, dtype=self.engine.dtype,
            mesh=self.engine.mesh)
        backend = "pallas" if pallas_ms < xla_ms else "xla"
        bench = {"backend": backend, "xla_ms": round(xla_ms, 3),
                 "pallas_ms": round(pallas_ms, 3),
                 "pallas_speedup": round(xla_ms / pallas_ms, 3)}
        emit("decode_backend_selected", **bench)
        return backend, bench

    # ---- jitted programs ---------------------------------------------

    def _first_call(self, kind: str, shape, built: bool):
        """What a call of a jitted program runs under: its first — the one
        that traces, lowers and compiles or loads it — under the
        ``ds:setup.program`` of its build, every later one under nothing."""
        return contextlib.nullcontext() if built \
            else self._builds.program(kind, shape)

    def _sample(self, logits, key):
        import jax
        import jax.numpy as jnp
        t = self.config.temperature
        with jax.named_scope("sample"):
            if t and t > 0:
                return jax.random.categorical(key, logits / t, axis=-1
                                              ).astype(jnp.int32)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _get_prefill_fn(self, P: int):
        """One compile per prompt bucket P: prefill + block scatter + first
        sampled tokens, all one program (one dispatch per row). The row
        holds up to ``_SEGMENTS`` prompts, each from a block's edge
        (``starts``, ``lengths``; a segment of length 0 is not there): the
        ONE program of a bucket serves a prompt alone and the prompts of a
        round that ``_pack_prefills`` put together, and it returns a first
        token per segment, each an output of its own, so that nothing on
        the device is first met when a row is shared. A model that keeps a
        recurrent state per slot prefills one prompt, into its slot."""
        fn = self._prefill_fns.get(P)
        if fn is None:
            import jax
            from deepspeed_tpu.models.looped import exit_tap
            from deepspeed_tpu.moe.sharded_moe import expert_load_tap

            def run(params, ids, pools, block_ids, key, **prompts):
                with expert_load_tap() as tap, exit_tap() as gate:
                    last, pools = self.model.prefill_paged(
                        params, ids, pools, block_ids, **prompts)
                self._moe_forms[f"prefill_{P}"] = tap.form      # trace time
                # the first tokens travel with the program's counters: the
                # expert load [L, E + 1] of the REAL prompt tokens of every
                # segment (None for a model without experts) and the exit
                # distribution [passes + 1] summed over the positions they
                # were sampled at (None for a model that is not looped)
                return (self._sample(last, key),
                        (tap.stacked(), gate.summed()), pools)

            if self._slot_state:
                def prefill(params, ids, pools, block_ids, length, key, slot):
                    # slot: the request's slot, whose recurrent state the
                    # prompt overwrites
                    toks, counters, pools = run(params, ids, pools, block_ids,
                                                key, length=length, slot=slot)
                    return (toks, counters), pools
            else:
                def prefill(params, ids, pools, block_ids, starts, lengths,
                            key):
                    toks, counters, pools = run(params, ids, pools, block_ids,
                                                key, segments=(starts, lengths))
                    return (tuple(toks[k] for k in range(_SEGMENTS)),
                            counters), pools

            outs = ((self._repl_sharding, self._pool_shardings)
                    if self._pool_shardings is not None else None)
            fn = jax.jit(prefill, donate_argnums=(2,), out_shardings=outs)
            self._prefill_fns[P] = fn
        return fn

    def _quantum_step_fn(self):
        """The single decode step all slots share, jitted (not yet compiled
        for any shape): dispatched `decode_quantum` times back-to-back with
        no host sync in between (the PR-2 dispatch-window idea). Only the
        pools and the length vector are donated: the sampled-token arrays
        are collected across the quantum and fetched once. The function is
        named ``step``: the benchmark finds the decode step in a device
        trace by the program name ``jit_step``."""
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.models.looped import exit_tap
        from deepspeed_tpu.moe.sharded_moe import expert_load_tap

        backend = self.decode_backend

        def step(params, pools, tokens, tables, seq_lens, active, key,
                 apool=None, aidx=None):
            # apool rides as a trailing NON-donated arg: read-only
            # shared weights — donating it would force a re-page of
            # every resident adapter each quantum step
            lora = (apool, aidx) if apool is not None else None
            # the step is sized by what it is handed: the first n of the
            # engine's slots (it works on the first n pending tokens and
            # hands the vector back whole, so a round that widens again
            # finds every slot's token) and, on the XLA backend, the list
            # of the blocks they hold (a ``BlockList``)
            n = active.shape[0]
            with expert_load_tap() as tap, exit_tap() as gate:
                logits, pools = self.model.decode_step_paged(
                    params, tokens[:n], pools, tables, seq_lens,
                    active=active, backend=backend, lora=lora)
            self._moe_forms["step"] = tap.form                  # trace time
            nxt = self._sample(logits, key)
            nxt = jnp.where(active, nxt, tokens[:n])
            if n < tokens.shape[0]:
                nxt = jnp.concatenate([nxt, tokens[n:]])
            # the tokens travel with the step's counters over the ACTIVE
            # slots — the expert load [L, E + 1] (None for a model without
            # experts) and the exit distribution [passes + 1] (None for a
            # model that is not looped): collected and fetched together
            return (pools, (nxt, (tap.stacked(), gate.summed())),
                    seq_lens + active.astype(jnp.int32))

        r = self._repl_sharding
        outs = ((self._pool_shardings, r, r)
                if self._pool_shardings is not None else None)
        return jax.jit(step, donate_argnums=(1, 4), out_shardings=outs)

    def _step_shapes(self) -> dict:
        """{(slot count, columns a slot): 0} over the shapes a plain decode
        round may be dispatched at, each with a step program of its own and
        a counter of its rounds, in the order of what a step gathers:
        slots x columns blocks a plane, the LENGTH of the block list the
        round is handed (``_tables_device``) — the slot count times a MEAN
        number of columns, no slot's own. A round takes the first that
        holds it. As many programs as the two ladders had when the second
        was over the table's width (PR 29, PR 33): at ``max_seqs`` a
        quarter, a half and the whole of ``max_seqs x MB`` — the whole is
        the worst case, every slot at ``max_model_len`` —, and where the
        slot ladder has a narrower count, an eighth and a quarter of ITS
        ``slots x MB`` there and the half and the whole at ``max_seqs``
        (48 slots x 32 columns: 16 x 4, 16 x 8, 48 x 16, 48 x 32; few
        requests hold few blocks, and a round that overflows the narrow
        count's lists runs at ``max_seqs``). The Pallas backend resolves
        the tables inside its kernel and reads the blocks below each slot's
        length whatever the table's width: a program a slot count, at
        ``MB``."""
        few, top = self._slot_counts[:-1], self._slot_counts[-1]
        if self.decode_backend != "xla":
            return {(S, self.MB): 0 for S in self._slot_counts}
        shapes = [(S, W) for S in few for W in _list_ladder(self.MB, (8, 4))] \
            + [(top, W) for W in _list_ladder(self.MB,
                                              (2, 1) if few else (4, 2, 1))]
        return dict.fromkeys(sorted(shapes, key=lambda sh: (sh[0] * sh[1],
                                                            sh)), 0)

    def _get_quantum_step(self):
        """{(slot count, columns a slot): the decode step compiled for it},
        one program per entry of ``_step_shapes``, keyed like the ``shape``
        of a round. All of them are built when the first is asked for — the
        first decode round, and again after a backend swap — by lowering on
        abstract arguments: nothing runs, no pool is donated, and no shape
        is ever compiled inside a serving window, whichever lengths and
        however many requests the traffic brings. On the XLA backend the
        step's read of the pool — block gathers, scores, P.V — is sized by
        the list it is handed, ``slots x columns`` blocks whoever holds
        them, and only the float32 scores and their softmax by
        ``slots x MB``; the matmuls over the weights have ``slots`` rows.
        The per-slot token vector stays ``max_seqs`` long in every program
        (a prefill writes its first token at its slot)."""
        if self._quantum_step is None:
            import jax
            import jax.numpy as jnp
            from deepspeed_tpu.utils.memory import abstractify

            sds = jax.ShapeDtypeStruct
            fn = self._quantum_step_fn()
            # the big operands carry the shardings they live in; the small
            # ones are host-built each round and follow
            params, pools, apool = abstractify(
                (self.engine.params, self.pools,
                 self.adapter_pool if self._lora else None))
            # the round this call fell in: the worker thread is in none
            rnd = self._clock.thread().round

            def build(S, W):
                # a shape's ONE record: its lowering there, its compile here
                return self._builds.program("step", f"{S}x{W}", rnd)

            def lower(S, W):
                tables = jax.tree.map(
                    lambda a: sds(a.shape, jnp.int32),
                    self._blank_tables(S, W))
                with build(S, W), self.engine.mesh:
                    return fn.lower(
                        params, pools, sds(self._tokens.shape, jnp.int32),
                        tables, sds((S,), jnp.int32),
                        sds((S,), jnp.bool_), sds((2,), jnp.uint32), apool,
                        sds((S,), jnp.int32))

            # traced and lowered on a fresh thread, one shape after the
            # other, and compiled (a cache load, once warm) on this one
            # meanwhile: below the serving loop's frames a lowering costs
            # twice what it costs on an empty stack (three programs 3.3 s
            # against 1.5 s in Mixtral's cell, PERF.md section 6, PR 29),
            # and the shapes have to fit a cell's set-up (why:
            # ``_in_one_chunk``). The thread sees no thread-local jax.config
            # context of the caller's; the mesh is entered there
            t0, build_s = time.perf_counter(), self._clock.seconds
            with ThreadPoolExecutor(1) as pool:
                lowered = {shape: pool.submit(_in_one_chunk, lower, *shape)
                           for shape in self._step_shapes()}
                steps = {}
                for shape, lo in lowered.items():
                    lo = lo.result()
                    with build(*shape):
                        steps[shape] = lo.compile()
            self._quantum_step = steps
            # the two threads' build seconds past the wall's: a lowering
            # that ran beside a compile is in the records twice over
            self._build_overlap_s += max(0.0, self._clock.seconds - build_s
                                         - (time.perf_counter() - t0))
        return self._quantum_step

    def _get_spec_step(self):
        """The speculation verify step: ONE decode_span_paged pass scores
        the pending token plus the K proposals for every slot, the greedy
        accept rule runs in-graph (no extra host sync), and the per-slot
        cursor advances by exactly the accepted prefix + the model's own
        correction token — rows written for rejected proposals stay in
        place, masked by the rolled-back length until overwritten."""
        if self._spec_step is None:
            import jax
            import jax.numpy as jnp
            from deepspeed_tpu.inference.spec_decode import greedy_accept_len

            def step(params, pools, tok_mat, tables, seq_lens, active, key,
                     apool=None, aidx=None):
                lora = (apool, aidx) if apool is not None else None
                logits, pools = self.model.decode_span_paged(
                    params, tok_mat, pools, tables, seq_lens, active=active,
                    lora=lora)
                with jax.named_scope("sample"):
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    acc = greedy_accept_len(nxt, tok_mat[:, 1:])  # [S]
                    pend = jnp.take_along_axis(nxt, acc[:, None],
                                               axis=1)[:, 0]
                pend = jnp.where(active, pend, tok_mat[:, 0])
                new_lens = seq_lens + jnp.where(
                    active, acc + 1, 0).astype(jnp.int32)
                return pools, nxt, acc, pend, new_lens

            r = self._repl_sharding
            outs = ((self._pool_shardings, r, r, r, r)
                    if self._pool_shardings is not None else None)
            self._spec_step = jax.jit(step, donate_argnums=(1,),
                                      out_shardings=outs)
        return self._spec_step

    def _proposals_device(self):
        """Host-side drafting: one proposal row per decoding slot (the
        n-gram lookup or the draft hook), padded to K with zeros (pads
        verify as ordinary wrong guesses). Returns a [S, K] device array;
        the pending-token column is concatenated on device so the round
        still has exactly one host sync."""
        import jax.numpy as jnp
        c = self.config
        props = np.zeros((c.max_seqs, c.spec_tokens), np.int32)
        for req in self.scheduler.running:
            if not req.prefill_done:
                continue
            got = np.asarray(self._proposer(req.context, c.spec_tokens),
                             np.int32).reshape(-1)[:c.spec_tokens]
            props[req.slot, :got.size] = got
        return jnp.asarray(props)

    def _next_key(self):
        import jax
        self._rng_counter += 1
        if self._base_key is None:
            # made once: a PRNGKey is two device programs, eight times a
            # round, queued between one round's last step and the next
            # round's first
            self._base_key = jax.random.PRNGKey(20260803)
        return jax.random.fold_in(self._base_key, self._rng_counter)

    # ---- request API -------------------------------------------------

    def register_adapter(self, adapter_id: int, tables,
                         alpha: Optional[float] = None) -> None:
        """Register a LoRA adapter's host A/B stacks (``{proj: (A [L, In,
        r], B [L, r, Out])}`` — ``models/hf_import.load_peft_adapter``
        emits exactly this) under ``adapter_id``; requests can route to it
        immediately. ``alpha``: PEFT scaling, folded into B at
        registration (None = tables already scaled). Host RAM only — the
        device slot pool pages it in on first demand."""
        if not self._lora:
            raise ValueError("adapter_slots=0: LoRA serving is off — set "
                             "ServingConfig.adapter_slots/lora_rank")
        self.adapter_store.register(adapter_id, tables, alpha=alpha)

    def _acquire_adapter(self, req: Request) -> bool:
        """Pin the request's adapter to a device slot (page-in on miss).
        False = every slot is pinned by other in-flight adapters: the
        caller preempts the request back to the queue (retried when a
        slot frees) instead of failing the round."""
        from deepspeed_tpu.inference.kv_cache import BlockPoolExhausted
        if not self._lora or req.adapter_id == 0:
            req.adapter_slot = 0 if self._lora else None
            return True
        try:
            slot, page_in = self.adapter_slots.acquire(req.adapter_id)
        except BlockPoolExhausted:
            return False
        req.adapter_slot = slot
        if page_in:
            import jax.numpy as jnp
            with self._rspan(req.rid, "adapter_page_in",
                             adapter=req.adapter_id, slot=int(slot)):
                tabs = {
                    p: {"a": jnp.asarray(t["a"]), "b": jnp.asarray(t["b"])}
                    for p, t in self.adapter_store.table_for_slot(
                        req.adapter_id, self.engine.dtype).items()}
                with self.engine.mesh:
                    self.adapter_pool = self._page_in_fn(
                        self.adapter_pool, tabs, np.int32(slot))
        return True

    def _release_adapter(self, req: Request) -> None:
        """Drop the request's pin when it leaves the running set (finish /
        cancel / preempt). The slot stays resident at refcount 0 — the
        next request for the same adapter is a hit, not a page-in."""
        if self._lora and req.adapter_id and req.adapter_slot is not None:
            self.adapter_slots.release(req.adapter_id, owner=req.rid)
        req.adapter_slot = None

    def add_request(self, prompt_ids, max_new_tokens: int = 64,
                    request_id: Optional[int] = None,
                    ttft_deadline_ms: Optional[float] = None,
                    deadline_ms: Optional[float] = None,
                    adapter_id: int = 0) -> int:
        """Submit one request. Raises the typed ``AdmissionRejected`` when
        a watermark sheds it or the engine is draining — shed requests are
        counted (stats()["shed"]) and evented, never silently queued.
        ``adapter_id`` routes the request through a registered LoRA
        adapter (0 = base model); unknown ids refuse at submission, not
        at dispatch. The engine's part of a submission — validation, the
        scheduler's ``submit`` — is one ``ds:serve.submit`` span; on an
        engine that held nothing it ends the empty interval that
        ``step()``'s ``ds:serve.drained`` began."""
        with span("ds:serve.submit"):
            prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
            if adapter_id:
                if not self._lora:
                    raise ValueError(
                        f"adapter_id={adapter_id} with adapter_slots=0: "
                        "LoRA serving is off")
                if adapter_id not in self.adapter_store:
                    raise ValueError(
                        f"adapter_id={adapter_id} is not registered "
                        "(register_adapter first)")
            if max_new_tokens < 1:
                # the prefill inherently samples one token; a 0-budget request
                # would still emit it
                raise ValueError(f"max_new_tokens={max_new_tokens}: must be "
                                 ">= 1")
            if prompt.size + max_new_tokens > self.max_model_len:
                raise ValueError(
                    f"prompt ({prompt.size}) + max_new_tokens "
                    f"({max_new_tokens}) exceeds max_model_len "
                    f"{self.max_model_len}")
            if self._draining:
                self._counters["shed"] += 1
                rb_events.emit("request_shed", reason="draining")
                raise AdmissionRejected("draining")
            try:
                req = self.scheduler.submit(
                    prompt, max_new_tokens, rid=request_id,
                    ttft_deadline_ms=(ttft_deadline_ms
                                      if ttft_deadline_ms is not None
                                      else self.config.ttft_deadline_ms),
                    deadline_ms=(deadline_ms if deadline_ms is not None
                                 else self.config.deadline_ms),
                    adapter_id=adapter_id)
            except AdmissionRejected as e:
                self._counters["shed"] += 1
                rb_events.emit("request_shed", reason=e.reason, **e.detail)
                raise
            self._requests[req.rid] = req
            if self._tracer is not None:
                self._tracer.begin(req.rid)
                self._tracer.instant(req.rid, "admitted",
                                     prompt_tokens=int(prompt.size),
                                     adapter=adapter_id)
            # queue-wait clock: spans from here (or the latest preemption)
            # until the request's next dispatch
            req._trace_wait_t0 = req.submit_t
            # an engine that held nothing holds this one now; the stats
            # window opens with its first request
            self._occupied(req.submit_t)
            if self._stats_t0 is None:
                self._stats_t0 = req.submit_t
            return req.rid

    def _pack_prefills(self, reqs: List[Request]) -> list:
        """A round's whole-prompt prefills as ``[(bucket, [requests])]``,
        one prefill program each. Prompts share a program's row only where
        that compiles nothing and hides no compile: a prompt is a candidate
        if the program of ITS OWN bucket is built; candidates go first-fit,
        longest first, each padded to whole blocks, into rows no longer
        than the longest bucket built, ``_SEGMENTS`` to a row, and a shared
        row runs at the smallest built bucket that holds it. A prompt whose
        bucket is not built runs alone and builds it, so an engine that has
        built none — a warm-up that sends a prompt a bucket in one round —
        builds them all. Never for a model with a recurrent state per slot:
        its scans run the whole row from a zero state."""
        bs = self.config.block_size
        built = sorted(self._prefill_fns)
        alone, rows = [], []            # rows: [tokens of its blocks, requests]
        for req in sorted(reqs, key=lambda req: -len(req.context)):
            P = self._pad_prompt(len(req.context))
            if self._slot_state or P not in self._prefill_fns:
                alone.append((P, [req]))
                continue
            n = blocks_for(len(req.context), bs) * bs
            row = next((row for row in rows if len(row[1]) < _SEGMENTS
                        and row[0] + n <= built[-1]), None)
            if row is None:
                rows.append([n, [req]])
            else:
                row[0] += n
                row[1].append(req)
        # a row of one prompt is at its own bucket: the smallest that holds it
        return alone + [(next(P for P in built if P >= n), row)
                        for n, row in rows]

    def _dispatch_prefill(self, reqs: List[Request], P: int):
        """Dispatch (no sync) the (re-)prefill of the requests of one row
        of ``_pack_prefills`` as the program of bucket ``P``: writes their
        context rows into their blocks, leaves each one's next sampled
        token pending in the device token vector AND as a per-request
        handle fetched at the round boundary. Returns the padded length."""
        import jax.numpy as jnp
        from deepspeed_tpu.models.transformer import flash_takes
        from deepspeed_tpu.ops.flash_attention import packed_walk
        bs = self.config.block_size
        buf = np.zeros((1, P), np.int32)
        # a bucket's first prompt traces and lowers its program: not across
        # a chunk boundary of the interpreter's frame stack, and under the
        # span of its build
        build = self._first_call("prefill", P, P in self._prefill_fns)
        fn = self._get_prefill_fn(P) if P in self._prefill_fns else \
            functools.partial(_in_one_chunk, self._get_prefill_fn(P))
        if self._slot_state:
            req, = reqs
            buf[0, :req.context.size] = req.context
            block_ids = req.block_ids[:P // bs]
            what = (jnp.int32(req.context.size), self._next_key(),
                    np.int32(req.slot))
        else:
            # every prompt from a block's edge, so the row's blocks are the
            # requests' one after the other; the rows behind the last go
            # to the trash block
            starts = np.zeros(_SEGMENTS, np.int32)
            lengths = np.zeros(_SEGMENTS, np.int32)
            block_ids = []
            for k, req in enumerate(reqs):
                starts[k], lengths[k] = len(block_ids) * bs, req.context.size
                buf[0, starts[k]:starts[k] + lengths[k]] = req.context
                block_ids += req.block_ids[:blocks_for(lengths[k], bs)]
            block_ids += [0] * (P // bs - len(block_ids))
            what = (starts, lengths, self._next_key())
        ids, block_ids = jnp.asarray(buf), jnp.asarray(block_ids, jnp.int32)
        with self.engine.mesh, build:
            (toks, counters), self.pools = fn(
                self.engine.params, ids, self.pools, block_ids, *what)
        for k, req in enumerate(reqs):
            tok = toks[0] if self._slot_state else toks[k]
            self._tokens = self._tokens.at[req.slot].set(tok)
            req.cached_rows = req.context.size
            req.prefill_done = True
            # (token, the program's counters — with ONE of its requests, so
            # that every sum over requests stays a sum over programs):
            # fetched at round boundary
            req._first_dev = (tok, counters if k == 0 else (None, None))
            self._publish_prefill(req, req.context)
        self._lat["prefill_prompts"] += len(reqs)
        self._lat["prefill_programs"] += 1
        self._lat["prefill_packed_prompts"] += len(reqs) if len(reqs) > 1 \
            else 0
        mcfg = self.model.config
        if not self._slot_state and not mcfg.attn_windows \
                and flash_takes(mcfg, P):
            # the row's attention was the packed forward's ONE call a layer
            walked, causal = packed_walk(starts, lengths, P,
                                         mcfg.num_heads // mcfg.kv_heads)
            self._lat["prefill_attn_tiles_walked"] += walked
            self._lat["prefill_attn_tiles_looped"] += len(reqs) * causal
        return P

    def _publish_prefill(self, req: Request, ctx) -> None:
        """Index a prefill's FULL blocks in the prefix cache as soon as
        they are dispatched — they are immutable from here on (appends
        only write past them), so concurrent same-prefix tenants share
        them while this request still runs. Device ordering is free: the
        pool array threads through every dispatch, so a consumer's read
        depends on this write. The partial boundary block waits for
        ``finish`` (scheduler._publish) — its owner still appends."""
        if self._prefix_cache is not None and not req.adapter_id:
            # adapter KV is adapter-specific — never published under the
            # content-only hash (see scheduler._publish)
            self._prefix_cache.insert_full(ctx, req.block_ids,
                                           req.cached_rows)

    def _dispatch_fork(self, req: Request):
        """Copy-on-write fork (dispatch, no sync): the shared boundary
        block a prefix-cache match reached into is copied to the fresh
        block the scheduler put at the same table index, then the match's
        pin on the shared block is dropped. Runs BEFORE any of the
        request's own writes — full shared blocks stay referenced, the
        partial one is never written in place."""
        self._by_blocks_alone("a copy-on-write fork")
        src, dst = req.cow_src, req.cow_dst
        with self.engine.mesh:
            self.pools = self._copy_block_fn(self.pools, np.int32(src),
                                             np.int32(dst))
        self.allocator.free([src], owner=req.rid)
        req.cow_src = req.cow_dst = None
        self._lat["cow_forks"] += 1    # the one fork counter (stats())

    def _pad_chunk(self, n: int) -> int:
        bs = self.config.block_size
        return -(-n // bs) * bs

    def _get_chunk_fn(self, C: int):
        """One compile per chunk width C: a [1, C] span appended behind
        ``start`` rows already in the slot's blocks (prefix-cache hit or
        an earlier chunk), pad rows routed to the trash block, plus the
        sampled token at the last REAL position (used only by the final
        chunk — mid-prompt chunks discard it)."""
        fn = self._chunk_fns.get(C)
        if fn is None:
            import jax
            import jax.numpy as jnp

            def chunk(params, ids, pools, table, start, n, key,
                      apool=None, aidx=None):
                lora = (apool, aidx) if apool is not None else None
                logits, pools = self.model.decode_span_paged(
                    params, ids, pools, table,
                    jnp.reshape(start, (1,)), n_rows=jnp.reshape(n, (1,)),
                    lora=lora)
                last = jax.lax.dynamic_index_in_dim(logits[0], n - 1, 0,
                                                    keepdims=False)
                return self._sample(last[None], key), pools

            outs = ((self._repl_sharding, self._pool_shardings)
                    if self._pool_shardings is not None else None)
            fn = jax.jit(chunk, donate_argnums=(2,), out_shardings=outs)
            self._chunk_fns[C] = fn
        return fn

    def _dispatch_chunk(self, req: Request, start: int, n: int):
        """Dispatch (no sync) one prefill chunk: rows ``[start, start+n)``
        of the request's context computed against the rows already in its
        blocks. The final chunk samples the request's first token and
        flips it into the decoding set (same pending-token protocol as the
        whole-prompt prefill). Returns the padded chunk length."""
        import jax.numpy as jnp
        ctx = req.context
        final = start + n == ctx.size
        C = self._pad_chunk(n)
        buf = np.zeros((1, C), np.int32)
        buf[0, :n] = ctx[start:start + n]
        tab = np.zeros((1, self.MB), np.int32)
        tab[0, :len(req.block_ids)] = req.block_ids
        build = self._first_call("span", C, C in self._chunk_fns)
        fn = self._get_chunk_fn(C)
        lora_args = ()
        if self._lora:
            lora_args = (self.adapter_pool,
                         jnp.asarray([req.adapter_slot or 0], jnp.int32))
        ids, tab = jnp.asarray(buf), jnp.asarray(tab)
        rest = (jnp.int32(start), jnp.int32(n), self._next_key(), *lora_args)
        with self.engine.mesh, build:
            first, self.pools = fn(self.engine.params, ids, self.pools, tab,
                                   *rest)
        req.cached_rows = start + n
        self._lat["prefill_chunks"] += 1
        self._lat["prefill_chunk_tokens"] += n
        self._publish_prefill(req, ctx)        # full blocks so far
        if final:
            self._tokens = self._tokens.at[req.slot].set(first[0])
            req.prefill_done = True
            req._first_dev = (first, (None, None))   # (token, no counters)
        return C

    def _blank_tables(self, S: int, W: int, full: bool = False):
        """What a round of shape ``(S, W)`` hands its step in place of
        block tables, on the host and holding no request: on the XLA
        backend a ``BlockList`` of ``S x W`` blocks in runs of ``_RUN``
        (every entry padding: the trash block, no place in the view); for
        the Pallas kernel, and with ``full`` (a span), the rectangular
        ``ids[S, W]``."""
        from deepspeed_tpu.models.transformer import BlockList
        if full or self.decode_backend != "xla":
            return np.zeros((S, W), np.int32)
        runs, wide = S * W // _RUN, -(-self.MB // _RUN)
        return BlockList(np.zeros((runs * _RUN,), np.int32),
                         np.full((runs,), S * wide, np.int32),
                         np.full((S, wide), runs, np.int32))

    def _tables_device(self, full: bool = False):
        """What the round's step reads the pool through, with the lengths,
        the active mask and the adapter indices, on the device, and the
        round's shape (a key of ``_step_shapes``) with the blocks the
        running requests hold.

        The plain quantum step on the XLA backend is handed a FLAT LIST of
        those blocks (a ``BlockList``): every request's ``block_ids``,
        each padded to whole runs of ``_RUN`` columns with the
        trash block, and the list padded to ``S x W`` blocks, the first
        shape of ``_step_shapes`` — the one whose step gathers least —
        with ``S`` above the highest running slot and ``S x W`` no less
        than the blocks listed. What a step gathers, scores and contracts
        follows that SUM, not ``S`` x the longest request's table. The rows
        dropped hold no request (the scheduler gives out the lowest free
        slot) and a row's attention sees no other row; the blocks already
        cover the quantum's writes (``Scheduler._grow``), so one list
        serves all its steps, as one table did; what the list does not
        hold lies past every slot's length, where the probabilities are
        exact zeros. Inactive slots among the first ``S`` have no entry
        and length 0. The Pallas backend keeps rectangular tables
        ``ids[S, MB]`` and the per-slot lengths: its kernel reads only the
        blocks below a slot's length. ``full`` (the speculation verify
        step) keeps all ``max_seqs`` rows and ``MB`` columns, as do chunk
        dispatch (its own ``tab[1, MB]``), the fork and KV import /
        export: each is a program family of its own to warm, and no
        benchmark cell runs them."""
        import jax
        import jax.numpy as jnp
        running = self.scheduler.running
        held = sum(len(req.block_ids) for req in running)
        if full:
            S, W = self.config.max_seqs, self.MB
        else:
            top = max((req.slot for req in running), default=0)
            listed = sum(-(-len(req.block_ids) // _RUN) for req in running) \
                * _RUN
            S, W = next(sh for sh in self._table_rounds
                        if sh[0] > top and sh[0] * sh[1] >= listed)
        tables = self._blank_tables(S, W, full)
        lens = np.zeros((S,), np.int32)
        act = np.zeros((S,), bool)
        # per-slot adapter index into the device slot pool (0 = the null
        # adapter): free slots read slot 0 — an exact-zero delta
        aidx = np.zeros((S,), np.int32)
        n = 0
        for req in running:
            k = len(req.block_ids)
            if isinstance(tables, np.ndarray):
                tables[req.slot, :k] = req.block_ids
            else:
                runs = -(-k // _RUN)
                tables.ids[n * _RUN:n * _RUN + k] = req.block_ids
                tables.where[n:n + runs] = \
                    req.slot * tables.inv.shape[1] + np.arange(runs)
                tables.inv[req.slot, :runs] = np.arange(n, n + runs)
                n += runs
            # rows of a round still on the device's queue are written by
            # the time this round's first step reads the lengths
            lens[req.slot] = req.cached_rows + req.inflight_rows
            # a mid-prefill request (chunked prompt still landing) holds
            # its slot but must not decode yet
            act[req.slot] = req.prefill_done
            aidx[req.slot] = req.adapter_slot or 0
        if self.model.ring_rows and not full:
            # a step reads a window plane's whole ring for every live slot;
            # of those rows, min(length, window) are inside the slot's band
            self._win["slot_rounds"] += int(act.sum())
            self._win["rows_in_window"] += int(
                np.minimum(lens[act], self.model.ring_rows).sum())
        return (jax.tree.map(jnp.asarray, tables), jnp.asarray(lens),
                jnp.asarray(act), jnp.asarray(aidx)), (S, W), held

    def step(self) -> List[Request]:
        """One scheduling round: enforce deadlines, evict/admit/preempt at
        the boundary, then one decode quantum. Prefill dispatches and the
        quantum's K decode dispatches issue with NO host sync between them;
        the single sync is the token fetch at the end — which leaves the
        quantum's last steps (``_ahead_steps``) in flight for the next call
        to fetch, so the chip has work while the host commits, schedules
        and dispatches the next round (``_round``). Returns requests
        finished this round. A call that leaves the engine holding nothing
        marks the moment (``ds:serve.drained``, zero length, after the
        round's span has closed; ``_empty_since``).

        Reliability: a latched SIGTERM drains the engine first (raising
        ``Preempted``); a round failure — failed/hung dispatch, injected
        fault, backend failure — recovers by preempting every running
        request, rebuilding the pool, and retrying (``round_retries``
        times) before the error propagates."""
        if self._preemption is not None and self._preemption.requested:
            path = self.drain(self._drain_dir)
            raise Preempted("serving engine drained on SIGTERM",
                            ckpt_path=path)
        self._enforce_deadlines()
        finished: Optional[List[Request]] = None
        last_err: Optional[BaseException] = None
        # what is built on this thread from here on was built in this round
        here = self._clock.thread()
        here.round = self._rounds
        try:
            for _attempt in range(max(0, self.config.round_retries) + 1):
                try:
                    gc_s, build_s = self._gc.seconds, self._clock.seconds
                    with span("ds:serve.round", index=self._rounds) as rs:
                        finished, ph = self._round()
                        rs.note(running=len(self.scheduler.running),
                                tokens=self._round_tokens)
                    # only a round that completed counts in the window's totals
                    self._note_phases({
                        **ph, "round_ms": rs.seconds * 1e3,
                        "tokens": float(self._round_tokens),
                        "gc_ms": (self._gc.seconds - gc_s) * 1e3,
                        "build_ms": (self._clock.seconds - build_s) * 1e3})
                    break
                except (Preempted, KeyboardInterrupt):
                    raise
                except rb_faults.BackendFault as e:
                    last_err = e
                    self._degrade_backend()
                    self._recover("backend_fault")
                except Exception as e:  # noqa: BLE001 — ANY round failure
                    # (injected or real) must not kill every in-flight
                    # request: preempt-all + pool rebuild makes the retry
                    # bit-exact
                    last_err = e
                    self._recover(type(e).__name__)
        finally:
            here.round = None
        self._drain_events()
        if finished is None:
            raise RuntimeError(
                "serving round failed after "
                f"{self.config.round_retries} recovery retries") from last_err
        if self._empty_since is None and self._inflight is None \
                and self.scheduler.done:
            # nothing running, waiting or in flight: the engine is empty
            # from here to the next ds:serve.submit, on both clocks
            self._empty_since = time.perf_counter()
            with span("ds:serve.drained"):
                pass
        return finished

    def _round(self):
        """The round proper, inside ``step()``'s ``ds:serve.round`` span:
        (requests finished, the round's record). Each phase is one
        ``ds:serve.<phase>`` span (telemetry.tracing.span: on the
        profiler's clock in any session), in the order schedule ->
        housekeeping -> prefill_dispatch -> decode_dispatch -> fetch ->
        commit, and its host milliseconds are in the record
        (``schedule_ms`` ... ``commit_ms``; ``step()`` adds ``round_ms``,
        ``tokens``, ``gc_ms``, the collections inside the round, and
        ``build_ms``, the programs traced, lowered, compiled or loaded
        inside it on any thread — the build listener's running total diffed
        as the collector's is; 0.0 in every round of a sound window: the
        program's own "not a compile") beside what the round was:
        ``index``; ``t_s``, seconds into the stats
        window at its start; ``running_before``, the requests running when
        it began; ``prefills`` / ``prefill_programs`` / ``prefill_tokens``,
        the prompts or chunks dispatched in it, the programs that took them
        (fewer where whole prompts shared a row: ``_pack_prefills``) and
        their padded tokens; ``shape``, the ``(slots,
        columns)`` of the decode round it dispatched, or None;
        ``empty_before_ms``, how long the engine had held nothing when it
        began; and ``ahead_covered``, the probe (``_dispatch_round``):
        True if the chip still had work when this round's first step was
        issued, False if its queue had run dry and it waited for the host,
        None if nothing was in flight from the call before. The loop looks
        ahead: the fetch takes the LAST steps of the
        plain decode round the call before dispatched (``_inflight``), this
        call's first tokens and the FIRST steps of the round this call
        dispatched, and leaves that round's last ``_ahead_steps`` on the
        device's queue — the chip runs them while the host commits,
        schedules, builds tables and dispatches the next round, so between
        a round's last step and the next round's first it has only this
        call's prefills to run and nothing to wait for. What keeps that
        sound:

        * commit works from the dispatched round's record, not from
          ``scheduler.running`` (a slot may have changed hands);
        * a finish by LENGTH that the steps in flight bring is counted
          ahead (``RequestScheduler.schedule``: the request is ``ending``,
          its slot goes to an admission of this very call); a finish only
          the tokens show (an eos in those steps) is found one round late,
          and the quantum that slot ran meanwhile is dropped and counted
          (``dropped_slot_rounds``);
        * first tokens of this call's prefills ride this call's fetch;
        * ``Request.cached_rows`` stays rows whose tokens the host holds;
          tables and growth add ``inflight_rows``;
        * a speculation round needs the last tokens on the host and leaves
          nothing behind (dispatch, then fetch all of it, in one call); a
          recovery discards the steps in flight with the round it was
          dispatching."""
        self._round_tokens = 0
        now = time.perf_counter()
        if not self.scheduler.done:
            self._occupied(now)      # a request that came by another door
        ph = {"index": self._rounds,
              "t_s": (now - self._stats_t0
                      if self._stats_t0 is not None else 0.0),
              "running_before": len(self.scheduler.running),
              "prefills": 0, "prefill_programs": 0, "prefill_tokens": 0,
              "shape": None,
              "ahead_covered": None,
              "empty_before_ms": self._empty_before_s * 1e3,
              "schedule_ms": 0.0, "housekeeping_ms": 0.0, "prefill_ms": 0.0,
              "decode_ms": 0.0, "fetch_ms": 0.0, "commit_ms": 0.0}
        self._empty_before_s = 0.0
        info = rb_faults.serving_round_seam()
        keep = info.get("squeeze")
        if keep is not None:
            # pool_exhaust storm: hide all but `keep` free blocks for this
            # round — the scheduler's queue/preempt paths run under real
            # exhaustion, then the reserve lifts
            self.allocator.set_reserve(
                max(0, self.allocator.free_blocks - int(keep)))
        try:
            with span("ds:serve.schedule") as sp:
                decisions = self.scheduler.schedule(
                    token_budget=self.config.prefill_token_budget)
            ph["schedule_ms"] = sp.seconds * 1e3
            if self._tracer is not None:
                now = time.perf_counter()
                for req in decisions["preempted"]:
                    self._tracer.instant(req.rid, "preempted",
                                         preemptions=req.preemptions)
                    req._trace_wait_t0 = now    # queue wait restarts
                for req in decisions["admitted"]:
                    # begin() is idempotent; restored/migrated requests
                    # that never passed add_request get their id here
                    self._tracer.begin(req.rid)
                    w0 = getattr(req, "_trace_wait_t0", req.submit_t)
                    # a first admission's wait ends where admit_t says
                    w1 = now if req.preemptions else req.admit_t
                    self._tracer.add_span(
                        req.rid, "queue_wait", self._tracer.epoch(w0),
                        self._tracer.epoch(w1),
                        preemptions=req.preemptions)
            with span("ds:serve.housekeeping") as sp:
                if self._lora:
                    # adapter pins track the running set: scheduler-
                    # preempted victims drop theirs first (their slots
                    # become LRU candidates), then each admission pins — if
                    # EVERY slot is held by another in-flight adapter the
                    # admission bounces back to the queue head, exactly the
                    # KV-pool-exhaustion discipline applied to the adapter
                    # pool
                    for req in decisions["preempted"] + decisions["ended"]:
                        self._release_adapter(req)
                    for req in decisions["admitted"]:
                        if not self._acquire_adapter(req):
                            self.scheduler.preempt(req)
                            self._drop_kv_payload(req)
                            rb_events.emit("adapter_slots_exhausted",
                                           rid=req.rid,
                                           adapter=req.adapter_id)
                for req in decisions["preempted"]:
                    # an eviction consumes an unscattered import payload:
                    # the re-admission recomputes (scheduler.preempt zeroed
                    # kv_rows) — stale bytes never outlive their blocks
                    self._drop_kv_payload(req)
                for req in decisions["admitted"]:
                    if req.cow_src is not None and req.state == "running":
                        # the copy-on-write fork runs BEFORE any of the
                        # request's own dispatches can write the boundary
                        # block
                        self._dispatch_fork(req)
                    if req.state == "running" and \
                            getattr(req, "_kv_payload", None) is not None:
                        # imported KV bytes scatter into the admission's
                        # fresh blocks BEFORE the tail prefill span below
                        # reads them
                        with self._rspan(req.rid, "kv_import",
                                         rows=int(req.kv_rows)):
                            self._dispatch_kv_import(req)
            ph["housekeeping_ms"] = sp.seconds * 1e3
            newest = None    # the newest array on the device's queue
            with span("ds:serve.prefill_dispatch") as sp:
                whole = []
                for req, start, n in decisions["prefill"]:
                    if req.state != "running":
                        continue     # bounced by the adapter-slot pin above
                    ph["prefills"] += 1
                    if start == 0 and n == len(req.context) \
                            and not self._lora:
                        # whole prompt in one go: the PR-9 program (and its
                        # warm compiles), below — chunking/prefix hits take
                        # the span. LoRA-armed engines route ALL prefills
                        # through the span program: it carries the adapter
                        # delta, and one program family keeps the compile
                        # count flat
                        whole.append(req)
                        continue
                    with self._rspan(req.rid, "prefill_chunk",
                                     start=int(start), tokens=int(n)):
                        ph["prefill_tokens"] += self._dispatch_chunk(
                            req, start, n)
                    ph["prefill_programs"] += 1
                    if getattr(req, "_first_dev", None) is not None:
                        newest = req._first_dev[0]
                # the round's whole prompts share prefill programs where
                # the programs are there: the weights are read once a
                # program, not once a prompt
                for P, reqs in self._pack_prefills(whole):
                    with contextlib.ExitStack() as spans:
                        for req in reqs:
                            spans.enter_context(self._rspan(
                                req.rid, "prefill", tokens=len(req.context),
                                reprefill=req.preemptions > 0,
                                packed=len(reqs)))
                        ph["prefill_tokens"] += self._dispatch_prefill(reqs,
                                                                       P)
                    ph["prefill_programs"] += 1
                    newest = reqs[-1]._first_dev[0]
            ph["prefill_ms"] = sp.seconds * 1e3
            prior = self._inflight
            if prior is None and not self.scheduler.running:
                return [], ph
            if prior is None:
                newest = None    # the chip had nothing when the call began
            elif newest is None:
                newest = prior.tail[0]

            # a prefill-role engine NEVER runs decode quanta: requests sit
            # prefill_done until the router hands them (with their KV
            # bytes) to the decode tier. Their FIRST token still commits
            # through the pending-firsts fetch below, so TTFT is measured
            # where the prefill ran.
            decode = self.config.role != "prefill" and any(
                r.prefill_done for r in self.scheduler.running)
            # a verify step's proposals are drafted from the tokens the
            # host holds: a speculation round is fetched whole by the call
            # that dispatches it and leaves nothing in flight, so a
            # speculating engine never finds anything in flight either
            spec = decode and self.config.spec_tokens > 0
            rec = None
            with span("ds:serve.decode_dispatch") as sp_dec:
                if decode:
                    rec = self._dispatch_round(spec, sp_dec.t0, newest)
                    ph["shape"], ph["ahead_covered"] = rec.shape, rec.covered
                    if not spec:
                        self._table_rounds[rec.shape] += 1
                        self._lat["kv_blocks_gathered"] += \
                            rec.shape[0] * rec.shape[1]
                        self._lat["kv_blocks_held"] += rec.held
                        self._lat["rounds_ahead"] += prior is not None
                        self._lat["ahead_covered_rounds"] += \
                            rec.covered is True
                        self._lat["ahead_dry_rounds"] += rec.covered is False
                # first tokens ride THIS call's fetch: a prefill dispatched
                # above sits on the device's queue behind the steps in
                # flight and ahead of the round just dispatched
                pending = [(req, req._first_dev)
                           for req in self.scheduler.running
                           if getattr(req, "_first_dev", None) is not None]
            ph["decode_ms"] = sp_dec.seconds * 1e3
        finally:
            if keep is not None:
                self.allocator.set_reserve(0)
        # what this call fetches and commits: the last steps of the round
        # the call before dispatched, which the chip ran while the host did
        # all of the above, and the first steps of the round just
        # dispatched — whose last steps stay on the device's queue
        self._inflight = rec if rec is not None and rec.tail else None
        finished = self._land(prior, rec, pending, ph)
        if decode:
            self._quantum_warm = True
        if self._inflight is not None and not self.scheduler.running:
            # everything it decodes for ended in this commit: nobody is
            # left to take the rest of its tokens
            if rec.head is None:
                self._lat["dropped_slot_rounds"] += len(rec.entries)
            self._inflight = None
        if self._tracer is not None:
            for req in finished:
                self._tracer.instant(req.rid, "finish",
                                     tokens=len(req.generated))
                self._tracer.end(req.rid)
        return finished, ph

    def _dispatch_round(self, spec: bool, t0: float,
                        newest=None) -> _DispatchedRound:
        """Dispatch (no sync) one decode round for the running requests
        that have their prompt in — the quantum's steps, or ONE verify step
        — and thread the pools and the token vector through it. Returns the
        round's record; its tokens are fetched by ``_land``, a plain
        round's in two parts (``_ahead_steps``). ``newest``: the newest
        array the engine had put on the device's queue before this round —
        the stacked tokens of the last steps in flight, or the first token
        of the call's last prefill — or None when nothing was in flight.
        It is asked ``is_ready()`` once the tables and keys are built, just
        before the first step is issued (no sync, no copy): not ready means
        the chip still had work when the host got there (``covered``)."""
        import jax.numpy as jnp
        (tables, seq_lens, active, aidx), shape, held = \
            self._tables_device(full=spec)
        # the plain step runs as the program of the round's shape, built
        # before any round; the verify step is built by its first call
        build = self._first_call(
            "spec_step", f"{shape[0]}x{self.config.spec_tokens + 1}",
            self._spec_step is not None or not spec)
        step_fn = self._get_spec_step() if spec \
            else self._get_quantum_step()[shape]
        tok_mat = None
        if spec:
            props = self._proposals_device()
            tok_mat = jnp.concatenate([self._tokens[:, None], props], axis=1)
        # keys precomputed so the watchdogged closure touches NO engine
        # state: an abandoned (hung) round thread finishing late can only
        # drop its local result, never clobber recovered state
        keys = [self._next_key() for _ in range(self.config.decode_quantum)]
        pools, tokens = self.pools, self._tokens
        apool = self.adapter_pool if self._lora else None
        params, mesh = self.engine.params, self.engine.mesh
        epoch = self._epoch
        n_head = len(keys) - _ahead_steps(len(keys))

        def part(outs):
            # stacked HERE, behind the part's last step and ahead of the
            # next, so that fetching it waits for nothing dispatched later
            return (jnp.stack([o[0] for o in outs]),
                    [o[1] for o in outs]) if outs else None

        def dispatch():
            # the decode_dispatch fault seam lives INSIDE the guard: a hang
            # here is exactly what the watchdog must time out
            rb_faults.dispatch_seam()
            if self._epoch != epoch:
                return None  # abandoned by a recovery: bail before
            p, t, lens = pools, tokens, seq_lens  # touching the device
            outs, head = [], None
            with mesh:
                if spec:
                    # ONE verify step per round: pending + K proposals
                    # scored in a single span pass
                    with build:
                        p, nxt, acc, t, lens = step_fn(
                            params, p, tok_mat, tables, lens, active, keys[0],
                            apool, aidx)
                    return p, t, (nxt, acc), None
                for k in keys:
                    if self._epoch != epoch:
                        return None
                    # t: (tokens, the step's counters)
                    p, t, lens = step_fn(params, p, t, tables, lens, active,
                                         k, apool, aidx)
                    outs.append(t)
                    t = t[0]
                    if head is None and len(outs) == n_head:
                        head, outs = part(outs), []
                return p, t, head, part(outs)

        covered = None if newest is None else not newest.is_ready()
        dev = self._with_watchdog(dispatch, armed=self._quantum_warm)
        if dev is None:     # only reachable through a stale epoch
            raise DecodeDispatchHang("round abandoned by recovery")
        self.pools, self._tokens, head, tail = dev
        entries = [(req, req.slot, req.preemptions)
                   for req in self.scheduler.running if req.prefill_done]
        if not spec:
            for req, _, _ in entries:
                req.inflight_rows += len(keys)
        return _DispatchedRound(entries, shape, held, head, tail, spec, t0,
                                covered)

    def _land(self, prior: Optional[_DispatchedRound],
              rec: Optional[_DispatchedRound], pending: list,
              ph: Dict[str, float]) -> List[Request]:
        """The ONE sync of a call and its commit: the last steps of the
        round in flight (``prior.tail``), the first token of every prefill
        / last chunk dispatched since (``pending``) and the first steps of
        the round just dispatched (``rec.head``; a verify step: all of it)
        ride a single ``device_get`` (under its own watchdog: a device that
        never answers hangs HERE), with the counters of each decode step
        and of each pending prefill. It returns when the device has run
        those — ``rec.tail`` is still queued behind them. Writes its two
        phases into ``ph``; returns the requests that finished."""
        import jax
        # dispatch done / fetch begins: the split the doctor uses to tell
        # dispatch-bound from fetch-bound
        with span("ds:serve.fetch") as sp:
            firsts, tail, head = self._with_watchdog(
                lambda: jax.device_get((
                    [f for _, f in pending],
                    prior.tail if prior is not None else None,
                    rec.head if rec is not None else None)),
                armed=self._quantum_warm)
        ph["fetch_ms"] = sp.seconds * 1e3
        with span("ds:serve.commit") as sp_c:
            counters = [c for r, part in ((prior, tail), (rec, head))
                        if part is not None and not r.spec for c in part[1]]
            firsts = self._note_counters((firsts, counters))
            finished = self._commit(prior, tail, pending, firsts, rec, head)
        ph["commit_ms"] = sp_c.seconds * 1e3
        return finished

    def _note_tokens(self, req: Request, m: int, now: float) -> None:
        """Inter-token-latency bookkeeping: a commit burst of ``m`` tokens
        arriving ``gap`` after the request's previous tokens records m
        samples of gap/m (the per-token delivery latency a streaming
        client averages over the burst). The first token is TTFT's, not
        ITL's — it only starts the clock."""
        if m <= 0:
            return
        self._round_tokens += m        # phase ring's per-token denominator
        if req.last_token_t is not None:
            gap_ms = (now - req.last_token_t) * 1e3
            self._itl_ms.extend([gap_ms / m] * m)
            req.max_gap_ms = max(req.max_gap_ms or 0.0, gap_ms)
        req.last_token_t = now

    def _note_counters(self, fetched) -> list:
        """One round's program counters, from arrays the round's one fetch
        brought. ``fetched``: ([(first token, counters) of each pending
        prefill], [counters of each decode step]); counters are (expert
        load, exit distribution). A load is [L, E + 1] int32 — assignments
        kept per expert and layer, then the assignments asked for
        (``sharded_moe._LoadTap``) — over the ACTIVE slots of a decode step
        or the real tokens of a whole-prompt prefill, or None (a model
        without experts, a chunked prefill). An exit distribution is
        float32 [passes + 1] — per pass the probability of leaving there,
        summed over the active slots of a decode step or taken at the
        position a whole-prompt prefill samples from, then how many were
        summed (``looped._ExitTap``) — or None (a model that is not looped).
        Speculation verify spans are not counted. Returns the first tokens
        alone."""
        firsts, step_counters = fetched
        prefill_counters = [c for _, c in firsts]
        firsts = [f for f, _ in firsts]
        exits = [c[1] for c in step_counters + prefill_counters
                 if c[1] is not None]
        if exits:
            self._exit += np.sum(np.asarray(exits, np.float64), axis=0)
        step_loads = [c[0] for c in step_counters if c[0] is not None]
        prefill_loads = [c[0] for c in prefill_counters if c[0] is not None]
        if not (step_loads or prefill_loads):
            return firsts
        m = self._moe
        loads = np.asarray(step_loads + prefill_loads, np.int64)  # [n, L, E+1]
        kept = loads[..., :-1]
        m["kept"] += int(kept.sum())
        m["asked"] += int(loads[..., -1].sum())
        per_layer = kept.sum(axis=0)                               # [L, E]
        busy = per_layer[per_layer.sum(axis=1) > 0]
        if busy.size:
            m["max_over_mean"] += float(np.mean(busy.max(axis=1)
                                                / busy.mean(axis=1)))
            m["rounds"] += 1
        touched = (kept > 0).sum(axis=2).mean(axis=1)              # [n]
        live = loads[:len(step_loads), :, -1].any(axis=1)  # steps with an active slot
        m["touched"] += float(touched[:len(step_loads)][live].sum())
        m["steps"] += int(live.sum())
        m["prefill_touched"] += float(touched[len(step_loads):].sum())
        m["prefills"] += len(prefill_loads)
        return firsts

    def _commit(self, prior, tail, pending, firsts, rec,
                head) -> List[Request]:
        """Hand the fetched tokens to their requests, oldest first: the
        last steps (``tail``) of the round in flight (``prior``), each
        pending first token, the first steps (``head``) of the round just
        dispatched (``rec``) — each from its round's RECORD, whose slots
        may since have gone to other requests. A plain part gives every
        live entry its steps' tokens up to the request's budget or eos (the
        rest, like the rows behind them, are overshoot); a verify round
        (argmaxes, accepted lengths) its accepted proposal prefix plus the
        model's correction / bonus token, 1..K+1 tokens — the emitted
        stream is the target model's own argmaxes, so output is
        token-identical to the unspeculated run; the cursor advanced by
        accepted + 1 on device, rejected rows sit beyond it, stale until
        overwritten. An entry whose request is no longer the one dispatched
        is dropped, and counted where it had ended before anything of the
        round was committed for it. Returns the requests that finished."""
        now = time.perf_counter()
        eos = self.config.eos_token_id
        got: Dict[int, list] = {}         # rid -> [request, tokens delivered]

        def deliver(req, tokens):
            n = got.setdefault(req.rid, [req, 0])
            for tok in tokens:
                if self._done(req):
                    break
                self._append(req, tok, eos)
                n[1] += 1

        def part(rnd, fetched, first):
            # still owed tokens, and not ended by an older part just above
            live = [(req, slot) for req, slot in rnd.live()
                    if not self._done(req)]
            if first:
                self._lat["dropped_slot_rounds"] += (len(rnd.entries)
                                                     - len(live))
            for req, slot in live:
                if rnd.spec:
                    nxt, acc = fetched
                    row = nxt[slot, :int(acc[slot]) + 1]
                    self._lat["spec_steps"] += 1
                    self._lat["spec_proposed"] += self.config.spec_tokens
                    self._lat["spec_accepted"] += row.size - 1
                else:
                    row = fetched[0][:, slot]
                    req.inflight_rows -= row.size
                req.cached_rows += row.size
                deliver(req, row.tolist())
                if self._tracer is not None:
                    self._tracer.add_span(
                        req.rid, "decode_quantum",
                        self._tracer.epoch(rnd.t0), self._tracer.epoch(now),
                        steps=1 if rnd.spec else row.size)

        if tail is not None:
            part(prior, tail, first=prior.head is None)
        for (req, _), f in zip(pending, firsts):
            # prefill's pending token: its KV row is written by the next
            # step that decodes for it, so it is part of the sequence now
            deliver(req, [int(np.ravel(f)[0])])
            req._first_dev = None
            if req.first_token_t is None:
                req.first_token_t = now
        if head is not None:
            part(rec, head, first=True)
        finished: List[Request] = []
        for req, n in got.values():
            self._note_tokens(req, n, now)
            if self._done(req):
                self.scheduler.finish(req)
                self._release_adapter(req)
                self._finished.append(req)
                finished.append(req)
        return finished

    # ---- reliability: watchdog / recovery / degradation --------------

    def _with_watchdog(self, fn, armed: bool = True):
        timeout = self.config.dispatch_timeout_s
        if not timeout or not armed:
            return fn()
        box: Dict[str, Any] = {}

        def run():
            try:
                box["result"] = fn()
            except BaseException as e:  # noqa: BLE001 — relayed below
                box["error"] = e

        t = threading.Thread(target=run, daemon=True, name="serving-round")
        self._round_thread = t
        t.start()
        t.join(timeout)
        if t.is_alive():
            # the zombie thread holds only locals (the caller commits
            # pools/tokens on success), so its late result is dropped
            raise DecodeDispatchHang(
                f"decode round exceeded dispatch_timeout_s={timeout}")
        if "error" in box:
            raise box["error"]
        return box["result"]

    def _recover(self, reason: str) -> None:
        """Fault recovery: every running request preempts back to the
        queue (host cursors — prompt + generated — are authoritative), the
        device pool rebuilds fresh, and normal re-admission re-prefills.
        Bit-exact by the same recompute math preemption resume uses."""
        import jax.numpy as jnp
        t0 = time.perf_counter()
        self._epoch += 1          # abandoned round threads see this and bail
        # the round in flight dies with the pool it wrote into: preempt_all
        # takes every request back to what the host holds
        self._inflight = None
        self.allocator.set_reserve(0)
        n = self.scheduler.preempt_all()
        for req in self._requests.values():
            req._first_dev = None
            req.adapter_slot = None   # pool rebuilt below; re-pin on resume
            if req.cow_src is not None:     # un-forked admission caught
                self.scheduler._release_cow(req)   # mid-round by the fault
            if getattr(req, "_kv_payload", None) is not None \
                    and req.kv_rows == 0:
                # preempt_all zeroed kv_rows mid-round before the import
                # could scatter: the payload is orphaned — drop it, the
                # re-admission recomputes (host bytes of STILL-waiting
                # imports keep their kv_rows and survive the pool rebuild)
                self._drop_kv_payload(req)
        if self._lora:
            self.adapter_slots.reset()
            with self.engine.mesh:
                self.adapter_pool = self._init_apool_fn()
        if self._prefix_cache is not None:
            # cached rows die with the pool being rebuilt below; drop the
            # cache's references so the fresh pool starts fully free
            self._prefix_cache.clear()
        self._tokens = jnp.zeros((self.config.max_seqs,), jnp.int32)
        with span("ds:setup.pools") as sp, self.engine.mesh:
            self.pools = self._init_pools_fn()
        self._pools_s += sp.seconds
        ms = (time.perf_counter() - t0) * 1e3
        self._counters["recoveries"] += 1
        self._counters["recovery_ms"] += ms
        rb_events.emit("serving_recovered", reason=reason, preempted=n,
                       ms=round(ms, 2))

    def _degrade_backend(self) -> None:
        """Degradation ladder pallas -> XLA gather: a kernel failure
        mid-serve swaps the quantum step to the gather backend (same math
        on a gathered view — the parity the serving tests pin). Already at
        the floor: nothing to swap; the recovery retry covers it."""
        old = self.decode_backend
        if old == "xla":
            return
        self.decode_backend = "xla"
        self._quantum_step = None      # recompile with the gather backend
        self._table_rounds = self._step_shapes()    # ... at its own shapes
        self._quantum_warm = False     # and re-warm before re-arming
        self._counters["degraded"] += 1
        self.backend_bench = dict(self.backend_bench, backend="xla",
                                  degraded_from=old)
        rb_events.emit("backend_degraded", **{"from": old, "to": "xla",
                                              "reason": "backend_fault"})

    def _enforce_deadlines(self) -> None:
        """Round-boundary deadline sweep: TTFT deadlines apply until the
        first token reached the host, total deadlines until completion.
        A missed request is CANCELLED — a running one returns its slot and
        blocks to the pool mid-decode — and its partial output stays
        readable on ``cancelled``."""
        now = time.perf_counter()
        for req in (list(self.scheduler.waiting)
                    + list(self.scheduler.running)):
            elapsed_ms = (now - req.submit_t) * 1e3
            if req.deadline_ms is not None and elapsed_ms > req.deadline_ms:
                kind, budget = "total", req.deadline_ms
            elif (req.ttft_deadline_ms is not None
                  and req.first_token_t is None
                  and elapsed_ms > req.ttft_deadline_ms):
                kind, budget = "ttft", req.ttft_deadline_ms
            else:
                continue
            self.scheduler.cancel(req, reason=f"{kind}_deadline")
            self._release_adapter(req)   # no-op for never-pinned waiters
            self._drop_kv_payload(req, count=False)   # died, not fell back
            if self._tracer is not None:
                self._tracer.instant(req.rid, "cancelled",
                                     reason=f"{kind}_deadline")
                self._tracer.end(req.rid)
            self._cancelled.append(req)
            self._counters["deadline_misses"] += 1
            rb_events.emit("deadline_miss", rid=req.rid, kind=kind,
                           budget_ms=budget,
                           elapsed_ms=round(elapsed_ms, 1),
                           generated=len(req.generated))

    def _drain_events(self) -> None:
        """Round-boundary drain of pending robustness events into the
        configured JSONL sink. Without a sink the queue is left pending
        (a co-resident training engine's monitor may own the drain)."""
        if self._jsonl is None or not self._jsonl.enabled:
            return
        recs = rb_events.drain()
        if recs:
            self._jsonl.write_records(recs)

    # ---- reliability: drain & resume ---------------------------------

    def attach_preemption(self, handler, save_dir: Optional[str]) -> None:
        """SIGTERM contract (PR-6 PreemptionHandler): the handler latches
        the signal; the next step() boundary drains the engine into
        ``save_dir`` and raises ``Preempted``. A restarted engine picks the
        work back up with ``resume(save_dir)``."""
        self._preemption = handler
        self._drain_dir = save_dir

    @property
    def cancelled(self) -> List[Request]:
        """Requests shed by deadline enforcement (partial outputs kept)."""
        return list(self._cancelled)

    # ---- disaggregated prefill/decode handoff (ISSUE 19) -------------

    def _kv_geometry(self) -> Dict[str, Any]:
        """The pool geometry a KV payload must match to be scattered in:
        logical shapes (mesh-independent — a head-sharded engine's export
        assembles the full head dim, so tp2->tp2 and tp1->tp1 both ship
        the same bytes; tp CROSSING is refused by _check_geometry for the
        continuation-determinism reason, not here)."""
        if "k" not in self.pools:
            raise ResumeIncompatible(
                "the KV handoff geometry describes per-head K/V planes; this "
                f"engine's block pool holds {sorted(self.pools)} (latent "
                "rows), which no payload carries yet — the re-prefill "
                "migration path recomputes them")
        k = self.pools["k"]              # [planes, NB, bs, nkv, hd]
        mcfg = self.model.config
        # the planes a block carries, and what they are planes OF: a looped
        # engine and an unlooped one of equal plane counts hold different
        # things at the same index
        return {"kv_planes": int(k.shape[0]),
                "num_layers": int(getattr(mcfg, "num_layers", k.shape[0])),
                "ut_steps": int(getattr(mcfg, "ut_steps", 1)),
                "kv_heads": int(k.shape[3]),
                "head_dim": int(k.shape[4]),
                "block_size": int(self.config.block_size),
                "kv_bits": int(getattr(self.model.config,
                                       "kv_cache_bits", 0) or 0),
                "dtype": str(k.dtype)}

    def export_kv(self, request_ids: List[int]
                  ) -> Dict[int, Dict[str, Any]]:
        """Serialize requests' pool blocks into dense host payloads — the
        KV-byte half of a prefill->decode handoff. One gather dispatch +
        one device_get per request (the `_copy_block_fn` idiom widened to
        a padded block-id vector), NOT a prompt-length recompute. Each
        payload carries its geometry (typed refusal at import) and a crc32
        over the buffers (a torn payload must fall back to re-prefill,
        never decode garbage). int8 pools ship payload + scales — the
        payload keys mirror the pool tree. Requests without pool rows
        (still waiting / nothing cached) are skipped: the caller's
        fallback is the ordinary re-prefill migration.

        The bytes stage on the host until ``release_requests`` hands the
        request away (or the payload is consumed) — ``stats()`` prices
        them in ``pool_bytes``/``kv_staging_bytes``."""
        import jax
        import jax.numpy as jnp
        self._by_blocks_alone("K/V export")
        geometry = self._kv_geometry()      # refuses a pool it cannot describe
        bs = self.config.block_size
        out: Dict[int, Dict[str, Any]] = {}
        for rid in request_ids:
            req = self._requests.get(rid)
            if req is None or req.state != "running" \
                    or req.cached_rows <= 0 or not req.block_ids:
                continue
            rows = int(req.cached_rows)
            n = blocks_for(rows, bs)
            ids = np.zeros((self.MB,), np.int32)   # pads -> trash block 0
            ids[:n] = req.block_ids[:n]
            with self.engine.mesh:
                gathered = self._gather_blocks_fn(self.pools,
                                                  jnp.asarray(ids))
            host = jax.device_get(gathered)
            data = {name: np.ascontiguousarray(a[:, :n])
                    for name, a in host.items()}
            payload = {"schema": KV_PAYLOAD_SCHEMA, "rows": rows, "blocks": n,
                       "geometry": geometry,
                       "data": data, "crc": kv_payload_crc(data)}
            nbytes = kv_payload_nbytes(data)
            self._kv_staging[rid] = nbytes
            self._counters["handoffs"] += 1
            self._counters["handoff_bytes"] += nbytes
            if self._tracer is not None:
                self._tracer.instant(rid, "kv_export", bytes=nbytes,
                                     rows=rows)
            out[rid] = payload
        return out

    def _validate_kv_payload(self, req: Request, payload: Dict[str, Any],
                             source: Optional[str] = None) -> None:
        """Typed refusal (``ResumeIncompatible``) for any payload this
        engine cannot scatter bit-faithfully: geometry/bits/dtype
        mismatch, wrong pool tree, rows outside the pending-token
        protocol, or a checksum failure (torn payload). The caller falls
        back to the ordinary re-prefill migration — old drain records
        (no kv) never reach here."""
        src = f" (exported by {source})" if source else ""

        def refuse(why: str) -> None:
            self._counters["handoff_fallbacks"] += 1
            raise ResumeIncompatible(
                f"kv payload for request {req.rid}{src}: {why} — "
                "falling back to the re-prefill migration path keeps the "
                "continuation correct (just slower)")

        geom, local = payload.get("geometry") or {}, self._kv_geometry()
        for key, want in local.items():
            got = geom.get(key)
            if got is not None and got != want:
                refuse(f"pool geometry mismatch on {key!r} "
                       f"(payload {got!r}, this engine {want!r})")
        if set(payload.get("data") or {}) != set(self.pools):
            refuse(f"payload tree {sorted(payload.get('data') or {})} != "
                   f"pool tree {sorted(self.pools)} (kv-bits mismatch "
                   "ships/omits the scale leaves)")
        rows, n = int(payload.get("rows", 0)), int(payload.get("blocks", 0))
        ctx = len(req.context)
        if not 0 < rows < ctx:
            # pending-token protocol: the row at cached_rows is computed
            # by the receiver's tail span, so a full-context payload is
            # as malformed as an empty one
            refuse(f"rows={rows} outside (0, {ctx}) for a context of "
                   f"{ctx} tokens")
        if n != blocks_for(rows, self.config.block_size) or n > self.MB:
            refuse(f"blocks={n} does not cover rows={rows} at block_size="
                   f"{self.config.block_size} (table width {self.MB})")
        k = payload["data"].get("k")
        want_shape = (local["kv_planes"], n, local["kv_heads"],
                      local["block_size"], local["head_dim"])
        if getattr(k, "shape", None) != want_shape:
            refuse(f"k payload shape {getattr(k, 'shape', None)} != "
                   f"{want_shape}")
        if self.model.decode_span_paged is None:
            refuse("this engine has no span protocol (decode_span_paged) "
                   "to run the post-import tail span")
        if kv_payload_crc(payload["data"]) != payload.get("crc"):
            refuse("checksum failure (torn/corrupt payload)")

    def import_kv(self, request_id: int,
                  payload: Dict[str, Any]) -> None:
        """Attach an exported KV payload to a WAITING request on this
        engine (the receive half of the handoff; ``accept_migration``'s
        ``kv=`` fast path calls this per record). Validation is typed —
        ``ResumeIncompatible`` on geometry/bits/checksum mismatch, and
        the request is left untouched for the re-prefill fallback. The
        actual scatter happens at admission: blocks come from the normal
        ``BlockAllocator`` path, the payload scatters into them before
        the 1-tail-span prefill runs, and the continuation is
        token-identical to the colocated engine."""
        self._by_blocks_alone("K/V import")
        req = self._requests.get(request_id)
        if req is None or req.state != "waiting":
            raise ResumeIncompatible(
                f"import_kv: request {request_id} is not waiting on this "
                "engine (accept_migration enqueues it; the kv= fast path "
                "does both in one call)")
        self._validate_kv_payload(req, payload)
        req._kv_payload = payload
        req.kv_rows = int(payload["rows"])
        self._kv_staging[request_id] = kv_payload_nbytes(payload["data"])

    def _dispatch_kv_import(self, req: Request) -> None:
        """Scatter an imported payload into the request's freshly-admitted
        blocks (dispatch, no sync — the round's single fetch stays the
        only host sync). Pads write into trash block 0, which is never
        read. Runs before the request's tail prefill span, which then
        computes only rows [kv_rows, ctx)."""
        import jax.numpy as jnp
        payload, req._kv_payload = req._kv_payload, None
        n = int(payload["blocks"])
        ids = np.zeros((self.MB,), np.int32)
        ids[:n] = req.block_ids[:n]
        data = {}
        for name, arr in payload["data"].items():
            buf = np.zeros((arr.shape[0], self.MB) + arr.shape[2:],
                           arr.dtype)
            buf[:, :n] = arr
            data[name] = buf
        with self.engine.mesh:
            self.pools = self._scatter_blocks_fn(self.pools,
                                                 jnp.asarray(ids), data)
        nbytes = self._kv_staging.pop(req.rid, 0)
        self._counters["handoffs"] += 1
        self._counters["handoff_bytes"] += nbytes

    def _drop_kv_payload(self, req: Request, count: bool = True) -> None:
        """Forget an unconsumed import payload (preemption / adapter
        bounce / recovery / cancel): the request falls back to plain
        re-prefill — stale bytes must never be scattered into blocks
        allocated by a LATER admission. ``count=False`` for exits that
        aren't fallbacks (cancel/release)."""
        if getattr(req, "_kv_payload", None) is None:
            return
        req._kv_payload = None
        req.kv_rows = 0
        self._kv_staging.pop(req.rid, None)
        if count:
            self._counters["handoff_fallbacks"] += 1

    def release_requests(self, request_ids: List[int]
                         ) -> List[Dict[str, Any]]:
        """Extract live requests for a handoff: returns drain-schema
        records (plus live-only ``submit_t``/``first_token_t`` stamps so
        TTFT, ITL and deadlines stay honest across the hop — in-process
        replicas share the clock) and removes the requests from this
        engine — blocks/slot back to the pool, prefix cache offered the
        KV first, nothing counted as cancelled. Call ``export_kv`` BEFORE
        this (the gather reads the pool rows this frees); export staging
        for these rids is consumed here."""
        recs: List[Dict[str, Any]] = []
        for rid in request_ids:
            req = self._requests.get(rid)
            if req is None or req.state not in ("running", "waiting"):
                continue
            if self._tracer is not None:
                self._tracer.instant(req.rid, "handoff_out")
            recs.append({
                "rid": req.rid,
                "prompt": np.asarray(req.prompt).tolist(),
                "generated": list(req.generated),
                "max_new_tokens": req.max_new_tokens,
                "preemptions": req.preemptions,
                "cached_rows": req.cached_rows,
                "block_ids": list(req.block_ids),
                "slot": req.slot,
                "state": req.state,
                "ttft_deadline_ms": req.ttft_deadline_ms,
                "deadline_ms": req.deadline_ms,
                "adapter_id": req.adapter_id,
                "submit_t": req.submit_t,
                "first_token_t": req.first_token_t,
                "last_token_t": req.last_token_t,
                "trace": (self._tracer.context(req.rid)
                          if self._tracer is not None else None),
            })
            self._drop_kv_payload(req, count=False)  # moving, not falling
            self._kv_staging.pop(req.rid, None)      # export consumed
            if req.state == "running":
                # tokens of a round in flight stay behind: the record and
                # the exported rows both stand at what the host holds
                self.scheduler.vacate(req)
            else:
                try:
                    self.scheduler.waiting.remove(req)
                except ValueError:
                    pass
            self._release_adapter(req)
            req._first_dev = None
            req.state = "migrated"
            del self._requests[req.rid]
            if self._tracer is not None:
                self._tracer.end(req.rid)
        return recs

    def drain(self, save_dir: Optional[str] = None,
              tag: str = "serving_drain",
              source: Optional[str] = None) -> Optional[str]:
        """Stop admission and checkpoint every unfinished request — block
        tables + host cursors + generated tokens — through the integrity
        chain (state payload, then manifest, then the COMMITTED marker
        LAST, so a torn drain reads as torn). Returns the tag dir (None
        when no save_dir: admission stops, nothing persists). ``source``
        names the draining replica in the state (the router namespaces
        each replica's drains by tag AND directory; the name also rides
        every ``request_migrated`` event a failover emits).

        Only the host cursors (prompt + generated + budget) drive
        ``resume`` (tokens of decode steps still in flight are not among
        them: those steps are dropped, not fetched) — the restarted engine
        rebuilds device state by re-prefilling. The block table / slot /
        cached_rows snapshot is
        recorded for post-mortems (which slot held what at the drain),
        not restored: a fresh pool has no use for the old physical ids.
        The drained engine's geometry (``max_model_len``, block size,
        table width) is recorded too, so a FOREIGN engine resuming this
        state can refuse a smaller pool loudly (``ResumeIncompatible``)
        instead of corrupting past its table width."""
        import json
        import os
        from deepspeed_tpu.robustness import integrity

        self._draining = True
        live = (sorted(self.scheduler.running,
                       key=lambda r: r.admission_seq or 0)
                + list(self.scheduler.waiting))
        if save_dir is None:
            rb_events.emit("serving_drained", requests=len(live), tag=None)
            self._drain_events()
            return None
        tag_dir = os.path.join(save_dir, tag)
        os.makedirs(tag_dir, exist_ok=True)
        integrity.invalidate(tag_dir)      # rewriting in place: torn-able
        if self._tracer is not None:
            # marked BEFORE the context snapshot below so the drain point
            # itself rides the migrated trace
            for req in live:
                self._tracer.instant(req.rid, "drained", tag=tag)
        state = {
            # v3 (ISSUE 18): per-request "trace" context (id + spans) so a
            # migrated request's trace stitches across replicas. Readers
            # ignore unknown fields — v2 consumers interop unchanged.
            "version": DRAIN_STATE_VERSION,
            "rng_counter": self._rng_counter,
            "source": source,
            "engine": {
                "max_model_len": self.max_model_len,
                "block_size": self.config.block_size,
                "table_width": self.MB,
                "max_seqs": self.config.max_seqs,
                # mesh topology (ISSUE 15): a resume/migration target must
                # match these degrees — see _check_geometry
                "tp": self.tp,
                "ep": self.ep,
            },
            "requests": [{
                "rid": req.rid,
                "prompt": np.asarray(req.prompt).tolist(),
                "generated": list(req.generated),
                "max_new_tokens": req.max_new_tokens,
                "preemptions": req.preemptions,
                "cached_rows": req.cached_rows,
                "block_ids": list(req.block_ids),
                "slot": req.slot,
                "state": req.state,
                "ttft_deadline_ms": req.ttft_deadline_ms,
                "deadline_ms": req.deadline_ms,
                "adapter_id": req.adapter_id,
                "trace": (self._tracer.context(req.rid)
                          if self._tracer is not None else None),
            } for req in live],
        }
        integrity.atomic_write(os.path.join(tag_dir, "state.json"),
                               json.dumps(state, indent=1),
                               what="serving drain state write")
        integrity.write_manifest(tag_dir)
        integrity.write_commit_marker(tag_dir)
        # a round in flight is not waited for (the device may be why the
        # engine drains) and nobody finishes outside step(): the state
        # holds its requests at the tokens the host has, whoever resumes
        # them recomputes the rest, and here they go back to the queue like
        # any preemption, so this engine agrees with what it wrote
        rec, self._inflight = self._inflight, None
        for req, _ in (rec.live() if rec is not None else ()):
            self.scheduler.preempt(req)
            self._release_adapter(req)
        rb_events.emit("serving_drained", requests=len(live), tag=tag,
                       path=tag_dir)
        self._drain_events()
        return tag_dir

    def accept_migration(self, recs: List[Dict[str, Any]],
                         rng_counter: Optional[int] = None,
                         source: Optional[str] = None,
                         geometry: Optional[Dict[str, Any]] = None,
                         kv: Optional[Dict[int, Dict[str, Any]]] = None
                         ) -> List[int]:
        """Restore drained request records (the ``state.json`` schema) onto
        THIS engine — the remote-drain handoff the router's failover uses
        to re-place a dead replica's in-flight work onto survivors. Each
        record re-validates against the LOCAL geometry before anything is
        enqueued (all-or-nothing: a failover must never half-land a batch):
        a request whose context + budget exceeds this engine's block-table
        reach raises the typed ``ResumeIncompatible`` — the caller tries
        the next survivor. Admission watermarks are bypassed
        (``scheduler.restore``): this work was already admitted once;
        shedding it on migration would drop accepted requests.

        ``geometry`` is the drained engine's envelope (the state.json
        ``engine`` dict): when it records a mesh topology (tp/ep), a
        mismatched local geometry refuses the whole batch with the typed
        ``ResumeIncompatible`` — the failover tries the next survivor
        (see _check_geometry for why a continuation must not cross mesh
        geometries).

        ``kv`` (ISSUE 19) is the handoff fast path: ``{rid: payload}``
        from the source's ``export_kv``. Each payload validates against
        the LOCAL pool geometry/bits and its checksum BEFORE anything is
        enqueued — a mismatch or torn payload raises the typed
        ``ResumeIncompatible`` and the caller retries WITHOUT ``kv``
        (the re-prefill path old drain records already take). Accepted
        payloads make the handoff cost one scatter + a tail span instead
        of a prompt-length recompute, token-identically."""
        self._check_geometry(geometry, source)
        kv = kv or {}
        reqs: List[Any] = []   # (Request, rec, payload or None)
        for rec in recs:
            aid = int(rec.get("adapter_id", 0))
            if aid and (not self._lora or aid not in self.adapter_store):
                src = f" (drained by {source})" if source else ""
                raise ResumeIncompatible(
                    f"migrated request {rec.get('rid')}{src} routes to "
                    f"LoRA adapter {aid}, which this engine "
                    + ("has LoRA serving disabled for"
                       if not self._lora else "has no registration for")
                    + " — register the adapter here first, or place the "
                    "request on a replica that serves it")
            req = Request(rid=int(rec["rid"]),
                          prompt=np.asarray(rec["prompt"], np.int32),
                          max_new_tokens=int(rec["max_new_tokens"]),
                          generated=[int(x) for x in rec.get("generated",
                                                             [])],
                          preemptions=int(rec.get("preemptions", 0)),
                          ttft_deadline_ms=rec.get("ttft_deadline_ms"),
                          deadline_ms=rec.get("deadline_ms"),
                          adapter_id=aid)
            # the add_request context-cap validation, re-applied per
            # record: restoring into an engine with a SMALLER
            # max_model_len must refuse loudly — past the block-table
            # width the growth clamp would overwrite the last block and
            # silently corrupt the continuation
            if req.prompt.size + req.max_new_tokens > self.max_model_len:
                src = f" (drained by {source})" if source else ""
                raise ResumeIncompatible(
                    f"migrated request {req.rid}{src}: prompt "
                    f"({req.prompt.size}) + max_new_tokens "
                    f"({req.max_new_tokens}) exceeds this engine's "
                    f"max_model_len {self.max_model_len} "
                    f"(block-table width {self.MB} x "
                    f"{self.config.block_size}-token blocks) — place it "
                    "on an engine at least as large as the drained one")
            payload = kv.get(req.rid)
            if payload is not None:
                self._by_blocks_alone("K/V import")
                # all-or-nothing with the rest of the batch: a bad payload
                # refuses HERE, before anything is enqueued
                self._validate_kv_payload(req, payload, source)
            reqs.append((req, rec, payload))
        if rng_counter is not None:
            self._rng_counter = max(self._rng_counter, int(rng_counter))
        rids: List[int] = []
        for req, rec, payload in reqs:
            self.scheduler.restore(req)
            self._requests[req.rid] = req
            if payload is not None:
                req._kv_payload = payload
                req.kv_rows = int(payload["rows"])
                self._kv_staging[req.rid] = \
                    kv_payload_nbytes(payload["data"])
            if self._tracer is not None:
                # stitch: inherit the drained trace id + spans (v3 record)
                # so the merged export shows ONE trace across replicas
                self._tracer.adopt(req.rid, rec.get("trace"))
                self._tracer.instant(req.rid, "migrated_in",
                                     source=source or "",
                                     kv=payload is not None)
            req._trace_wait_t0 = req.submit_t    # restore() re-stamps it
            # live-handoff stamps (release_requests records only — drain
            # records never carry them): keep TTFT/ITL/deadlines honest
            # across the hop instead of restarting the clocks
            if rec.get("submit_t") is not None:
                req.submit_t = float(rec["submit_t"])
            if rec.get("first_token_t") is not None:
                req.first_token_t = float(rec["first_token_t"])
            if rec.get("last_token_t") is not None:
                req.last_token_t = float(rec["last_token_t"])
            rids.append(req.rid)
        if self._stats_t0 is None and rids:
            self._stats_t0 = time.perf_counter()
        return rids

    def resume(self, save_dir: str, tag: Optional[str] = None) -> List[int]:
        """Re-enqueue the requests a drained engine checkpointed: each
        resumes by re-prefilling prompt + generated, so its continuation
        is byte-identical to the uninterrupted run (the chaos soak pins
        this). ``tag=None`` resolves the newest tag that passes integrity
        validation — a torn drain is skipped, not loaded.

        Cross-replica: a whole-drain resume from a FOREIGN engine's
        snapshot re-validates the drained geometry against the local one
        — a smaller block-table width or ``max_model_len`` refuses with
        the typed ``ResumeIncompatible`` even if every individual request
        would fit (an operator restoring a replica wholesale wants the
        original envelope back, not a silent downgrade whose next long
        request corrupts). The router's per-request migration path
        (``accept_migration``) applies the per-request check instead."""
        state = load_drain_state(save_dir, tag)
        tag = state["tag"]
        eng = state.get("engine")
        if eng is not None:        # version-1 drains predate the geometry
            # compare capacity in TOKENS (table_width x block_size == the
            # drained max_model_len): raw widths are block-size-relative,
            # so a larger-capacity engine with bigger blocks must not be
            # falsely refused
            drained_cap = int(eng.get("max_model_len")
                              or (int(eng.get("table_width", 0))
                                  * int(eng.get("block_size", 0))))
            if drained_cap > self.max_model_len:
                src = state.get("source")
                raise ResumeIncompatible(
                    "drain tag "
                    f"'{tag}'{f' (replica {src})' if src else ''} came "
                    f"from an engine with max_model_len {drained_cap} "
                    f"(table width {eng.get('table_width')} x "
                    f"{eng.get('block_size')}-token blocks); this engine "
                    f"caps at max_model_len {self.max_model_len} (width "
                    f"{self.MB}) — resume into an engine at least as "
                    "large, or migrate per-request via accept_migration")
        rids = self.accept_migration(state["requests"],
                                     rng_counter=state.get("rng_counter"),
                                     source=state.get("source"),
                                     geometry=eng)
        rb_events.emit("serving_resumed", requests=len(rids), tag=tag)
        self._drain_events()
        return rids

    @staticmethod
    def _append(req: Request, token: int, eos) -> None:
        req.generated.append(token)
        if eos is not None and token == eos:
            req.eos_seen = True      # generated ends AT the eos token

    def _done(self, req: Request) -> bool:
        return req.remaining <= 0 or req.eos_seen

    def run(self, requests, max_new_tokens: int = 64,
            max_rounds: int = 100000,
            shed_ok: bool = False) -> Dict[int, np.ndarray]:
        """Submit-and-drain convenience: requests is a list of prompt-id
        arrays or (prompt, max_new) tuples. Returns {rid: output ids} for
        THIS call's COMPLETED requests only — deadline-cancelled ones keep
        their partial output on ``cancelled``, and watermark-shed
        submissions raise ``AdmissionRejected`` (``shed_ok=True`` drops
        them instead: they are already counted and evented). stats() still
        aggregates across the engine's lifetime — reset_stats() starts a
        fresh window."""
        rids = []
        for r in requests:
            aid = 0
            if isinstance(r, tuple):
                prompt, n = r[0], r[1]
                if len(r) > 2:     # (prompt, max_new, adapter_id)
                    aid = int(r[2])
            else:
                prompt, n = r, max_new_tokens
            try:
                rids.append(self.add_request(prompt, n, adapter_id=aid))
            except AdmissionRejected:
                if not shed_ok:
                    raise
        rounds = 0
        while not self.scheduler.done:
            self.step()
            rounds += 1
            if rounds > max_rounds:
                raise RuntimeError("serving run did not converge "
                                   f"({rounds} rounds)")
        mine = set(rids)
        return {r.rid: r.output for r in self._finished if r.rid in mine}

    # ---- stats -------------------------------------------------------

    def reset_stats(self) -> None:
        """Start a fresh measurement window: completed-request records,
        cancellations, reliability counters and the throughput clock reset
        (pool/scheduler state untouched — the bench warms its compiles,
        resets, then serves the timed load). ``stats()["setup"]`` is left
        alone: it is the ENGINE's life — what it built and what that cost —
        not a window's, and the first reset is only marked in it
        (``built_after_first_reset``: what a warm-up did not build)."""
        if self._built_at_reset is None:
            self._built_at_reset = self._setup()["programs_built"]
        self._finished = []
        self._cancelled = []
        self._stats_t0 = None
        self._counters = {"shed": 0, "deadline_misses": 0, "degraded": 0,
                          "recoveries": 0, "recovery_ms": 0.0,
                          "handoffs": 0, "handoff_bytes": 0,
                          "handoff_fallbacks": 0}
        self._itl_ms = []
        self._moe = dict(_MOE_COUNTERS)
        self._exit[:] = 0.0
        self._lat = dict(_LAT_COUNTERS)
        self._win = {"slot_rounds": 0, "rows_in_window": 0}
        self._table_rounds = self._step_shapes()
        if self._prefix_cache is not None:
            self._prefix_cache.reset_stats()
        if self._lora:
            p = self.adapter_slots
            p.hits = p.evictions = p.page_ins = 0
        # fleet observability (ISSUE 18): the rounds' records, the
        # blind-stall latch and the tracer's sync self-report are
        # window-scoped too — the reset-parity sweep pins that every rollup
        # counter clears. An empty interval still open is counted from the
        # new window's first request (_occupied)
        self._reset_round_records()
        self._empty_before_s = 0.0
        if self._tracer is not None:
            self._tracer.device_syncs = 0

    def _setup(self) -> Dict[str, Any]:
        """``stats()["setup"]``: where this engine's set-up went, from its
        ``InferenceEngine``'s construction on. ``engine_init_s``: the two
        constructors' seconds, of which ``weights_s`` (``ds:setup.weights``),
        ``pools_s`` (``ds:setup.pools``, a recovery's fresh pool included)
        and ``init_build_s``, building programs — those are among
        ``programs`` too. ``programs``: ONE record a program built
        (``telemetry.tracing.BuildLog``), in the order built — this engine's
        (every ``ds:setup.program``) and, ``kind`` ``other``, what the
        process built since under no such span, by function name: the small
        programs nobody named. Over them: ``programs_built`` (lowerings),
        ``trace_lower_s`` (Python's part, paid warm or cold),
        ``compile_or_load_s`` (the backend's: compiles when cold, the
        persistent cache's key and read when warm), ``overlap_s`` (of those
        two, the seconds a step shape's lowering on the worker thread ran
        beside another's compile on the caller's: in the records twice, in
        the wall once), ``cache_hits`` (equal to ``programs_built`` when
        everything was warm) and ``built_after_first_reset`` (programs built
        since the first ``reset_stats()``: a warm-up's omissions)."""
        eng = getattr(self.engine, "setup", {})
        programs = sorted(
            self._builds.records() + build_log().records(self._builds.t0),
            key=lambda r: r["built_at_s"])
        built = sum(r["builds"] for r in programs)
        return {
            "engine_init_s": eng.get("init_s", 0.0) + self._init_s,
            "weights_s": eng.get("weights_s", 0.0),
            "pools_s": self._pools_s,
            "init_build_s": eng.get("build_s", 0.0) + self._init_build_s,
            "programs": programs,
            "programs_built": built,
            "trace_lower_s": sum(r["trace_s"] + r["lower_s"]
                                 for r in programs),
            "compile_or_load_s": sum(r["compile_or_load_s"]
                                     for r in programs),
            "overlap_s": self._build_overlap_s,
            "cache_hits": sum(r["cache_hit"] for r in programs),
            "built_after_first_reset": (
                0 if self._built_at_reset is None
                else built - self._built_at_reset)}

    def close(self, timeout: Optional[float] = None) -> bool:
        """Stop admission and join the latest watchdog round thread with
        a bounded timeout (default: ``dispatch_timeout_s``, else 5s). A
        hung round's thread is daemon — it cannot block interpreter exit
        — but anything rebuilding engines in-process (the router's
        failover path, test harnesses) must not let an abandoned round
        outlive the engine that spawned it. Returns False when the round
        thread outlived the budget (handle kept for a retry)."""
        self._draining = True
        if timeout is None:
            timeout = self.config.dispatch_timeout_s or 5.0
        t = self._round_thread
        if t is not None and t.is_alive():
            t.join(timeout)
            if t.is_alive():
                return False
        self._round_thread = None
        return True

    def stats(self) -> Dict[str, Any]:
        """TTFT p50/p99 (ms) + aggregate generated-token throughput across
        everything finished so far — the SLO numbers the serving bench
        emits — plus the reliability counters (shed / deadline_misses /
        cancelled / degraded / recoveries / recovery_ms). TTFT is measured
        at the first round boundary where the request's first token reached
        the host (includes the quantum it landed in — the honest,
        observable number).

        Latency-frontier additions (ISSUE 12): ``p50/p99_itl_ms``
        (inter-token delivery latency, sampled per commit burst as
        gap/tokens — the chunked-prefill win's metric), the speculation
        counters (``spec_steps/proposed/accepted`` + ``spec_accept_rate``),
        the chunking counters (``prefill_chunks/chunk_tokens``),
        ``cow_forks``, and — with the cache armed — the ``prefix_*``
        counters incl. ``prefix_hit_rate`` and ``prefix_held_blocks``.

        Request lifecycle (always on): ``queue_wait_p50/p90_ms``
        (``admit_t - submit_t``), ``first_token_wait_p50/p90_ms``
        (``first_token_t - admit_t``) and ``token_gap_max_p50/p90_ms``
        (each request's longest gap between two token deliveries).

        Expert routing (always on, a model with experts only; counted over
        active slots and real prompt tokens, ``_note_counters``):
        ``moe_load_max_over_mean`` (fullest expert's assignments over the
        mean expert's, per layer; mean over layers and rounds),
        ``moe_experts_touched_per_step`` (distinct experts a decode step's
        active slots read, mean over layers and steps; ``..._per_prefill``:
        the same for a whole-prompt prefill's real tokens) and
        ``moe_dropped_share`` (assignments the dispatch dropped; 0 for a
        dropless model). A model whose expert layers hold THE CHIP'S SHARE
        of a deployment's experts (``TransformerConfig.moe_router_experts``)
        reports ``moe_held`` and ``moe_router_width`` (experts held, experts
        the router scores) and, in place of the dropped share,
        ``moe_assignments_asked`` / ``moe_assignments_held``: of the
        assignments the router made, those on the experts held here (their
        ratio is about held / width under an even router). The other
        counters are over the experts held.

        A looped model (always on, ``ut_steps`` > 1 only): ``ut_steps``,
        ``kv_planes`` (K/V planes a token keeps: passes x layers), and from
        the exit gate, over every position a token was
        sampled at (the active slots of the plain decode steps, the last
        prompt position of a whole-prompt prefill; ``_note_counters``):
        ``exit_step_expected`` (the mean of sum_t (t + 1) p_t, in 1 ..
        ut_steps: the passes an exit policy at that distribution would run)
        and ``exit_cdf`` (mean cumulative p after each pass).

        Whole-prompt prefills (always on; ``_pack_prefills``):
        ``prefill_prompts`` (prompts the prefill program took whole; chunks
        and LoRA spans are ``prefill_chunks``), ``prefill_programs`` (calls
        of it: fewer than the prompts where a round's prompts shared a row)
        and ``prefill_packed_prompts`` (the prompts that shared one);
        ``prefill_attn_tiles_walked`` (counted only for the rows whose
        attention was the packed flash forward's, ``transformer
        .flash_takes`` — 0 for an engine with a state per slot, on the XLA
        path, at a bucket off the kernel's tiles: the key tiles one kv
        head's pass of ONE layer walks, a shared row's one call only what
        its segments reach) and ``prefill_attn_tiles_looped`` (a whole
        causal pass a prompt of those rows, in the SAME tiles: what a call
        a live segment would walk, derived and never run): their ratio is
        how far sharing a row spares attention where the kernel runs.

        The two kinds of state (always on): ``kv_pool_bytes`` (the K/V block
        pool's share of ``pool_bytes``), ``kv_bytes_per_token`` (what the
        block pool holds a cached token: every plane, scale planes included;
        of a model with window blocks the FULL planes, the only ones that
        grow with the context) and ``state_bytes_per_slot`` (what the engine
        holds a slot whatever its context: every state layer with its
        convolution tail, every ring; 0 for a model whose request is its
        blocks alone) — what a cost model is held to —; ``hc_mult`` and
        ``stream_bytes_per_token`` (the rows of the residual stream a token
        carries between blocks, 1 for every stack but a hyper-connected one,
        and their bytes: what every block reads and writes a token); for a
        model with recurrent or
        window blocks, ``state_pool_bytes`` (the per-slot state, every
        state leaf of the pool summed) and
        ``state_slots_live`` (slots whose state belongs to a running
        request); for a model with window blocks also ``window_blocks``,
        ``window_rows`` (rows of a ring),
        ``ring_bytes_per_slot`` (all its rings, whatever the context),
        ``window_rows_read`` (rows of ONE window plane the plain rounds'
        first steps read, a ring a live slot) and ``window_rows_in_window``
        (of those, the rows inside the slots' bands: min(length, window));
        ``moe_dispatch`` — a dict ``{"step" | "prefill_<bucket>": form}``
        of the expert layers' dispatch form (``one-hot`` | ``sorted/moe_gmm``
        | ``sorted/ragged_dot`` | ``capacity``) in each program built so far,
        recorded when the program is traced.

        The decode step's shape (always on; ``_tables_device``; speculation
        rounds keep the full tables and are not counted):
        ``step_shape_rounds`` — a dict ``{"<slots>x<columns a slot>": plain
        decode rounds dispatched at it}`` over ``_step_shapes``, slots x
        columns being the blocks the step gathered a plane (the length of
        its block list) —, ``kv_blocks_gathered``, that product summed over
        those rounds, and ``kv_blocks_held``, the blocks the running
        requests held, summed likewise: their ratio is how much of what the
        steps gathered was anyone's.

        The round order (always on; ``_round``): ``rounds_ahead`` — of the
        rounds ``step_shape_rounds`` counts, those dispatched while the
        last steps of the round before were still unfetched (all but the
        first after an idle moment) —, of those ``ahead_covered_rounds``
        (the chip still had work when the round's first step was issued:
        it never waited for the host) and ``ahead_dry_rounds`` (its queue
        had run dry), and ``dropped_slot_rounds`` — slot-rounds run
        for a request that had already ended: the quantum a slot ran after
        an eos among the steps the host had not fetched yet, or behind a
        one-token budget (0 where every request ends by length past its
        first token). Tokens of the steps in flight are in neither
        ``generated`` nor ``cached_rows`` until the next ``step()`` commits
        them.

        The rounds' records (always on; ``_round``, ``_note_phases``), over
        the stats window: ``slow_rounds`` — the eight slowest
        decode-dominated rounds (fewer prompts admitted than requests were
        decoding), slowest first, each ``[its record, the record of the
        round that followed it]`` —, ``round_ms_max`` and ``phase_ms_max``
        (``{phase: ms}``) over those rounds, ``round_ms_median`` over the
        ring's, ``gc_ms_total`` (garbage collection inside rounds),
        ``build_ms_total`` (programs built inside rounds: 0.0 in a window
        that compiled nothing), and the empty engine: ``engine_empty_s``
        (seconds of the window in which it held no request, an interval
        still open included) of ``stats_window_s`` (since the window's first
        request).

        Set-up (always on; the engine's LIFE, which ``reset_stats`` leaves
        alone): ``setup`` — ``_setup``."""
        done = [r for r in self._finished if r.first_token_t is not None]
        out: Dict[str, Any] = {
            "completed": float(len(self._finished)),
            "preemptions": float(sum(r.preemptions
                                     for r in self._finished)),
            # PER-DEVICE pool shard (what a chip's HBM actually pays — on
            # a tp-sharded engine logical / tp; the logical size rides
            # alongside so the memory law stays checkable)
            # in-flight handoff payloads are host memory the engine is
            # still responsible for — price them alongside the pool so
            # export staging can't hide from the memory accounting
            "pool_bytes": float(self.pool_bytes
                                + sum(self._kv_staging.values())),
            "pool_bytes_logical": float(self.pool_bytes_logical),
            "kv_staging_bytes": float(sum(self._kv_staging.values())),
            "tp": float(self.tp),
            "ep": float(self.ep),
            "cancelled": float(len(self._cancelled)),
            "queue_depth": float(self.scheduler.num_waiting),
            # multi-tenancy (ISSUE 17): adapter slot-pool traffic + the
            # weight-quantization mode the engine decodes with (0 = full
            # precision / activation-quantized path)
            "adapter_hits": float(self.adapter_slots.hits
                                  if self._lora else 0),
            "adapter_evictions": float(self.adapter_slots.evictions
                                       if self._lora else 0),
            "adapter_page_ins": float(self.adapter_slots.page_ins
                                      if self._lora else 0),
            "weight_bits": float(getattr(self.engine.config,
                                         "weight_bits", 0) or 0),
        }
        out.update({k: float(round(v, 3)) if isinstance(v, float)
                    else float(v) for k, v in self._counters.items()})
        if done:
            ttft = np.asarray([(r.first_token_t - r.submit_t) * 1e3
                               for r in done])
            out["p50_ttft_ms"] = float(np.percentile(ttft, 50))
            out["p99_ttft_ms"] = float(np.percentile(ttft, 99))
        if self._itl_ms:
            itl = np.asarray(self._itl_ms)
            out["p50_itl_ms"] = float(np.percentile(itl, 50))
            out["p99_itl_ms"] = float(np.percentile(itl, 99))
        # the request lifecycle over the window's finished requests: with
        # the caller's own due -> add_request lateness these split TTFT
        # into queue wait and admission -> first token
        for key, vals in (
                ("queue_wait", [(r.admit_t - r.submit_t) * 1e3
                                for r in done if r.admit_t is not None]),
                ("first_token_wait", [(r.first_token_t - r.admit_t) * 1e3
                                      for r in done if r.admit_t is not None]),
                ("token_gap_max", [r.max_gap_ms for r in done
                                   if r.max_gap_ms is not None])):
            if vals:
                out[f"{key}_p50_ms"] = float(np.percentile(vals, 50))
                out[f"{key}_p90_ms"] = float(np.percentile(vals, 90))
        m = self._moe
        mcfg = self.model.config
        held, width = mcfg.num_experts, mcfg.moe_router_width
        if m["asked"] and held == width:
            out["moe_assignments"] = float(m["kept"])
            out["moe_dropped_share"] = 1.0 - m["kept"] / m["asked"]
        elif m["asked"]:
            # this chip's share of a deployment's experts: of the
            # assignments the router made, those on the experts held here
            out.update(moe_held=float(held), moe_router_width=float(width),
                       moe_assignments_asked=float(m["asked"]),
                       moe_assignments_held=float(m["kept"]))
        if m["rounds"]:
            out["moe_load_max_over_mean"] = m["max_over_mean"] / m["rounds"]
        if m["steps"]:
            out["moe_experts_touched_per_step"] = m["touched"] / m["steps"]
        if m["prefills"]:
            out["moe_experts_touched_per_prefill"] = (m["prefill_touched"]
                                                      / m["prefills"])
        forms = {k: v for k, v in self._moe_forms.items() if v}
        if forms:
            out["moe_dispatch"] = forms
        # what the model keeps per slot is never split over a mesh (a
        # tensor-parallel pool is refused with it): per device = logical
        state_bytes = self._cache_bytes["state"] + self._cache_bytes["rings"]
        out["kv_pool_bytes"] = float(self.pool_bytes - state_bytes)
        # what the engine ALLOCATED a cached token (all planes of the block
        # pool, scale planes included) and a slot (all state layers, tails
        # and rings included): a family's cost model is held to these
        out["kv_bytes_per_token"] = float(self._cache_bytes["kv"] // (
            self.num_blocks * self.config.block_size))
        out["state_bytes_per_slot"] = float(
            state_bytes // self.config.max_seqs)
        # the residual stream a token carries between blocks: its rows (1:
        # every stack but a hyper-connected one) x hidden x the itemsize
        out["hc_mult"] = float(getattr(mcfg, "hc_mult", 1))
        out["stream_bytes_per_token"] = float(
            out["hc_mult"] * mcfg.hidden_size
            * np.dtype(self.engine.dtype).itemsize)
        if self._ut_steps > 1:
            out["ut_steps"] = float(self._ut_steps)
            out["kv_planes"] = float(mcfg.kv_planes)
            if self._exit[-1]:
                p = self._exit[:-1] / self._exit[-1]
                out["exit_step_expected"] = float(
                    np.dot(np.arange(1, p.size + 1), p))
                out["exit_cdf"] = [float(x) for x in np.cumsum(p)]
        if getattr(mcfg, "latent_planes", 0):
            # the planes that are latent rows, and one row's bytes in the pool
            out["latent_planes"] = float(mcfg.latent_planes)
            out["latent_row_bytes"] = float(
                mcfg.latent_row_width
                * np.dtype(self.engine.dtype).itemsize)
        if self._slot_state:
            out["state_pool_bytes"] = float(state_bytes)
            out["state_slots_live"] = float(len(self.scheduler.running))
        if self.model.ring_rows:
            # a ring leaf holds one array a window block
            out["window_blocks"] = float(len(next(iter(
                ring_leaves(self.model, self.pools).values()))))
            out["window_rows"] = float(self.model.ring_rows)
            out["ring_bytes_per_slot"] = float(
                self._cache_bytes["rings"] // self.config.max_seqs)
            out["window_rows_read"] = float(
                self.model.ring_rows * self._win["slot_rounds"])
            out["window_rows_in_window"] = float(self._win["rows_in_window"])
        out.update({k: float(v) for k, v in self._lat.items()})
        out["step_shape_rounds"] = {
            f"{S}x{W}": n for (S, W), n in self._table_rounds.items()}
        # what reads the pool in the decode step, and what decided it (the
        # price's two sides on an int8 pool: ``_select_backend``)
        out["decode_backend"] = self.decode_backend
        out["decode_backend_choice"] = dict(self.backend_bench)
        out["slow_rounds"] = [[dict(rec), nxt and dict(nxt)]
                              for rec, nxt in self._slow]
        out["gc_ms_total"] = float(self._gc_ms_total)
        out["build_ms_total"] = float(self._build_ms_total)
        out["setup"] = self._setup()
        typical = [e["round_ms"] for e in self._phases
                   if self._decode_dominated(e)]
        if typical:
            out["round_ms_median"] = float(np.median(typical))
            out["round_ms_max"] = float(self._phase_max["round_ms"])
            out["phase_ms_max"] = {p: float(self._phase_max[f"{p}_ms"])
                                   for p in self._PHASES}
        now = time.perf_counter()
        out["stats_window_s"] = (now - self._stats_t0
                                 if self._stats_t0 is not None else 0.0)
        out["engine_empty_s"] = self._empty_s + self._open_empty_s(now)
        if self._lat["spec_proposed"]:
            out["spec_accept_rate"] = float(round(
                self._lat["spec_accepted"] / self._lat["spec_proposed"], 4))
        if self._prefix_cache is not None:
            cs = self._prefix_cache.stats
            out.update({f"prefix_{k}": float(v) for k, v in cs.items()})
            if cs["lookups"]:
                out["prefix_hit_rate"] = float(round(
                    cs["hits"] / cs["lookups"], 4))
            out["prefix_held_blocks"] = float(
                self._prefix_cache.held_blocks)
        if self._finished and self._stats_t0 is not None:
            total = sum(len(r.generated) for r in self._finished)
            span = max(r.finish_t for r in self._finished) - self._stats_t0
            out["tok_per_sec"] = float(total / span) if span > 0 else 0.0
            out["generated_tokens"] = float(total)
        return out


def init_serving(model, config=None, serving: Optional[dict] = None,
                 mesh=None, params=None, rng=None, **kwargs):
    """One-call constructor: init_inference + ServingEngine. `serving`
    takes ServingConfig field names. The InferenceEngine's context-aware
    int8-KV default keys off the serving context cap (long-context pools
    quantize, short ones keep the compute dtype — the measured
    crossover).

    Mesh-native (ISSUE 15): pass ``tensor_parallel=N`` /
    ``expert_parallel=N`` (InferenceConfig fields, via `config` or
    kwargs) to build the serving mesh, or hand an explicit ``mesh`` —
    the mesh is authoritative for the degrees, the block pools shard on
    the kv-head dim over `tensor`, and the MoE expert stacks over
    `expert`. Greedy outputs stay token-identical to the single-chip
    engine (the tp-parity tests pin it)."""
    from deepspeed_tpu.inference.engine import init_inference
    sc = ServingConfig(**(serving or {}))
    model_cap = getattr(getattr(model, "config", None), "max_seq_len", None)
    max_len = sc.max_model_len or model_cap or 2048
    if model_cap:
        # same clamp ServingEngine applies to the serving cap: max_tokens
        # drives the context-aware int8-KV default, and deriving it from
        # an over-asked max_model_len would quantize a short-context
        # model's pool (the exact r5 regression class)
        max_len = min(max_len, model_cap)
    # default the engine's context budget to the serving cap WITHOUT
    # overriding an explicit user setting: kwargs beat dict configs inside
    # init_inference, so the default goes into the config dict itself; an
    # InferenceConfig instance is respected verbatim
    if (config is None or isinstance(config, dict)) \
            and "max_tokens" not in kwargs:
        config = dict(config or {})
        config.setdefault("max_tokens", max_len)
    eng = init_inference(model, config=config, mesh=mesh, params=params,
                         rng=rng, **kwargs)
    return ServingEngine(eng, sc)
