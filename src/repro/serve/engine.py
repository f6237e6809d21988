"""Continuous-batching inference engine over a paged KV cache.

Each ``step()`` is ONE fused fixed-shape ``paged_step`` call carrying
mixed prefill+decode rows:

  * the row layout adapts to the step: decode-only steps use the plain
    (bucket, 1) shape, prefill-only steps use chunk-wide rows, and
    mixed steps split prefill chunks into one width-1 row per prompt
    token (later chunk tokens attend their siblings' KV because every
    row's scatter lands before any row's gather inside the call)
    instead of padding decode rows to the chunk width.  A chunk's
    width-1 rows sit in consecutive rows with one block table and
    consecutive positions, so the paged attend (``flash_decode_paged``)
    reads them as one query run: its work follows the step's runs and
    their live pages, not rows x table length.  A per-row
    ``valid_len`` input routes padded/inactive rows' KV writes to the
    trash block, so a stale row can never clobber a live sequence's
    blocks;
  * sampling happens on device inside the call — greedy argmax AND
    temperature/top-k (per-row keys derived fold_in(rid, position), so
    the draw is identical at any dispatch depth and across preemption
    recompute) — and only each row's frontier logits are sliced out;
    the host never sees a ``(rows, chunk, vocab)`` logits block;
  * a device-resident per-slot token buffer feeds step k's sampled
    tokens into step k+1's decode rows without a host round-trip, so
    the host can dispatch step k+1 BEFORE fetching step k's tokens
    (depth-1 pipelined dispatch — the serving analogue of LSGD hiding
    the slow collective under the next minibatch's compute).  Eos
    stopping is optimistic: the engine keeps the pipeline full and
    discards speculative tokens past the eos at fetch time, so eos and
    stochastic requests pipeline too — nothing forces a synchronous
    fetch anymore;
  * with ``steps_per_dispatch = N > 1``, decode-only steps run as ONE
    ``paged_decode_loop`` dispatch: N fused steps inside a
    ``lax.fori_loop`` on device, with per-row stop conditions (step
    budget, eos, block-capacity predicate) evaluated on device and a
    packed (rows, N) token buffer read back.  The host's per-token
    work — meta packing, block-table rebuilds, dispatch overhead — is
    paid once per N tokens; admission and preemption happen only at
    dispatch boundaries, with N-token block/slot headroom reserved
    up front (``PagedKVCache.reserve``, partial grants truncate the
    row's loop early instead of preempting).

Because block tables, positions, and tokens are rebuilt for every call,
rows carry no state between steps — a sequence's identity lives in its
block table, its recurrent-state slot (ssm/rglru families), and its slot
in the device token buffer.  Admission isn't tied to a decode row: the
engine admits ``admission_lookahead`` sequences beyond max_batch so a
freshly finished row is backfilled by an already-prefilled ("ready")
sequence with zero idle steps.

Per-family paged state (``Model.paged_spec``): block-pool families
(plain attention, MLA latent KV) page per-token state and may split
prefill chunks into width-1 rows on mixed steps; slot-state families
(ssm, rglru) keep O(1) recurrent state in fixed-size slots, so mixed
steps keep chunk-wide rows (a token's state depends on the previous
token *within the call*) and preemption relies on recompute — the
replayed first chunk reads zeros because its pos is 0, never the
evicted slot's stale state.
"""
from __future__ import annotations

import functools
import re
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding
from repro.models import transformer
from repro.serve.kv_cache import PagedKVCache, StateSlotAllocator
from repro.serve.scheduler import Request, RequestQueue, Scheduler
from repro.serve.telemetry import LatencyHists, MetricsRegistry, Telemetry

# the flat integer counters in ``metrics_snapshot()["counters"]`` (plus
# ``jit_compiles``); each is a registry counter labeled with this
# engine's replica/arch.  ``paged_query_runs`` / ``paged_kv_pages``: the
# query runs of each fused step whose attends run flash_decode_paged,
# and the pages its tiles visit (``flash_decode.query_tiles``, at the
# widest window), summed over dispatches; over rows x blocks_per_seq
# per dispatch they give the share of a whole-table walk left
_STAT_KEYS = ("steps", "decode_steps", "decode_slot_steps",
              "decode_active_slot_steps", "prefill_tokens",
              "generated_tokens", "preemptions", "faulted", "model_calls",
              "host_syncs", "loop_dispatches", "loop_truncations",
              "paged_query_runs", "paged_kv_pages")

_DISPATCH_PHASES = ("prefill", "decode", "mixed", "loop")


class _EngineMetrics:
    """Struct-of-handles for the engine hot path: every event is one
    attribute access + an int add, no registry lookup, no lock, no
    device sync.  Labels: ``replica`` (the engine's replica_id) and
    ``arch`` (the model config name)."""

    def __init__(self, registry: MetricsRegistry, **labels):
        for k in _STAT_KEYS:
            setattr(self, k, registry.counter("engine_" + k, **labels))
        self.jit_compiles = registry.counter("engine_jit_compiles",
                                             **labels)
        self.live_seqs = registry.gauge("engine_live_seqs", **labels)
        self.state_slots_free = registry.gauge("engine_state_slots_free",
                                               **labels)
        # tensor-parallel visibility: slice width, and the number of
        # collective ops XLA placed in the compiled decode step (0 for
        # single-device replicas; the per-dispatch wall time those
        # collectives cost is already inside the dispatch_s histograms,
        # so width + op count + dispatch_s give collective-time
        # attribution without device profiling)
        self.tp_degree = registry.gauge("engine_tp_degree", **labels)
        self.tp_collective_ops = registry.gauge("engine_tp_collective_ops",
                                                **labels)
        # host wall time per device dispatch, split by step phase —
        # the per-phase timing that tells a compute-bound regime from a
        # dispatch-bound one without opening a trace
        self.dispatch_s = {ph: registry.histogram("engine_dispatch_s",
                                                  phase=ph, **labels)
                           for ph in _DISPATCH_PHASES}
        self.latency = LatencyHists(registry, **labels)


def traced_on_mesh(mesh: Optional[Mesh], fn):
    """``fn`` traced with ``mesh`` as the context mesh, so the Pallas
    wrappers in ``repro.kernels.ops`` run their kernels per shard of a
    tensor-parallel replica (a compiled Mosaic kernel cannot be split
    by the SPMD partitioner).  Identity for single-device replicas."""
    if mesh is None:
        return fn

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args, **kwargs)

    return traced


# positional argnums of (cache, slot_buf) in paged_step /
# paged_decode_loop — the device state donated (aliased in place)
# across dispatches.  ``repro.analysis.hotpath_check`` lints traced
# outputs against THIS list, so the analyzer and the engine cannot
# drift apart.
PAGED_DONATE_ARGNUMS = (1, 2)


@dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 8              # decode rows per step
    block_size: int = 16            # tokens per KV block
    num_blocks: int = 257           # pool size incl. trash block 0
    max_seq_len: int = 256          # per-sequence prompt+gen ceiling
    prefill_chunk: int = 32         # tokens per prefill row (padded shape)
    prefill_token_budget: int = 64  # max prefill tokens per engine step
    admission_lookahead: int = 2    # prompts prefilled ahead of a free row
    temperature: float = 0.0        # 0 => greedy (sampled ON DEVICE)
    top_k: int = 0                  # 0 => full-vocab temperature sampling
    seed: int = 0
    steps_per_dispatch: int = 1     # decode steps per device dispatch (N)
    fused: bool = True              # False: PR-1 two-call loop (baseline)
    pipeline: bool = True           # overlap host bookkeeping with device
    donate: bool = True             # alias cache/slot buffers across steps

    @property
    def blocks_per_seq(self) -> int:
        return -(-self.max_seq_len // self.block_size)

    @property
    def num_slots(self) -> int:
        """Device token-buffer slots: one per admittable sequence."""
        return self.max_batch + self.admission_lookahead

    @property
    def prefill_rows(self) -> int:
        """Prefill rows in the chunk-wide prefill-only call (also the
        legacy unfused prefill call) — enough for a full budget of
        max-size chunks (the scheduler grants no more per step)."""
        return max(1, min(self.max_batch,
                          self.prefill_token_budget // self.prefill_chunk))

    @property
    def mixed_buckets(self) -> List[int]:
        """Row counts for fused steps that carry BOTH decode rows and
        prefill work.  Prefill chunks are split into width-1 rows (one
        row per prompt token, all in the same call — later tokens attend
        siblings' KV written earlier in the call) instead of padding
        every decode row to the chunk width.  The per-token layers cost
        one row per token; the paged attend costs each query run (a
        decode row, or a chunk's consecutive rows) over its live pages,
        whatever the bucket's padding rows add (one page each)."""
        full = self.max_batch + self.prefill_token_budget
        half = self.max_batch + max(self.prefill_chunk,
                                    self.prefill_token_budget // 2)
        small = self.max_batch + self.prefill_chunk
        return sorted({full, half, small})

    @property
    def mixed_chunk_rows(self) -> int:
        """Row count for mixed steps of slot-state families (ssm/rglru):
        prefill chunks cannot split into width-1 rows (the recurrent
        state of token i+1 depends on token i *within the call*), so the
        mixed layout is chunk-wide rows — decode rows ride along with
        valid_len=1."""
        return self.max_batch + self.prefill_rows

    @property
    def decode_buckets(self) -> List[int]:
        """Decode batch shapes, largest first: full batch plus half/quarter
        buckets so the drain phase (few live sequences left) doesn't pay
        full-batch compute per step."""
        out = []
        b = self.max_batch
        while b >= 1 and len(out) < 3:
            out.append(b)
            b = -(-b // 2) if b > 1 else 0
        return out


@dataclass
class RequestResult:
    rid: int
    prompt_len: int
    tokens: List[int]
    arrival_time: float = 0.0
    first_token_time: float = 0.0
    finish_time: float = 0.0
    preempted: int = 0
    # non-None marks a FAULT terminal ("deadline", "poison",
    # "no_live_replicas"): the request did not finish; ``tokens`` holds
    # whatever partial output had materialized
    fault: Optional[str] = None


@dataclass(eq=False)        # identity equality (held in ordered lists)
class _Seq:
    req: Request
    slot: int
    out: List[int] = field(default_factory=list)  # host-materialized tokens
    gen_count: int = 0      # generated incl. in-flight (out lags by pending)
    first_token_time: float = 0.0
    prefill_done: bool = False
    done: bool = False      # finished by count; awaiting final fetch/evict
    desync: bool = False    # device truncated past host bookkeeping

    @property
    def next_pos(self) -> int:
        """Position of the next token fed to decode (the last sampled
        token goes in at prompt_len + generated-so-far - 1)."""
        return len(self.req.prompt) + self.gen_count - 1


@dataclass
class _Inflight:
    """One dispatched step whose token values the host hasn't read yet.

    A single-step record carries (rows,) tokens; an N-step decode-loop
    record carries (rows, N) tokens plus the per-row valid counts and
    eos flags the device's stop conditions produced, and ``planned``
    (the per-row step budget the host granted) so the fetch can
    reconcile optimistic bookkeeping."""
    toks: jax.Array                       # (rows,) or (rows, N) int32
    emits: List[Tuple[int, "_Seq", bool]]  # (row, seq, is_first_token)
    now: float
    counts: Optional[jax.Array] = None    # (rows,) int32, loop only
    eos_hit: Optional[jax.Array] = None   # (rows,) bool, loop only
    planned: Optional[Dict[int, int]] = None   # row -> granted steps
    step: int = 0                         # the engine step that dispatched it


# analysis: single-writer — an Engine is thread-confined by contract:
# exactly one thread (the owning ServeCluster worker, or the caller in
# single-engine use) drives warmup/submit/step/drain_progress after
# construction.  Cross-thread visibility goes through the internally
# locked Telemetry registry and the RequestQueue in front of submit();
# nothing else reads engine state from another thread.
class Engine:
    """Continuous-batching engine; one tensor-parallel replica.

    ``devices`` gives the replica a mesh slice (one fast-fabric group
    from ``launch.mesh.replica_slices``).  A single-device slice commits
    params, cache, and the token slot buffer to that device.  A
    multi-device slice becomes a ("model",)-axis sub-mesh spanning the
    slice: params shard per family (attention/MLA head projections and
    mlp hidden over heads, routed experts expert-parallel, ssm/rglru
    channels — ``sharding.serve_param_pspecs``), the paged pools shard
    on the same axes (``sharding.serve_cache_pspecs``) while block
    tables, MLA latent pools, and the slot token buffer replicate, and
    the unmodified ``paged_step``/``paged_decode_loop`` run under GSPMD
    — XLA inserts the intra-slice collectives (the paper's fast-fabric
    layer), and the host-side engine loop, np inputs, and donation are
    byte-identical to the single-device path.  Multiple engines on
    disjoint slices execute concurrently (``serve.ServeCluster`` drives
    one worker thread per replica) with no cross-slice communication.
    ``devices=None`` keeps the PR-3 behaviour: whatever device JAX
    defaults to."""

    def __init__(self, model, params, cfg: EngineConfig = EngineConfig(),
                 devices: Optional[Sequence] = None,
                 telemetry: Optional[Telemetry] = None,
                 replica_id: int = 0):
        if model.paged_step is None or model.paged_spec is None:
            raise ValueError(
                f"{model.cfg.name}: family {model.cfg.family!r} has no "
                "paged serving path")
        self.spec = model.paged_spec
        if not cfg.fused and self.spec.has_state:
            raise ValueError(
                "the unfused baseline path has no per-row state slots; "
                "slot-state families (ssm/rglru) serve fused-only")
        if cfg.steps_per_dispatch < 1:
            raise ValueError("steps_per_dispatch must be >= 1")
        if cfg.steps_per_dispatch > 1 and not cfg.fused:
            raise ValueError(
                "the N-step on-device decode loop requires the fused "
                "step (device-side sampling + slot buffer)")
        self.model = model
        # the fused steps' attends run flash_decode_paged: its query
        # tiles feed the paged_* counters.  Kernel modules (and Pallas)
        # load only when used, as in models/
        self._query_tiles = None
        if model.cfg.attn_impl == "pallas" and any(
                op.endswith("/flash_decode_paged")
                for _, op in self.spec.kernel_spec):
            from repro.kernels.flash_decode import query_tiles
            self._query_tiles = query_tiles
        # telemetry: one bundle per frontend (ServeCluster shares its
        # bundle across replicas; a standalone engine builds its own).
        # Counters/gauges are always on; span tracing only runs when the
        # bundle's tracer is enabled.
        self.telemetry = telemetry or Telemetry()
        self.replica_id = replica_id
        self._m = _EngineMetrics(self.telemetry.registry,
                                 replica=replica_id, arch=model.cfg.name)
        self.devices = tuple(devices) if devices else None
        self.device = self.devices[0] if self.devices else None
        self.tp_degree = len(self.devices) if self.devices else 1
        # a multi-device slice serves tensor-parallel: one ("model",)
        # sub-mesh spanning the slice, everything partitioned by GSPMD
        self.mesh = (Mesh(np.asarray(self.devices), ("model",))
                     if self.tp_degree > 1 else None)
        self._m.tp_degree.set(self.tp_degree)
        if self.mesh is not None:
            abstract = jax.eval_shape(lambda p: p, params)
            params = jax.device_put(params, sharding.named_sharding_tree(
                sharding.serve_param_pspecs(abstract, self.mesh), self.mesh))
        elif self.device is not None:
            # each replica owns a full copy of the params on its slice
            params = jax.device_put(params, self.device)
        self.params = params
        self.cfg = cfg
        # the host-side block accounting runs for EVERY family — for pure
        # slot-state models (no device block pools) it still meters token
        # capacity, so admission/preemption semantics are uniform across
        # families and pool starvation forces the same recompute path.
        # When every block-pooled layer is windowed, blocks that fall out
        # of the window are reclaimed as the frontier advances (pure
        # slot-state metering keeps window=0: its "blocks" are tokens).
        self.kv = PagedKVCache(
            cfg.num_blocks, cfg.block_size, cfg.blocks_per_seq,
            window=self.spec.reclaim_window if self.spec.has_blocks else 0)
        self.kv.attach_metrics(self.telemetry.registry,
                               replica=replica_id, arch=model.cfg.name)
        self.state_slots = (StateSlotAllocator(cfg.num_slots + 1)
                            if self.spec.has_state else None)
        self.scheduler = Scheduler(
            cfg.max_batch + cfg.admission_lookahead, cfg.prefill_chunk,
            cfg.prefill_token_budget, max_chunks_per_step=cfg.prefill_rows)
        self.scheduler.attach_metrics(self.telemetry.registry,
                                      replica=replica_id,
                                      arch=model.cfg.name)
        self.cache = model.init_paged_cache(
            cfg.num_blocks, cfg.block_size, cfg.max_batch,
            cfg.blocks_per_seq, num_state_slots=cfg.num_slots + 1)
        if self.mesh is not None:
            # pools shard on the family axis (heads/channels); block
            # tables, latent pools, and token buffers replicate so the
            # host's np writes address every shard identically
            self.cache = jax.device_put(
                self.cache, sharding.named_sharding_tree(
                    sharding.serve_cache_pspecs(self.cache, self.mesh),
                    self.mesh))
        elif self.device is not None:
            # commit the device state to the replica's slice; committed
            # operands pin every jit dispatch (and the np input
            # transfers) to that device
            self.cache = jax.device_put(self.cache, self.device)
        # cache + slot buffer are pure device state threaded through every
        # call; donating them lets XLA scatter into the KV pools in place
        # instead of copying the pools every step.  Note for the
        # pipelined mode: on the CPU PJRT runtime a call with donated
        # inputs blocks *dispatch* until the producer of those buffers
        # finishes — that block lands where the data dependency would
        # have stalled the device anyway, and the host has already built
        # this step's inputs by then, so donation keeps both the overlap
        # and the zero-copy update.  cfg.donate=False exists for
        # backends/benchmarks where the aliasing stall does matter.
        donate = PAGED_DONATE_ARGNUMS if cfg.donate else ()
        # sampling runs on device, inside the step: temperature/top_k/
        # seed are Python statics baked into the jit wrapper (the greedy
        # executable carries no RNG at all), so the jit cache keys on
        # them alongside the donation layout
        sample_kw = dict(temperature=float(cfg.temperature),
                         top_k=int(cfg.top_k), seed=int(cfg.seed))
        skey = tuple(sorted(sample_kw.items()))
        # jit wrappers are shared across Engine instances through the
        # model (same compiled executables; a fresh Engine costs no
        # recompilation) — but only across SAME-PLACED engines: the key
        # carries the device/mesh identity, so two engines on different
        # slices keep separate wrappers and one's warmup compiles never
        # show up in the other's jit-compile watermark (the mid-serving
        # `jit_compiles` churn this fixes)
        pkey = (("mesh",) + tuple(d.id for d in self.devices)
                if self.mesh is not None
                else ("dev", self.device.id) if self.device is not None
                else None)
        on_mesh = functools.partial(traced_on_mesh, self.mesh)
        self._step_fn = model.jit_cache.setdefault(
            ("paged_step", donate, skey, pkey),
            jax.jit(on_mesh(functools.partial(model.paged_step,
                                              **sample_kw)),
                    donate_argnums=donate))
        self._loop_fn = (model.jit_cache.setdefault(
            ("paged_decode_loop", donate, skey, cfg.steps_per_dispatch,
             pkey),
            jax.jit(on_mesh(functools.partial(
                model.paged_decode_loop, num_steps=cfg.steps_per_dispatch,
                **sample_kw)), donate_argnums=donate))
            if cfg.steps_per_dispatch > 1 else None)
        self._legacy_fn = (model.jit_cache.setdefault(
            ("paged_step_logits", (1,), pkey),
            jax.jit(on_mesh(model.paged_step_logits), donate_argnums=(1,)))
            if not cfg.fused else None)
        self._slot_buf = jnp.zeros((cfg.num_slots + 1,), jnp.int32)
        if self.mesh is not None:
            self._slot_buf = jax.device_put(
                self._slot_buf, NamedSharding(self.mesh, P()))
        elif self.device is not None:
            self._slot_buf = jax.device_put(self._slot_buf, self.device)
        self._free_slots: List[int] = list(range(cfg.num_slots - 1, -1, -1))
        self._live: List[_Seq] = []     # admission (FCFS) order
        self._pending: Deque[_Inflight] = deque()
        self._desynced: List[_Seq] = []
        self._rng = np.random.default_rng(cfg.seed)
        self._preempt_counts: Dict[int, int] = {}
        self._first_token_times: Dict[int, float] = {}
        # per-request tokens materialized since the last drain — the
        # dispatcher turns these into router progress (load accounting
        # in N-token quanta)
        self._progress_tokens: Dict[int, int] = {}
        # deadline policing is pay-for-use: the per-step expiry sweep
        # only runs once a request with a budget has been submitted
        self._has_deadlines = False
        # jit-compile watermark: sum of the jitted wrappers' cache sizes
        # last time we looked.  Any growth mid-serving is a compile the
        # warmup missed (the PR-5 recompile bug, now a permanent metric
        # + regression test).  Wrappers are shared through
        # Model.jit_cache, so an engine observes — and counts — cache
        # growth its siblings trigger too; per-replica jit_compiles is a
        # guard metric, not an attribution.
        self._jit_cache_seen: Optional[int] = None
        self._note_compiles()

    # -- metrics ------------------------------------------------------------

    def metrics_snapshot(self) -> Dict[str, object]:
        """This replica's counters + derived-latency percentiles."""
        m = self._m
        counters = {k: int(getattr(m, k).value) for k in _STAT_KEYS}
        counters["jit_compiles"] = int(m.jit_compiles.value)
        return {"counters": counters,
                "latency": {"queue_wait": m.latency.queue_wait.snapshot(),
                            "ttft": m.latency.ttft.snapshot(),
                            "tpot": m.latency.tpot.snapshot(),
                            "e2e": m.latency.e2e.snapshot()},
                "dispatch_s": {ph: h.snapshot()
                               for ph, h in m.dispatch_s.items()
                               if h.count}}

    # -- jit-compile accounting ---------------------------------------------

    def _jit_fns(self):
        return [f for f in (self._step_fn, self._loop_fn, self._legacy_fn)
                if f is not None]

    @staticmethod
    def _jit_cache_total(fns) -> int:
        """Sum of compiled-executable cache sizes across ``fns``."""
        return sum(int(f._cache_size()) for f in fns)

    _COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")

    def _note_tp_collectives(self) -> None:
        """AOT-compile the smallest decode shape and count the
        collective ops XLA's SPMD partitioner placed in it — the
        per-dispatch fast-fabric communication a TP replica pays.  The
        extra compile happens at warmup (never mid-serving)."""
        rows = self.cfg.decode_buckets[-1]
        meta = np.zeros((6, rows), np.int32)
        meta[2:4] = -1
        txt = self._step_fn.lower(
            self.params, self.cache, self._slot_buf,
            np.zeros((rows, 1), np.int32),
            self.kv.table_array([None] * rows), meta).compile().as_text()
        self._m.tp_collective_ops.set(sum(
            len(re.findall(rf"\b{op}\(", txt))
            for op in self._COLLECTIVE_OPS))

    def _note_compiles(self) -> None:
        cur = self._jit_cache_total(self._jit_fns())
        if self._jit_cache_seen is None:
            self._jit_cache_seen = cur
        elif cur > self._jit_cache_seen:
            self._m.jit_compiles.inc(cur - self._jit_cache_seen)
            self._jit_cache_seen = cur

    # -- submission ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        total = len(req.prompt) + req.max_new_tokens
        if total > self.cfg.max_seq_len:
            raise ValueError(
                f"request {req.rid}: prompt+max_new_tokens={total} exceeds "
                f"max_seq_len={self.cfg.max_seq_len}")
        # first-wins no-op when the dispatcher already stamped it at the
        # cluster front door
        self.telemetry.requests.stamp(req.rid, "submit")
        # arm deadline budgets (first caller wins: re-dispatch after a
        # replica death carries the ORIGINAL absolute instants)
        req.start_clock()
        if req.deadline_at is not None or req.queue_deadline_at is not None:
            self._has_deadlines = True
        self.scheduler.add(req)

    # -- internals ----------------------------------------------------------

    def _sample(self, logits_row: np.ndarray) -> int:
        if self.cfg.temperature <= 0.0:
            return int(np.argmax(logits_row))
        z = logits_row.astype(np.float64) / self.cfg.temperature
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._rng.choice(p.size, p=p))

    def _seq_of(self, rid: int) -> Optional[_Seq]:
        for s in self._live:
            if s.req.rid == rid:
                return s
        return None

    def _admit(self, req: Request) -> _Seq:
        seq = _Seq(req, slot=self._free_slots.pop())
        if self.state_slots is not None:
            # one state slot per admittable sequence — sized to num_slots,
            # so a free token-buffer slot implies a free state slot
            slot = self.state_slots.alloc(req.rid)
            if slot is None:
                raise RuntimeError("state-slot pool exhausted despite a "
                                   "free token-buffer slot (engine bug)")
            self._m.state_slots_free.set(self.state_slots.num_free)
        self._live.append(seq)
        # first admission retires the queue-wait budget: the queue
        # deadline bounds time-to-first-slot, not recompute churn after
        # a preemption sends the request back to the waiting line
        req.queue_deadline_at = None
        # first-wins: a preempted request's re-admit keeps its original
        # admit stamp, so queue-wait stays submit -> first admission
        self.telemetry.requests.stamp(req.rid, "admit")
        self._m.live_seqs.set(len(self._live))
        return seq

    def _evict(self, seq: _Seq, now: float, finished: List[RequestResult]
               ) -> None:
        self._live.remove(seq)
        self._free_slots.append(seq.slot)
        self.kv.free_seq(seq.req.rid)
        if self.state_slots is not None:
            self.state_slots.free_if_held(seq.req.rid)
        self.scheduler.forget(seq.req)
        self._first_token_times.pop(seq.req.rid, None)
        # tokens a preempted request generated pre-eviction live in the
        # recompute prompt suffix; stitch the full generation back together
        regen = list(seq.req.prompt[seq.req.orig_prompt_len:])
        finished.append(RequestResult(
            rid=seq.req.rid, prompt_len=seq.req.orig_prompt_len,
            tokens=regen + list(seq.out),
            arrival_time=seq.req.arrival_time,
            first_token_time=seq.first_token_time, finish_time=now,
            preempted=self._preempt_counts.pop(seq.req.rid, 0)))
        self._m.live_seqs.set(len(self._live))
        if self.state_slots is not None:
            self._m.state_slots_free.set(self.state_slots.num_free)
        # terminal lifecycle event (real wall clock, not the caller's
        # possibly-simulated ``now``): derives queue-wait/TTFT/TPOT/e2e
        # into this replica's latency histograms
        self.telemetry.requests.finish(
            seq.req.rid, "complete", tokens=len(regen) + len(seq.out),
            replica=self.replica_id, hists=self._m.latency)

    def _preempt_seq(self, victim: _Seq) -> None:
        """Send ``victim`` back to the waiting line (recompute mode) and
        reclaim its blocks/slots.  The caller must have flushed in-flight
        steps first: preemption folds the victim's generated tokens into
        its prompt, which requires their values on host."""
        assert not self._pending
        self._live.remove(victim)
        self._free_slots.append(victim.slot)
        self.kv.free_seq(victim.req.rid)
        if self.state_slots is not None:
            # the victim's recurrent state is abandoned in its slot;
            # recompute mode replays the prompt (incl. generated
            # tokens) through the chunked scan, and pos==0 on the
            # first replayed chunk reads zeros, not the stale slot
            self.state_slots.free_if_held(victim.req.rid)
        self.scheduler.preempt(victim.req, victim.out)
        rid = victim.req.rid
        if victim.prefill_done:
            self._first_token_times[rid] = victim.first_token_time
        self._preempt_counts[rid] = self._preempt_counts.get(rid, 0) + 1
        self._m.preemptions.inc()
        self.telemetry.requests.note_preempt(rid)
        self._m.live_seqs.set(len(self._live))
        if self.state_slots is not None:
            self._m.state_slots_free.set(self.state_slots.num_free)

    def _preempt_one(self, exclude_rid: int) -> bool:
        """Kick the most recently admitted live sequence back to the
        waiting line (LIFO victim selection)."""
        for victim in reversed(self._live):
            if victim.req.rid == exclude_rid or victim.done:
                continue
            self._preempt_seq(victim)
            return True
        return False

    # -- fault terminals / deadline enforcement -----------------------------

    def _fault_result(self, req: Request, reason: str, out: Sequence[int],
                      first_token_time: float = 0.0,
                      finished: Optional[List[RequestResult]] = None
                      ) -> RequestResult:
        """Terminal a request with a FAULT verdict (deadline blown,
        poison quarantine, ...): stitch whatever partial output
        materialized (recompute-prompt suffix + host tokens), stamp the
        ``fault`` lifecycle terminal, and count it."""
        regen = list(req.prompt[req.orig_prompt_len:])
        res = RequestResult(
            rid=req.rid, prompt_len=req.orig_prompt_len,
            tokens=regen + list(out), arrival_time=req.arrival_time,
            first_token_time=first_token_time,
            finish_time=time.perf_counter(),
            preempted=self._preempt_counts.pop(req.rid, 0), fault=reason)
        self._m.faulted.inc()
        self.telemetry.requests.finish(
            req.rid, "fault", tokens=len(res.tokens),
            replica=self.replica_id)
        if finished is not None:
            finished.append(res)
        return res

    def _evict_fault(self, seq: _Seq, reason: str,
                     finished: List[RequestResult]) -> None:
        """``_evict``'s teardown with a fault verdict instead of a
        completion.  Caller must have flushed in-flight steps first
        (``seq.out`` must be host-complete)."""
        assert not self._pending
        self._live.remove(seq)
        self._free_slots.append(seq.slot)
        self.kv.free_seq(seq.req.rid)
        if self.state_slots is not None:
            self.state_slots.free_if_held(seq.req.rid)
        self.scheduler.forget(seq.req)
        self._first_token_times.pop(seq.req.rid, None)
        self._fault_result(seq.req, reason, seq.out,
                           first_token_time=seq.first_token_time,
                           finished=finished)
        self._m.live_seqs.set(len(self._live))
        if self.state_slots is not None:
            self._m.state_slots_free.set(self.state_slots.num_free)

    def _expire_deadlines(self, finished: List[RequestResult]) -> None:
        """Enforce queue-wait and e2e budgets at the dispatch boundary.
        Waiting-line expiry is cheap (no device state to unwind); a live
        sequence past its e2e deadline is flushed first so its partial
        output lands in the fault result."""
        mono = time.monotonic()
        for req in self.scheduler.expire(mono):
            # a refused first-chunk admission can leave an empty table
            self.kv.free_seq(req.rid)
            reason = ("queue_deadline"
                      if req.queue_deadline_at is not None
                      and mono > req.queue_deadline_at else "deadline")
            self._fault_result(req, reason, (), finished=finished)
        expired = [s for s in self._live
                   if not s.done and s.req.deadline_at is not None
                   and mono > s.req.deadline_at]
        if expired:
            self._flush(finished)
            for seq in expired:
                if seq in self._live and not seq.done:
                    self._evict_fault(seq, "deadline", finished)

    # -- post-mortem reclaim ------------------------------------------------

    def reclaim_requests(self) -> Tuple[List[Request], List[RequestResult]]:
        """Empty this engine and hand every request back for re-dispatch
        elsewhere — the failover path after this replica's worker died.

        MUST only be called once the owning thread has stopped driving
        the engine (the worker's exception handler, post-exit): the
        engine is thread-confined and this walks all of its state.

        In-flight dispatches are abandoned unfetched — their token
        values are lost, but sampling keys are ``fold_in(rid, position)``
        so a recompute re-dispatch regenerates them bit-identically.
        Each live sequence's host-materialized tokens fold into its
        prompt (recompute mode, same as preemption); sequences that
        already finished (eos on host, or budget exhausted) return as
        completed results instead of re-dispatch work.  Returns
        ``(requests_to_redispatch, finished_results)``."""
        requests: List[Request] = []
        finished: List[RequestResult] = []
        self._pending.clear()
        self._desynced.clear()
        now = time.perf_counter()
        for seq in list(self._live):
            req, out = seq.req, list(seq.out)
            if req.eos_id is not None and req.eos_id in out:
                out = out[:out.index(req.eos_id) + 1]
            remaining = req.max_new_tokens - len(out)
            regen = list(req.prompt[req.orig_prompt_len:])
            if (req.eos_id is not None and req.eos_id in out) \
                    or remaining <= 0:
                finished.append(RequestResult(
                    rid=req.rid, prompt_len=req.orig_prompt_len,
                    tokens=regen + out, arrival_time=req.arrival_time,
                    first_token_time=seq.first_token_time, finish_time=now,
                    preempted=self._preempt_counts.pop(req.rid, 0)))
                self.telemetry.requests.finish(
                    req.rid, "complete", tokens=len(regen) + len(out),
                    replica=self.replica_id, hists=self._m.latency)
                continue
            # recompute fold, exactly like preemption: position-stable
            # keys make the continuation replica-independent
            req.prompt = np.concatenate(
                [req.prompt, np.asarray(out, np.int32)])
            req.max_new_tokens = remaining
            requests.append(req)
        requests.extend(self.scheduler.reset())
        self._live = []
        self._pending.clear()
        self.kv.release_all()
        if self.state_slots is not None:
            self.state_slots.release_all()
            self._m.state_slots_free.set(self.state_slots.num_free)
        self._free_slots = list(range(self.cfg.num_slots - 1, -1, -1))
        self._first_token_times.clear()
        self._progress_tokens.clear()
        self._m.live_seqs.set(0)
        return requests, finished

    # -- in-flight bookkeeping ----------------------------------------------

    def _note_tokens(self, rid: int, n: int) -> None:
        """Account ``n`` tokens MATERIALIZED for ``rid`` — called at
        fetch, not dispatch, so optimistic steps whose tokens are
        discarded (past an eos, or refused by the device capacity
        predicate) never inflate ``generated_tokens`` or the router
        progress quanta."""
        self._progress_tokens[rid] = self._progress_tokens.get(rid, 0) + n
        self._m.generated_tokens.inc(n)

    def drain_progress(self) -> Dict[int, int]:
        """Tokens materialized per request since the last drain — the
        dispatcher feeds these to ``ReplicaRouter.progress`` so routed
        load decays in N-token quanta instead of only at completion."""
        out, self._progress_tokens = self._progress_tokens, {}
        return out

    def _fetch_one(self, finished: List[RequestResult]) -> None:
        """Materialize the oldest dispatched step's tokens on host,
        reconcile stop conditions the device applied (eos, loop
        truncation), and evict sequences whose last token just landed.

        Dispatch is optimistic: steps may already be in flight for a
        sequence that — we now learn — hit eos.  Those later records'
        tokens for it are discarded (the ``seq not in _live`` guard); the
        junk they compute on device lands in the trash block / trash
        slot / spare token slot or in blocks that are rewritten before
        any live query attends them, so nothing live is perturbed."""
        rec = self._pending.popleft()
        with self.telemetry.tracer.span("engine", "fetch", step=rec.step):
            toks = np.asarray(rec.toks)        # sync point
            if rec.counts is not None:         # N-step decode-loop record
                counts = np.asarray(rec.counts)
                eos_hit = np.asarray(rec.eos_hit)
        self._m.host_syncs.inc()
        if rec.counts is not None:
            for row, seq, _ in rec.emits:
                if seq not in self._live or seq.desync:
                    continue                   # evicted by an earlier fetch
                c = int(counts[row])
                seq.out.extend(int(t) for t in toks[row, :c])
                self._note_tokens(seq.req.rid, c)
                planned = rec.planned[row]
                if eos_hit[row]:
                    seq.done = True
                    seq.gen_count = len(seq.out)
                elif c < planned:
                    # the device's capacity predicate refused steps the
                    # host had reserved (defensive — the two are derived
                    # from the same table).  Roll the optimistic count
                    # back — including any done-by-count verdict, which
                    # was reached counting steps the device refused —
                    # and mark for recompute: any already-dispatched
                    # follow-up ran from wrong positions, so the flush
                    # preempts the sequence back to host-known tokens.
                    seq.gen_count -= planned - c
                    seq.done = False
                    seq.desync = True
                    self._desynced.append(seq)
                if seq.done and len(seq.out) >= seq.gen_count \
                        and seq in self._live:
                    self._evict(seq, rec.now, finished)
            return
        for row, seq, is_first in rec.emits:
            if seq not in self._live or seq.desync:
                continue                       # evicted by an earlier fetch
            tok = int(toks[row])
            seq.out.append(tok)
            self._note_tokens(seq.req.rid, 1)
            if is_first:
                # a recomputed (preempted) request already delivered its
                # first token before eviction — keep the original TTFT
                seq.first_token_time = self._first_token_times.pop(
                    seq.req.rid, rec.now)
                self.telemetry.requests.stamp(seq.req.rid, "first_token")
            if (seq.req.eos_id is not None and tok == seq.req.eos_id
                    and not seq.done):
                # eos discovered after later steps were optimistically
                # dispatched: keep everything up to (and incl.) the eos,
                # discard the speculative rest
                seq.done = True
                seq.gen_count = len(seq.out)
            if seq.done and len(seq.out) >= seq.gen_count \
                    and seq in self._live:
                self._evict(seq, rec.now, finished)

    def _flush(self, finished: List[RequestResult]) -> None:
        while self._pending:
            self._fetch_one(finished)
        if self._desynced:
            for seq in self._desynced:
                if seq in self._live:
                    # a desynced sequence is never legitimately finished
                    # (its optimistic bookkeeping counted steps the
                    # device refused, and later records were discarded)
                    # — recompute unconditionally restores exact state
                    seq.done = False
                    self._preempt_seq(seq)
                seq.desync = False
            self._desynced.clear()

    # -- fused step ---------------------------------------------------------

    def _dispatch(self, tokens, meta, tables):
        """One fused call.  tokens (B,C), meta (6,B) packed
        pos/valid/src/dst/state_slot/rid, tables (B,NB) — three
        host->device transfers total; the layer broadcast of the tables
        happens inside the jit.  Returns the (B,) sampled tokens; no
        logits ever leave the device."""
        self._m.model_calls.inc()
        toks, self._slot_buf, self.cache = self._step_fn(
            self.params, self.cache, self._slot_buf, tokens, tables, meta)
        return toks

    def _step_fused(self, now: float, finished: List[RequestResult]) -> None:
        cfg = self.cfg
        span = self.telemetry.tracer.span
        if self._desynced:
            # a device-truncated sequence has mis-positioned dispatches
            # in flight; resolve (flush + recompute) before planning
            self._flush(finished)
        with span("engine", "schedule"):
            plan = self.scheduler.schedule(len(self._live), self.kv)
        active = [s for s in self._live
                  if s.prefill_done and not s.done][:cfg.max_batch]
        if cfg.steps_per_dispatch > 1 and active and not plan:
            # decode-only regime: run N steps per dispatch entirely on
            # device.  Prefill/mixed steps stay single-step calls —
            # admission and preemption only happen at these dispatch
            # boundaries, every N tokens.
            self._dispatch_decode_loop(active, now, finished)
            return
        # grow each decoding sequence's table to cover the token being
        # written; preempt LIFO victims if the pool is out of blocks
        with span("engine", "reserve"):
            for seq in active:
                if seq not in self._live:
                    # a preemption on an earlier row's behalf evicted this
                    # one — growing its table now would hand the just-freed
                    # blocks straight back to the dead rid
                    continue
                while not self.kv.ensure_capacity(
                        seq.req.rid, seq.next_pos + 1,
                        query_start=seq.next_pos):
                    if self._pending:
                        # finished-but-unfetched sequences may be holding
                        # blocks; materialize them before sacrificing a
                        # victim (preemption also needs token values on host)
                        self._flush(finished)
                        continue
                    if not self._preempt_one(exclude_rid=seq.req.rid):
                        raise RuntimeError(
                            "KV pool too small for a single sequence; raise "
                            "num_blocks or lower max_seq_len")
        # preemption (or an eos eviction inside the flush) may have
        # removed members of `active` or owners of planned chunks
        active = [s for s in active if s in self._live]
        plan = [ch for ch in plan if self.scheduler.planned(ch.req)]
        if not active and not plan:
            self._flush(finished)
            return

        # Nothing forces a synchronous fetch anymore: sampling
        # (temperature/top-k included) happens on device, and eos
        # stopping is optimistic — the engine keeps dispatching and
        # discards any tokens past the eos when the fetch reveals it.

        # ONE fused fixed-shape call per step; the row layout adapts to
        # the step's composition, each shape matching the cheapest legacy
        # layout for that regime or beating it:
        #   decode-only  -> (bucket, 1): the plain batched-decode shape;
        #   prefill-only -> (prefill_rows, chunk): chunk-wide rows (the
        #                   fused call handles C>1 via per-row valid_len),
        #                   same shape the legacy prefill call used —
        #                   fewer rows means fewer per-row KV-pool
        #                   gathers;
        #   mixed        -> (bucket, 1): width-1 rows with prefill chunks
        #                   SPLIT into one row per token, in consecutive
        #                   rows with one table and consecutive
        #                   positions, so the paged attend reads a
        #                   chunk as one query run over its live pages
        #                   (and each decode row as a run of one), not
        #                   each row over the whole table; ONE dispatch
        #                   instead of padding every decode row to the
        #                   chunk width.  Chunk token i attends its
        #                   siblings' KV because every row's scatter
        #                   lands before any row's gather within the
        #                   call.  Slot-state families
        #                   (ssm/rglru) can't split — a token's recurrent
        #                   state depends on the previous token *within
        #                   the call* — so their mixed layout keeps
        #                   chunk-wide prefill rows and pads decode rows
        #                   to the chunk width (valid_len=1).
        with span("engine", "build"):
            n_dec = len(active)
            n_pre = sum(ch.length for ch in plan)
            if n_pre == 0:
                rows, width = min(k for k in cfg.decode_buckets
                                  if k >= n_dec), 1
            elif n_dec == 0:
                rows, width = cfg.prefill_rows, cfg.prefill_chunk
            elif self.spec.width1_mixed:
                rows, width = min(k for k in cfg.mixed_buckets
                                  if k >= n_dec + n_pre), 1
            else:
                rows, width = cfg.mixed_chunk_rows, cfg.prefill_chunk
            tokens = np.zeros((rows, width), np.int32)
            meta = np.zeros((6, rows), np.int32)
            meta[2:4] = -1
            pos, valid, src, dst, state, rid_row = meta
            rids: List[Optional[int]] = [None] * rows
            emits: List[Tuple[int, _Seq, bool]] = []
            slot_of = (self.state_slots.slot_of if self.state_slots is not None
                       else lambda rid: 0)

            for row, seq in enumerate(active):
                pos[row] = seq.next_pos
                valid[row] = 1
                rids[row] = seq.req.rid
                rid_row[row] = seq.req.rid
                state[row] = slot_of(seq.req.rid)
                dst[row] = seq.slot
                # the slot buffer always holds this sequence's latest
                # sampled token (greedy AND stochastic — sampling is on
                # device) — no host round-trip
                src[row] = seq.slot
                emits.append((row, seq, False))
                self.telemetry.requests.note_dispatch(seq.req.rid)
                seq.gen_count += 1
                if seq.gen_count >= seq.req.max_new_tokens:
                    seq.done = True
            row = n_dec
            for ch in plan:
                seq = self._seq_of(ch.req.rid)
                if seq is None:                    # fresh admission
                    seq = self._admit(ch.req)
                self._m.prefill_tokens.inc(ch.length)
                self.telemetry.requests.stamp(ch.req.rid, "prefill_start")
                completes = ch.start + ch.length >= len(ch.req.prompt)
                chunk_tok = ch.req.prompt[ch.start:ch.start + ch.length]
                if width > 1:                      # chunk-wide: one row/chunk
                    tokens[row, :ch.length] = chunk_tok
                    pos[row] = ch.start
                    valid[row] = ch.length
                    rids[row] = ch.req.rid
                    rid_row[row] = ch.req.rid
                    state[row] = slot_of(ch.req.rid)
                    if completes:
                        # prompt complete: the frontier logit is the first
                        # generated token
                        dst[row] = seq.slot
                        seq.prefill_done = True
                        emits.append((row, seq, True))
                        seq.gen_count += 1
                        if seq.gen_count >= seq.req.max_new_tokens:
                            seq.done = True
                    row += 1
                    continue
                for i in range(ch.length):         # mixed: one row/token
                    tokens[row, 0] = chunk_tok[i]
                    pos[row] = ch.start + i
                    valid[row] = 1
                    rids[row] = ch.req.rid
                    rid_row[row] = ch.req.rid
                    if completes and i == ch.length - 1:
                        dst[row] = seq.slot
                        seq.prefill_done = True
                        emits.append((row, seq, True))
                        seq.gen_count += 1
                        if seq.gen_count >= seq.req.max_new_tokens:
                            seq.done = True
                    row += 1

            phase = ("decode" if n_pre == 0
                     else "prefill" if n_dec == 0 else "mixed")
            tables = self.kv.table_array(rids)
            if self._query_tiles is not None:
                qt = self._query_tiles(tables, pos, width,
                                       block_size=cfg.block_size,
                                       window=self.spec.reclaim_window)
                self._m.paged_query_runs.inc(int(qt.n_runs))
                self._m.paged_kv_pages.inc(int(qt.n_pages))
        with span("engine", "dispatch", phase=phase, rows=rows, width=width):
            t0 = time.perf_counter()
            toks = self._dispatch(tokens, meta, tables)
            t1 = time.perf_counter()
        self._m.dispatch_s[phase].observe(t1 - t0)
        if n_dec:
            self._m.decode_steps.inc()
            self._m.decode_slot_steps.inc(rows if n_pre == 0
                                          else cfg.max_batch)
            self._m.decode_active_slot_steps.inc(n_dec)
        self._pending.append(_Inflight(toks, emits, now,
                                       step=self._m.steps.value))
        if not cfg.pipeline:
            self._flush(finished)
        else:
            # depth-1 pipeline: this step computes while the host reads
            # the previous step's tokens and plans the next
            while len(self._pending) > 1:
                self._fetch_one(finished)

    def _dispatch_decode_loop(self, active: List[_Seq], now: float,
                              finished: List[RequestResult]) -> None:
        """One N-step on-device decode dispatch (N =
        ``steps_per_dispatch``): reserve per-row headroom for up to N
        tokens (blocks for block-pool families, metered tokens for
        slot-state families), hand the device per-row step budgets, and
        read back a packed (rows, N) token buffer one dispatch later.

        Headroom reservation rules: a row asks for min(N, max_new
        remaining) steps; ``PagedKVCache.reserve`` grants as many
        leading positions as the pool can back (reclaiming dead
        sliding-window blocks first), partial grants are used in full
        this same dispatch, and a row that can't even get one step
        triggers the flush-then-preempt path.  The device's own
        capacity predicate (trash frontier entry) enforces the same
        boundary, so a partially-granted row exits its loop early
        instead of writing through the trash block."""
        cfg = self.cfg
        span = self.telemetry.tracer.span
        n_steps = cfg.steps_per_dispatch
        grants: Dict[int, Tuple[int, int]] = {}    # rid -> (want, granted)
        with span("engine", "reserve"):
            for seq in active:
                if seq not in self._live:
                    continue     # evicted/preempted on an earlier row's behalf
                want = min(n_steps, seq.req.max_new_tokens - seq.gen_count)
                while True:
                    covered = self.kv.reserve(seq.req.rid,
                                              seq.next_pos + want,
                                              query_start=seq.next_pos)
                    granted = min(want, covered - seq.next_pos)
                    if granted >= 1:
                        break
                    if self._pending:
                        # finished-but-unfetched sequences may be holding
                        # blocks; materialize them before sacrificing a
                        # victim
                        self._flush(finished)
                        if seq not in self._live:
                            break
                        continue
                    if not self._preempt_one(exclude_rid=seq.req.rid):
                        raise RuntimeError(
                            "KV pool too small for a single sequence; raise "
                            "num_blocks or lower max_seq_len")
                if seq in self._live:
                    grants[seq.req.rid] = (want, granted)
        rows_seqs = [s for s in active
                     if s in self._live and s.req.rid in grants]
        if not rows_seqs:
            self._flush(finished)
            return
        with span("engine", "build"):
            rows = min(k for k in cfg.decode_buckets if k >= len(rows_seqs))
            meta = np.zeros((6, rows), np.int32)
            pos0, steps, slot, state, rid_row, eos = meta
            eos[:] = -1
            slot_of = (self.state_slots.slot_of if self.state_slots is not None
                       else lambda rid: 0)
            emits: List[Tuple[int, _Seq, bool]] = []
            planned: Dict[int, int] = {}
            rids: List[Optional[int]] = [None] * rows
            for row, seq in enumerate(rows_seqs):
                want, granted = grants[seq.req.rid]
                pos0[row] = seq.next_pos
                steps[row] = granted
                slot[row] = seq.slot
                state[row] = slot_of(seq.req.rid)
                rid_row[row] = seq.req.rid
                eos[row] = (-1 if seq.req.eos_id is None else seq.req.eos_id)
                rids[row] = seq.req.rid
                if granted < want:
                    self._m.loop_truncations.inc()
                planned[row] = granted
                emits.append((row, seq, False))
                self.telemetry.requests.note_dispatch(seq.req.rid)
                seq.gen_count += granted
                if seq.gen_count >= seq.req.max_new_tokens:
                    seq.done = True
            tables = self.kv.table_array(rids)
        self._m.model_calls.inc()
        self._m.loop_dispatches.inc()
        max_granted = max(planned.values())
        self._m.decode_steps.inc(max_granted)
        self._m.decode_slot_steps.inc(rows * max_granted)
        self._m.decode_active_slot_steps.inc(sum(planned.values()))
        with span("engine", "dispatch", phase="loop", rows=rows, width=1,
                  steps=n_steps):
            t0 = time.perf_counter()
            out, counts, eos_hit, self._slot_buf, self.cache = self._loop_fn(
                self.params, self.cache, self._slot_buf, tables, meta)
            t1 = time.perf_counter()
        self._m.dispatch_s["loop"].observe(t1 - t0)
        self._pending.append(_Inflight(out, emits, now, counts=counts,
                                       eos_hit=eos_hit, planned=planned,
                                       step=self._m.steps.value))
        if not cfg.pipeline:
            self._flush(finished)
        else:
            # depth-1 pipeline over depth-N loops: this N-step loop
            # computes while the host reads the previous loop's packed
            # tokens and plans the next dispatch
            while len(self._pending) > 1:
                self._fetch_one(finished)

    # -- legacy two-call step (PR-1 baseline, kept for benchmarking) --------

    def _run_model_legacy(self, tokens: np.ndarray, pos: np.ndarray,
                          tables: np.ndarray):
        self._m.model_calls.inc()
        self._m.host_syncs.inc()
        cache = transformer.with_block_tables(self.cache,
                                              jnp.asarray(tables))
        logits, self.cache = self._legacy_fn(
            self.params, cache, jnp.asarray(tokens), jnp.asarray(pos))
        return np.asarray(jax.device_get(logits), np.float32)

    def _prefill_legacy(self, chunks, now: float,
                        finished: List[RequestResult]) -> None:
        if not chunks:
            return
        b, c = self.cfg.prefill_rows, self.cfg.prefill_chunk
        assert len(chunks) <= b
        tokens = np.zeros((b, c), np.int32)
        pos = np.zeros((b,), np.int32)
        rids: List[Optional[int]] = [None] * b
        for row, ch in enumerate(chunks):
            tokens[row, :ch.length] = \
                ch.req.prompt[ch.start:ch.start + ch.length]
            pos[row] = ch.start
            rids[row] = ch.req.rid
            if self._seq_of(ch.req.rid) is None:     # fresh admission
                self._admit(ch.req)
        logits = self._run_model_legacy(tokens, pos,
                                        self.kv.table_array(rids))
        for row, ch in enumerate(chunks):
            self._m.prefill_tokens.inc(ch.length)
            self.telemetry.requests.stamp(ch.req.rid, "prefill_start")
            if ch.start + ch.length >= len(ch.req.prompt):
                seq = self._seq_of(ch.req.rid)
                tok = self._sample(logits[row, ch.length - 1])
                seq.out.append(tok)
                seq.gen_count = len(seq.out)
                seq.prefill_done = True
                seq.first_token_time = self._first_token_times.pop(
                    ch.req.rid, now)
                self.telemetry.requests.stamp(ch.req.rid, "first_token")
                self._m.generated_tokens.inc()
                if (len(seq.out) >= seq.req.max_new_tokens
                        or (seq.req.eos_id is not None
                            and tok == seq.req.eos_id)):
                    self._evict(seq, now, finished)

    def _decode_legacy(self, now: float,
                       finished: List[RequestResult]) -> None:
        active = [s for s in self._live if s.prefill_done]
        active = active[:self.cfg.max_batch]
        if not active:
            return
        for seq in active:
            if seq not in self._live:   # evicted by an earlier preemption
                continue
            while not self.kv.ensure_capacity(seq.req.rid, seq.next_pos + 1,
                                              query_start=seq.next_pos):
                if not self._preempt_one(exclude_rid=seq.req.rid):
                    raise RuntimeError(
                        "KV pool too small for a single sequence; raise "
                        "num_blocks or lower max_seq_len")
        active = [s for s in active if s in self._live]
        if not active:
            return
        b = min(k for k in self.cfg.decode_buckets if k >= len(active))
        tokens = np.zeros((b, 1), np.int32)
        pos = np.zeros((b,), np.int32)
        rids: List[Optional[int]] = [None] * b
        for row, seq in enumerate(active):
            tokens[row, 0] = seq.out[-1]
            pos[row] = seq.next_pos
            rids[row] = seq.req.rid
        logits = self._run_model_legacy(tokens, pos,
                                        self.kv.table_array(rids))
        self._m.decode_steps.inc()
        self._m.decode_slot_steps.inc(b)
        self._m.decode_active_slot_steps.inc(len(active))
        for row, seq in enumerate(active):
            tok = self._sample(logits[row, 0])
            seq.out.append(tok)
            seq.gen_count = len(seq.out)
            self._m.generated_tokens.inc()
            done = (len(seq.out) >= seq.req.max_new_tokens
                    or (seq.req.eos_id is not None
                        and tok == seq.req.eos_id))
            if done:
                self._evict(seq, now, finished)

    # -- public loop --------------------------------------------------------

    def warmup(self) -> None:
        """Compile every fixed shape this engine can emit against the
        trash block, so no XLA compile lands mid-serving.  Cache contents
        are untouched: writes go to block 0 and no sequence state exists
        yet (valid_len 0 masks every write there anyway)."""
        shapes = [(b, 1) for b in self.cfg.decode_buckets]
        shapes += [(self.cfg.prefill_rows, self.cfg.prefill_chunk)]
        if self.cfg.fused:
            if self.spec.width1_mixed:
                shapes += [(b, 1) for b in self.cfg.mixed_buckets]
            else:
                shapes += [(self.cfg.mixed_chunk_rows,
                            self.cfg.prefill_chunk)]
        for rows, width in shapes:
            tables = self.kv.table_array([None] * rows)
            if self.cfg.fused:
                meta = np.zeros((6, rows), np.int32)
                meta[2:4] = -1
                toks = self._dispatch(np.zeros((rows, width), np.int32),
                                      meta, tables)
                jax.block_until_ready(toks)
            else:
                self._run_model_legacy(np.zeros((rows, width), np.int32),
                                       np.zeros((rows,), np.int32), tables)
        if self._loop_fn is not None:
            # the N-step loop compiles once per decode bucket; a meta of
            # all-zero step budgets keeps every row inactive, so the
            # trace touches only the trash block/slot
            for rows in self.cfg.decode_buckets:
                meta = np.zeros((6, rows), np.int32)
                meta[5] = -1
                out, _, _, self._slot_buf, self.cache = self._loop_fn(
                    self.params, self.cache, self._slot_buf,
                    self.kv.table_array([None] * rows), meta)
                jax.block_until_ready(out)
        if self.mesh is not None and self.cfg.fused:
            self._note_tp_collectives()
        # compile dispatches are not serving work — keep the calls/syncs
        # telemetry about the traffic itself, the dispatch-time
        # histograms free of compile outliers, and re-baseline the
        # jit-compile watermark so only MID-SERVING compiles (the bug
        # class the jit_compiles metric guards against) count
        for h in (self._m.model_calls, self._m.host_syncs,
                  self._m.loop_dispatches, self._m.jit_compiles):
            h.reset()
        for h in self._m.dispatch_s.values():
            h.reset()
        self._jit_cache_seen = self._jit_cache_total(self._jit_fns())

    @property
    def has_work(self) -> bool:
        return (self.scheduler.has_waiting or bool(self._live)
                or bool(self._pending))

    def device_wait(self) -> None:
        """Block until every dispatched step's device work has finished
        (without fetching or applying stop conditions).  Benchmarks that
        interleave two engines on one device use this at block
        boundaries so in-flight (pipelined) work is charged to the
        engine that dispatched it, not to whichever engine's timer runs
        while the device drains it."""
        if self._pending:
            jax.block_until_ready(self._pending[-1].toks)

    def step(self, now: Optional[float] = None) -> List[RequestResult]:
        """One engine iteration; returns requests finished this step.
        With tracing on it is the ``repro:engine.step`` span, numbered
        as the ``steps`` counter counts; the dispatch it makes is
        fetched under an ``engine.fetch`` span carrying that number."""
        now = time.perf_counter() if now is None else now
        finished: List[RequestResult] = []
        span = self.telemetry.tracer.span
        with span("engine", "step", step=self._m.steps.value):
            if self._has_deadlines:
                self._expire_deadlines(finished)
            if self.cfg.fused:
                self._step_fused(now, finished)
            else:
                with span("engine", "schedule"):
                    plan = self.scheduler.schedule(len(self._live), self.kv)
                self._prefill_legacy(plan, now, finished)
                self._decode_legacy(now, finished)
            self._m.steps.inc()
            self._note_compiles()
        return finished

    def run(self, requests: Sequence[Request] = (),
            request_queue: Optional[RequestQueue] = None,
            max_steps: Optional[int] = None) -> Dict[int, RequestResult]:
        """Drive until all submitted work (and the queue, if given) is
        exhausted.  Returns {rid: RequestResult}."""
        for r in requests:
            self.submit(r)
        results: Dict[int, RequestResult] = {}
        steps = 0
        while True:
            if request_queue is not None:
                for r in request_queue.drain():
                    self.submit(r)
            if not self.has_work:
                if request_queue is None or request_queue.exhausted:
                    break
                time.sleep(0.0005)   # idle: wait for producers
                continue
            for res in self.step():
                results[res.rid] = res
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return results
