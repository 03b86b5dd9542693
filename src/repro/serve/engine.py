"""Batched serving engine: slot-based continuous batching over the
decode step, with contiguous or paged KV.

A fixed pool of B slots shares one jitted ``decode_step``. Requests are
admitted into free slots, decode ticks advance every active slot by one
token, and finished slots (EOS or max_tokens) are freed for the next
queued request — so throughput stays at the batch width even with ragged
request lengths (the vLLM scheduling idea).

Two cache disciplines:

  * **contiguous** (``paged=False``) — every slot owns a private
    ``max_len`` cache lane and all slots share one tick counter (the
    cache write position). Late-admitted requests replay their prompts at
    shifted positions over a lane that still holds the previous
    occupant's KV below the admission tick, so recycled slots are
    approximate; the tick counter also bounds the *total* run length at
    ``max_len``. This path stays as the parity oracle for first-wave
    slots and for the pim-vs-jit backends.
  * **paged** (``paged=True``) — KV lives in a shared block pool
    (``repro.serve.kv.PagedKVCache``); slots hold block tables and
    *per-slot* positions. Recycled slots restart at position 0 with fresh
    blocks (exact, not approximate), capacity is provisioned in blocks
    rather than worst-case lanes, and requests whose prompts extend a
    cached prefix skip replaying the shared full blocks entirely.

The engine can be driven whole (``run``) or tick-by-tick (``tick_once``)
— the latter is how ``repro.serve.router.Router`` interleaves several
engines. ``run``'s default tick budget scales with the total remaining
work (sum of unreplayed prompt + ungenerated tokens), not with
``max_len``: a deep queue of short requests drains through slot
recycling on the paged path. The contiguous path additionally stops when
the shared tick reaches its lane bound — that is capacity exhaustion,
reported as starvation.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig
from repro.core import quant
from repro.models.transformer import DecoderLM, build_model
from repro.serve import kv as kv_mod
from repro.serve.kv import KVCacheOOM, PagedKVCache


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # [L] int32
    max_tokens: int = 16
    eos: int | None = None
    # SLO class: preemption victims are picked from the *lowest* class
    # first (youngest admission within a class); the default 0 for every
    # request preserves plain youngest-first
    priority: int = 0
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # wall-clock stamps (time.monotonic): submit / first generated token /
    # completion — the raw material of the TTFT/TPOT histograms
    t_submit: float | None = None
    t_first: float | None = None
    t_done: float | None = None
    # virtual-clock stamps (decode ticks): arrival set by the workload
    # generator, first/done stamped by the replay driver — TTFT measured
    # from *arrival*, queue wait included (repro.serve.workload)
    t_arrival: float | None = None
    first_tick: int | None = None
    done_tick: int | None = None
    # preemption: bumped per swap-out; ``resume`` holds the engine's saved
    # decode state + scratch pages between swap-out and re-admission
    preemptions: int = 0
    resume: dict | None = dataclasses.field(default=None, repr=False)

    @property
    def ttft_s(self) -> float | None:
        """Time to first token (None until one is generated)."""
        if self.t_submit is None or self.t_first is None:
            return None
        return self.t_first - self.t_submit

    @property
    def tpot_s(self) -> float | None:
        """Mean time per output token after the first (needs >= 2)."""
        if self.t_first is None or self.t_done is None or len(self.out) < 2:
            return None
        return (self.t_done - self.t_first) / (len(self.out) - 1)

    @property
    def ttft_ticks(self) -> float | None:
        """Virtual-clock TTFT: decode ticks from arrival to first token
        (None until the replay driver stamps both ends)."""
        if self.t_arrival is None or self.first_tick is None:
            return None
        return self.first_tick - self.t_arrival


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, *, batch: int = 4,
                 max_len: int = 128, sample: Callable | None = None,
                 backend: str = "jit", pim_tech: str = "proposed",
                 weight_dtype: str = "fp32",
                 partitions: int = 1, microbatches: int = 8,
                 paged: bool = False, kv_blocks: int | None = None,
                 kv_block_size: int = 16, prefill: str = "replay",
                 attn_kernel: bool = False,
                 pim_compile: dict | None = None,
                 expand_scans: bool = False,
                 scheduler: str = "continuous",
                 admission: str | None = None,
                 preempt: bool = True,
                 kv_dtype: str = "fp32",
                 act_dtype: str = "fp32"):
        """``backend="jit"`` jits the decode step; ``backend="pim"`` maps
        it onto the PIM hierarchy and decodes through the compiled
        schedule (``repro.mapper.compile``) — placed matmuls run as
        blocked ``pim_matmul`` calls per resident weight block.

        ``paged=True`` swaps the contiguous per-slot cache lanes for a
        paged block pool: ``kv_blocks`` physical blocks of
        ``kv_block_size`` tokens (default: scratch + ``batch *
        ceil(max_len / kv_block_size)``, i.e. contiguous-equivalent
        capacity — pass fewer to actually oversubscribe). On the pim
        backend the KV pool is additionally *placed* onto subarrays near
        the attention consumers and its per-tick block traffic is priced
        into the schedule (``self.schedule.kv``).

        ``partitions=K`` (pim backend only) compiles the decode step as K
        pipeline partition programs with explicit transfer points and
        decodes through them (token-identical to the unpartitioned
        program: same equations, same order). ``expand_scans=True``
        expands the scanned layer stack into resident per-layer copies
        first (``mapper.expand_graph``), so the K cut points can land
        *inside* the stack — without it a deep decoder partitions into
        one monolithic stage. When ``pim_compile`` carries ``devices``,
        each stage is pinned to its own JAX device and decode runs
        through the async chain (``PartitionedProgram.run_async``). ``microbatches`` sets the
        streaming depth of the modeled microbatch timeline exposed as
        ``self.pipeline_timeline`` (steady-state decode throughput of the
        partitioned plan — ``Schedule.pipeline``).

        ``prefill="batch"`` (paged only) admits a prompt by writing whole
        KV blocks in one shot (``DecoderLM.prefill_paged``) instead of
        replaying it token by token through the decode step — one call
        per admission rather than one tick per prompt token; the decode
        tick that feeds the final prompt token (and samples the first
        output) is unchanged. ``attn_kernel=True`` (paged only) runs
        every decode site's KV gather + attention through the grouped
        paged Pallas kernel — one launch covering all slots, blocks
        streamed via the scalar-prefetched block table.

        ``weight_dtype`` (pim backend only) stores placed weights on a
        reduced-precision grid (``int8`` / ``fp8_e4m3`` / ``fp8_e5m2``
        / ``fp16``): weights pack denser per subarray, the freed area
        becomes extra throughput replicas of the hottest layers, and
        placed matmuls dequantize on load with fp32 accumulation
        (``repro.core.quant``).

        ``kv_dtype`` (paged only) stores the KV pool on a reduced grid:
        packed absmax-scaled codes plus one f32 scale per (token,
        kv-head) vector (``quant.quantize_kv``), dequantized on gather
        with f32 score accumulation. The same pool bytes hold ~2-4x more
        blocks — pass the equal-bytes block count via ``kv_blocks``
        (see ``repro.serve.kv.blocks_for_bytes``) to convert that into
        ``admission="kv"`` headroom. Swap/CoW/prefix-share round-trip
        codes+scales bit-exactly; on the pim backend KV traffic is
        priced at the reduced width. ``act_dtype`` (pim backend only)
        prices inter-subarray activation transfers at a reduced width
        (``Schedule.act_bits``); fp32 for both keeps today's paths
        bit-identical.

        ``pim_compile`` forwards knobs to the schedule compiler (e.g.
        ``{"group": False, "fuse": False}`` for the legacy
        one-launch-per-block program — grouped launches model the
        hardware but serialize under CPU interpret emulation).

        Control-plane knobs:

        ``scheduler="continuous"`` (default) refills any slot the moment
        it frees — a finished slot is re-admitted *the same tick*;
        ``"static"`` is the wave-batching baseline (admit a full batch,
        drain it completely, admit the next), kept for the goodput
        benchmark. ``admission`` gates what the scheduler may admit:
        ``"kv"`` (paged default) admits the queue head only when the
        pool's free + evictable blocks cover the request's *peak* fresh
        footprint (prompt + max_tokens, minus cached shared prefix
        blocks) — oversubscribed offered load queues instead of OOMing;
        ``"slot"`` (contiguous default, and the pre-admission-control
        behavior) admits into any free slot. A request whose peak
        footprint exceeds the whole pool raises ``KVCacheOOM`` at
        admission — it could never run. ``preempt=True`` (paged default)
        arms preemption: when a decode tick cannot allocate a block, the
        youngest-admitted slot's pages are swapped out to host scratch
        (``PagedKVCache.swap_out``) and the request requeued at the
        front; re-admission migrates the pages back (``swap_in``) and
        decode resumes token-identically."""
        self.cfg = cfg
        self.model: DecoderLM = build_model(cfg)
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.backend = backend
        self.paged = paged
        self.slots: list[Request | None] = [None] * batch
        self.queue: deque[Request] = deque()
        self.sample = sample or (lambda logits: jnp.argmax(logits, -1))
        self.pim_program = None
        self.pipeline_timeline = None
        self.schedule = None
        self.kv_placement = None
        if partitions < 1 or microbatches < 1:
            raise ValueError("partitions and microbatches must be >= 1")
        if partitions > 1 and backend != "pim":
            raise ValueError("partitions require backend='pim' (the jit "
                             "backend has no partitioned plan)")
        if prefill not in ("replay", "batch"):
            raise ValueError(f"prefill must be 'replay' or 'batch', "
                             f"got {prefill!r}")
        if prefill == "batch" and not paged:
            raise ValueError("prefill='batch' requires paged=True (the "
                             "contiguous lanes have no block writes)")
        if attn_kernel and not paged:
            raise ValueError("attn_kernel=True requires paged=True (it is "
                             "the paged gather path)")
        if pim_compile and backend != "pim":
            raise ValueError("pim_compile only applies to backend='pim'")
        if weight_dtype != "fp32" and backend != "pim":
            raise ValueError(
                "weight_dtype only applies to backend='pim' (the jit "
                "backend has no placed weight grid to quantize)")
        self.kv_dtype = quant.spec(kv_dtype).name
        self.act_dtype = quant.spec(act_dtype).name
        if self.kv_dtype != "fp32" and not paged:
            raise ValueError(
                "kv_dtype only applies to paged=True (the contiguous "
                "lanes have no block pool to quantize)")
        if self.act_dtype != "fp32" and backend != "pim":
            raise ValueError(
                "act_dtype only applies to backend='pim' (it prices the "
                "schedule's inter-subarray transfers; the jit backend "
                "has no modeled NoC)")
        if scheduler not in ("continuous", "static"):
            raise ValueError(f"scheduler must be 'continuous' or "
                             f"'static', got {scheduler!r}")
        if admission is None:
            admission = "kv" if paged else "slot"
        if admission not in ("kv", "slot"):
            raise ValueError(f"admission must be 'kv' or 'slot', "
                             f"got {admission!r}")
        if admission == "kv" and not paged:
            raise ValueError("admission='kv' requires paged=True (the "
                             "contiguous lanes have no block pool to "
                             "gate on)")
        self.scheduler = scheduler
        self.admission = admission
        self.preempt = bool(preempt) and paged
        self.preemptions = 0
        self.resumes = 0
        self.swapped_blocks = 0   # pages currently on host scratch
        self.weight_dtype = weight_dtype
        self.prefill = prefill
        self.attn_kernel = attn_kernel
        self.expand_scans = expand_scans
        self.prefill_batched_tokens = 0
        self._pim_compile = dict(pim_compile or {})

        if paged:
            self.block_size = kv_block_size
            self.max_blocks = math.ceil(max_len / kv_block_size)
            if kv_blocks is None:
                kv_blocks = 1 + batch * self.max_blocks
            self.kv: PagedKVCache | None = PagedKVCache(
                kv_blocks, kv_block_size, batch, max_len,
                kv_dtype=self.kv_dtype)
            self.cache = self.model.init_paged_cache(
                kv_blocks, kv_block_size, kv_dtype=self.kv_dtype)
        else:
            self.kv = None
            self.cache = self.model.init_cache(batch, max_len)

        # per-token KV footprint (bytes, all attention sites) for the
        # bytes-moved accounting; 0 for non-attn patterns (no KV)
        if cfg.block_pattern == "attn":
            n = max(cfg.moe_interleave, 1) if cfg.n_experts else 1
            sites = self.model.layout.n_units * n
            itemsize = jnp.dtype(cfg.dtype).itemsize
            self._kv_sites = sites
            if self.kv_dtype == "fp32":
                self._tok_bytes = (sites * 2 * cfg.n_kv_heads
                                   * cfg.resolved_head_dim * itemsize)
            else:
                # quantized pool: packed codes + per-(token, head) scales
                self._tok_bytes = kv_mod.kv_token_bytes(
                    cfg.n_kv_heads, cfg.resolved_head_dim, sites,
                    self.kv_dtype)
        else:
            self._kv_sites = 0
            self._tok_bytes = 0
        self.kv_bytes_read = 0
        self.kv_bytes_written = 0
        self.prefix_skipped_tokens = 0

        if backend == "jit":
            self._decode = jax.jit(self._decode_impl_paged if paged
                                   else self._decode_impl)
        elif backend == "pim":
            self._build_pim(pim_tech, partitions, microbatches,
                            weight_dtype)
        else:
            raise ValueError(f"backend must be 'jit' or 'pim', "
                             f"got {backend!r}")
        # whole-block prompt admission (prefill='batch'): one jitted call
        # per admitted prompt, retraced only per padded-length bucket.
        # Shared by both backends — decode ticks still run through the
        # backend's own program, so pim-vs-jit token parity is preserved.
        self._prefill_fn = (
            jax.jit(functools.partial(self.model.prefill_paged,
                                      kv_dtype=self.kv_dtype))
            if paged and prefill == "batch" else None)
        self.completed: list[Request] = []
        self.starved: list[int] = []        # rids pending at last run() exit
        # per-slot decode state (persistent so tick_once can be driven
        # externally by the router)
        self._prompt_idx = np.zeros(batch, np.int64)
        self._last_tok = np.zeros(batch, np.int32)
        self._pos = np.zeros(batch, np.int32)    # paged: per-slot position
        self._tick = 0                           # contiguous: shared tick
        # admission order per slot (monotone): the preemption victim is
        # the youngest-admitted active slot — deterministic, and older
        # requests are never starved by later arrivals
        self._adm_seq = np.full(batch, -1, np.int64)
        self._adm_counter = 0
        # incrementally maintained total remaining work (see
        # ``pending_work``): O(1) per tick instead of O(queue)
        self._work = 0

    def _build_pim(self, pim_tech: str, partitions: int,
                   microbatches: int,
                   weight_dtype: str = "fp32") -> None:
        from repro import mapper
        if self.paged:
            args = (mapper.abstract_like(self.params),
                    mapper.abstract_like(self.cache),
                    jax.ShapeDtypeStruct((self.batch,), jnp.int32),
                    jax.ShapeDtypeStruct((self.batch, self.max_blocks),
                                         jnp.int32),
                    jax.ShapeDtypeStruct((self.batch,), jnp.int32))
            fn = self._decode_impl_paged
        else:
            args = (mapper.abstract_like(self.params),
                    mapper.abstract_like(self.cache),
                    jax.ShapeDtypeStruct((self.batch,), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32))
            fn = self._decode_impl
        sched = mapper.build_schedule(
            fn, *args, tech=pim_tech, weight_dtype=weight_dtype,
            act_dtype=self.act_dtype,
            partitions=partitions if partitions > 1 else None,
            expand_scans=self.expand_scans)
        if self.paged and self._kv_sites:
            # place the KV pool near its attention consumers and price
            # its per-tick block reads/writes into the schedule — at the
            # pool's own storage width (codes + scales when quantized)
            spec = mapper.KVBlockSpec(
                sites=self._kv_sites, num_blocks=self.kv.num_blocks,
                block_size=self.block_size,
                token_bits=kv_mod.kv_token_bits(
                    self.cfg.n_kv_heads, self.cfg.resolved_head_dim,
                    self.kv_dtype))
            with obs.span("place:kv", lane="compile"), \
                    obs.mapper_phase("place_kv"):
                self.kv_placement = mapper.place_kv(sched.graph,
                                                    sched.placement, spec)
                sched.attach_kv(self.kv_placement,
                                resident_tokens=max(1, self.max_len // 2),
                                batch=self.batch)
        self.schedule = sched
        # use_cache=False: the cache keys on fn identity and this is
        # a bound method — per-engine keys would never hit but would
        # pin the engine (params, KV cache) in the global cache
        if partitions > 1:
            self.pim_program = mapper.compile_partitioned(
                sched, use_cache=False, **self._pim_compile)
            self.pipeline_timeline = sched.pipeline(microbatches)
        else:
            self.pim_program = mapper.compile_schedule(
                sched, use_cache=False, **self._pim_compile)
        if getattr(self.pim_program, "stages", None) and any(
                st.device is not None for st in self.pim_program.stages):
            # device-pinned partitions: decode through the async chain so
            # each stage runs on its own device queue (bit-identical
            # tokens; the tick loop syncs when it reads the sampled ids)
            self._decode = self.pim_program.run_async
        else:
            self._decode = self.pim_program

    # one batched decode tick
    def _decode_impl(self, params, cache, tokens, pos):
        # NOTE: the shared cache is advanced with a single scalar position
        # per tick; slots joining mid-stream replay their prompts so all
        # active slots share the tick counter (contiguous-lane batching).
        return self.model.decode_step(params, cache, tokens, pos)

    def _decode_impl_paged(self, params, cache, tokens, block_table, pos):
        return self.model.decode_step_paged(params, cache, tokens,
                                            block_table, pos,
                                            kernel=self.attn_kernel,
                                            kv_dtype=self.kv_dtype)

    def submit(self, req: Request) -> None:
        if req.t_submit is None:      # router stamps before delegating
            req.t_submit = time.monotonic()
        obs.metrics().counter("serve.submitted").inc()
        self._work += self._work_of(req)
        self.queue.append(req)

    def prefix_lookup(self, prompt) -> int:
        """Prompt tokens this engine's paged cache already holds (0 when
        contiguous) — the router's prefix-affinity signal."""
        return self.kv.lookup_prefix(prompt) if self.paged else 0

    def kv_headroom(self) -> int:
        """Blocks the pool could hand out right now (free + evictable);
        effectively unbounded for contiguous engines — the router's
        KV-pressure routing signal."""
        return self.kv.available_blocks if self.paged else (1 << 30)

    def kv_blocks_needed(self, req: Request) -> int:
        """Fresh blocks admitting ``req`` here would eventually allocate
        (0 when contiguous)."""
        return (self.kv.blocks_needed(req.prompt, req.max_tokens)
                if self.paged else 0)

    @staticmethod
    def _work_of(req: Request) -> int:
        """Decode ticks this request still needs: unreplayed prompt
        tokens (resume state included) plus ungenerated tokens."""
        k = req.resume["prompt_idx"] if req.resume is not None else 0
        return (max(0, len(req.prompt) - 1 - k)
                + req.max_tokens - len(req.out))

    def pending_work(self) -> int:
        """Upper bound on the decode ticks needed to drain queue + slots:
        unreplayed prompt tokens plus ungenerated tokens. Maintained
        incrementally (O(1) per tick/submit) — deep queues don't pay an
        O(queue) rescan per tick or per routing decision."""
        return self._work

    def _pending_work_recompute(self) -> int:
        """O(queue + slots) reference for the incremental counter
        (tests assert they agree after churn/preemption)."""
        w = sum(self._work_of(r) for r in self.queue)
        for s, r in enumerate(self.slots):
            if r is not None:
                w += (max(0, len(r.prompt) - 1 - int(self._prompt_idx[s]))
                      + r.max_tokens - len(r.out))
        return w

    def pending_rids(self) -> list[int]:
        return ([r.rid for r in self.slots if r is not None]
                + [r.rid for r in self.queue])

    def _admissible(self, req: Request) -> bool:
        """KV-aware admission gate: admit only when the pool can cover
        the request's peak fresh-block footprint, keeping one spare block
        per already-active slot so imminent growth doesn't immediately
        preempt the admission (anti-thrash headroom)."""
        total = self.kv.total_blocks_for(len(req.prompt), req.max_tokens)
        if total > self.kv.allocatable_blocks:
            raise KVCacheOOM(
                f"request rid={req.rid} needs {total} KV blocks at peak "
                f"(prompt {len(req.prompt)} + max_tokens "
                f"{req.max_tokens}, block_size {self.block_size}) but the "
                f"pool only has {self.kv.allocatable_blocks} allocatable "
                f"blocks; raise kv_blocks or shrink the request")
        if total > self.kv.max_blocks:
            raise KVCacheOOM(
                f"request rid={req.rid} needs {total} KV blocks at peak "
                f"(prompt {len(req.prompt)} + max_tokens "
                f"{req.max_tokens}) but a slot's table holds only "
                f"{self.kv.max_blocks} blocks (max_len {self.max_len}); "
                f"raise max_len or shrink the request")
        reserve = sum(1 for r in self.slots if r is not None)
        needed = self.kv_blocks_needed(req)
        return self.kv.available_blocks >= needed + reserve

    def _admit(self) -> None:
        if self.scheduler == "static" and any(
                r is not None for r in self.slots):
            return          # wave batching: drain the batch first
        for s in range(self.batch):
            if self.slots[s] is None and self.queue:
                req = self.queue[0]
                if (self.paged and self.admission == "kv"
                        and not self._admissible(req)):
                    break   # FIFO: the head waits, nothing overtakes it
                self.queue.popleft()
                with obs.span("admit", lane="serve",
                              lazy=lambda: {"rid": req.rid, "slot": s}):
                    self.slots[s] = req
                    self._adm_seq[s] = self._adm_counter
                    self._adm_counter += 1
                    obs.metrics().counter("serve.admitted").inc()
                    # explicit per-slot state reset on (re)admission — a
                    # recycled slot must never rely on the prompt phase
                    # masking the previous occupant's sample/cursor
                    self._prompt_idx[s] = 0
                    self._last_tok[s] = 0
                    if self.paged and req.resume is not None:
                        self._resume_slot(s, req)
                    elif self.paged:
                        shared = self.kv.alloc_slot(s, req.prompt)
                        self._pos[s] = shared
                        self._prompt_idx[s] = shared   # skip cached prefix
                        self.prefix_skipped_tokens += shared
                        self._work -= shared
                        if self.prefill == "batch":
                            self._prefill_slot(s, req, shared)

    def _resume_slot(self, s: int, req: Request) -> None:
        """Re-admit a preempted request: migrate its scratch pages back
        into the pool and restore the saved decode cursor — the next tick
        continues exactly where the swap-out interrupted."""
        st = req.resume
        self.swapped_blocks -= st["pages"].n_blocks
        self.cache, _ = self.kv.swap_in(self.cache, s, req.prompt,
                                        st["pages"])
        self._pos[s] = st["pos"]
        self._prompt_idx[s] = st["prompt_idx"]
        self._last_tok[s] = st["last_tok"]
        req.resume = None
        self.resumes += 1
        obs.metrics().counter("serve.resumed").inc()
        tr = obs.tracer()
        if tr.enabled:
            tr.instant("resume", lane="serve", rid=req.rid, slot=s)

    def _preempt(self, s: int) -> None:
        """Swap the slot's KV pages out to host scratch, save its decode
        cursor on the request, and requeue it at the *front* — it resumes
        as soon as capacity frees, ahead of new arrivals."""
        req = self.slots[s]
        pages = self.kv.swap_out(self.cache, s)
        req.resume = dict(pages=pages, pos=int(self._pos[s]),
                          prompt_idx=int(self._prompt_idx[s]),
                          last_tok=int(self._last_tok[s]))
        req.preemptions += 1
        self.preemptions += 1
        self.swapped_blocks += pages.n_blocks
        obs.metrics().counter("serve.preempted").inc()
        tr = obs.tracer()
        if tr.enabled:
            tr.instant("preempt", lane="serve", rid=req.rid, slot=s,
                       blocks=pages.n_blocks)
        self.slots[s] = None
        self._adm_seq[s] = -1
        self._prompt_idx[s] = 0
        self._last_tok[s] = 0
        self._pos[s] = 0
        self.queue.appendleft(req)

    def _ensure_active(self, active: list[int]) -> list[int]:
        """Make every active slot's next position writable, swapping out
        victims when the pool runs dry: lowest ``priority`` class first,
        youngest admission within a class — all-default priorities
        reduce to plain youngest-first. Returns the surviving active
        slots. With ``preempt=False`` the allocator's ``KVCacheOOM``
        propagates — the legacy behavior."""
        # oldest admissions ensure first, so a same-class victim is
        # always younger than (or equal to) the slot that triggered the
        # shortfall
        for s in sorted(active, key=lambda s: self._adm_seq[s]):
            while self.slots[s] is not None:
                try:
                    self.cache = self.kv.ensure(self.cache, s,
                                                int(self._pos[s]))
                    break
                except KVCacheOOM:
                    if not self.preempt:
                        raise
                    victims = [v for v in range(self.batch)
                               if v != s and self.slots[v] is not None]
                    if not victims:
                        raise
                    self._preempt(max(
                        victims,
                        key=lambda v: (-self.slots[v].priority,
                                       self._adm_seq[v])))
        return [s for s in active if self.slots[s] is not None]

    def _prefill_slot(self, s: int, req: Request, p0: int) -> None:
        """Write the slot's uncached prompt KV (all but the final prompt
        token) into its blocks in one shot. Replaces ``n_new`` replayed
        decode ticks with a single jitted call; the subsequent decode
        tick feeds the final prompt token exactly as the replay path
        would."""
        n_new = len(req.prompt) - 1 - p0
        if n_new < 1:
            return
        bs = self.block_size
        t_pad = -(-n_new // bs) * bs            # bucket: bounded retraces
        with obs.span("prefill:batch", lane="serve",
                      lazy=lambda: {"rid": req.rid, "slot": s,
                                    "tokens": n_new, "bucket": t_pad}):
            self._prefill_slot_inner(s, req, p0, n_new, t_pad)
        obs.metrics().counter("serve.prefill_tokens").inc(n_new)

    def _prefill_slot_inner(self, s: int, req: Request, p0: int,
                            n_new: int, t_pad: int) -> None:
        bs = self.block_size
        # p0 is block-aligned (admission attaches whole cached blocks),
        # so one ensure/note_filled per covered block suffices
        for pos in range(p0, p0 + n_new, bs):   # allocate covering blocks
            self.cache = self.kv.ensure(self.cache, s, pos)
        toks = np.zeros(t_pad, np.int32)
        toks[:n_new] = req.prompt[p0:p0 + n_new]
        args = (self.params, self.cache, jnp.asarray(toks),
                self.kv.device_table()[s], jnp.int32(p0), jnp.int32(n_new))
        with obs.program("serve.prefill", t_pad):
            self.cache = self._prefill_fn(*args)
        for pos in range(p0 + bs - 1, p0 + n_new, bs):
            self.kv.note_filled(s, pos)         # register full prompt blocks
        self._pos[s] = p0 + n_new
        self._prompt_idx[s] = len(req.prompt) - 1
        self._work -= n_new          # prompt positions consumed tick-free
        self.prefill_batched_tokens += n_new
        self.kv_bytes_written += n_new * self._tok_bytes
        # block-granular reads, closed form: sum over the n_new written
        # positions of ceil((p0+i+1)/bs)*bs — p0 is block-aligned, so the
        # per-position ceil term is p0 + ceil(t/bs)*bs for t = 1..n_new
        full, rem = divmod(n_new, bs)
        ceil_sum = bs * (full * (full + 1) // 2) + rem * (full + 1)
        self.kv_bytes_read += (n_new * p0 + bs * ceil_sum) * self._tok_bytes

    def _recycle(self, s: int) -> None:
        """Free the slot and explicitly reset all of its decode state."""
        self.slots[s] = None
        self._adm_seq[s] = -1
        self._prompt_idx[s] = 0
        self._last_tok[s] = 0
        if self.paged:
            self.kv.free_slot(s)
            self._pos[s] = 0

    def step(self, tick: int, tokens: np.ndarray) -> np.ndarray:
        """Advance every slot one token (contiguous path); returns next
        tokens [B]."""
        args = (self.params, self.cache, jnp.asarray(tokens),
                jnp.int32(tick))
        with obs.program("serve.decode"):
            logits, self.cache = self._decode(*args)
        with obs.span("sample:sync", lane="serve"):
            return np.asarray(self.sample(logits), np.int32)

    def tick_once(self) -> bool:
        """Advance every active slot one token. Any slot that finishes is
        refilled from the queue *within this same tick* (continuous
        batching — see the trailing ``_admit``). Returns False when no
        progress is possible: nothing admitted, or — contiguous only —
        the shared tick reached the lane bound (capacity exhaustion)."""
        with obs.span("tick", lane="serve",
                      lazy=lambda: {"tick": self._tick}):
            self._admit()
            active = [s for s in range(self.batch)
                      if self.slots[s] is not None]
            if not active:
                return False
            if not self.paged and self._tick >= self.max_len - 1:
                return False      # shared lanes full; caller reports starved
            if self.paged:
                # writability first: this may preempt (swap out) victims, so
                # the feed is built only from the survivors
                active = self._ensure_active(active)
            feed = np.zeros(self.batch, np.int32)
            for s in active:
                req = self.slots[s]
                k = int(self._prompt_idx[s])
                feed[s] = (req.prompt[k] if k < len(req.prompt)
                           else self._last_tok[s])
            if self.paged:
                with obs.span("decode:tick", lane="serve",
                              lazy=lambda: self._decode_args(active)):
                    args = (self.params, self.cache, jnp.asarray(feed),
                            self.kv.device_table(), jnp.asarray(self._pos))
                    with obs.program("serve.decode"):
                        logits, self.cache = self._decode(*args)
                    with obs.span("sample:sync", lane="serve"):
                        nxt = np.asarray(self.sample(logits), np.int32)
                bs = self.block_size
                for s in active:
                    self.kv.note_filled(s, int(self._pos[s]))
                    self._pos[s] += 1
                    # block-granular read + one-token write per site
                    self.kv_bytes_read += (math.ceil(int(self._pos[s]) / bs)
                                           * bs * self._tok_bytes)
                self.kv_bytes_written += len(active) * self._tok_bytes
            else:
                with obs.span("decode:tick", lane="serve",
                              lazy=lambda: self._decode_args(active)):
                    nxt = self.step(self._tick, feed)
                # contiguous lanes stream their full provisioned length
                self.kv_bytes_read += len(active) * self.max_len \
                    * self._tok_bytes
                self.kv_bytes_written += len(active) * self._tok_bytes
            for s in active:
                req = self.slots[s]
                self._work -= 1        # one prompt or output token per tick
                if self._prompt_idx[s] < len(req.prompt) - 1:
                    self._prompt_idx[s] += 1
                else:
                    # gen: feed samples
                    self._prompt_idx[s] = len(req.prompt)
                    req.out.append(int(nxt[s]))
                    self._last_tok[s] = nxt[s]
                    if req.t_first is None:
                        req.t_first = time.monotonic()
                        if req.t_submit is not None:
                            obs.metrics().histogram("serve.ttft_s").observe(
                                req.t_first - req.t_submit)
                    hit_eos = req.eos is not None and int(nxt[s]) == req.eos
                    if len(req.out) >= req.max_tokens or hit_eos:
                        req.done = True
                        # early EOS
                        self._work -= req.max_tokens - len(req.out)
                        req.t_done = time.monotonic()
                        if req.tpot_s is not None:
                            obs.metrics().histogram("serve.tpot_s").observe(
                                req.tpot_s)
                        obs.metrics().counter("serve.completed").inc()
                        self.completed.append(req)
                        self._recycle(s)
            self._admit()
            self._tick += 1
            m = obs.metrics()
            m.counter("serve.ticks").inc()
            m.gauge("serve.queue_depth").set(len(self.queue))
            if self.paged:
                m.gauge("serve.kv_live_blocks").set(self.kv.live_blocks)
                m.gauge("serve.kv_cached_blocks").set(self.kv.cached_blocks)
                m.gauge("serve.kv_free_blocks").set(self.kv.free_blocks)
                m.gauge("serve.kv_swapped_blocks").set(self.swapped_blocks)
            return True

    def _decode_args(self, active: list[int]) -> dict:
        """The ``decode:tick`` span's args: the tick, the slots decoded
        and the keys they attend over (their cached lengths plus the new
        token). Paged, also the KV pages the attention kernel streams
        over every lane (an idle lane, at position 0, streams one) and
        the ``batch x max_blocks`` pages of the whole block table."""
        if not self.paged:
            return {"tick": self._tick, "active": len(active),
                    "keys": len(active) * (self._tick + 1)}
        return {"tick": self._tick, "active": len(active),
                "keys": int(self._pos[active].sum()) + len(active),
                "pages": int((self._pos // self.block_size + 1).sum()),
                "grid_pages": self.batch * self.max_blocks}

    def run(self, max_ticks: int | None = None, *,
            on_starvation: str = "raise") -> list[Request]:
        """Drive until queue + slots drain. Simple synchronous scheduler:
        all slots advance per tick; a slot in 'prompt phase' feeds its
        next prompt token, a 'gen phase' slot feeds its last sampled
        token; finished slots recycle.

        The tick budget defaults to the total remaining work (unreplayed
        prompt + ungenerated tokens over queue and slots) — it scales
        with the queue, so a deep queue of short requests drains through
        slot recycling instead of being starved by a fixed bound. If the
        budget elapses — or the contiguous path exhausts its shared
        ``max_len`` lanes — with requests still pending, that is
        starvation, not completion: ``on_starvation="raise"`` (default)
        raises ``RuntimeError``; ``"return"`` records the pending request
        ids in ``self.starved`` and returns what finished."""
        if on_starvation not in ("raise", "return"):
            raise ValueError(f"on_starvation must be 'raise' or 'return', "
                             f"got {on_starvation!r}")
        budget = max_ticks if max_ticks is not None \
            else max(1, self.pending_work())
        ticks = 0
        while ticks < budget and self.tick_once():
            ticks += 1
        self.starved = self.pending_rids()
        if self.starved and on_starvation == "raise":
            raise RuntimeError(
                f"serve loop stopped after {ticks} ticks (budget {budget}, "
                f"max_len {self.max_len}) with requests still pending "
                f"(rids {self.starved}); raise max_ticks/max_len or pass "
                f"on_starvation='return'")
        return self.completed

    def kv_dequant_errors(self, ref) -> np.ndarray:
        """Measured per-site KV dequantization error against a golden
        fp32 twin: dequantize this engine's stored codes+scales and
        compare to ``ref``'s fp32 pool entry-by-entry, relative to the
        golden per-(token, head) absmax — directly comparable to
        ``quant.layer_error_budget(self.kv_dtype)``. ``ref`` is a
        ``ServeEngine`` (or its raw cache pytree) that ran the same
        requests with ``kv_dtype="fp32"`` and the same ``kv_blocks`` (the
        allocator is deterministic, so block trajectories match). Each
        per-unit error is recorded into the
        ``serve.kv_dequant_rel_error`` histogram (picked up by
        ``drift_report``); returns the errors as a flat array."""
        from repro.models import attention
        if not self.paged:
            raise ValueError("kv_dequant_errors requires paged=True")
        ref_cache = ref.cache if isinstance(ref, ServeEngine) else ref
        sites = self.cache["layers"]
        ref_sites = ref_cache["layers"]
        errs = []
        for name in sorted(sites):
            e = attention.paged_kv_dequant_error(
                sites[name], ref_sites[name], self.kv_dtype)
            errs.append(np.asarray(e, np.float32))
        out = np.concatenate(errs)
        h = obs.metrics().histogram("serve.kv_dequant_rel_error")
        for v in out:
            h.observe(float(v))
        return out

    def drift_report(self, tracer=None):
        """Join recorded execute-lane spans against the pim schedule's
        modeled stage costs (``repro.obs.drift``). Requires
        ``backend='pim'`` and a run made with observability enabled."""
        if self.schedule is None:
            raise ValueError(
                "drift_report requires backend='pim' (the jit backend "
                "has no modeled schedule to drift against)")
        return obs.drift_report(self.schedule, tracer)
