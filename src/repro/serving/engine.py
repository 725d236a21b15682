"""Continuous-batching serving engine running a real JAX model.

This is the executable counterpart of the simulator: the same scheduler
protocol and request lifecycle, but tokens actually come out of a model.
Two decode backends:

- ``slots``  — per-slot contiguous caches via ``model.decode_step`` with
  per-request positions; works for every assigned architecture (SSM /
  hybrid / MLA / MoE / enc-dec included).
- ``paged``  — paged KV pools + the Pallas paged-attention kernel
  (``repro.kernels.paged_attention``); the vLLM-style production path for
  uniform dense-GQA stacks (the paper's Llama-2 testbed shape).

Timing uses a dual clock: wall-clock for real measurements and the
analytic cost model for target-hardware metrics fed back to the
scheduler (this container's CPU timings are not meaningful for an
accelerator-bound system).

Scheduling decisions (admission, ``canSchedule`` KV reservation, the
chunked-prefill plan, the completion feedback loop) are NOT
re-implemented here: the engine drives the same
``repro.serving.batch_core.BatchCore`` as the simulator (DESIGN.md §6),
so simulator and engine cannot drift apart.  Prefill is *stall-free*:
prompts stream in as ``prefill_chunk``-budgeted chunks
(``models.prefill_chunk`` extends the request's cache incrementally) and
each iteration mixes prefill-chunk rows with the batched decode of every
DECODING request, so running decodes never wait on a long prompt and the
engine runs with ``stall_free=True, adaptive_batching=True`` — the
paper's TTFT mechanism, same knobs as the simulator.  Architectures
without incremental-prefill support (``supports_chunked_prefill``) fall
back to whole-prompt prefill at admission.

Timing rule for partial prefills (the corrected TTFT definition): a
request's first token exists only when its *last* chunk has executed, and
is stamped after the modeled clock has advanced over that iteration —
never at admission.  Like the simulator it exposes the replica protocol
(``submit``/``step``/``clock``/``has_work``) for the cluster layer
(DESIGN.md §7).
"""
from __future__ import annotations

import heapq
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN, ModelConfig
from repro.core.request import DECODING, Request
from repro.core.schedulers import SchedulerBase
from repro.kernels import paged_attention
from repro.models import (decode_step, init_cache, init_params, prefill,
                          prefill_chunk, supports_chunked_prefill)
from repro.models.layers import dtype_of, embed, mlp, rmsnorm, unembed
from repro.models.model import model_stages
from repro.models.attention import apply_rope, quantize_kv
from repro.models.moe import moe_ffn
from repro.serving.batch_core import BatchConfig, BatchCore
from repro.serving.costmodel import CostModel
from repro.serving.kv_cache import PagePool, make_pools, scatter_prefill

def _next_pow2(n: int) -> int:
    """Static-shape bucketing for the jitted decode step (DESIGN.md §16):
    row counts and table widths round up to powers of two, bounding the
    number of distinct traces logarithmically."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


class ServingEngine:
    def __init__(self, cfg: ModelConfig, scheduler: SchedulerBase, *,
                 params=None, max_slots: int = 8, max_len: int = 512,
                 kv_budget_tokens: Optional[int] = None,
                 cost_model: Optional[CostModel] = None,
                 backend: str = "slots", page_size: int = 16,
                 seed: int = 0, sample_temp: float = 0.0,
                 chunked: Optional[bool] = None,
                 prefill_chunk_tokens: int = 512,
                 target_iter_time: float = 0.25,
                 slo_budget: str = "static",
                 prefix_cache: bool = False,
                 kv_quant: bool = False,
                 keep_first_logits: bool = False,
                 observer=None, admission=None, device=None):
        self.cfg = cfg
        # the jax device holding this replica's params and KV (None: the
        # default device); the jitted steps run where their inputs live
        self.device = device
        self.sched = scheduler
        self.max_slots = max_slots
        self.max_len = max_len
        # debug/test probe: retain each request's first-token logits row
        # (vocab-sized per request — off by default so long runs don't
        # accumulate dead arrays)
        self.keep_first_logits = keep_first_logits
        self.cm = cost_model or CostModel(cfg)
        if chunked is None:
            chunked = supports_chunked_prefill(cfg)
        elif chunked:
            assert supports_chunked_prefill(cfg), \
                f"{cfg.name}: no incremental-prefill support (see " \
                "models.supports_chunked_prefill)"
        self.chunked = chunked
        if kv_quant:
            # int8 KV pages (DESIGN.md §16) live in the paged pools and
            # are dequantized inside the Pallas kernel; the slots backend
            # keeps its own fp caches
            assert backend == "paged" and self.chunked, \
                "kv_quant requires the paged backend + chunked prefill"
        self.kv_quant = kv_quant
        self.core = BatchCore(
            scheduler, self.cm,
            BatchConfig(max_batch=max_slots,
                        kv_budget_tokens=kv_budget_tokens
                        # int8 pages halve KV bytes/token, so the same
                        # physical memory holds ~2x the token budget
                        or max_slots * max_len * (2 if kv_quant else 1),
                        kv_quant=kv_quant,
                        default_reserve=128,      # engine's legacy reserve
                        prefill_chunk=prefill_chunk_tokens,
                        target_iter_time=target_iter_time,
                        # SLO-controllable per-iteration budget (§12);
                        # the decisions live in BatchCore, so sim and
                        # engine solve identically
                        slo_budget=slo_budget,
                        # stall-free chunked prefill + adaptive batching
                        # when the model layer supports cache continuation
                        adaptive_batching=chunked,
                        stall_free=chunked,
                        # page-rounded KV accounting on the paged backend
                        # (DESIGN.md §10): budget respected => pool never
                        # physically exhausts
                        kv_page_size=page_size if backend == "paged"
                        else 1),
            observer=observer, admission=admission)
        self.kv_budget = self.core.kv_budget
        self.sample_temp = sample_temp
        self.rng = jax.random.key(seed)
        if params is None:
            params = init_params(jax.random.key(seed + 1), cfg)
        self.params = self._place(params)
        self.backend = backend
        self.k_scales = self.v_scales = None
        if backend == "paged":
            kinds = {k for k, _, _ in model_stages(cfg)}
            assert kinds == {ATTN} and not cfg.is_encoder_decoder, \
                "paged backend supports uniform dense-GQA stacks"
            n_pages = -(-self.kv_budget // page_size)
            self.pool = PagePool(n_pages, page_size)
            # the device pools carry one extra sacrificial page at index
            # n_pages, invisible to the allocator and its invariants: the
            # fused ragged launch (DESIGN.md §16) pads its row count to
            # powers of two and every padding row writes to (and attends
            # over) this scratch page, never a live request's pages
            self._scratch_page = n_pages
            if kv_quant:
                (self.k_pools, self.v_pools, self.k_scales,
                 self.v_scales) = self._place(make_pools(
                    cfg.n_layers, n_pages + 1, page_size, cfg.n_kv_heads,
                    cfg.resolved_head_dim(), quantized=True))
            else:
                self.k_pools, self.v_pools = self._place(make_pools(
                    cfg.n_layers, n_pages + 1, page_size, cfg.n_kv_heads,
                    cfg.resolved_head_dim(), dtype_of(cfg)))
        else:
            self.cache = self._place(init_cache(cfg, max_slots, max_len))
            # inactive slots decode garbage into slot 0 tokens — masked out
        if prefix_cache:
            # shared-prefix radix KV cache (DESIGN.md §9): only the paged
            # backend can point several block tables at one physical page,
            # and only chunked prefill can resume from a cached offset
            assert backend == "paged" and self.chunked, \
                "prefix_cache requires the paged backend + chunked prefill"
            from repro.serving.prefix_cache import PrefixCache
            self.core.prefix_cache = PrefixCache(self.pool)
        self.slots: List[Optional[Request]] = [None] * max_slots
        self.running = self.core.running    # alias: core owns the batch
        #                                     (admission order = sim order)
        self.reserved = self.core.reserved  # alias: core owns KV accounting
        self.t_model = 0.0            # modeled target-hardware clock
        self.t_wall0 = time.monotonic()
        self.finished: List[Request] = []
        self._prefill_jit: Dict[int, object] = {}
        self._chunk_jit = None
        self._decode_jit = None
        self.iterations = 0

    # -- helpers ----------------------------------------------------------------
    def _place(self, tree):
        """Commit arrays (or host buffers) to this replica's device."""
        return tree if self.device is None else jax.device_put(tree,
                                                               self.device)

    def now(self) -> float:
        return self.t_model

    # replica protocol (cluster layer) ------------------------------------------
    @property
    def clock(self) -> float:
        return self.t_model

    def advance_to(self, t: float):
        self.t_model = max(self.t_model, t)

    def has_work(self) -> bool:
        return bool(self.running) or self.sched.has_waiting()

    @property
    def n_finished(self) -> int:
        return len(self.finished)

    @property
    def n_preemptions(self) -> int:
        """Preemption events on this replica (cluster metric)."""
        return self.core.n_preemptions

    def kv_load(self) -> float:
        return self.core.kv_load()

    def queued_prompt_tokens(self) -> int:
        return self.core.queued_prompt_tokens()

    def _free_slot(self) -> int:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return -1

    def submit(self, req: Request):
        # overload-aware admission gate (DESIGN.md §13) — same decision
        # point as Simulator.submit, so sim and engine throttle the
        # identical request set
        if not self.core.accept(req, self.now()):
            return
        if req.prompt_tokens is None:
            req.prompt_tokens = np.random.default_rng(req.rid).integers(
                0, self.cfg.vocab_size, req.prompt_len).astype(np.int32)
        elif len(req.prompt_tokens) > req.prompt_len:
            # workload post-capped prompt_len: the cache key and the model
            # input must agree on the prompt's extent
            req.prompt_tokens = req.prompt_tokens[:req.prompt_len]
        self.sched.on_arrival(req, self.now())

    # -- prefill ------------------------------------------------------------------
    def _prefill_fn(self, plen: int):
        if plen not in self._prefill_jit:
            cfg, max_len = self.cfg, self.max_len
            if cfg.frontend == "vision_stub":
                def fn(params, tokens, patches):
                    return prefill(params, {"tokens": tokens,
                                            "patch_embeds": patches},
                                   cfg, max_len)
            else:
                def fn(params, tokens):
                    return prefill(params, {"tokens": tokens}, cfg, max_len)

            self._prefill_jit[plen] = jax.jit(fn)
        return self._prefill_jit[plen]

    def _chunk_fn(self):
        if self._chunk_jit is None:
            cfg = self.cfg

            def fn(params, tokens, cache):
                return prefill_chunk(params, tokens, cfg, cache)

            # one wrapper: jit's own cache handles per-chunk-length traces
            self._chunk_jit = jax.jit(fn)
        return self._chunk_jit

    def _bind_slot(self, req: Request, slot: int):
        """Admission bookkeeping only — no model work happens here.  The
        prompt runs later through the shared chunk plan."""
        req._slot = slot
        req._vlm_prefix = 0
        req._pcache = None            # slots backend: partial prefill cache
        req._pos = 0
        self.slots[slot] = req
        self.running.append(req)

    def _drop_backend_state(self, req: Request):
        """Preemption (DESIGN.md §10): free the victim's physical KV —
        pool pages on the paged backend (already released through the
        prefix cache's refcounts when one is attached), the partial
        prefill cache on the slots backend — and vacate its slot.  The
        recompute path rebuilds everything at re-admission."""
        if self.backend == "paged":
            self.pool.release_request(req.rid)
        req._pcache = None
        slot = getattr(req, "_slot", None)
        if slot is not None and self.slots[slot] is req:
            self.slots[slot] = None
        req._slot = None

    def _prefill_whole(self, req: Request):
        """Legacy one-shot prompt prefill (architectures without
        incremental-prefill support, incl. the modality frontends)."""
        tokens = jnp.asarray(req.prompt_tokens[None, :])
        if self.cfg.frontend == "vision_stub":
            # stubbed modality frontend: each request carries one image's
            # worth of precomputed patch embeddings
            patches = jnp.asarray(np.random.default_rng(req.rid).
                                  standard_normal((1,
                                                   self.cfg.n_frontend_tokens,
                                                   self.cfg.d_model)),
                                  dtype_of(self.cfg))
            logits, cache1 = self._prefill_fn(req.prompt_len)(
                self.params, tokens, patches)
            req._vlm_prefix = self.cfg.n_frontend_tokens
        else:
            logits, cache1 = self._prefill_fn(req.prompt_len)(self.params,
                                                              tokens)
        req._pcache = cache1
        return logits[0]

    def _prefill_chunk_slots(self, req: Request, start: int, chunk: int):
        if req._pcache is None:
            req._pcache = init_cache(self.cfg, 1, self.max_len)
        tokens = jnp.asarray(req.prompt_tokens[None, start:start + chunk])
        logits, req._pcache = self._chunk_fn()(self.params, tokens,
                                               req._pcache)
        return logits[0]

    def _run_prefill(self, req: Request, start: int, chunk: int):
        """Execute one planned chunk; returns the last-token logits row
        (meaningful only when this chunk completes the prompt).  Chunked
        paged prefill does not come through here — it rides the fused
        ragged launch (``_run_mixed_paged``)."""
        if not self.chunked:
            assert start == 0 and chunk == req.prompt_len
            return self._prefill_whole(req)
        return self._prefill_chunk_slots(req, start, chunk)

    def _run_mixed_paged(self, plan, decoding: List[Request]):
        """The fused mixed iteration (DESIGN.md §16): every planned
        prefill-chunk token and every decode row of this iteration goes
        down in ONE ``_paged_decode_step`` call — a ragged launch where
        row r writes its K/V at position ``ctx[r]`` of request
        ``row_map[r]``'s pages and attends its causal prefix.  A prompt
        chunk is just a run of rows with staggered ctx over one table
        row; a decode is a single row.  The scheduler already prices
        these as one fused pass (``mixed_step_time``) — now the kernel
        launch agrees with the cost model.

        Shapes are bucketed to powers of two (rows, table rows, table
        width) so the jitted step never retraces on page-boundary
        crossings or batch jitter; padding rows write token 0 at pos 0 of
        the sacrificial scratch page and their logits are sliced away.

        Returns ({rid: last-chunk-row logits}, {rid: decode logits})."""
        if not plan and not decoding:
            return {}, {}
        tokens: List[int] = []
        ctx: List[int] = []
        rmap: List[int] = []
        last_row: Dict[int, int] = {}
        for t, (req, chunk) in enumerate(plan):
            start = req.prefill_done - chunk
            self.pool.ensure(req.rid, start + chunk)
            tokens.extend(int(x) for x in
                          req.prompt_tokens[start:start + chunk])
            ctx.extend(range(start, start + chunk))
            rmap.extend([t] * chunk)
            last_row[req.rid] = len(tokens) - 1
        n_chunk_rows = len(tokens)
        for i, r in enumerate(decoding):
            self.pool.extend(r.rid, r._pos, r._pos + 1)
            tokens.append(int(r._next_token))
            ctx.append(r._pos)
            rmap.append(len(plan) + i)
        rids = [req.rid for req, _ in plan] + [r.rid for r in decoding]
        n_t = len(rids)
        # static-unless-overflowing table width: normally
        # pages_needed(max_len), but requests may legitimately outgrow
        # max_len (output length is not capped by it), so widen in
        # power-of-two buckets instead of truncating their tables
        width = self.pool.pages_needed(self.max_len)
        for rid in rids:
            width = max(width, len(self.pool.owned.get(rid, ())))
        width = _next_pow2(width)
        n_tab = _next_pow2(n_t + 1)       # >=1 spare row: the scratch page
        bt = np.full((n_tab, width), self._scratch_page, np.int32)
        bt[:n_t] = self.pool.block_table(rids, width)
        n_rows = len(tokens)
        n_pad = _next_pow2(n_rows)
        if n_pad > n_rows:                # padding rows: token 0 at pos 0
            tokens += [0] * (n_pad - n_rows)   # on the scratch page (all
            ctx += [0] * (n_pad - n_rows)      # write identical values);
            rmap += [n_t] * (n_pad - n_rows)   # ctx=0 => fully masked
        step_args = (self.params, *self._place((
            np.asarray(tokens, np.int32), np.asarray(ctx, np.int32), bt,
            np.asarray(rmap, np.int32))))
        if self.kv_quant:
            (logits, self.k_pools, self.v_pools, self.k_scales,
             self.v_scales) = _paged_decode_step(
                *step_args, self.k_pools, self.v_pools, self.k_scales,
                self.v_scales, self.cfg, self.pool.page_size)
        else:
            logits, self.k_pools, self.v_pools = _paged_decode_step(
                *step_args, self.k_pools, self.v_pools, None, None,
                self.cfg, self.pool.page_size)
        logits = np.asarray(logits, np.float32)
        first_rows = {rid: logits[i] for rid, i in last_row.items()}
        rows = {r.rid: logits[n_chunk_rows + i]
                for i, r in enumerate(decoding)}
        return first_rows, rows

    def _install_prefill(self, req: Request, row):
        """Prompt fully prefilled: make the request decodable.  For the
        slots backend the per-request partial cache is copied into its
        slot here (after this iteration's decode, so the full-width decode
        step never clobbers a partially prefilled slot)."""
        slot = req._slot
        if self.backend == "paged":
            if not self.chunked:
                # copy contiguous prefill cache into this request's pages
                # (shared pool-scatter helper — one implementation of the
                # page-boundary pad-and-set logic)
                self.pool.alloc(req.rid, req.prompt_len + 1)
                sc = req._pcache["stages"]["stage_0"]
                pages = self.pool.owned[req.rid]
                ps = self.pool.page_size
                self.k_pools = scatter_prefill(
                    self.k_pools, sc["k"][:, 0], pages, ps,
                    n_tokens=req.prompt_len)
                self.v_pools = scatter_prefill(
                    self.v_pools, sc["v"][:, 0], pages, ps,
                    n_tokens=req.prompt_len)
        else:
            def put(dst, src):
                return dst.at[:, slot].set(src[:, 0])
            for i in range(len(model_stages(self.cfg))):
                key = f"stage_{i}"
                self.cache["stages"][key] = jax.tree.map(
                    put, self.cache["stages"][key],
                    req._pcache["stages"][key])
            self.cache["pos"] = self.cache["pos"].at[slot].set(
                req.prompt_len + req._vlm_prefix)
        req._pcache = None
        req._next_token = int(np.argmax(row))
        if self.keep_first_logits:
            req._first_row = np.asarray(row, np.float32)
        req._pos = req.prompt_len + req._vlm_prefix

    # -- decode -------------------------------------------------------------------
    def _decode_slots(self, tokens_np):
        if self._decode_jit is None:
            cfg = self.cfg

            def fn(params, tokens, cache):
                return decode_step(params, tokens, cache, cfg)

            self._decode_jit = jax.jit(fn)
        logits, self.cache = self._decode_jit(
            self.params, jnp.asarray(tokens_np), self.cache)
        return logits

    def _decode(self, decoding: List[Request]):
        """Batched one-token decode; returns {rid: logits row (np)}."""
        if not decoding:
            return {}
        if self.backend == "paged":
            return self._run_mixed_paged([], decoding)[1]
        tokens = np.zeros(self.max_slots, np.int32)
        for r in decoding:
            tokens[r._slot] = r._next_token
        logits = np.asarray(self._decode_slots(tokens), np.float32)
        return {r.rid: logits[r._slot] for r in decoding}

    def _sample(self, row) -> int:
        if self.sample_temp > 0:
            self.rng, sub = jax.random.split(self.rng)
            return int(jax.random.categorical(
                sub, jnp.asarray(row) / self.sample_temp))
        return int(np.argmax(row))

    # -- main loop -----------------------------------------------------------------
    def step(self):
        """One continuous-batching iteration (mirrors ``Simulator.step``
        statement for statement — both drive the shared BatchCore).
        Returns #running requests (1 when only quota-blocked queued work
        exists — the clock still advanced), 0 when idle."""
        now = self.now()
        # 1. admission (Algorithm 1 inner loop, the one BatchCore.admit
        #    skip-protocol implementation; slot bookkeeping rides its
        #    callbacks, so sim and engine cannot drift)
        admitted = self.core.admit(
            now, len(self.running),
            has_capacity=lambda: self._free_slot() >= 0,
            on_admitted=lambda req: self._bind_slot(req,
                                                    self._free_slot()))
        if not self.running:
            if not self.sched.has_waiting():
                return 0
            # quota/window-blocked scheduler (e.g. RPM): nothing popped
            # but requests are queued — run an empty iteration so the
            # modeled clock advances to when the scheduler unblocks,
            # exactly as Simulator.step does
            self.t_model += self.core.iteration_time([], [], True)
            self.iterations += 1
            return 1

        # 1b. reservation reconciliation + fairness-aware preemption
        #     (DESIGN.md §10, mirrors Simulator.step): grow reservations
        #     to the KV this iteration will actually write and preempt
        #     fairly if the budget would be exceeded — BEFORE any model
        #     work, so victims neither prefill nor decode (and the paged
        #     pool never reaches physical exhaustion)
        preempted = self.core.prepare_iteration(now, self.running)
        for req in preempted:
            self._drop_backend_state(req)
            self.running.remove(req)

        # 2+3. chunked prefill + batched decode of every request that was
        #    DECODING at iteration start (requests finishing prefill this
        #    iteration emit their first token below and decode from the
        #    next one).  On the chunked paged backend both go down in ONE
        #    ragged kernel launch (DESIGN.md §16) — the fused pass the
        #    cost model already prices as ``mixed_step_time``.
        plan = self.core.plan_prefill(self.running)
        decoding = [r for r in self.running if r.state == DECODING]
        if self.backend == "paged" and self.chunked:
            first_rows, rows = self._run_mixed_paged(plan, decoding)
            done_prefill = [(req, first_rows[req.rid]) for req, _ in plan
                            if req.prefill_done >= req.prompt_len]
        else:
            done_prefill = []
            for req, chunk in plan:
                row = self._run_prefill(req, req.prefill_done - chunk,
                                        chunk)
                if req.prefill_done >= req.prompt_len:
                    done_prefill.append((req, row))
            rows = self._decode(decoding)

        # 4. modeled clock advance (timing rule shared with the simulator)
        ctxs = [r.prompt_len + r.generated for r in decoding]
        fresh = bool(admitted) or bool(preempted)
        t_iter = self.core.iteration_time(plan, ctxs, fresh)
        self.t_model += t_iter
        now = self.now()

        # 5. lifecycle — the shared iteration body (DESIGN.md §15).
        #    First-token time is stamped inside, *after* the clock
        #    advanced over the iteration that completed the prompt —
        #    stamping at admission under-reported TTFT by the entire
        #    prefill iteration.  The engine supplies the physical-KV
        #    hooks: install the prefilled cache when a first token is
        #    emitted, sample the next token per decode, and free pool
        #    pages + the slot when a request completes.
        n_running = len(self.running)
        first_rows = {req.rid: row for req, row in done_prefill}

        def on_first(req):
            self._install_prefill(req, first_rows[req.rid])

        def on_decode(req):
            req._next_token = self._sample(rows[req.rid])
            req._pos += 1

        def post_complete(req):
            self.finished.append(req)
            if self.backend == "paged":
                self.pool.free_request(req.rid)
            self.slots[req._slot] = None

        self.core.execute_iteration(
            now, plan, decoding, t_iter=t_iter, fresh=fresh,
            firsts=[req for req, _ in done_prefill],
            admitted=admitted, preempted=preempted,
            on_first=on_first, on_decode=on_decode,
            post_complete=post_complete)
        self.iterations += 1
        return n_running

    def run(self, requests: List[Request] = None,
            max_iters: int = 1_000_000, interactions=None):
        """Submit everything (arrivals honored on the modeled clock) and
        run to completion.  ``interactions`` are released closed-loop:
        turn k+1 enters the arrival heap when ``BatchCore.complete``
        fires the turn-release hook at turn k's modeled finish time plus
        think time — the same rule (and the same ``BatchCore`` code
        path) as ``Simulator.run``, so the frontends stay in lockstep
        (DESIGN.md §13)."""
        heap: List[tuple] = []        # (arrival, seq, req); seq preserves
        seq = 0                       # submission order on arrival ties

        def push(req):
            nonlocal seq
            heapq.heappush(heap, (req.arrival, seq, req))
            seq += 1

        for r in sorted(requests or [], key=lambda r: r.arrival):
            push(r)
        for inter in interactions or []:
            self.core.register_interaction(inter)
            first = inter.next_request()  # keeps its stamped arrival
            if first is not None:
                push(first)
        self.core.on_turn_release = lambda nxt, now: push(nxt)

        for _ in range(max_iters):
            while heap and heap[0][0] <= self.now():
                self.submit(heapq.heappop(heap)[2])
            n = self.step()
            if n == 0:
                if not heap:
                    break             # drained: closed-loop releases only
                #                       happen inside step's completions
                self.t_model = max(self.t_model, heap[0][0])
        return self.finished


# ---------------------------------------------------------------------------
# Paged dense-GQA decode step (jit'd; Pallas kernel inside)
# ---------------------------------------------------------------------------
import functools


@functools.partial(jax.jit, static_argnames=("cfg", "page_size"))
def _paged_decode_step(params, tokens, ctx_lens, block_tables, row_map,
                       k_pools, v_pools, k_scales, v_scales,
                       cfg: ModelConfig, page_size: int):
    """The fused ragged mixed-iteration step (DESIGN.md §16).

    tokens/ctx_lens/row_map: (R,) — row r writes its K/V at position
    ctx_lens[r] of table row row_map[r]'s pages, then attends its causal
    prefix (ctx_lens[r]+1 tokens).  block_tables: (T, W) compact
    per-request table, T decoupled from R so a prompt chunk is a run of
    rows with staggered ctx over one table row and a decode is a single
    row — one launch covers both.

    int8 KV pages: when ``k_pools``/``v_pools`` are int8, ``k_scales``/
    ``v_scales`` are the per-(slot, head) bf16 scale pools; new tokens
    are quantized with ``quantize_kv`` before the pool write and the
    Pallas kernel dequantizes in-VMEM (the dtype is static under jit, so
    the quant path costs nothing when disabled)."""
    R = tokens.shape[0]
    quant = k_pools.dtype == jnp.int8
    x = embed(params["embed"], tokens)[:, None].astype(dtype_of(cfg))
    pos = ctx_lens
    stage = params["stages"]["stage_0"]
    rarange = jnp.arange(R)
    my_table = block_tables[row_map]                     # (R, W)
    page_idx = my_table[rarange, pos // page_size]       # (R,)
    slot_idx = pos % page_size
    moe_flag = cfg.moe is not None

    def body(x, lp, kp, vp, ks, vs):
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wv"])
        q = apply_rope(q, pos[:, None], cfg.rope_theta)[:, 0]
        k = apply_rope(k, pos[:, None], cfg.rope_theta)[:, 0]
        v = v[:, 0]
        if quant:
            k, k_s = quantize_kv(k)
            v, v_s = quantize_kv(v)
            ks = ks.at[page_idx, :, slot_idx].set(k_s)
            vs = vs.at[page_idx, :, slot_idx].set(v_s)
        kp = kp.at[page_idx, :, slot_idx].set(k)          # head-major pages
        vp = vp.at[page_idx, :, slot_idx].set(v)
        out = paged_attention(q, kp, vp, block_tables, pos + 1,
                              row_map=row_map, k_scale=ks, v_scale=vs)
        y = jnp.einsum("bhk,hkd->bd", out, lp["attn"]["wo"])[:, None]
        x = x + y
        h2 = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        if moe_flag:
            f, _ = moe_ffn(lp["ffn"], h2, cfg)
        else:
            f = mlp(lp["ffn"], h2, cfg.act)
        x = x + f
        return x, kp, vp, ks, vs

    if quant:
        def scan_body(x, layer_inputs):
            lp, kp_l, vp_l, ks_l, vs_l = layer_inputs
            x, kp_l, vp_l, ks_l, vs_l = body(x, lp, kp_l, vp_l, ks_l,
                                             vs_l)
            return x, (kp_l, vp_l, ks_l, vs_l)

        x, (k_new, v_new, ks_new, vs_new) = jax.lax.scan(
            scan_body, x, (stage, k_pools, v_pools, k_scales, v_scales))
    else:
        def scan_body(x, layer_inputs):
            lp, kp_l, vp_l = layer_inputs
            x, kp_l, vp_l, _, _ = body(x, lp, kp_l, vp_l, None, None)
            return x, (kp_l, vp_l)

        x, (k_new, v_new) = jax.lax.scan(
            scan_body, x, (stage, k_pools, v_pools))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x[:, 0])
    if quant:
        return logits, k_new, v_new, ks_new, vs_new
    return logits, k_new, v_new
