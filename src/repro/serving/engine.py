"""Shard engine — continuous batching with chunked prefill over one
SMR-managed pool.

The paper bounds the blast radius of one stalled participant (a stalled
thread pins O(1) unreclaimed nodes); the step loop applies the same rule to
prompt ingestion.  Admission only *reserves* pages and enqueues the sequence
in a ``prefilling`` state; each ``step()`` spends at most
``ServingConfig.prefill_chunk_tokens`` advancing prefill chunks (divided by
the named scheduler policy) and then runs the batched decode for every
in-flight sequence — so admitting a 4k-token prompt delays active decoders
by one chunk of work, never one prompt (DESIGN.md §12).

Thread roles (this is where the paper's concurrency actually happens):
  * client threads: ``submit()`` does the *optimistic prefix-cache lookup*
    (SCOT Harris-list traversal) and pins any hit pages;
  * the shard's engine thread: admission (via the named admission policy),
    chunked paged prefill (via the named scheduler policy), batched paged
    decode (kernels/ops.paged_attention), page alloc/release;
  * the session janitor thread: evicts prefix entries under pool pressure
    (retiring entry nodes and unpinning pages through the SMR scheme).

A page freed by the SMR is recycled to another sequence — if any of the
above threads still held an unprotected reference, decode would read another
request's KV (the serving-world version of Figure 1's SEGFAULT).  The SMR +
SCOT discipline prevents exactly that; tests/test_serving.py checks paged
outputs equal the contiguous-cache reference decode, token for token.

One :class:`_ShardEngine` is one SMR domain: in a :class:`ShardedEngine`
session each shard owns its own pool + prefix cache + (by default) its own
scheme instance, so a stalled thread pins O(K) pages *of one shard* and the
others keep reclaiming — the paper's robustness property applied as an
architecture decision (DESIGN.md §11).

:class:`PagedServingEngine` survives one release as a ``DeprecationWarning``
shim mapping the old kwargs onto :class:`ServingConfig`; new code goes
through :func:`repro.serving.serve`.

Dense-family models only (engine v1) — the restriction is the usual one for
paged serving stacks, recorded in DESIGN.md.
"""

from __future__ import annotations

import itertools
import threading
import time
import traceback
import warnings
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.smr.base import SmrScheme
from ..kernels import ops
from ..kernels import ref as kref
from ..models.layers import apply_rope, rms_norm, rope_angles
from ..models.transformer import _qkv
from ..runtime.block_pool import BlockPool, PageNode
from ..runtime.prefix_cache import PrefixCache
from ..runtime.swap import SwapArena, SwapArenaFullError, SwapChecksumError
from .config import ServingConfig
from .faults import build_fault_line
from .policies import as_admission_policy, as_scheduler_policy
from .sampling import SamplingPolicy, as_sampling_policy


@dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int = 16
    priority: int = 0               # consumed by the 'priority' admission
    # named priority class (ServingConfig.priority_classes): resolves to
    # ``priority`` plus per-class TTFT/ITL SLOs at submit() time
    priority_class: Optional[str] = None
    # per-request deadline: timeout_s resolves at submit() (falling back
    # to ServingConfig.default_timeout_s); deadline is the absolute
    # perf_counter stamp — set once, kept across migration (a request
    # does not get a fresh budget by moving shards)
    timeout_s: Optional[float] = None
    deadline: Optional[float] = None
    # TTFT SLO deadline (priority-class ttft_slo_s): enforced by the sweep
    # only while no token has been emitted — once out_times is non-empty
    # the SLO is either met or already violated, never enforceable
    ttft_deadline: Optional[float] = None
    # terminal diagnostics (crash tracebacks, migration failures,
    # deadline expiry) — surfaced by RequestHandle.result()
    error: Optional[str] = None
    # named sampling policy (or instance): resolved to a SamplingPolicy by
    # _validate() on the caller thread.  None → greedy (bit-identical to
    # the pre-sampling engine).  The policy carries the per-request seed,
    # stop sequences and the logprobs flag (DESIGN.md §17)
    sampling: Optional[object] = None
    req_id: int = field(default_factory=itertools.count().__next__)
    out_tokens: List[int] = field(default_factory=list)
    # sampled-token log-probabilities under the FILTERED distribution, one
    # per out_tokens entry — recorded only when sampling.logprobs is set
    out_logprobs: List[float] = field(default_factory=list)
    done: threading.Event = field(default_factory=threading.Event)
    cancelled: threading.Event = field(default_factory=threading.Event)
    # "waiting" → "prefilling" → "active" → "done" | "cancelled" | "failed"
    # (engine-owned; "prefilling" = pages reserved, prompt chunks still
    # being ingested under the step budget).  A preempted request parks as
    # "swapped" — K/V pages spilled to the host arena, re-queued — and
    # goes back through "prefilling" when re-admitted (DESIGN.md §15)
    status: str = "waiting"
    # times this request was preempted into the host swap arena
    preemptions: int = 0
    # latency surface: submit() stamp + one perf_counter per emitted token,
    # so TTFT and inter-token latencies are measurable without polling
    t_submit: float = 0.0
    out_times: List[float] = field(default_factory=list)
    # set on every generated token and on completion (stream wakeups)
    _progress: threading.Event = field(default_factory=threading.Event)
    # filled at submit time (client thread): prefix-cache hit
    _hit_pages: List[PageNode] = field(default_factory=list)
    _hit_tokens: int = 0
    # observed-only ITL SLO (priority class), counted in stats()
    _itl_slo_s: Optional[float] = None
    # replay-prompt cursor: out_tokens[:_folded] are already folded into
    # ``prompt`` by an earlier preemption/migration — folding ALL emitted
    # tokens again would duplicate them in the replay prompt
    _folded: int = 0
    # page-aligned positions currently held by the shard's swap arena
    _swap_tokens: int = 0
    # ITL gap accounting (DESIGN.md §17): set by preemption/migration, the
    # next _emit() marks the incoming inter-token interval as a service
    # gap — excluded from RequestHandle.itl() and the ITL-SLO observation,
    # reported separately via gaps()/stats()
    _gap_pending: bool = False
    _gap_marks: List[int] = field(default_factory=list)
    # a stop sequence matched the emitted suffix: generation halts with
    # status "done" (the matched tokens are included in out_tokens)
    _stop_hit: bool = False

    def fold_emitted(self) -> None:
        """Fold tokens emitted since the last fold into the replay prompt.

        This IS the teacher-forcing mechanism every resume path relies on:
        folded tokens are re-ingested as PROMPT tokens by prefill (their
        K/V reproduced from the recorded ids, never re-sampled), so the
        emitted stream is force-fed on replay whatever the sampling policy
        — the engine does not depend on greedy determinism here.  Fresh
        positions after the fold re-enter the sampler with the same
        (seed, absolute_position) PRNG key the uninterrupted run would
        have used, which is the second half of the replay-exactness
        argument (DESIGN.md §17).  ``max_new_tokens`` shrinks by the same
        count so the request's total budget is unchanged.  Idempotent per
        token via the ``_folded`` cursor — a request preempted or migrated
        twice must not fold the first leg's tokens twice."""
        new = self.out_tokens[self._folded:]
        if new:
            self.prompt = list(self.prompt) + new
            self.max_new_tokens -= len(new)
            self._folded = len(self.out_tokens)

    def next_position(self) -> int:
        """Absolute position (in the request's original prompt + output
        stream) of the NEXT token to be sampled — invariant under
        fold_emitted(), the counter-PRNG's replay coordinate."""
        return len(self.prompt) + len(self.out_tokens) - self._folded


class _Seq:
    def __init__(self, req: Request, pages: List[PageNode], owned_from: int,
                 page_row: "np.ndarray"):
        self.req = req
        self.pages = pages              # full block run (shared prefix + owned)
        self.owned_from = owned_from    # pages[owned_from:] are owned
        self.tokens = list(req.prompt)
        self.new_tokens = 0
        # chunked-prefill cursor: prompt tokens whose K/V already sit in
        # pages (starts at the page-aligned prefix-cache hit; the scheduler
        # advances it one page-aligned chunk at a time until it reaches
        # len(prompt) and the first token is emitted)
        self.filled = req._hit_tokens
        # block-table row is fixed for the sequence's lifetime (pages are
        # allocated up front at admission) — precomputed once, reused every
        # decode step instead of re-walking the page list
        self.page_row = page_row


class _ShardEngine:
    """One shard: one pool, one prefix cache, one SMR domain, one thread."""

    def __init__(self, model, params, config: ServingConfig, *,
                 smr: Optional[SmrScheme] = None, shard_id: int = 0,
                 prefix_traversal=None):
        cfg = model.cfg
        assert cfg.family == "dense", "engine v1 serves dense models"
        self.model = model
        self.cfg = cfg
        self.params = params
        self.config = config
        self.shard_id = shard_id
        self.page_size = config.page_size
        self.max_batch = config.max_batch
        self.max_pages = config.max_pages
        # SMR domain: per-shard fresh instance unless the session shares one
        self.smr = smr if smr is not None else config.build_scheme()
        self.pool = BlockPool(self.smr, config.num_pages,
                              pool_scheme=config.pool_scheme)
        self.prefix_cache = PrefixCache(
            self.smr, self.pool, config.page_size,
            max_entries=config.prefix_cache_entries,
            # prefix_traversal= lets the legacy shim pass a live
            # TraversalPolicy instance (config carries names only)
            traversal=(prefix_traversal if prefix_traversal is not None
                       else config.prefix_traversal),
            eviction=config.eviction)
        self.admission = as_admission_policy(config.admission)
        self.scheduler = as_scheduler_policy(config.scheduler)
        L = cfg.n_layers
        kv = (L, config.num_pages, config.page_size, cfg.n_kv_heads,
              cfg.head_dim)
        self.k_pages = jnp.zeros(kv, getattr(jnp, cfg.dtype))
        self.v_pages = jnp.zeros(kv, getattr(jnp, cfg.dtype))
        self._waiting = self.admission.new_queue()
        self._wlock = threading.Lock()
        # scheduler states: _prefilling (pages reserved, prompt chunks
        # pending) and _active (decoding); together they share max_batch
        self._prefilling: List[_Seq] = []
        self._active: List[_Seq] = []
        self._stop = threading.Event()
        self._run_started = threading.Event()
        self._run_done = threading.Event()
        # serializes step()/drain: stop() may not tear pages out from under
        # a decode iteration that already read the block tables
        self._step_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        # donate the page arrays: the KV cache is updated in place instead
        # of being copied through every prefill/decode call (the copy was
        # ~MBs per step — it dwarfed the actual decode compute)
        self._decode = jax.jit(self._paged_decode_step,
                               donate_argnums=(1, 2))
        self._prefill = jax.jit(self._paged_prefill, donate_argnums=(1, 2))
        self._prefill_packed = jax.jit(self._paged_prefill_packed,
                                       donate_argnums=(1, 2))
        self._packed_flat = jax.jit(self._paged_step_packed_flat,
                                    donate_argnums=(1, 2))
        # speculative decoding (ROADMAP item 5): a sliced-parameter draft
        # proposes spec_k tokens per round; the target verifies them in ONE
        # packed chunk call with fused on-device rejection sampling.  The
        # draft runs as a pure function of the recorded token stream (its
        # cache is rebuilt inside the propose call each round), so draft
        # behavior — and with it the accept pattern and the emitted stream
        # — is replay-exact by construction (DESIGN.md §17)
        self.spec_k = config.spec_k
        self.draft_cfg = None
        self.draft_params = None
        if self.spec_k > 0:
            from ..models.registry import derive_draft
            draft_model, self.draft_params = derive_draft(
                model, params, config.spec_draft, config.spec_draft_layers)
            self.draft_cfg = draft_model.cfg
            self._draft_propose = jax.jit(self._draft_propose_fn)
            self._spec_verify = jax.jit(self._spec_verify_fn,
                                        donate_argnums=(1, 2))
        # host swap tier (DESIGN.md §15): the arena exists whenever the
        # config budgets host bytes; PREEMPTION additionally requires the
        # eviction policy to opt in via its ``swaps`` marker (resolved from
        # the cache's bound policy so instances work, not just names)
        self.swap_arena: Optional[SwapArena] = None
        if config.swap_bytes > 0:
            # the arena's slot allocator negotiates the same scheme as the
            # BlockPool free list (lock-free by default, "locked" fallback)
            self.swap_arena = SwapArena(
                config.swap_bytes, n_layers=L, page_size=config.page_size,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                dtype=cfg.dtype, scheme=config.pool_scheme)
        self.swap_enabled = self.swap_arena is not None and \
            getattr(self.prefix_cache.eviction, "swaps", False)
        # per-page fixed-shape device↔host movers: page id is a traced
        # scalar, so ONE compile each serves every page.  The gather does
        # NOT donate (the pool arrays live on); the scatter does (in-place
        # .at[].set like the decode path)
        self._gather_page = jax.jit(lambda k, v, pid: (k[:, pid], v[:, pid]))
        self._scatter_page = jax.jit(
            lambda k, v, pid, kp, vp: (k.at[:, pid].set(kp),
                                       v.at[:, pid].set(vp)),
            donate_argnums=(0, 1))
        self.steps = 0
        self.n_completed = 0
        self.n_cancelled = 0
        self.n_failed = 0
        # swap tier + SLO counters (stats())
        self.n_preemptions = 0          # sequences preempted to the arena
        self.n_resumed = 0              # swapped sequences re-admitted
        self.n_slo_cancelled = 0        # TTFT SLO expiries (subset of
        #                                 n_cancelled)
        self.n_itl_violations = 0       # observed inter-token SLO misses
        # ITL gap accounting: intervals spanning a preemption park or a
        # migration stall, excluded from itl() and the SLO observation
        self.n_gap_intervals = 0
        self.gap_seconds = 0.0
        # speculative decoding counters (stats()): accept_rate =
        # draft_accepted / draft_proposed
        self.n_draft_proposed = 0
        self.n_draft_accepted = 0
        # prefill efficiency counters (stats()): every fixed-shape chunk
        # call pays for C lanes — `prefill_tokens_wasted` counts the padded
        # lanes that bought nothing, and the packed pair shows how many
        # segments shared each packed chunk (the whole point of `packed`)
        self.prefill_chunks = 0
        self.prefill_tokens_wasted = 0
        self.packed_chunks = 0
        self.packed_segments = 0
        # fault tolerance (DESIGN.md §14): the shard's scheduled faults,
        # its loop heartbeat, and the recovery counters stats() exposes
        self.fault_line = build_fault_line(config.faults, shard_id)
        self.beat = 0               # bumped once per run()-loop iteration
        self.crashed = False        # engine-thread-owned (crash guard)
        self.degraded = False       # watchdog-owned
        self.error: Optional[str] = None
        self.heartbeat_misses = 0
        self.degraded_steps = 0
        self.n_migrated_in = 0
        self.n_migrated_out = 0

    # ---------------------------------------------------------- client API
    def _attach_hit(self, req: Request, pages: List[PageNode],
                    n_tok: int) -> None:
        # only reuse *strictly shorter than prompt* prefixes (need ≥1 token
        # to prefill so we have logits for the first generated token).
        # lookup() caps n_tok at the longest page-aligned prefix, so the
        # boundary case is exactly n_tok == len(prompt) with a page-aligned,
        # fully-cached prompt — drop is then 1 (the last page), and each
        # dropped page gives back exactly the one pin lookup took on it
        # (tests/test_serving.py::test_attach_hit_page_aligned_boundary).
        if n_tok >= len(req.prompt):
            drop = (n_tok - len(req.prompt)) // self.page_size + 1
            for p in pages[len(pages) - drop:]:
                self.pool.unpin(p)
            pages = pages[:len(pages) - drop]
            n_tok = len(pages) * self.page_size
        req._hit_pages, req._hit_tokens = pages, n_tok

    def _check_open(self):
        if self.crashed:
            head = self.error.strip().splitlines()[-1] if self.error else ""
            raise RuntimeError(f"shard {self.shard_id} crashed ({head}); "
                               f"no new submissions")
        if self._stop.is_set():
            raise RuntimeError("engine is stopped; no new submissions")

    def _stamp_deadline(self, req: Request) -> None:
        if req.priority_class is not None:
            # class wins over a hand-set priority: the class IS the
            # scheduling contract (raises ValueError on an unknown name,
            # still on the client thread)
            cls = self.config.priority_class(req.priority_class)
            req.priority = cls.priority
            if cls.ttft_slo_s is not None and req.ttft_deadline is None:
                req.ttft_deadline = req.t_submit + cls.ttft_slo_s
            req._itl_slo_s = cls.itl_slo_s
        t = req.timeout_s if req.timeout_s is not None \
            else self.config.default_timeout_s
        if t is not None and req.deadline is None:
            req.deadline = req.t_submit + t

    def _validate(self, req: Request) -> None:
        # resolve the sampling policy HERE, on the caller thread: an
        # unknown name raises at submit()/receive_migrated() time, never
        # inside the step loop (idempotent — instances pass through)
        req.sampling = as_sampling_policy(req.sampling)
        if not req.prompt:
            raise ValueError(f"request {req.req_id} has an empty prompt "
                             f"(need >= 1 token to prefill)")
        total = len(req.prompt) + req.max_new_tokens
        if total > self.config.max_seq_len:
            raise ValueError(
                f"request {req.req_id} needs {total} tokens but "
                f"max_seq_len={self.config.max_seq_len}; raise the config "
                f"limit or shorten the request")

    def submit(self, req: Request) -> Request:
        """Client-thread path: optimistic prefix lookup happens HERE,
        concurrently with the engine and janitor threads."""
        self._check_open()
        self._validate(req)
        req.t_submit = time.perf_counter()
        self._stamp_deadline(req)
        pages, n_tok = self.prefix_cache.lookup(req.prompt)
        self._attach_hit(req, pages, n_tok)
        with self._wlock:
            # re-check under the queue lock: stop() sets the flag BEFORE its
            # drain takes this lock, so a push that wins the lock after the
            # drain must see the flag — no request can strand in a dead
            # queue with its hit pages pinned
            stopped = self._stop.is_set()
            if not stopped:
                self.admission.push(self._waiting, req)
        if stopped:
            self._drop_hits([req])
        return req

    def _drop_hits(self, reqs: Sequence[Request]):
        for req in reqs:
            for pg in req._hit_pages:
                self.pool.unpin(pg)
            req._hit_pages = []
            req._hit_tokens = 0
        raise RuntimeError("engine is stopped; no new submissions")

    def submit_many(self, reqs: Sequence[Request]) -> Sequence[Request]:
        """Batched admission (DESIGN.md §4): ALL prompts' prefix lookups run
        under one SMR guard scope — one reservation lifecycle for the whole
        admission wave instead of one per request — and the waiting queue is
        extended under a single lock acquisition."""
        self._check_open()
        for req in reqs:
            self._validate(req)
        now = time.perf_counter()
        for req in reqs:
            req.t_submit = now
            self._stamp_deadline(req)
        hits = self.prefix_cache.lookup_many([r.prompt for r in reqs])
        for req, (pages, n_tok) in zip(reqs, hits):
            self._attach_hit(req, pages, n_tok)
        with self._wlock:
            stopped = self._stop.is_set()  # see submit(): drain-vs-push race
            if not stopped:
                for req in reqs:
                    self.admission.push(self._waiting, req)
        if stopped:
            self._drop_hits(reqs)
        return reqs

    def waiting_count(self) -> int:
        with self._wlock:
            return len(self._waiting)

    # ----------------------------------------------------- migration API
    # (watchdog-thread entry points; protocol in DESIGN.md §14 and the
    # serving/watchdog.py module docstring)
    def steal_waiting(self) -> List[Request]:
        """Drain a degraded shard's waiting queue.  Queue-lock only —
        safe whatever the (possibly wedged) engine thread is doing."""
        with self._wlock:
            return self.admission.drain(self._waiting)

    def steal_live(self, timeout: float) -> Optional[List["_Seq"]]:
        """Take ownership of the live (prefilling + active) sequences.
        Needs the step lock — a shard stalled INSIDE a step still owns
        its lists and its device buffers; returns ``None`` when the lock
        cannot be had within ``timeout`` (the watchdog backs off
        exponentially and eventually fails the handles out)."""
        if not self._step_lock.acquire(timeout=timeout):
            return None
        try:
            seqs = self._prefilling + self._active
            self._prefilling = []
            self._active = []
            return seqs
        finally:
            self._step_lock.release()

    def receive_migrated(self, req: Request) -> Request:
        """Adopt a migrated request: pin THIS domain's prefix hit for the
        (replayed) prompt, record the handoff, and enqueue.  The caller
        retires the SOURCE domain's claim only after this returns — so
        between lookup-pin here and export there, both domains pin, and
        at no instant does neither.  ``t_submit``/``deadline`` are kept:
        migration does not grant a fresh time budget."""
        self._check_open()
        self._validate(req)
        pages, n_tok = self.prefix_cache.lookup(req.prompt)
        self._attach_hit(req, pages, n_tok)
        self.pool.import_claim(req._hit_pages)
        req.status = "waiting"
        with self._wlock:
            stopped = self._stop.is_set()  # see submit(): drain-vs-push race
            if not stopped:
                self.admission.push(self._waiting, req)
        if stopped:
            self._drop_hits([req])
        self.n_migrated_in += 1
        return req

    # ------------------------------------------------------------- device fns
    @staticmethod
    def _layer_params(params, i):
        # slice the TRACED argument: closing over self.params would bake
        # every block's weights into the program as constants
        return jax.tree_util.tree_map(lambda p: p[i], params["blocks"])

    def _paged_prefill(self, params, k_pages, v_pages, tokens, page_ids,
                       start, n_valid, sampf, sampi):
        """Ingest ONE fixed-size prefill chunk into the owned pages.

        tokens: (1, C) — prompt[start : start+n_valid] zero-padded to the
        configured chunk size C (a FIXED shape: one jit compile per engine,
        however long prompts get — variable-shape prefill recompiled per
        length, and those compiles landed inside the step loop where every
        decoder paid for them); page_ids: (max_pages,) block run; start:
        scalar — tokens already in pages (page-aligned: a prefix-cache hit
        or the previous chunk's boundary); n_valid: scalar ≤ C.

        Only the chunk's C positions run through the model; attention reads
        the earlier prefix K/V back from the PAGES (exactly like the decode
        step, so chunk N resumes bit-identically from chunk N-1's boundary
        whether that boundary came from a cache hit or an earlier chunk).
        Padded lanes scatter out of bounds (dropped) and are causally
        invisible.

        sampf (2,) f32 [temperature, top_p] and sampi (2,) i32
        [top_k, seed] are the request's sampling operands; the next token
        after position start+n_valid-1 is sampled ON DEVICE at absolute
        position start+n_valid (the counter-PRNG replay coordinate) —
        meaningful only on the final chunk.  Returns (token, logprob,
        k_pages, v_pages)."""
        cfg = self.cfg
        c = tokens.shape[1]
        hkv, dh = cfg.n_kv_heads, cfg.head_dim
        n_heads = cfg.n_heads
        g = n_heads // hkv
        s_max = self.max_pages * self.page_size
        scale = 1.0 / (dh ** 0.5)
        x = jnp.take(params["embed"], tokens, axis=0)   # (1, C, D)
        abs_pos = start + jnp.arange(c)                  # (C,)
        angles = rope_angles(abs_pos[None, :], cfg.head_dim, cfg.rope_theta)
        valid = jnp.arange(c) < n_valid
        page_of = page_ids[abs_pos // self.page_size]
        slot_of = abs_pos % self.page_size
        # padded lanes point out of bounds and are DROPPED — nothing
        # rewrites a cached (possibly shared) page, no scratch page needed
        upd_page = jnp.where(valid, page_of, k_pages.shape[1])
        # keys visible to chunk query q: every position ≤ its absolute
        # position (the cached/earlier-chunk prefix + the chunk's own
        # causal triangle); pages past the prompt are never unmasked
        kmask = jnp.arange(s_max)[None, :] <= abs_pos[:, None]   # (C, S)
        for i in range(cfg.n_layers):
            p = self._layer_params(params, i)
            h = rms_norm(x, p["ln1"])
            q, k, v = _qkv(p["attn"], cfg, h)
            q = apply_rope(q, angles)
            k = apply_rope(k, angles)
            k_pages = k_pages.at[i, upd_page, slot_of].set(
                k[0].astype(k_pages.dtype), mode="drop")
            v_pages = v_pages.at[i, upd_page, slot_of].set(
                v[0].astype(v_pages.dtype), mode="drop")
            # gather the sequence's whole block run (fixed S_max width) and
            # attend the C chunk queries against it — per-chunk attention
            # cost is C × S_max, not (start+C)², and the shape never varies
            k_seq = k_pages[i, page_ids].reshape(s_max, hkv, dh)
            v_seq = v_pages[i, page_ids].reshape(s_max, hkv, dh)
            qf = q[0].reshape(c, hkv, g, dh).astype(jnp.float32) * scale
            sc = jnp.einsum("qkgd,skd->kgqs", qf,
                            k_seq.astype(jnp.float32))
            sc = jnp.where(kmask[None, None], sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            out = jnp.einsum("kgqs,skd->qkgd", pr,
                             v_seq.astype(jnp.float32)).astype(x.dtype)
            x = x + out.reshape(1, c, -1) @ p["attn"]["wo"]
            h = rms_norm(x, p["ln2"])
            ff = jax.nn.silu(h @ p["ffn"]["wi_gate"]) * (h @ p["ffn"]["wi_up"])
            x = x + ff @ p["ffn"]["wo"]
        x = rms_norm(x, params["final_norm"])
        logits = x[0, n_valid - 1] @ params["lm_head"]
        # fused sampling ON DEVICE: the engine only ever consumes the next
        # token id (+ its logprob), so ship two scalars to the host instead
        # of a vocab-sized logits row (the host-side np.argmax was a
        # GIL-held cost on every step — it capped multi-shard thread
        # scaling).  temperature <= 0 is exact argmax (greedy bit-compat)
        tok, lp = ops.sample_tokens(
            logits[None, :], sampf[0:1], sampi[0:1], sampf[1:2],
            sampi[1:2], (start + n_valid)[None])
        return tok[0], lp[0], k_pages, v_pages

    def _paged_prefill_packed(self, params, k_pages, v_pages, tokens,
                              seg_ids, positions, page_rows, seg_ctx,
                              emit_lanes, sampf, sampi, spos):
        """Ingest ONE packed multi-segment chunk (the ``packed`` scheduler).

        tokens: (1, L) — several sequences' prompt slices laid end to end
        in one fixed-shape chunk (L = prefill_chunk_tokens + max_batch:
        the C-token prefill budget plus one lane per possible decode
        rider); seg_ids (L,) int32 says which segment each lane belongs to
        (-1 = padding) and positions (L,) its absolute position in its OWN
        sequence.  page_rows (S, max_pages) carries one block-table row
        per segment (S = the power-of-2 segment bucket; unused rows are
        whatever, their seg_ctx is 0), seg_ctx (S,) each segment's context
        end AFTER this chunk.  Like the single-sequence chunk path, K/V is
        scattered into the pages per layer BEFORE attention reads them, so
        lanes of the same segment see their earlier same-chunk neighbours
        through the pages — same-chunk causality needs no extra masking.

        A decode-batch member fuses in as one more segment holding a
        single lane: its current token at position ctx-1, emit lane set —
        the same scatter/attend/emit path that serves a finishing prompt
        serves a decode step, so prefill and decode share one dispatch.

        emit_lanes (S,): the lane holding each segment's LAST token when
        the segment emits from this chunk (prompt completing, or a decode
        rider), else L (sentinel — clamped on device, ignored on host).
        sampf (S, 2) f32 [temperature, top_p], sampi (S, 2) i32
        [top_k, seed] and spos (S,) i32 — each segment's sampling operands
        and the absolute position its next token is sampled AT (the
        counter-PRNG replay coordinate).  Returns ((S,) tokens,
        (S,) logprobs) so every emitting segment streams its token from
        the same call."""
        cfg = self.cfg
        c = tokens.shape[1]
        valid = seg_ids >= 0
        x = jnp.take(params["embed"], tokens, axis=0)   # (1, C, D)
        angles = rope_angles(positions[None, :], cfg.head_dim,
                             cfg.rope_theta)
        lane_rows = page_rows[jnp.maximum(seg_ids, 0)]  # (C, max_pages)
        page_of = lane_rows[jnp.arange(c), positions // self.page_size]
        slot_of = positions % self.page_size
        # padding lanes scatter out of bounds and are DROPPED — they can
        # never touch a page, whatever their (clamped) row aliases
        upd_page = jnp.where(valid, page_of, k_pages.shape[1])
        for i in range(cfg.n_layers):
            p = self._layer_params(params, i)
            h = rms_norm(x, p["ln1"])
            q, k, v = _qkv(p["attn"], cfg, h)
            q = apply_rope(q, angles)
            k = apply_rope(k, angles)
            k_pages = k_pages.at[i, upd_page, slot_of].set(
                k[0].astype(k_pages.dtype), mode="drop")
            v_pages = v_pages.at[i, upd_page, slot_of].set(
                v[0].astype(v_pages.dtype), mode="drop")
            out = ops.packed_prefill_attention(
                q[0], k_pages[i], v_pages[i], page_rows, seg_ids,
                positions, seg_ctx, backend=self.config.backend)
            x = x + out.reshape(1, c, -1) @ p["attn"]["wo"]
            h = rms_norm(x, p["ln2"])
            ff = jax.nn.silu(h @ p["ffn"]["wi_gate"]) * (h @ p["ffn"]["wi_up"])
            x = x + ff @ p["ffn"]["wo"]
        x = rms_norm(x, params["final_norm"])
        # one lm_head row per SEGMENT (S rows), not per lane: only each
        # finishing segment's last-token logits matter, and S << C keeps
        # the head matmul off the chunk's critical path
        lanes = jnp.clip(emit_lanes, 0, c - 1)
        logits = x[0, lanes] @ params["lm_head"]         # (S, V)
        toks, lps = ops.sample_tokens(logits, sampf[:, 0], sampi[:, 0],
                                      sampf[:, 1], sampi[:, 1], spos)
        return toks, lps, k_pages, v_pages

    def _paged_step_packed_flat(self, params, k_pages, v_pages, lanes,
                                pages, emit_lanes, sampf, sampi, spos):
        """XLA-backend variant of the fused packed step with a RAGGED key
        layout: the host lays every segment's live pages end to end into
        one flat page list, so attention cost is proportional to the
        chunk's ACTUAL aggregate context instead of the
        (segments × max_pages) rectangle the generic formulation gathers.
        (The Pallas kernel path keeps the rectangle — it prunes dead
        pages in-grid via seg_ctx, which XLA's dense gather cannot.)

        lanes: (5, L) int32 rows [tokens; seg_ids; positions; upd_page;
        slot] — seg -1 lanes are padding, their upd_page is out of bounds
        (scatter drops).  pages: (3, P) int32 rows [page_id; page_seg;
        page_base] — one entry per LIVE page of some segment, page_base
        its first token's absolute position, page_seg -1 for bucket
        padding.  P is bucketed to a power of two; shared physical pages
        appear once per owning segment, each under its own page_seg.
        emit_lanes / sampf / sampi / spos: (max_batch,·) as in the
        rectangle path.  Returns ((max_batch,) tokens, logprobs)."""
        cfg = self.cfg
        hkv, dh = cfg.n_kv_heads, cfg.head_dim
        g = cfg.n_heads // hkv
        pgsz = self.page_size
        scale = 1.0 / (dh ** 0.5)
        toks = lanes[0][None, :]                         # (1, L)
        seg_ids, positions = lanes[1], lanes[2]
        upd_page, slot_of = lanes[3], lanes[4]
        flat, page_seg, page_base = pages[0], pages[1], pages[2]
        c = toks.shape[1]
        x = jnp.take(params["embed"], toks, axis=0)      # (1, L, D)
        angles = rope_angles(positions[None, :], cfg.head_dim,
                             cfg.rope_theta)
        # key ownership: each flat key slot belongs to ONE (segment,
        # position) — a lane attends exactly its own segment's causal keys
        key_seg = jnp.repeat(page_seg, pgsz)             # (P*pgsz,)
        key_pos = (page_base[:, None] +
                   jnp.arange(pgsz, dtype=jnp.int32)[None, :]).reshape(-1)
        allowed = (seg_ids[:, None] == key_seg[None, :]) & \
            (key_pos[None, :] <= positions[:, None])     # (L, P*pgsz)
        for i in range(cfg.n_layers):
            p = self._layer_params(params, i)
            h = rms_norm(x, p["ln1"])
            q, k, v = _qkv(p["attn"], cfg, h)
            q = apply_rope(q, angles)
            k = apply_rope(k, angles)
            k_pages = k_pages.at[i, upd_page, slot_of].set(
                k[0].astype(k_pages.dtype), mode="drop")
            v_pages = v_pages.at[i, upd_page, slot_of].set(
                v[0].astype(v_pages.dtype), mode="drop")
            k_seq = k_pages[i, flat].reshape(-1, hkv, dh) \
                .astype(jnp.float32)
            v_seq = v_pages[i, flat].reshape(-1, hkv, dh) \
                .astype(jnp.float32)
            qf = q[0].reshape(c, hkv, g, dh).astype(jnp.float32) * scale
            sc = jnp.einsum("ckgd,tkd->ckgt", qf, k_seq)
            sc = jnp.where(allowed[:, None, None, :], sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            # padding lanes match no key: pin their NaN softmax to zero
            pr = jnp.where((seg_ids >= 0)[:, None, None, None], pr, 0.0)
            out = jnp.einsum("ckgt,tkd->ckgd", pr, v_seq).astype(x.dtype)
            x = x + out.reshape(1, c, -1) @ p["attn"]["wo"]
            h = rms_norm(x, p["ln2"])
            ff = jax.nn.silu(h @ p["ffn"]["wi_gate"]) * (h @ p["ffn"]["wi_up"])
            x = x + ff @ p["ffn"]["wo"]
        x = rms_norm(x, params["final_norm"])
        lanes_e = jnp.clip(emit_lanes, 0, c - 1)
        logits = x[0, lanes_e] @ params["lm_head"]       # (max_batch, V)
        toks, lps = ops.sample_tokens(logits, sampf[:, 0], sampi[:, 0],
                                      sampf[:, 1], sampi[:, 1], spos)
        return toks, lps, k_pages, v_pages

    def _paged_decode_step(self, params, k_pages, v_pages, block_tables,
                           ctx_lens, tokens, occ, sampf, sampi):
        """One token for every occupied batch row.  ctx_lens INCLUDE the new
        token; its K/V is written at position ctx_lens-1.  ``occ`` (B,) bool
        marks real sequences: padded rows scatter out of bounds (dropped —
        they can never write a page, reused or otherwise) and their
        attention output is masked to zero, so padding needs no reserved
        scratch page and is inert whatever the pool does with page ids.

        sampf (B, 2) f32 [temperature, top_p] / sampi (B, 2) i32
        [top_k, seed]: per-row sampling operands; the next token is
        sampled at absolute position ctx_lens (the counter-PRNG replay
        coordinate — ctx_lens already counts the incoming token, so the
        sampled token will sit at stream index ctx_lens)."""
        cfg = self.cfg
        b = tokens.shape[0]
        x = jnp.take(params["embed"], tokens, axis=0)[:, None, :]  # (B,1,D)
        pos = (ctx_lens - 1)[:, None]
        angles = rope_angles(pos, cfg.head_dim, cfg.rope_theta)
        bidx = jnp.arange(b)
        page_idx = block_tables[bidx, (ctx_lens - 1) // self.page_size]
        # padded rows' writes land out of bounds and are dropped
        page_idx = jnp.where(occ, page_idx, k_pages.shape[1])
        slot_idx = (ctx_lens - 1) % self.page_size
        for i in range(cfg.n_layers):
            p = self._layer_params(params, i)
            h = rms_norm(x, p["ln1"])
            q, k, v = _qkv(p["attn"], cfg, h)
            q = apply_rope(q, angles)
            k = apply_rope(k, angles)
            k_pages = k_pages.at[i, page_idx, slot_idx].set(
                k[:, 0].astype(k_pages.dtype), mode="drop")
            v_pages = v_pages.at[i, page_idx, slot_idx].set(
                v[:, 0].astype(v_pages.dtype), mode="drop")
            out = ops.paged_attention(q[:, 0], k_pages[i], v_pages[i],
                                      block_tables, ctx_lens, occupancy=occ,
                                      backend=self.config.backend)
            x = x + out.reshape(b, 1, -1) @ p["attn"]["wo"]
            h = rms_norm(x, p["ln2"])
            ff = jax.nn.silu(h @ p["ffn"]["wi_gate"]) * (h @ p["ffn"]["wi_up"])
            x = x + ff @ p["ffn"]["wo"]
        x = rms_norm(x, params["final_norm"])
        logits = x[:, 0] @ params["lm_head"]
        # fused sampling on device (see _paged_prefill): two (B,) arrays out
        toks, lps = ops.sample_tokens(logits, sampf[:, 0], sampi[:, 0],
                                      sampf[:, 1], sampi[:, 1], ctx_lens)
        return toks, lps, k_pages, v_pages

    def _draft_propose_fn(self, dparams, tok_buf, ctx, sampf, sampi):
        """Draft model: propose spec_k tokens per batch row, as a PURE
        function of the recorded token stream.

        tok_buf (B, S_max) i32 — each row's full recorded stream (prompt +
        emitted tokens), zero-padded; ctx (B,) i32 its length.  The draft
        has NO persistent KV cache: every round re-prefills the stream
        densely, reads the hidden state at ctx-1, then runs spec_k-1
        incremental steps against the just-built cache.  That costs a
        re-prefill per round but buys the replay property outright: draft
        proposals depend only on (recorded stream, seed, position), never
        on which schedule of preemptions/migrations built a cache — so the
        accept pattern and the emitted stream are resume-exact by
        construction (DESIGN.md §17).

        sampf (B, 2) f32 [temperature, top_p] / sampi (B, 2) i32
        [top_k, seed]: the draft proposes through the SAME filter as the
        target (q and p supported on the same candidate set keeps the
        rejection-sampling correctness argument clean) and draws with keys
        (seed, ctx + j, STREAM_DRAFT).  Greedy rows propose exact argmax,
        which makes spec-greedy ≡ plain-greedy token for token.

        Returns (d_toks (B, spec_k) i32, q_dists (B, spec_k, V) f32) where
        slot j is the proposal for absolute position ctx + j."""
        dcfg = self.draft_cfg
        kd = self.spec_k
        b, s = tok_buf.shape
        hkv, dh = dcfg.n_kv_heads, dcfg.head_dim
        g = dcfg.n_heads // hkv
        scale = 1.0 / (dh ** 0.5)
        n_l = dcfg.n_layers
        sk = s + kd                     # prefill keys + incremental writes
        bidx = jnp.arange(b)
        x = jnp.take(dparams["embed"], tok_buf, axis=0)      # (B, S, D)
        pos = jnp.arange(s, dtype=jnp.int32)
        angles = rope_angles(jnp.broadcast_to(pos[None, :], (b, s)),
                             dcfg.head_dim, dcfg.rope_theta)
        causal = pos[None, :] <= pos[:, None]                # (S, S)
        k_cache = jnp.zeros((n_l, b, sk, hkv, dh), jnp.float32)
        v_cache = jnp.zeros((n_l, b, sk, hkv, dh), jnp.float32)
        for i in range(n_l):
            p = jax.tree_util.tree_map(lambda t: t[i], dparams["blocks"])
            h = rms_norm(x, p["ln1"])
            q, k, v = _qkv(p["attn"], dcfg, h)
            q = apply_rope(q, angles)
            k = apply_rope(k, angles)
            kf = k.astype(jnp.float32)
            vf = v.astype(jnp.float32)
            k_cache = k_cache.at[i, :, :s].set(kf)
            v_cache = v_cache.at[i, :, :s].set(vf)
            qf = q.reshape(b, s, hkv, g, dh).astype(jnp.float32) * scale
            sc = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf)
            sc = jnp.where(causal[None, None, None], sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            out = jnp.einsum("bkgqs,bskd->bqkgd", pr, vf).astype(x.dtype)
            x = x + out.reshape(b, s, -1) @ p["attn"]["wo"]
            h = rms_norm(x, p["ln2"])
            ff = jax.nn.silu(h @ p["ffn"]["wi_gate"]) * (h @ p["ffn"]["wi_up"])
            x = x + ff @ p["ffn"]["wo"]
        xf = rms_norm(x, dparams["final_norm"])
        # rows past their ctx are garbage but unread: only the hidden state
        # at ctx-1 leaves the prefill (clamped for empty padding rows)
        hidden = xf[bidx, jnp.maximum(ctx - 1, 0)]           # (B, D)
        d_toks, q_dists = [], []
        for j in range(kd):
            logits = hidden @ dparams["lm_head"]             # (B, V)
            qd = jax.vmap(kref.filtered_dist_ref)(
                logits, sampf[:, 0], sampi[:, 0], sampf[:, 1])
            keys = jax.vmap(kref.sample_key_ref, in_axes=(0, 0, None))(
                sampi[:, 1], ctx + j, kref.STREAM_DRAFT)
            tok, _ = jax.vmap(kref.gumbel_pick_ref)(qd, keys)
            tok = jnp.where(sampf[:, 0] <= 0.0,
                            jnp.argmax(logits, axis=-1).astype(jnp.int32),
                            tok)
            d_toks.append(tok)
            q_dists.append(qd)
            if j == kd - 1:
                break
            # incremental draft step: feed the proposal at position ctx+j
            pj = ctx + j                                     # (B,)
            xs = jnp.take(dparams["embed"], tok, axis=0)[:, None, :]
            ang = rope_angles(pj[:, None], dcfg.head_dim, dcfg.rope_theta)
            kmask = jnp.arange(sk, dtype=jnp.int32)[None, :] <= pj[:, None]
            for i in range(n_l):
                p = jax.tree_util.tree_map(lambda t: t[i],
                                           dparams["blocks"])
                h = rms_norm(xs, p["ln1"])
                q, k, v = _qkv(p["attn"], dcfg, h)
                q = apply_rope(q, ang)
                k = apply_rope(k, ang)
                k_cache = k_cache.at[i, bidx, pj].set(
                    k[:, 0].astype(jnp.float32))
                v_cache = v_cache.at[i, bidx, pj].set(
                    v[:, 0].astype(jnp.float32))
                qf = q[:, 0].reshape(b, hkv, g, dh).astype(jnp.float32) \
                    * scale
                sc = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache[i])
                sc = jnp.where(kmask[:, None, None, :], sc, -jnp.inf)
                pr = jax.nn.softmax(sc, axis=-1)
                out = jnp.einsum("bkgs,bskd->bkgd", pr,
                                 v_cache[i]).astype(xs.dtype)
                xs = xs + out.reshape(b, 1, -1) @ p["attn"]["wo"]
                h = rms_norm(xs, p["ln2"])
                ff = jax.nn.silu(h @ p["ffn"]["wi_gate"]) * \
                    (h @ p["ffn"]["wi_up"])
                xs = xs + ff @ p["ffn"]["wo"]
            hidden = rms_norm(xs, dparams["final_norm"])[:, 0]
        return jnp.stack(d_toks, axis=1), jnp.stack(q_dists, axis=1)

    def _spec_verify_fn(self, params, k_pages, v_pages, x_last, d_toks,
                        ctx, nd, occ, rows, sampf, sampi, q_dists):
        """Target verify: score every draft chain in ONE packed chunk call
        and rejection-sample on device.

        Lane layout: LV = max_batch * (spec_k + 1) lanes; lane i*(k+1)+j
        holds row i's token j (j == 0 → x_last, the latest emitted token
        whose K/V is not yet written; j >= 1 → d_toks[i, j-1]) at absolute
        position ctx[i] - 1 + j.  Dead lanes (j > nd[i], or unoccupied
        rows) get seg -1 / out-of-bounds scatter, exactly like packed
        prefill padding.  The j == 0 lane REWRITES position ctx-1 each
        round — the write is bit-identical to what the plain decode step
        would have written there, and it restores cross-run page
        exactness after a restore-from-swap.

        The target's K/V for accepted positions lands in the pages as a
        side effect (lanes j = 0..nd at positions ctx-1..ctx+nd-1); the
        correction/bonus token's K/V is NOT written — the next round's
        x_last lane writes it, preserving the engine invariant that the
        latest token's K/V is written by the step that consumes it.

        Returns (toks (B, k+1), n_emit (B,), lps (B, k+1), k_pages,
        v_pages); n_emit is zeroed for unoccupied rows."""
        cfg = self.cfg
        kd = self.spec_k
        b = x_last.shape[0]
        lanes_per = kd + 1
        lv = b * lanes_per
        pgsz = self.page_size
        lane_row = jnp.arange(lv, dtype=jnp.int32) // lanes_per   # (LV,)
        lane_j = jnp.arange(lv, dtype=jnp.int32) % lanes_per      # (LV,)
        tok_grid = jnp.concatenate([x_last[:, None], d_toks], axis=1)
        toks = tok_grid[lane_row, lane_j][None, :]                # (1, LV)
        positions = ctx[lane_row] - 1 + lane_j                    # (LV,)
        live = (lane_j <= nd[lane_row]) & occ[lane_row]
        seg_ids = jnp.where(live, lane_row, -1)
        page_of = rows[lane_row, positions // pgsz]
        upd_page = jnp.where(live, page_of, k_pages.shape[1])
        slot_of = positions % pgsz
        seg_ctx = jnp.where(occ, ctx + nd, 0)                     # (B,)
        x = jnp.take(params["embed"], toks, axis=0)               # (1,LV,D)
        angles = rope_angles(positions[None, :], cfg.head_dim,
                             cfg.rope_theta)
        for i in range(cfg.n_layers):
            p = self._layer_params(params, i)
            h = rms_norm(x, p["ln1"])
            q, k, v = _qkv(p["attn"], cfg, h)
            q = apply_rope(q, angles)
            k = apply_rope(k, angles)
            k_pages = k_pages.at[i, upd_page, slot_of].set(
                k[0].astype(k_pages.dtype), mode="drop")
            v_pages = v_pages.at[i, upd_page, slot_of].set(
                v[0].astype(v_pages.dtype), mode="drop")
            out = ops.packed_prefill_attention(
                q[0], k_pages[i], v_pages[i], rows, seg_ids,
                positions, seg_ctx, backend=self.config.backend)
            x = x + out.reshape(1, lv, -1) @ p["attn"]["wo"]
            h = rms_norm(x, p["ln2"])
            ff = jax.nn.silu(h @ p["ffn"]["wi_gate"]) * (h @ p["ffn"]["wi_up"])
            x = x + ff @ p["ffn"]["wo"]
        x = rms_norm(x, params["final_norm"])
        logits = x[0] @ params["lm_head"]                         # (LV, V)
        p_dists = jax.vmap(kref.filtered_dist_ref)(
            logits, sampf[lane_row, 0], sampi[lane_row, 0],
            sampf[lane_row, 1])
        p_dists = p_dists.reshape(b, lanes_per, -1)               # (B,k+1,V)
        toks_o, n_emit, lps = ops.spec_verify_rows(
            p_dists, q_dists, d_toks, nd, sampi[:, 1], ctx)
        n_emit = jnp.where(occ, n_emit, 0)
        return toks_o, n_emit, lps, k_pages, v_pages

    # ------------------------------------------------------------- engine
    def _fault_dispatch(self) -> None:
        """Chaos hook immediately before a device dispatch (the ``delay``
        kind: a slow device, not a dead thread)."""
        if self.fault_line is not None:
            self.fault_line.on_dispatch(self)

    def _sweep_deadlines(self) -> None:
        """Per-request deadlines, enforced through the EXISTING cancel
        path: waiting requests are purged and failed out immediately (a
        full decode batch must not hide an expired request until its
        admission turn), live ones get their ``cancelled`` event set and
        the step loop reaps them exactly like a client cancel."""
        now = time.perf_counter()
        with self._wlock:
            expired = self.admission.purge(
                self._waiting,
                lambda r: r.cancelled.is_set() or
                self._expiry_reason(r, now) is not None)
        for req in expired:
            if not req.cancelled.is_set():
                why = self._expiry_reason(req, now)
                if why.startswith("TTFT"):
                    self.n_slo_cancelled += 1
                req.error = f"{why} (waiting)"
                req.cancelled.set()
            self._fail_out(req, "cancelled")
        for seq in self._prefilling + self._active:
            req = seq.req
            why = self._expiry_reason(req, now)
            if why is not None and not req.cancelled.is_set():
                if why.startswith("TTFT"):
                    self.n_slo_cancelled += 1
                req.error = f"{why} ({req.status})"
                req.cancelled.set()

    def _expiry_reason(self, req: Request, now: float) -> Optional[str]:
        """Why this request should be cancelled now, or None.  The TTFT
        SLO only bites while NO token exists — a swapped request already
        streamed tokens, so parking it cannot retro-expire its TTFT."""
        if req.deadline is not None and now > req.deadline:
            return f"deadline exceeded after {now - req.t_submit:.3f}s"
        if req.ttft_deadline is not None and not req.out_times \
                and now > req.ttft_deadline:
            return (f"TTFT SLO exceeded (class {req.priority_class!r}: "
                    f"no first token after {now - req.t_submit:.3f}s)")
        return None

    def _fail_out(self, req: Request, status: str) -> None:
        """Drop a request that will never run: give back its hit pins
        and any host arena slots its swapped K/V still occupies."""
        for pg in req._hit_pages:
            self.pool.unpin(pg)
        req._hit_pages = []
        req._hit_tokens = 0
        self._release_swap(req)
        req.status = status
        if status == "cancelled":
            self.n_cancelled += 1
        else:
            self.n_failed += 1
        req._progress.set()
        req.done.set()

    def _release_swap(self, req: Request) -> None:
        """Discard the request's swap manifest (terminal paths and
        migration-away — the tokens themselves are the durable copy)."""
        if self.swap_arena is not None:
            self.swap_arena.release(req.req_id)
        req._swap_tokens = 0

    def _admit(self):
        """Admission reserves pages and enqueues — it NEVER runs model work,
        so a 4k-token prompt cannot stall the decode batch here.  The prompt
        is ingested chunk-by-chunk by :meth:`_step_locked` under the
        scheduler policy's token budget.

        With the ``swap`` eviction policy, a queue head that outranks the
        lowest-priority active sequence may PREEMPT it — both for a batch
        slot and for pages — spilling the victim's K/V to the host arena
        (DESIGN.md §15)."""
        while True:
            if len(self._active) + len(self._prefilling) >= self.max_batch:
                # batch full: a higher-priority head may still claim a slot
                # by preempting the lowest-priority active sequence
                if not self._preempt_for_slot():
                    return
                continue
            with self._wlock:
                req = self.admission.pop(self._waiting)
            if req is None:
                return
            if req.cancelled.is_set():
                self._fail_out(req, "cancelled")
                continue
            if not self._admit_one(req):
                return

    def _admit_one(self, req: Request) -> bool:
        """Reserve this request's pages and enqueue it for prefill;
        False stops this step's admission wave (pool pressure)."""
        resume = req.status == "swapped"
        if resume and not req._hit_pages:
            # restore prefix-cache hits FIRST: the replay prompt may have
            # become (partly) cache-resident while the request was parked —
            # any hit page supersedes the arena copy of the same positions.
            # Skipped when a failed resume attempt already holds pins
            # (re-looking-up would double-pin).
            pages, n_tok = self.prefix_cache.lookup(req.prompt)
            self._attach_hit(req, pages, n_tok)
        total = len(req.prompt) + req.max_new_tokens
        n_pages_needed = -(-total // self.page_size)
        pages = list(req._hit_pages)
        owned_from = len(pages)
        for _ in range(n_pages_needed - len(pages)):
            pg = self.pool.try_alloc(req.req_id)
            if pg is None:
                break
            pages.append(pg)
        if len(pages) < n_pages_needed and self.swap_enabled:
            # eviction pressure cannot be met from finished sequences:
            # preempt strictly-lower-priority ACTIVE sequences, reclaim
            # their retired pages into our own context, retry once
            if self._preempt_for_pages(req, n_pages_needed - len(pages)):
                self.smr.help_reclaim()
                for _ in range(n_pages_needed - len(pages)):
                    pg = self.pool.try_alloc(req.req_id)
                    if pg is None:
                        break
                    pages.append(pg)
        if len(pages) < n_pages_needed:
            # pool pressure: shed the eviction policy's quota for one
            # event, help reclamation, requeue ahead of peers (a swapped
            # request keeps its hit pins and its arena manifest for the
            # next attempt)
            for pg in pages[owned_from:]:
                self.pool.release(pg)
            self.prefix_cache.pressure_evict()
            self.smr.help_reclaim()
            with self._wlock:
                self.admission.requeue(self._waiting, req)
            return False
        page_ids = np.zeros((self.max_pages,), np.int32)
        for j, pg in enumerate(pages):
            page_ids[j] = pg.page_id
        seq = _Seq(req, pages, owned_from, page_ids)
        if resume:
            self._restore_swapped(req, seq)
        req.status = "prefilling"
        self._prefilling.append(seq)
        return True

    # ------------------------------------------------- preemption (swap)
    def _lowest_victim(self, below: int) -> Optional[_Seq]:
        """Lowest-priority active sequence STRICTLY below ``below`` —
        ties broken youngest-first (largest req_id: the sequence that got
        the least service loses).  Prefilling sequences are never victims
        (nothing decoded yet; their admission is about to be re-litigated
        anyway) and neither are cancelled ones (the reaper owns those)."""
        best = None
        best_key = None
        for seq in self._active:
            req = seq.req
            if req.cancelled.is_set() or req.priority >= below:
                continue
            key = (req.priority, -req.req_id)
            if best is None or key < best_key:
                best, best_key = seq, key
        return best

    def _preempt_for_slot(self) -> bool:
        """Batch full: preempt the lowest-priority active sequence iff the
        waiting-queue head strictly outranks it."""
        if not self.swap_enabled:
            return False
        with self._wlock:
            head = self.admission.peek(self._waiting)
        if head is None or head.cancelled.is_set():
            return False
        victim = self._lowest_victim(head.priority)
        if victim is None:
            return False
        return self._preempt_seq(victim)

    def _preempt_for_pages(self, req: Request, shortfall: int) -> bool:
        """Preempt strictly-lower-priority active sequences until their
        OWNED pages cover ``shortfall`` (all-or-nothing per victim: a
        victim whose spill does not fit the arena stays resident)."""
        freed = 0
        any_preempted = False
        while freed < shortfall:
            victim = self._lowest_victim(req.priority)
            if victim is None:
                return any_preempted
            owned = len(victim.pages) - victim.owned_from
            if not self._preempt_seq(victim):
                return any_preempted     # arena full: stop preempting
            any_preempted = True
            freed += owned
        return True

    def _preempt_seq(self, seq: _Seq) -> bool:
        """Spill one active sequence to the host arena and park it.

        ORDER (the mirror of migration's import-before-export): the
        device→host copy completes — np.asarray blocks on the transfer —
        and the manifest is recorded BEFORE ``_release_seq`` retires the
        device pages through the SMR, so at no instant does neither tier
        hold the K/V bytes.  Only full pages spill: the tail positions of
        a partly-filled page (and the not-yet-written K/V of the latest
        emitted token) are re-ingested by prefill chunks on resume, which
        reproduces them bit-identically.  False (victim stays resident,
        nothing released) when the arena cannot take the spill."""
        req = seq.req
        t = len(seq.tokens)
        # positions 0..t-2 are in pages (the latest token's K/V is written
        # by the NEXT step); spill the full pages among them
        aligned = ((t - 1) // self.page_size) * self.page_size
        if aligned > 0:
            ks, vs = [], []
            for j in range(aligned // self.page_size):
                kp, vp = self._gather_page(self.k_pages, self.v_pages,
                                           int(seq.page_row[j]))
                ks.append(np.asarray(kp))   # blocks: copy is complete
                vs.append(np.asarray(vp))
            try:
                self.swap_arena.store(req.req_id, np.stack(ks),
                                      np.stack(vs), aligned)
            except SwapArenaFullError:
                return False
        # bytes are safe in the arena (or recomputable): NOW retire the
        # device claim through the normal SMR paths
        self._active.remove(seq)
        self._release_seq(seq)
        req._hit_pages = []
        req._hit_tokens = 0
        req.fold_emitted()
        req._swap_tokens = aligned
        req.status = "swapped"
        req._gap_pending = True     # next emit closes a service-gap interval
        req.preemptions += 1
        self.n_preemptions += 1
        with self._wlock:
            self.admission.push(self._waiting, req)
        return True

    def _restore_swapped(self, req: Request, seq: _Seq) -> None:
        """Copy a resuming sequence's arena pages back into its freshly
        allocated device pages.  Prefix-cache hits win: arena pages the
        hit already covers are discarded; the device copy completes
        (block_until_ready) BEFORE the slots are freed — the swap-in half
        of the copy-before-free contract.  A checksum failure falls back
        to recompute-from-tokens (the prompt is authoritative) instead of
        decoding from corrupt KV."""
        start = req._hit_tokens          # page-aligned (lookup guarantees)
        man = self.swap_arena.manifest(req.req_id) \
            if self.swap_arena is not None else None
        if man is not None and man.n_tokens > start:
            from_page = start // self.page_size
            try:
                k_np, v_np = self.swap_arena.load(req.req_id, from_page)
            except SwapChecksumError:
                seq.filled = start       # recompute everything past the hit
            else:
                for i in range(k_np.shape[0]):
                    pid = int(seq.page_row[from_page + i])
                    self.k_pages, self.v_pages = self._scatter_page(
                        self.k_pages, self.v_pages, pid,
                        jnp.asarray(k_np[i]), jnp.asarray(v_np[i]))
                jax.block_until_ready(self.k_pages)
                seq.filled = man.n_tokens
        self._release_swap(req)
        self.n_resumed += 1

    def _emit(self, seq: _Seq, tok: int, lp: float = 0.0) -> None:
        """Append one generated token and wake streamers."""
        seq.tokens.append(tok)
        req = seq.req
        now = time.perf_counter()
        if req._gap_pending and req.out_times:
            # the incoming interval spans a preemption park or a migration
            # stall: mark it as a SERVICE GAP — excluded from itl() and
            # the ITL-SLO observation (the SLO observes decode cadence),
            # reported separately via RequestHandle.gaps() and stats().
            # The mark indexes the timestamp that CLOSES the gap interval
            req._gap_marks.append(len(req.out_times))
            self.n_gap_intervals += 1
            self.gap_seconds += now - req.out_times[-1]
        elif req._itl_slo_s is not None and req.out_times \
                and now - req.out_times[-1] > req._itl_slo_s:
            # ITL SLO is OBSERVED, never enforced: the request keeps running
            self.n_itl_violations += 1
        req._gap_pending = False
        req.out_tokens.append(tok)
        req.out_times.append(now)
        if req.sampling is not None and req.sampling.logprobs:
            req.out_logprobs.append(float(lp))
        # host-side stop-sequence match against the emitted suffix (the
        # matched tokens stay in the output; generation halts with "done")
        if req.sampling is not None and req.sampling.stop:
            for s in req.sampling.stop:
                if len(req.out_tokens) >= len(s) and \
                        tuple(req.out_tokens[-len(s):]) == s:
                    req._stop_hit = True
                    break
        req._progress.set()

    def _advance_prefill(self, seq: _Seq, grant: int) -> None:
        """Ingest the next ``grant`` prompt tokens of one prefilling
        sequence, one fixed-size chunk call at a time (grants larger than
        the chunk — the ``oneshot`` policy's whole prompts — just loop).
        The final chunk's logits yield the first generated token (streamed
        immediately) and move the sequence to decoding."""
        req = seq.req
        sp = req.sampling
        sampf = jnp.asarray([sp.temperature, sp.top_p], jnp.float32)
        sampi = jnp.asarray([sp.top_k, sp.seed], jnp.int32)
        n_prompt = len(req.prompt)
        chunk = self.config.prefill_chunk_tokens
        end = min(seq.filled + grant, n_prompt)
        tok = lp = None
        while seq.filled < end:
            n_valid = min(chunk, end - seq.filled)
            buf = np.zeros((1, chunk), np.int32)
            buf[0, :n_valid] = req.prompt[seq.filled:seq.filled + n_valid]
            self._fault_dispatch()
            tok, lp, self.k_pages, self.v_pages = self._prefill(
                self.params, self.k_pages, self.v_pages,
                jnp.asarray(buf), jnp.asarray(seq.page_row),
                jnp.int32(seq.filled), jnp.int32(n_valid),
                sampf, sampi)
            seq.filled += n_valid
            self.prefill_chunks += 1
            self.prefill_tokens_wasted += chunk - n_valid
        if seq.filled == n_prompt:
            # final chunk: its last-position logits ARE the first token
            self._finish_prefill(seq, int(tok), float(lp))
        # intermediate chunks never sync with the device (tok is dropped
        # untouched), so chunking adds no host round-trips

    def _finish_prefill(self, seq: _Seq, tok: int, lp: float = 0.0) -> None:
        """A sequence's prompt is fully in pages and its first token is in
        hand: stream it and move the sequence to decoding (or straight to
        done — a max_new_tokens=1 request used to overshoot to 2 because
        activation skipped the limit check and the same step's decode
        emitted before its own).

        In SPECULATIVE mode the chunk's sampled token is DISCARDED and
        nothing is emitted here: every token — including the first —
        comes out of a spec round, so a freshly admitted request and a
        resumed one take the exact same emission path (the first fresh
        position is drawn via accept/residual streams either way, which
        is what keeps the accept pattern replay-exact; DESIGN.md §17).
        The sequence just activates with ``new_tokens = 0``."""
        req = seq.req
        self._prefilling.remove(seq)
        if self.spec_k > 0:
            seq.new_tokens = 0
            if req.cancelled.is_set():
                self._finish(seq, "cancelled")
            else:
                req.status = "active"
                self._active.append(seq)
            return
        self._emit(seq, tok, lp)
        seq.new_tokens = 1
        if seq.new_tokens >= req.max_new_tokens \
                or req.cancelled.is_set() or req._stop_hit:
            self._finish(seq, "cancelled" if req.cancelled.is_set()
                         else "done")
        else:
            req.status = "active"
            self._active.append(seq)

    def _advance_packed(self, plan, riders):
        """Execute a whole prefill plan as packed fixed-shape chunks (the
        ``packed`` scheduler): every granted sequence's slice goes into ONE
        ``(1, L)`` chunk with sequence-indicator segment ids, so the chunk
        budget buys C tokens of aggregate progress per kernel call instead
        of per sequence.  With chunked-style grants (sum ≤ C, ≤ max_batch
        sequences) one chunk per step suffices; the loop still splits
        defensively if a plan ever overflows C lanes or max_batch
        segments.

        FUSED STEP: ``riders`` is the step's active decode batch — each
        rider becomes one more segment holding exactly one lane (its
        current token at position ctx-1, emit lane set), so the step's
        decode tokens come out of the SAME device call as the prefill
        chunk.  One dispatch + one host sync per step instead of two of
        each; the decode batch and prefill chunk never queue behind each
        other's dispatch latency.  The lane axis is C + max_batch wide so
        riders never eat into the prefill token budget (active +
        prefilling share max_batch, so segments always fit).  Riders ride
        the FIRST chunk only; returns their (next tokens, logprobs) pair
        of (n_riders,) arrays, or None when the plan was empty (caller
        falls back to the dedicated decode batch, which is cheaper than a
        mostly-empty packed chunk).

        The segment axis is BUCKETED to the next power of two above the
        actual segment count (1/2/4/.../max_batch) before the device call:
        attention cost scales with S·max_pages keys, so a 1-segment chunk
        must not pay the max_batch-wide gather.  At most log2(max_batch)+1
        jit variants exist, all compiled by :meth:`warm_packed` or first
        traffic."""
        chunk = self.config.prefill_chunk_tokens
        lanes_max = chunk + self.max_batch
        n_segs = self.max_batch
        pgsz = self.page_size
        flat_path = self.config.backend == "xla"
        queue = [(seq, grant) for seq, grant in plan if grant > 0]
        rider_toks = None
        first = True
        while queue:
            toks = np.zeros((1, lanes_max), np.int32)
            segs = np.full((lanes_max,), -1, np.int32)
            poss = np.zeros((lanes_max,), np.int32)
            # per-lane scatter targets (flat path); padding lanes point
            # out of bounds and are dropped on device
            upd = np.full((lanes_max,), self.config.num_pages, np.int32)
            slot = np.zeros((lanes_max,), np.int32)
            rows = np.zeros((n_segs, self.max_pages), np.int32)
            ctxs = np.zeros((n_segs,), np.int32)
            emit = np.full((n_segs,), lanes_max, np.int32)  # not finishing
            # per-segment sampling operands + the absolute position each
            # emitting segment samples AT (the counter-PRNG coordinate)
            sampf = np.zeros((n_segs, 2), np.float32)
            sampi = np.zeros((n_segs, 2), np.int32)
            spos = np.zeros((n_segs,), np.int32)
            seg_pages = []       # (page_row, n_live_pages) per segment
            members = []
            lane = 0
            budget = len(riders) if first else 0
            while queue and lane < chunk and len(members) + budget < n_segs:
                seq, grant = queue.pop(0)
                take = min(grant, chunk - lane)
                si = len(members)
                pos = np.arange(seq.filled, seq.filled + take)
                toks[0, lane:lane + take] = \
                    seq.req.prompt[seq.filled:seq.filled + take]
                segs[lane:lane + take] = si
                poss[lane:lane + take] = pos
                upd[lane:lane + take] = seq.page_row[pos // pgsz]
                slot[lane:lane + take] = pos % pgsz
                rows[si] = seq.page_row
                ctxs[si] = seq.filled + take
                sp = seq.req.sampling
                sampf[si] = (sp.temperature, sp.top_p)
                sampi[si] = (sp.top_k, sp.seed)
                spos[si] = seq.filled + take
                seg_pages.append((seq.page_row,
                                  -(-(seq.filled + take) // pgsz)))
                if seq.filled + take == len(seq.req.prompt):
                    emit[si] = lane + take - 1
                members.append((seq, take))
                lane += take
                if take < grant:
                    # chunk overflow: the remainder LEADS the next chunk.
                    # A mid-chunk split point need not be page-aligned —
                    # alignment only matters at STEP end (prefix-cache
                    # resume), and the full grant lands within this plan.
                    queue.insert(0, (seq, grant - take))
            n_riders = 0
            if first:
                for seq in riders:
                    si = len(members) + n_riders
                    ctx = len(seq.tokens)
                    toks[0, lane] = seq.tokens[-1]
                    segs[lane] = si
                    poss[lane] = ctx - 1
                    upd[lane] = seq.page_row[(ctx - 1) // pgsz]
                    slot[lane] = (ctx - 1) % pgsz
                    rows[si] = seq.page_row
                    ctxs[si] = ctx
                    sp = seq.req.sampling
                    sampf[si] = (sp.temperature, sp.top_p)
                    sampi[si] = (sp.top_k, sp.seed)
                    spos[si] = ctx
                    seg_pages.append((seq.page_row, -(-ctx // pgsz)))
                    emit[si] = lane
                    n_riders += 1
                    lane += 1
            self.prefill_chunks += 1
            self.packed_chunks += 1
            self.packed_segments += len(members)
            self.prefill_tokens_wasted += chunk - (lane - n_riders)
            total = len(members) + n_riders
            self._fault_dispatch()
            if flat_path:
                # ragged key layout: segments' LIVE pages laid end to end,
                # the page total bucketed to a power of two (≥ 8) — the
                # call pays for the aggregate context actually attended,
                # never the (segments × max_pages) rectangle
                n_pages = sum(n for _, n in seg_pages)
                p_b = max(8, 1 << max(0, n_pages - 1).bit_length())
                pages = np.zeros((3, p_b), np.int32)
                pages[1] = -1                      # padding owns no lane
                off = 0
                for si, (row, n) in enumerate(seg_pages):
                    pages[0, off:off + n] = row[:n]
                    pages[1, off:off + n] = si
                    pages[2, off:off + n] = np.arange(n) * pgsz
                    off += n
                lanes = np.stack([toks[0], segs, poss, upd, slot])
                out_toks, out_lps, self.k_pages, self.v_pages = \
                    self._packed_flat(
                        self.params, self.k_pages, self.v_pages,
                        jnp.asarray(lanes), jnp.asarray(pages),
                        jnp.asarray(emit), jnp.asarray(sampf),
                        jnp.asarray(sampi), jnp.asarray(spos))
            else:
                # power-of-2 segment bucket: pay for the segments actually
                # present, not max_batch (seg ids are compact, so a prefix
                # slice of the per-segment operands is sufficient)
                n_b = min(n_segs, 1 << max(0, total - 1).bit_length())
                out_toks, out_lps, self.k_pages, self.v_pages = \
                    self._prefill_packed(
                        self.params, self.k_pages, self.v_pages,
                        jnp.asarray(toks), jnp.asarray(segs),
                        jnp.asarray(poss), jnp.asarray(rows[:n_b]),
                        jnp.asarray(ctxs[:n_b]), jnp.asarray(emit[:n_b]),
                        jnp.asarray(sampf[:n_b]), jnp.asarray(sampi[:n_b]),
                        jnp.asarray(spos[:n_b]))
            finishing = any(emit[si] < lanes_max
                            for si in range(len(members)))
            # only a chunk that emits tokens (some prompt completed, or
            # decode riders aboard) syncs with the device
            out_np = lps_np = None
            if finishing or n_riders:
                out_np = np.asarray(out_toks)
                lps_np = np.asarray(out_lps)
            for si, (seq, take) in enumerate(members):
                seq.filled += take
                if emit[si] < lanes_max:
                    self._finish_prefill(seq, int(out_np[si]),
                                         float(lps_np[si]))
            if n_riders:
                rider_toks = (
                    out_np[len(members):len(members) + n_riders],
                    lps_np[len(members):len(members) + n_riders])
            first = False
        return rider_toks

    def _spec_round(self) -> None:
        """One speculative round for the whole active batch: the draft
        proposes up to spec_k tokens per row, the target verifies every
        chain in ONE packed chunk call with fused on-device rejection
        sampling, and each row emits its accepted prefix plus the
        correction/bonus token — always ≥ 1 token per row per round, so
        spec decode can never be slower than plain decode in tokens per
        device sync (two dispatches, one sync).

        Per-row draft depth ``nd = min(spec_k, remaining - 1, capacity -
        ctx)``: the round never emits past ``max_new_tokens`` and never
        scatters K/V past the page run.  Both bounds are INVARIANT under
        ``fold_emitted()`` (remaining = max_new - new_tokens and capacity
        - ctx are conserved by the fold), so a resumed request sees the
        same nd schedule — hence the same accept pattern and tokens — as
        the uninterrupted run (DESIGN.md §17)."""
        batch = list(self._active)
        b = self.max_batch
        kd = self.spec_k
        s_max = self.max_pages * self.page_size
        tok_buf = np.zeros((b, s_max), np.int32)
        ctx = np.ones((b,), np.int32)
        nd = np.zeros((b,), np.int32)
        occ = np.zeros((b,), bool)
        rows = np.zeros((b, self.max_pages), np.int32)
        x_last = np.zeros((b,), np.int32)
        sampf = np.zeros((b, 2), np.float32)
        sampi = np.zeros((b, 2), np.int32)
        for i, seq in enumerate(batch):
            t = len(seq.tokens)
            tok_buf[i, :t] = seq.tokens
            ctx[i] = t
            remaining = seq.req.max_new_tokens - seq.new_tokens
            capacity = len(seq.pages) * self.page_size
            nd[i] = max(0, min(kd, remaining - 1, capacity - t))
            occ[i] = True
            rows[i] = seq.page_row
            x_last[i] = seq.tokens[-1]
            sp = seq.req.sampling
            sampf[i] = (sp.temperature, sp.top_p)
            sampi[i] = (sp.top_k, sp.seed)
        self._fault_dispatch()
        d_toks, q_dists = self._draft_propose(
            self.draft_params, jnp.asarray(tok_buf), jnp.asarray(ctx),
            jnp.asarray(sampf), jnp.asarray(sampi))
        self._fault_dispatch()
        # d_toks/q_dists stay on device between the two dispatches — the
        # only host sync in the round is reading the verdict below
        toks_o, n_emit, lps, self.k_pages, self.v_pages = self._spec_verify(
            self.params, self.k_pages, self.v_pages, jnp.asarray(x_last),
            d_toks, jnp.asarray(ctx), jnp.asarray(nd), jnp.asarray(occ),
            jnp.asarray(rows), jnp.asarray(sampf), jnp.asarray(sampi),
            q_dists)
        toks_np = np.asarray(toks_o)
        n_np = np.asarray(n_emit)
        lps_np = np.asarray(lps)
        done = []
        for i, seq in enumerate(batch):
            req = seq.req
            self.n_draft_proposed += int(nd[i])
            self.n_draft_accepted += int(n_np[i]) - 1
            for j in range(int(n_np[i])):
                if seq.new_tokens >= req.max_new_tokens \
                        or req.cancelled.is_set() or req._stop_hit:
                    break
                self._emit(seq, int(toks_np[i, j]), float(lps_np[i, j]))
                seq.new_tokens += 1
            if seq.new_tokens >= req.max_new_tokens \
                    or req.cancelled.is_set() or req._stop_hit:
                done.append(seq)
        for seq in done:
            self._active.remove(seq)
            self._finish(seq, "cancelled" if seq.req.cancelled.is_set()
                         else "done")

    def _release_seq(self, seq: _Seq) -> None:
        for pg in seq.pages[seq.owned_from:]:
            self.pool.release(pg)
        for pg in seq.pages[:seq.owned_from]:  # drop admission pins
            self.pool.unpin(pg)

    def _finish(self, seq: _Seq, status: str = "done"):
        if seq.req.done.is_set():
            # the watchdog already failed this handle out (unstealable
            # crash path: status/counters stamped, ``cancelled`` set so
            # we reap it here) — just give the pages back
            self._release_seq(seq)
            return
        # cache this sequence's page-aligned prefix (cancelled sequences are
        # not worth caching — their generation was cut short), then release
        # ownership
        if status == "done":
            self.prefix_cache.insert(seq.tokens, seq.pages)
            self.n_completed += 1
        elif status == "cancelled":
            self.n_cancelled += 1
        else:
            self.n_failed += 1
        self._release_seq(seq)
        seq.req.status = status
        seq.req._progress.set()
        seq.req.done.set()

    def warm_swap(self) -> None:
        """Pre-compile the per-page device↔host movers so the FIRST
        preemption doesn't pay their jit cost inside a high-priority
        request's TTFT window.  Gathers page 0 and scatters the identical
        values straight back (the scatter donation replaces the pool
        arrays with bit-identical contents) — safe on a live engine,
        serialised with steps by the step lock.  No-op unless the swap
        tier is enabled."""
        if not self.swap_enabled:
            return
        with self._step_lock:
            kp, vp = self._gather_page(self.k_pages, self.v_pages, 0)
            kp_h, vp_h = np.asarray(kp), np.asarray(vp)
            self.k_pages, self.v_pages = self._scatter_page(
                self.k_pages, self.v_pages, 0, kp_h, vp_h)
            jax.block_until_ready(self.k_pages)

    def warm_packed(self) -> None:
        """Pre-compile every packed-prefill segment bucket (1, 2, 4, ...,
        max_batch) with an all-padding chunk: padding lanes drop their K/V
        writes and the emitted tokens are discarded, so this is a pure
        jit-cache warm — safe on a live engine (serialised with steps by
        the step lock).  No-op under a non-packing scheduler.  Latency-
        sensitive deployments call this before opening the doors; the
        serving benchmark calls it so bucket compiles don't masquerade as
        serving time."""
        if not getattr(self.scheduler, "packs", False):
            return
        lanes_max = self.config.prefill_chunk_tokens + self.max_batch
        toks = jnp.zeros((1, lanes_max), jnp.int32)
        segs = jnp.full((lanes_max,), -1, jnp.int32)
        poss = jnp.zeros((lanes_max,), jnp.int32)
        with self._step_lock:
            if self.config.backend == "xla":
                # flat path: one jit variant per page-count bucket
                lanes = jnp.stack([
                    toks[0], segs, poss,
                    jnp.full((lanes_max,), self.config.num_pages,
                             jnp.int32),
                    jnp.zeros((lanes_max,), jnp.int32)])
                emit = jnp.full((self.max_batch,), lanes_max, jnp.int32)
                sampf = jnp.zeros((self.max_batch, 2), jnp.float32)
                sampi = jnp.zeros((self.max_batch, 2), jnp.int32)
                spos = jnp.zeros((self.max_batch,), jnp.int32)
                p_b, p_top = 8, self.max_batch * self.max_pages
                while True:
                    pages = jnp.stack([
                        jnp.zeros((p_b,), jnp.int32),
                        jnp.full((p_b,), -1, jnp.int32),
                        jnp.zeros((p_b,), jnp.int32)])
                    out, _, self.k_pages, self.v_pages = self._packed_flat(
                        self.params, self.k_pages, self.v_pages, lanes,
                        pages, emit, sampf, sampi, spos)
                    jax.block_until_ready(out)
                    if p_b >= p_top:
                        break
                    p_b *= 2
                return
            # pallas backends: one jit variant per segment bucket
            n_b = 1
            while True:
                out, _, self.k_pages, self.v_pages = self._prefill_packed(
                    self.params, self.k_pages, self.v_pages, toks,
                    segs, poss,
                    jnp.zeros((n_b, self.max_pages), jnp.int32),
                    jnp.zeros((n_b,), jnp.int32),
                    jnp.full((n_b,), lanes_max, jnp.int32),
                    jnp.zeros((n_b, 2), jnp.float32),
                    jnp.zeros((n_b, 2), jnp.int32),
                    jnp.zeros((n_b,), jnp.int32))
                jax.block_until_ready(out)
                if n_b >= self.max_batch:
                    break
                n_b = min(self.max_batch, n_b * 2)

    def _idle_decode_args(self):
        """Decode-step operands of an all-padding batch: no row is
        occupied, so every K/V write drops and the tokens are discarded."""
        b = self.max_batch
        return (jnp.zeros((b, self.max_pages), jnp.int32),
                jnp.ones((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), bool), jnp.zeros((b, 2), jnp.float32),
                jnp.zeros((b, 2), jnp.int32))

    def warm_decode(self) -> None:
        """Pre-compile the batched decode step with an all-padding batch —
        a pure jit-cache warm, safe on a live engine (step lock).  No-op
        under speculative decoding, whose rounds replace the decode step
        (:meth:`warm_spec`)."""
        if self.spec_k:
            return
        with self._step_lock:
            toks, _, self.k_pages, self.v_pages = self._decode(
                self.params, self.k_pages, self.v_pages,
                *self._idle_decode_args())
            jax.block_until_ready(toks)

    def decode_hlo(self) -> str:
        """Optimized HLO of the compiled decode step — the program the
        device runs, e.g. to check that the Pallas kernels are in it."""
        with self._step_lock:
            return self._decode.lower(
                self.params, self.k_pages, self.v_pages,
                *self._idle_decode_args()).compile().as_text()

    def warm_spec(self) -> None:
        """Pre-compile the speculative round's two dispatches
        (draft-propose + verify) with an all-padding batch so the first
        real round doesn't pay their jit cost inside a request's latency
        window.  ``occ`` is all-False: every verify lane is dead, its K/V
        scatter drops, and ``n_emit`` comes back zero, so this is a pure
        jit-cache warm — safe on a live engine (step lock).  No-op unless
        speculative decoding is enabled."""
        if not self.spec_k:
            return
        b = self.max_batch
        s_max = self.max_pages * self.page_size
        with self._step_lock:
            sampf = jnp.zeros((b, 2), jnp.float32)
            sampi = jnp.zeros((b, 2), jnp.int32)
            ctx = jnp.ones((b,), jnp.int32)
            d_toks, q_dists = self._draft_propose(
                self.draft_params, jnp.zeros((b, s_max), jnp.int32), ctx,
                sampf, sampi)
            out, n_emit, _, self.k_pages, self.v_pages = self._spec_verify(
                self.params, self.k_pages, self.v_pages,
                jnp.zeros((b,), jnp.int32), d_toks, ctx,
                jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool),
                jnp.zeros((b, self.max_pages), jnp.int32), sampf, sampi,
                q_dists)
            jax.block_until_ready(out)

    def step(self) -> bool:
        """One engine iteration; returns False when idle."""
        with self._step_lock:
            return self._step_locked()

    def _step_locked(self) -> bool:
        self._sweep_deadlines()
        self._admit()
        if not self._active and not self._prefilling:
            return False
        # drop cancelled prefilling sequences before spending budget on
        # them — their reserved pages (and hit pins) go straight back
        for seq in [s for s in self._prefilling
                    if s.req.cancelled.is_set()]:
            self._prefilling.remove(seq)
            self._finish(seq, "cancelled")
        # prefill phase: at most prefill_chunk_tokens of prompt ingestion,
        # divided by the scheduler policy — the ITL bound for everyone
        # already decoding is one chunk, never one prompt
        decoded = None
        batch_seqs = []
        if self._prefilling:
            plan = self.scheduler.plan(
                list(self._prefilling), self.config.prefill_chunk_tokens,
                self.page_size)
            if getattr(self.scheduler, "packs", False):
                # packed path: the WHOLE plan rides one fixed-shape chunk,
                # and the step's decode batch rides it too (fused step) —
                # sequences activated DURING this call decode next step.
                # Under SPECULATIVE decoding the active set never rides:
                # every emission must come from the spec round's streams
                # (accept/residual), not a schedule-dependent mix with
                # plain TARGET draws (DESIGN.md §17)
                batch_seqs = [] if self.spec_k else list(self._active)
                decoded = self._advance_packed(plan, batch_seqs)
            else:
                for seq, grant in plan:
                    if grant > 0:
                        self._advance_prefill(seq, grant)
        # decode phase: one token for every decoding sequence.  Rows beyond
        # the active set are padding — masked out of attention and their
        # K/V writes dropped (no scratch page, no reserved id).  When the
        # fused packed chunk already produced this step's decode tokens,
        # consume those instead of a second device call.
        if decoded is None and self._active:
            if self.spec_k:
                # speculative mode replaces the dedicated decode step
                # entirely: one draft-propose + one verify per round
                self._spec_round()
            else:
                batch_seqs = list(self._active)
                bt = np.zeros((self.max_batch, self.max_pages), np.int32)
                ctx = np.ones((self.max_batch,), np.int32)
                toks = np.zeros((self.max_batch,), np.int32)
                occ = np.zeros((self.max_batch,), bool)
                sampf = np.zeros((self.max_batch, 2), np.float32)
                sampi = np.zeros((self.max_batch, 2), np.int32)
                for i, seq in enumerate(batch_seqs):
                    bt[i, :] = seq.page_row
                    ctx[i] = len(seq.tokens)
                    toks[i] = seq.tokens[-1]
                    occ[i] = True
                    sp = seq.req.sampling
                    sampf[i] = (sp.temperature, sp.top_p)
                    sampi[i] = (sp.top_k, sp.seed)
                self._fault_dispatch()
                toks_d, lps_d, self.k_pages, self.v_pages = self._decode(
                    self.params, self.k_pages, self.v_pages,
                    jnp.asarray(bt), jnp.asarray(ctx), jnp.asarray(toks),
                    jnp.asarray(occ), jnp.asarray(sampf),
                    jnp.asarray(sampi))
                decoded = (np.asarray(toks_d), np.asarray(lps_d))
        if decoded is not None:
            next_toks, next_lps = decoded
            done = []
            for i, seq in enumerate(batch_seqs):
                self._emit(seq, int(next_toks[i]), float(next_lps[i]))
                seq.new_tokens += 1
                if seq.new_tokens >= seq.req.max_new_tokens \
                        or seq.req.cancelled.is_set() or seq.req._stop_hit:
                    done.append(seq)
            for seq in done:
                self._active.remove(seq)
                self._finish(seq, "cancelled" if seq.req.cancelled.is_set()
                             else "done")
        self.steps += 1
        if self.degraded:
            # the watchdog flagged us stalled but the loop is advancing:
            # counted so recovery windows are visible in stats()
            self.degraded_steps += 1
        return True

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        """Spawn the shard's own engine thread (session mode)."""
        assert self._thread is None, "shard already started"
        self._thread = threading.Thread(
            target=self.run, name=f"shard-{self.shard_id}-engine",
            daemon=True)
        self._thread.start()

    def run(self, poll_s: Optional[float] = None):
        """Engine loop (the shard thread, or a caller-owned thread).

        Every iteration bumps ``beat`` — the heartbeat the session
        watchdog reads — and runs the shard's fault line OUTSIDE the
        step lock (an injected stall models a descheduled thread
        *between* steps, so the watchdog can still steal the live
        sequences).  ANY escape, injected or real, hits the crash
        guard: every request fails out with the traceback instead of
        hanging its client (DESIGN.md §14)."""
        sleep_s = self.config.poll_s if poll_s is None else poll_s
        self._run_started.set()
        if self.fault_line is not None:
            self.fault_line.on_start(self)
        try:
            while not self._stop.is_set():
                self.beat += 1      # single-writer; watchdog only reads
                if self.fault_line is not None:
                    self.fault_line.before_step(self)
                if not self.step():
                    time.sleep(sleep_s)
        except BaseException as exc:  # noqa: BLE001 — the crash guard
            self._crash(exc)
        finally:
            self._run_done.set()

    def _crash(self, exc: BaseException) -> None:
        """The engine loop died: fail EVERY request out — waiting,
        prefilling and active — with the traceback surfaced through
        ``RequestHandle.result()``, release every page, and leave the
        pool provably clean.  No client ever hangs on a crashed shard;
        the watchdog sees ``crashed`` and routes around it (a crashed
        shard never recovers)."""
        tb = "".join(traceback.format_exception(type(exc), exc,
                                                exc.__traceback__))
        self.error = tb
        self.crashed = True
        # the stop flag goes up BEFORE the drain: submit()'s under-lock
        # re-check must see it, so no late submission strands hit pins
        self._stop.set()
        if self.fault_line is not None:
            self.fault_line.release(self)
        self._drain(error=tb)
        free = self.pool.free_count()
        assert free == self.config.num_pages, \
            (f"shard {self.shard_id} crash drain leaked pages: "
             f"{free}/{self.config.num_pages} free")

    def stop(self, drain: bool = True, timeout: float = 30.0):
        """Stop the engine and (by default) drain it clean: join the engine
        thread, fail out waiting + prefilling + active sequences
        (releasing/unpinning their pages), purge the prefix cache, and flush
        reclamation — after which ``pool.stats()`` shows every page back on
        the free list (zero leaks)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        elif self._run_started.is_set():
            # legacy mode: the caller owns the run() thread — wait for the
            # loop to acknowledge the stop before tearing state down
            self._run_done.wait(timeout)
        if self.fault_line is not None:
            # after the join: anything a fault still holds (reader guard,
            # exhaustion pages) comes back before the drain accounts pages
            self.fault_line.release(self)
        if drain:
            self._drain()

    def _drain(self, error: Optional[str] = None) -> None:
        with self._step_lock:
            with self._wlock:
                leftover = self.admission.drain(self._waiting)
            for req in leftover:
                if error and req.error is None:
                    req.error = error
                self._fail_out(req, "cancelled" if req.cancelled.is_set()
                               else "failed")
            for seq in self._prefilling + self._active:
                if error and seq.req.error is None:
                    seq.req.error = error
                self._finish(seq, "failed")
            self._prefilling.clear()
            self._active.clear()
            self.prefix_cache.clear()
            self.smr.flush()

    def stats(self):
        return {
            "shard": self.shard_id,
            "pool": self.pool.stats(),
            "prefix_cache": self.prefix_cache.stats(),
            "smr": self.smr.stats(),
            "steps": self.steps,
            "active": len(self._active),
            "prefilling": len(self._prefilling),
            "waiting": self.waiting_count(),
            "completed": self.n_completed,
            "cancelled": self.n_cancelled,
            "failed": self.n_failed,
            "preemptions": self.n_preemptions,
            "resumed": self.n_resumed,
            "slo_cancelled": self.n_slo_cancelled,
            "itl_slo_violations": self.n_itl_violations,
            "gap_intervals": self.n_gap_intervals,
            "gap_seconds": self.gap_seconds,
            "draft_proposed": self.n_draft_proposed,
            "draft_accepted": self.n_draft_accepted,
            "accept_rate": (self.n_draft_accepted / self.n_draft_proposed
                            if self.n_draft_proposed else 0.0),
            "swap": (self.swap_arena.stats()
                     if self.swap_arena is not None else None),
            "prefill_chunks": self.prefill_chunks,
            "prefill_tokens_wasted": self.prefill_tokens_wasted,
            "packed_chunks": self.packed_chunks,
            "packed_segments": self.packed_segments,
            "packed_segments_per_chunk": (
                self.packed_segments / self.packed_chunks
                if self.packed_chunks else 0.0),
            "beat": self.beat,
            "degraded": self.degraded,
            "crashed": self.crashed,
            "heartbeat_misses": self.heartbeat_misses,
            "degraded_steps": self.degraded_steps,
            "migrated_in": self.n_migrated_in,
            "migrated_out": self.n_migrated_out,
        }


class PagedServingEngine(_ShardEngine):
    """One-release compatibility shim: the pre-session construction surface.

    ``PagedServingEngine(model, params, smr=..., num_pages=..., ...)`` maps
    the old kwargs onto a :class:`ServingConfig` (with a
    ``DeprecationWarning``) and behaves as a single shard.  New code builds
    a config and calls :func:`repro.serving.serve`.
    """

    def __init__(self, model, params, *, smr="IBR",
                 num_pages: int = 256, page_size: int = 8,
                 max_batch: int = 4, max_seq_len: int = 256,
                 prefix_cache_entries: int = 128,
                 prefix_optimistic: Optional[bool] = None,
                 prefix_traversal=None,
                 config: Optional[ServingConfig] = None):
        if config is not None:
            super().__init__(model, params, config)
            return
        warnings.warn(
            "PagedServingEngine(...) kwargs are deprecated; build a "
            "repro.serving.ServingConfig and open a session with "
            "repro.serving.serve(model, params, config)",
            DeprecationWarning, stacklevel=2)
        if prefix_optimistic is not None:
            # thin shim for the pre-facade flag (one release)
            if prefix_traversal is not None:
                raise TypeError("PagedServingEngine: pass either "
                                "prefix_traversal= or the deprecated "
                                "prefix_optimistic= flag, not both")
            warnings.warn("PagedServingEngine(prefix_optimistic=...) is "
                          "deprecated; pass prefix_traversal='hm' for the "
                          "Harris-Michael prefix-cache buckets",
                          DeprecationWarning, stacklevel=2)
            prefix_traversal = None if prefix_optimistic else "hm"
        # an already-constructed scheme instance (shared with other
        # subsystems) bypasses the config's name-based construction
        shared = smr if isinstance(smr, SmrScheme) else None
        is_name = isinstance(prefix_traversal, str) or \
            prefix_traversal is None
        cfg = ServingConfig(
            smr=smr if isinstance(smr, str) else smr.name,
            num_pages=num_pages, page_size=page_size, max_batch=max_batch,
            max_seq_len=max_seq_len,
            prefix_cache_entries=prefix_cache_entries,
            prefix_traversal=prefix_traversal if is_name else None)
        super().__init__(model, params, cfg, smr=shared,
                         prefix_traversal=None if is_name
                         else prefix_traversal)
