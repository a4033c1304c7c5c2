"""ServingEngine: continuous-batching inference over a fixed slot pool.

The request lifecycle::

    engine = ServingEngine(cfg, params, {"num_slots": 8, "num_blocks": 128})
    rid = engine.submit([1, 2, 3], max_new_tokens=32)
    while engine.has_work():
        for req in engine.step():
            print(req.rid, req.output)
    # or: outputs = engine.run()

One ``step()`` is: expire timeouts -> admit+prefill queued requests into
free slots (length-bucketed, backpressure when the block pool is dry) ->
grow block tables for the next write (preempting the youngest slot when
the pool is exhausted) -> LAUNCH one jitted decode step over ALL slots ->
COLLECT the step launched one call earlier: append its tokens, evict
finished requests.

The decode loop runs one step ahead. A step's tokens stay on the device
and feed the next step there (``prev``), so step n+1 is packed from what
the host knows without them (tables, lengths and sampling counts, all
advanced at launch) and queued behind step n before step n is read: the
host's work lies behind the device's. A caller sees a token, and a
finish, in the ``step()`` that READS it. A request that ends by length
is never launched beyond it (host arithmetic); one that ends on a token
the host has not seen (EOS) gets one row too many, whose token is
dropped (``decode_rows_discarded``) and whose write lands in a page the
slot still holds. Whatever needs the host's truth about a slot first
collects what is in flight (``_settle``: a cancel, a timeout, a
preemption, the end of ``run()`` and ``drain()``), and while a step is
in flight the host waits for nothing queued behind it: the first token
of a prompt whose last chunk went out this step is picked first thing in
the next. With speculation on a round needs the host's verdict, so its
fallback rows launch and collect back to back: today's order.

Every gap between two tokens of one request is recorded where it ends
(``_record_emitted``): its length under what it held
(``ServingMetrics.record_token_gap``; ``summary()["itl_ms"]``), and the
same gaps as spans of the family ``gap/`` on a lane of their own
(``_gap_turn``): one is open exactly while some request that holds a slot
has a token and is owed another, so the device's idle time inside them is
what a waiting reader paid for, and the idle time outside them is traffic.

Static-shape discipline: the decode step closes over (num_slots,
blocks_per_slot) and always runs the full slot array — idle slots carry
token 0 / length 0 / an all-null block table and their garbage lane is
ignored on the host. Requests joining and leaving change only the DATA
fed to the same compiled program, never its shapes, so the decode step
compiles exactly once per engine (asserted in tests via the jit cache
counter). Prefill compiles once per length bucket.

Decode math reuses ``models/gpt.decoder_block`` (the same layer the
training forward and ``models/generation`` use) with a paged-cache
``attend`` (serving/kv_cache.paged_attend_rows), which is what makes
greedy serving outputs token-identical to per-request ``make_generator``
calls. The pool stays where it is: layers read it, and the step writes
the new rows of all layers once, after the loop.

``PipelineServingBridge`` gives pipelined models (PipelineModule over a
'pipe' mesh) the same submit/step/run surface by driving
``PipelineEngine.inference_batch`` with full-prefix recompute per token —
the reference fork's serving mode, kept as the compatibility path until
pipelined KV caching lands.
"""

import dataclasses
import itertools
import time
import zlib
from collections import deque
from functools import partial
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generation import apply_with_cache, init_cache, \
    prep_sampling_logits
from ..models import mixers
from ..models.gpt import GPTConfig
from ..models.speculative import engine_sample_key
from ..monitor import get_monitor, init_monitor, install_compile_listener
from ..monitor.tracer import (
    RID_SEP,
    trace_counter,
    trace_instant,
    trace_span,
)
from ..utils.frames import on_one_stack_chunk
from ..utils.logging import logger
from .config import ServingConfig
# ``_paged_block``: serving/spec/steps.py takes the attention kind's body
# from here; ``eva_page_list``: tests/bench patches it under this name too
from .kinds import KINDS, _paged_block, counts_experts, \
    split_expert_counts, sum_expert_counts  # noqa: F401
from .kv_cache import (  # noqa: F401
    NULL_BLOCK,
    PagedKVCache,
    chosen_forms,
    chunk_view,
    decode_view,
    eva_page_list,
    page_rule_for,
    write_decode_step,
    position_bytes,
    write_prefill_chunk,
)
from .metrics import ServingMetrics
from .scheduler import Request, Scheduler


class EngineDrainingError(RuntimeError):
    """Raised by ``submit()`` while the engine is draining: it is
    finishing its in-flight requests and admits nothing new. Callers
    owning more than one engine (the fleet router) catch this and fail
    the request over to another replica instead of stranding it in a
    queue that will never be served."""


# ------------------------------------------------------------------ #
# deterministic per-request sampling
# ------------------------------------------------------------------ #


def derive_request_seed(base_seed: int, rid: str) -> int:
    """Stable per-request sampling seed: a pure function of the engine
    seed and the request id (crc32, NOT Python hash(), which is
    randomized per process) so every replica — and every retry of the
    same rid on a different replica — derives the same stream."""
    return (zlib.crc32(rid.encode("utf-8")) ^ (base_seed * 0x9E3779B1)) \
        & 0x7FFFFFFF


def request_sample_key(seed: int, count: int):
    """PRNG key for a request's ``count``-th sampled token. Sampling is
    a pure function of (seed, token index): no engine-global key stream,
    so a retried request replays token-identically anywhere. Delegates
    to models/speculative.engine_sample_key — the single definition of
    the key contract that plain decode, the spec draft/verify programs,
    and make_matched_speculative_generator all share."""
    return engine_sample_key(seed, count)


# ------------------------------------------------------------------ #
# the jitted decode step
# ------------------------------------------------------------------ #

# columns of the packed slot array past a slot's page table
SLOT_SCALARS = 5
# a slot's token in the packed array while the host has not read it: the
# decode program takes the slot's entry of the last step's output instead
TAKE_PREV = -1


def pack_slots(tables, lengths, tokens, temps, seeds, counts) -> np.ndarray:
    """The six per-slot inputs of a decode step as ONE int32 array
    ``(num_slots, blocks_per_slot + 5)``, so that they cross to the device
    in one transfer: columns ``[0, bps)`` a slot's page table, then its
    length, pending token (``TAKE_PREV``, a negative id, where the token
    still lies on the device in the last step's output: the program then
    reads it there), sampling seed, sampled-token count, and its
    temperature as the float32's BITS (it arrives bit for bit, and
    ``temps[i] <= 0`` still means greedy). A fresh array every call: a
    placement may still be reading the last one (on the CPU backend it
    may alias host memory)."""
    tables = np.asarray(tables, np.int32)
    bps = tables.shape[1]
    slots = np.empty((tables.shape[0], bps + SLOT_SCALARS), np.int32)
    slots[:, :bps] = tables
    slots[:, bps] = lengths
    slots[:, bps + 1] = tokens
    slots[:, bps + 2] = seeds
    slots[:, bps + 3] = counts
    slots[:, bps + 4] = np.ascontiguousarray(
        temps, np.float32).view(np.int32)
    return slots


def idle_slots(num_slots: int, bps: int) -> np.ndarray:
    """The packed array of a step with every slot idle: what a caller
    that only lowers or audits the decode program hands it."""
    idle = np.zeros(num_slots, np.int32)
    return pack_slots(np.zeros((num_slots, bps), np.int32), idle, idle,
                      np.zeros(num_slots, np.float32), idle, idle)


def unpack_slots(slots, bps: int):
    """``pack_slots`` undone inside a program (static slices and a
    bitcast): ``(tables, lengths, tokens, temps, seeds, counts)``."""
    lengths, tokens, seeds, counts, temp_bits = (
        slots[:, bps + i] for i in range(SLOT_SCALARS))
    temps = jax.lax.bitcast_convert_type(temp_bits, jnp.float32)
    return slots[:, :bps], lengths, tokens, temps, seeds, counts


def make_decode_step(cfg: GPTConfig, scfg: ServingConfig, mesh=None):
    """Build the jitted all-slots decode step: the FRAME of the program
    (unpack, embed, the layer loop, the cache's write, the sampler). What
    a layer reads, keeps and writes is its kind's to say (``kinds.KINDS``)
    and the cache's (``kv_cache.decode_view``, ``write_decode_step``):
    nothing here names a kind.

    decode_step(params, k_pool, v_pool, slots, prev, kc_pool, state) ->
    (next_tokens (N,), k_pool', v_pool', kc_pool', state'): one shape for
    every model. ``slots`` is the step's six per-slot inputs as ONE int32
    array (``pack_slots`` on the host, one transfer; the program's first
    line takes it apart again, ``unpack_slots``). ``prev`` (N,) int32 is
    the last step's ``next_tokens`` as the device handed them back (never
    donated: the host may not have read them yet; zeros before any step
    has run): a slot whose packed token is ``TAKE_PREV`` decodes its
    entry of ``prev``, so a step can be launched before the host has the
    last one's tokens. ``kc_pool`` and ``state`` (``PagedKVCache.kc``,
    ``.state``) are None, in and out, for a model that keeps neither
    pooled keys nor state rows; one that does passes them, donated like
    the pools, and the layer loop goes run by run of one kind
    (``mixers.scan_runs``; a classic model is one run) with the state
    rows in its carry, written in place. Pools are donated — the caller's
    old handles die each step (no second pool in HBM) — and stay in
    place: the layer loop only READS them (each layer reads what its
    kind's rule lets the new token see, plus the new token's own row),
    yields the new rows, and one write after the loop lays all layers'
    rows into the donated buffers. A stack under two page rules hands
    ``k_pool`` and ``v_pool`` as PAIRS (the pool of every key, the rings'
    pool: ``kv_cache`` has the layout) and gets pairs back; where its
    feed-forward is routed experts, ``next_tokens`` and ``prev`` carry
    three or four more entries behind the slots': the experts touched
    (summed over the layers), the assignments (summed), the largest
    expert's assignments (the largest of any layer) and, where counted,
    the assignments that left, of this step, so that the one read-back
    the loop makes brings them too. A LOOPED stack (``cfg.loop_steps`` >
    1) runs its layer loop that many times over the same weights inside
    ONE scan (``mixers.scan_passes``: the final norm after each pass, a
    cache layer of its own for every (pass, layer) pair, one write after
    the last pass) and hands ``loop_steps`` entries more behind the
    tokens: where its tokens would have left (``exits_behind``).
    temps[i] <= 0 selects greedy argmax for slot i; > 0 samples at
    that temperature under the config's static top_k, keyed by
    ``request_sample_key(seeds[i], counts[i])`` so the sampled stream is
    a pure per-request function — retries and cross-replica failovers
    replay it token-identically.
    """
    top_k = scfg.top_k
    if top_k is not None and top_k >= cfg.vocab_size:
        top_k = None  # full-vocab top-k is a no-op filter
    view_of = decode_view(cfg, scfg, mesh)

    # traced behind a frame with room for every frame below it: where the
    # interpreter's 16 KiB chunks of frames end is then no part of set-up
    @partial(jax.jit, donate_argnums=(1, 2, 5, 6))
    @on_one_stack_chunk
    def ds_decode_step(params, k_pool, v_pool, slots, prev, kc_pool=None,
                       state=None):
        tables, lengths, tokens, temps, seeds, counts = unpack_slots(
            slots, scfg.blocks_per_slot)
        N = tokens.shape[0]
        if behind_tokens(cfg):
            prev = prev[:N]     # behind the slots': the last step's counts
        tokens = jnp.where(tokens == TAKE_PREV, prev, tokens)
        positions = lengths[:, None]                        # (N, 1)
        with jax.named_scope("ds.embed"):
            x = mixers.embed_tokens(cfg, params, tokens,
                                    lengths)[:, None, :]    # (N, 1, D)
        view = view_of(params, k_pool, v_pool, kc_pool, state, tables,
                       lengths, positions)

        def layer_body(kind, carry, layer_params, layer, at):
            x, rows = carry
            x, rows, kept = KINDS[kind].block(view, KINDS[kind].decode, x,
                                              layer_params, layer, rows, at)
            return (x, rows), kept

        x, state, kept, lam = mixers.scan_passes(
            cfg, params, x, state, layer_body, lambda x: x[:, 0])
        kept, experts = split_expert_counts(kept)
        with jax.named_scope("ds.decode/kv_write"):
            k_pool, v_pool, kc_pool = write_decode_step(view, kept)
        with jax.named_scope("ds.decode/sample"):
            logits = mixers.served_logits(
                cfg, mixers.head_logits(cfg, params, x,
                                        looped(cfg))[:, 0])     # (N, V)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            l32 = logits.astype(jnp.float32) / jnp.maximum(
                temps, 1e-6)[:, None]
            if top_k is not None:
                kth = jax.lax.top_k(l32, top_k)[0][..., -1:]
                l32 = jnp.where(l32 < kth, -1e30, l32)
            keys = jax.vmap(request_sample_key)(seeds, counts)
            sampled = jax.vmap(
                lambda k, row: jax.random.categorical(k, row)
            )(keys, l32).astype(jnp.int32)
            nxt = jnp.where(temps > 0.0, sampled, greedy)
        if counts_experts(cfg):
            nxt = jnp.concatenate([nxt, sum_expert_counts(experts)])
        if looped(cfg):
            nxt = jnp.concatenate([nxt, exits_behind(lam, lengths > 0)])
        return nxt, k_pool, v_pool, kc_pool, state

    return ds_decode_step


def looped(cfg: GPTConfig) -> bool:
    return cfg.loop_steps > 1


def behind_tokens(cfg: GPTConfig) -> int:
    """Entries a decode step's ``next_tokens`` carries behind the slots'
    tokens: the routed experts' counts, then a looped stack's exit
    distribution (``exits_behind``)."""
    return counts_experts(cfg) * mixers.expert_counts_width(cfg) \
        + looped(cfg) * cfg.loop_steps


def exits_behind(lam, live):
    """A looped decode step's exit gates ``lam`` (passes, N) float32 ->
    (passes,) int32: the distribution over the pass a token would leave
    after (``mixers.exit_distribution``), SUMMED over the live lanes, its
    float32's bits (as a slot's temperature travels in), so that the one
    read-back a step brings it behind the tokens."""
    p = jnp.sum(jnp.where(live, mixers.exit_distribution(lam), 0.0), axis=1)
    return jax.lax.bitcast_convert_type(p, jnp.int32)


def prefill_chunk_for(cfg: GPTConfig, scfg: ServingConfig) -> int:
    """Tokens a chunk of a mixed stack's prompt holds: the configured
    ``prefill_chunk``, else the sparse layers' local window (1024 with
    none). Whole pages, whole pooling strides; every query of a chunk
    falls on one side of ``dense_len`` and has the chunk's own keys
    inside its local window."""
    sp, ev = cfg.sparse, cfg.eva
    ring = cfg.gqa.window if cfg.count("window_attn") else 0
    C = scfg.prefill_chunk or (sp.window_size if sp is not None else 1024)
    # a chunk's keys go over whole pages of the ring and never past its end
    bad = C % scfg.block_size != 0 or (ring and ring % C != 0)
    if sp is not None:
        bad = bad or C > sp.window_size or sp.dense_len % C \
            or C % sp.kernel_stride
    if ev is not None:
        # its summaries are whole pages, or a part of one page
        ns = C // ev.chunk
        bad = bad or ev.window % C or C % ev.chunk \
            or (ns % scfg.block_size and scfg.block_size % ns)
    if bad:
        raise ValueError(
            f"prefill_chunk {C} does not suit this model: it must be a "
            f"multiple of block_size ({scfg.block_size})"
            + (f" and of the pooling stride, at most window_size "
               f"({sp.window_size}), and divide dense_len ({sp.dense_len})"
               if sp is not None else "")
            + (f" and divide the window ({ev.window}: a prompt chunk never "
               f"straddles a window, whose pages are reused), its "
               f"{ev.chunk}-position chunks filling whole pages of summaries "
               f"or a part of one" if ev is not None else "")
            + (f" and divide the window ({ring}): a prompt chunk is written "
               f"over the ring of the last {ring} keys from row offset mod "
               f"{ring} on and may not run past its end" if ring else ""))
    return C


def make_chunk_step(cfg: GPTConfig, scfg: ServingConfig, mesh=None):
    """Build the jitted prompt-chunk program of a mixed stack: how every
    prompt of such a model enters. As ``make_decode_step``, the frame
    alone: the kinds' chunk cores (``kinds.KINDS``) read the cache through
    ``kv_cache.chunk_view``'s view and ``write_prefill_chunk`` writes.

    prefill_chunk(params, k_pool, v_pool, kc_pool, state, tokens (1, C),
    table_row (blocks_per_slot,), slot, offset, n_valid) -> (logits (V,)
    at the chunk's last real position, k_pool', v_pool', kc_pool',
    state'). ``offset`` (a multiple of C), ``slot`` and ``n_valid`` are
    TRACED: one lowering serves every chunk of every prompt. The chunk
    carries the slot's recurrent state in (zeros at offset 0: a row is
    cleared by whoever enters it, never by who left) and out, a position
    at or beyond ``n_valid`` leaving it as it was; it attends over the
    slot's pages as each layer's kind rules and writes its own keys,
    values and pooled keys after the layer loop, in place. Where the
    stack counts its experts (``counts_experts``) the first output is the
    pair (logits, the chunk's counts (3,) int32, or (4,):
    ``sum_expert_counts``); where it is looped, the pair (logits, the
    exit distribution at the chunk's last real position (passes,)
    float32)."""
    C = prefill_chunk_for(cfg, scfg)
    view_of = chunk_view(cfg, scfg, mesh)

    @partial(jax.jit, donate_argnums=(1, 2, 3, 4))
    @on_one_stack_chunk
    def ds_prefill_chunk(params, k_pool, v_pool, kc_pool, state, tokens,
                         table_row, slot, offset, n_valid):
        x = mixers.embed_tokens(cfg, params, tokens)        # (1, C, D)
        positions = offset + jnp.arange(C, dtype=jnp.int32)
        view = view_of(params, k_pool, v_pool, kc_pool, state, table_row,
                       slot, offset, n_valid, positions)

        def layer_body(kind, carry, layer_params, layer, at):
            x, _, kept = KINDS[kind].block(view, KINDS[kind].chunk, carry[0],
                                           layer_params, layer, None, at)
            return (x, None), kept

        def served(x):
            return jax.lax.dynamic_index_in_dim(x[0], n_valid - 1, 0)

        x, _, kept, lam = mixers.scan_passes(cfg, params, x, None,
                                             layer_body, served)
        kept, experts = split_expert_counts(kept)
        with jax.named_scope("ds.prefill/kv_write"):
            k_pool, v_pool, kc_pool, state = write_prefill_chunk(
                view, state, kept)
        logits = mixers.head_logits(cfg, params, served(x), looped(cfg))[0]
        if counts_experts(cfg):
            logits = (logits, sum_expert_counts(experts))
        if looped(cfg):
            logits = (logits, mixers.exit_distribution(lam[:, 0]))
        return logits, k_pool, v_pool, kc_pool, state

    return ds_prefill_chunk


# ------------------------------------------------------------------ #
# shared submit/run surface
# ------------------------------------------------------------------ #


class _ServingBase:
    """submit/step/run/metrics shared by ServingEngine and the pipeline
    bridge; subclasses implement _admit_one (prefill) and _decode_all."""

    def __init__(self, scfg: ServingConfig, scheduler: Scheduler,
                 clock, monitor, monitor_config=None):
        self.scfg = scfg
        self.sched = scheduler
        self.clock = clock
        # telemetry facade (monitor/ package): own it when a config is
        # passed, else adopt a process-global one if installed
        if monitor_config is not None:
            self.telemetry = init_monitor(monitor_config)
        else:
            self.telemetry = get_monitor()
        registry = (self.telemetry.registry
                    if self.telemetry is not None else None)
        # the compile account by program name (monitor.compile_account)
        # is kept whether or not a monitor is: a dict update per compile
        install_compile_listener()
        self.metrics = ServingMetrics(scfg.num_slots, clock, monitor,
                                      registry, slo=scfg.slo)
        self._rid_counter = itertools.count()
        self._requests: Dict[str, Request] = {}
        self._step_i = 0
        # preemption drain: while set, step() admits nothing new and only
        # finishes the requests already holding slots
        self._draining = False
        from ..resilience import get_resilience_manager

        mgr = get_resilience_manager()
        if mgr is not None:
            mgr.attach_serving(self)

    # -- queue surface ------------------------------------------------ #

    def submit(self, prompt: Union[Sequence[int], np.ndarray],
               max_new_tokens: Optional[int] = None,
               temperature: float = 0.0,
               request_id: Optional[str] = None,
               arrival_t: Optional[float] = None,
               seed: Optional[int] = None) -> str:
        """Queue one request; returns its id. Raises when the request
        could never fit (context cap / pool footprint) or while the
        engine is draining (``EngineDrainingError`` — the caller must
        fail over, not wait) — everything else is handled by scheduling,
        not by the caller."""
        if self._draining:
            raise EngineDrainingError(
                "engine is draining (preemption/restart in progress); "
                "admits nothing new — resubmit on another replica")
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        rid = request_id if request_id is not None else \
            f"req-{next(self._rid_counter)}"
        if rid in self._requests:
            raise ValueError(f"duplicate request id {rid!r}")
        req = Request(
            rid=rid,
            prompt=prompt,
            max_new_tokens=(self.scfg.max_new_tokens
                            if max_new_tokens is None else max_new_tokens),
            temperature=float(temperature),
            arrival_t=self.clock() if arrival_t is None else arrival_t,
            seed=(derive_request_seed(self.scfg.seed, rid)
                  if seed is None else int(seed)),
        )
        self.sched.submit(req)
        self._requests[rid] = req
        # the request ledger's clock-zero: every downstream wait bucket
        # (scheduler queue, HOL blocking, compile, prefill) is measured
        # against this instant
        trace_instant("req/submit", lane="serving", rid=rid,
                      prompt_len=len(prompt),
                      max_new=req.max_new_tokens)
        return rid

    def get(self, rid: str) -> Request:
        return self._requests[rid]

    def has_work(self) -> bool:
        return self.sched.has_work()

    def cancel(self, rid: str, reason: str = "timeout") -> bool:
        """Terminate one request wherever it is (queued or active),
        releasing its slot/blocks; partial output is kept. Returns False
        when the rid is unknown or already finished. The router's
        deadline enforcement lands here."""
        req = self._requests.get(rid)
        if req is not None and req.in_flight:
            # tokens of its lie on the device unread: they count as they
            # would have, and one of them may end the request by itself
            self._settle()
        if req is None or req.state == "finished":
            return False
        self.sched.finish(req, reason)
        self.metrics.record_finish(req, self.clock())
        self._gap_turn()
        return True

    # -- the scheduler loop ------------------------------------------- #

    def step(self) -> List[Request]:
        """One scheduler iteration; returns requests finished by it."""
        n_done = len(self.sched.finished)
        with trace_span("serving/step", lane="serving", step=self._step_i):
            now = self.clock()
            with trace_span("serving/schedule", lane="serving",
                            what="expire"):
                expired = self.sched.expire_timeouts(now, self._settle)
            for req in expired:
                self.metrics.record_finish(req, now)
            if expired:
                self._gap_turn()
            self._prefill_phase()
            with trace_span("serving/schedule", lane="serving",
                            what="capacity"):
                preempted = self.sched.ensure_decode_capacity(
                    self._decode_window(), self._settle)
            for _ in preempted:
                self.metrics.record_preemption()
            if preempted:
                self._gap_turn()
            trace_counter("serving/load", {
                "queued": len(self.sched.queue),
                "active": self.sched.num_active,
            }, lane="serving")
            if self._has_decodable():
                self._decode_all()
            self._step_i += 1
            self.metrics.export(self._step_i)
        return self.sched.finished[n_done:]

    def run(self, max_steps: Optional[int] = None) -> Dict[str, List[int]]:
        """Drive step() until idle (or max_steps); returns {rid: tokens}
        for every finished request."""
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        self._settle()
        return {r.rid: r.output for r in self.sched.finished}

    def drain(self, max_steps: Optional[int] = None) -> List[str]:
        """Preemption drain: stop admitting, run decode until every
        in-flight (slot-holding) request finishes, and return the rids
        left queued — the caller (the resilience preemption protocol, or
        an external LB) is expected to re-submit those elsewhere."""
        self._draining = True
        steps = 0
        while self.sched.num_active:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        self._settle()
        return [r.rid for r in self.sched.queue]

    # -- helpers ------------------------------------------------------ #

    def _prefill_phase(self) -> None:
        """Admit + prefill queued requests into free slots. Subclasses
        with chunked prefill override this to pump in-flight prompt
        chunks under the per-step token budget before admitting more —
        chunk pumping must keep running while draining (those requests
        hold slots), only NEW admissions stop."""
        if self._draining:
            return
        while (adm := self._pop_admissible()) is not None:
            self._admit_one(*adm)

    def _pop_admissible(self):
        """The scheduler's admission decision under its own span, apart
        from the prefill it admits."""
        with trace_span("serving/schedule", lane="serving", what="admit"):
            adm = self.sched.pop_admissible()
        if adm is not None:
            req = adm[1]
            if req.admissions == 1:
                self.metrics.record_queue_wait(req.admit_t - req.arrival_t)
        return adm

    def _has_decodable(self) -> bool:
        """Whether any slot has a pending token to decode this step
        (chunk-prefilling slots don't, until their final chunk lands)."""
        return self.sched.num_active > 0

    def _settle(self) -> None:
        """Bring the host up to date with the device: read and emit
        whatever a subclass has launched and not yet read (the engine's
        decode step ahead). Nothing to do for a loop that reads every
        step before it returns."""

    def _gap_turn(self, ended: bool = False,
                  name: Optional[str] = None) -> None:
        """Who is owed a token may have changed: open or close the
        ``gap/`` span (``ServingEngine._gap_turn``). A loop that reads
        every step before it returns keeps none."""

    def _decode_window(self) -> int:
        """Tokens of KV headroom each active slot needs for the next
        decode phase (1 for plain decode; draft_k + 1 with speculation
        on, so a round's window of writes always has rows)."""
        return 1

    def _record_emitted(self, req: Request, prefill: bool,
                        held: str = "decode", tokens: int = 1) -> None:
        """``req`` has just been handed a token (``tokens`` of them by a
        speculative round, which divides the round's length among them).
        For a request that had one before, a gap between two tokens ends
        here: its length goes to the metrics under what it ``held`` (one
        of ``metrics.GAP_HELD``; the decode step that ended it says,
        ``_Launched.held``). A request's FIRST gap starts at the pick of
        its first token, not at the read of a step, and is filed under
        the step that ends it all the same. A token picked off a prompt's
        logits by a request that had tokens before is its re-prefill
        after a preemption: that gap held its wait in the queue."""
        now = self.clock()
        if req.last_token_t is not None:
            self.metrics.record_token_gap(
                now - req.last_token_t, "preempt" if prefill else held,
                tokens)
        req.last_token_t = now    # progress clock for expire_timeouts
        if prefill:
            ttft = None
            if req.first_token_t is None:
                req.first_token_t = now
                ttft = now - req.arrival_t
            self.metrics.record_prefill(now, ttft)
        if self.sched.check_finished(req, now):
            self.metrics.record_finish(req, now)


@dataclasses.dataclass
class _Launched:
    """A decode step launched whose tokens the host has not read."""
    nxt: Any                            # (num_slots,) tokens, on the device
    lanes: List[Tuple[int, Request]]    # the (slot, request) rows it ran
    held_chunk: bool    # a prompt chunk ran before it in its step()
    ahead: bool         # the step before it was unread at its launch
    # the host waited a bucketed prefill out before it read this step, or
    # launched it after one with nothing in flight, while a token was owed
    held_prefill: bool = False
    discarded: int = 0  # rows whose token was dropped when it was read
    # the experts' counts of the prompt chunks dispatched before it (on
    # the device: finished when this step is)
    chunk_counts: Sequence[Any] = ()

    @property
    def held(self) -> str:
        """What the gap that ends with this step's tokens held beside it
        (``metrics.GAP_HELD``), and the name of its ``gap/`` span. A step
        launched with nothing in flight is ``cold`` whatever ran before it
        in its ``step()``: the loop restarts behind a prompt's last chunk,
        whose pick the host waited out, so the chunk ended before the gap
        began (``ServingMetrics.chunk_gaps`` counts those tokens too)."""
        if self.held_prefill:
            return "prefill"
        if not self.ahead:
            return "cold"
        return "chunk" if self.held_chunk else "decode"


class ServingEngine(_ServingBase):
    """Continuous batching with the slot-based paged KV cache (module
    docstring has the architecture)."""

    def __init__(self, cfg: GPTConfig, params,
                 serving_config: Union[ServingConfig, dict, None] = None,
                 clock=time.monotonic, monitor=None, monitor_config=None,
                 mesh=None, param_specs=None, drafter_params=None):
        scfg = (serving_config if isinstance(serving_config, ServingConfig)
                else ServingConfig.from_dict(serving_config))
        if not cfg.rotary and scfg.max_seq_len > cfg.max_seq:
            raise ValueError(
                f"serving max_seq_len ({scfg.max_seq_len}) exceeds the "
                f"model's learned-position table ({cfg.max_seq})"
            )
        self.cfg = cfg
        # how many pages a length needs is the cache's to say
        scfg = scfg.for_cache(page_rule_for(cfg))
        if not cfg.classic:
            kinds = sorted(set(cfg.mixer_types))
            if scfg.prefix_caching and not all(
                    KINDS[kind].prefix_reuse for kind in kinds):
                raise ValueError(
                    "prefix_caching cannot serve a model with a layer that "
                    "keeps recurrent state, or pages that are overwritten "
                    f"behind a window ({kinds}): a cached prefix's pages say "
                    "nothing of the state row after it, and no snapshot of "
                    "that row is kept; a reused page no longer holds the "
                    "prefix. Turn prefix_caching off")
            if mesh is not None or scfg.speculative is not None:
                raise NotImplementedError(
                    f"a stack of {kinds} layers is served on one device, "
                    "without speculation")
        # dp×tp serving: with a mesh, params place by their TP specs
        # (sharding rule table translates the model's legacy 'model'
        # specs onto a canonical tp axis), the paged KV pools shard
        # their heads dim over tp, and decode inputs shard the slot dim
        # over the batch axes — all through the one sharding/ module.
        self.mesh = mesh
        if mesh is not None:
            params = self._place_params(params, param_specs)
        self.params = params
        self.kv = PagedKVCache(cfg, scfg)
        if mesh is not None:
            self._place_kv_pools()
        super().__init__(scfg, Scheduler(scfg, self.kv.allocators, clock),
                         clock, monitor, monitor_config)
        self._decode_step = make_decode_step(cfg, scfg, mesh)
        # what the cache's choosers pick when the two programs are traced,
        # from shapes, mesh and platform alone, so known here too, for the
        # host's accounting: whether the decode step's list-sharing layers
        # copy a page once for all of a slot's key heads; how a prompt
        # chunk attends over pages of two rules or two roles, and how it
        # runs a delta rule ("kernel", "xla", None where it does neither)
        self._slot_rows, self._chunk_attn, self._kda_scan = chosen_forms(
            cfg, scfg, self.kv.k, mesh,
            None if cfg.classic else prefill_chunk_for(cfg, scfg))
        # whether the programs count their routed experts: the decode
        # step's tokens then come with that many counts behind them
        self._counts_experts = counts_experts(cfg) \
            * mixers.expert_counts_width(cfg)
        # a stack of full_attn layers alone (a looped stack is one): its
        # decode step's span says the pages listed and the passes run
        self._full_alone = set(cfg.layer_kinds) == {"full_attn"}
        # the last decode step's tokens as the device handed them back,
        # the next step's ``prev`` (zeros until a step has run), and the
        # steps launched and not yet read, oldest first: one between
        # step() calls, two between a launch and the collect after it
        self._prev = jnp.asarray(self._place_slot_array(
            np.zeros(scfg.num_slots + behind_tokens(cfg), np.int32)))
        # the counts of the prompt chunks dispatched since the last launch,
        # on the device: read with the decode step queued behind them
        self._chunk_counts: List[Any] = []
        self._inflight: Deque[_Launched] = deque()

        # retraces once per prefill bucket (toks.shape[1] varies)
        def ds_prefill(params, toks):
            return apply_with_cache(
                cfg, params, toks,
                init_cache(cfg, toks.shape[0], toks.shape[1]), 0)

        # suffix/chunked prefill over a gathered staging cache: the write
        # offset is TRACED, so one compile serves every (matched, chunk)
        # position and it retraces only per (chunk len, staging len)
        # shape pair; staging buffers are donated chunk to chunk
        def ds_suffix_prefill(params, toks, kc, vc, offset):
            return apply_with_cache(
                cfg, params, toks, {"k": kc, "v": vc}, offset)

        self._prefill_step = jax.jit(on_one_stack_chunk(ds_prefill))
        self._suffix_prefill = jax.jit(on_one_stack_chunk(ds_suffix_prefill),
                                       donate_argnums=(2, 3))
        # a mixed stack's prompts all enter chunk by chunk, straight into
        # the pool and the slot's state row (no staging cache)
        self._chunk_step = (None if cfg.classic
                            else make_chunk_step(cfg, scfg))
        # slot -> in-flight chunked-prefill state (staging cache, cursor)
        self._chunking: Dict[int, dict] = {}
        self._prefill_spent = 0   # prompt tokens prefilled this step
        self._chunk_ran = False   # ... of them any in a chunk
        # a bucketed prefill ran this step while a token was owed and no
        # step was in flight: the step launched next ends the gap it fell in
        self._prefill_ran = False
        # the open gap/ span and what its name says it holds; None while
        # nobody is owed a token
        self._gap = None
        self._gap_held = None
        self.metrics.state_bytes = sum(
            a.nbytes for a in jax.tree.leaves(self.kv.state))
        self.metrics.loop_steps = cfg.loop_steps
        self.metrics.kv_bytes_per_position = position_bytes(self.kv)
        if self.telemetry is not None:
            # decode must stay one-compile forever; prefill legitimately
            # retraces per length bucket, so it is deliberately unwatched
            self.telemetry.watchdog.watch("serving/decode_step",
                                          self._decode_step)
        # speculative decoding: a SpecRuntime owns the drafter (params,
        # paged pool, draft/verify programs) and takes over the decode
        # phase; the decode step above stays as the fallback program for
        # slots that cannot speculate a given round
        self._spec = None
        if scfg.speculative is not None:
            from .spec.runtime import SpecRuntime

            self._spec = SpecRuntime(self, scfg.speculative,
                                     drafter_params)

    # -- mesh placement (dp×tp serving) -------------------------------- #

    def _place_params(self, params, param_specs):
        from .. import sharding as shd

        if param_specs is None:
            from ..models.gpt import param_specs as gpt_param_specs

            try:
                param_specs = gpt_param_specs(self.cfg)
                jax.tree.flatten(params)  # sanity touch
                shardings = shd.named_shardings(self.mesh, param_specs)
                return jax.tree.map(jax.device_put, params, shardings)
            except Exception:
                # unknown param structure: replicate rather than refuse
                logger.warning(
                    "serving: params do not match the GPT spec tree; "
                    "replicating them over the mesh")
                import jax.sharding as js

                rep = js.NamedSharding(self.mesh, js.PartitionSpec())
                return jax.tree.map(lambda x: jax.device_put(x, rep), params)
        shardings = shd.named_shardings(self.mesh, param_specs)
        return jax.tree.map(jax.device_put, params, shardings)

    def _place_kv_pools(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .. import sharding as shd

        tp = shd.tp_axis(self.mesh)
        tps = shd.tp_size(self.mesh)
        n_kv = int(self.kv.k.shape[3])  # (layer, blocks, blk, Hkv, Dh)
        head_entry = tp if (tps > 1 and n_kv % tps == 0) else None
        # no trailing None: the decode jit returns pools with the
        # canonicalized spec, and a trailing-None mismatch would cost a
        # one-time retrace when the round-tripped pools feed back in
        sh = NamedSharding(self.mesh, P(None, None, None, head_entry))
        self.kv.k = jax.device_put(self.kv.k, sh)
        self.kv.v = jax.device_put(self.kv.v, sh)

    def _place_slot_array(self, x):
        """Shard a per-slot decode input over the mesh's batch axes (the
        slot dim is the serving analogue of the batch dim)."""
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .. import sharding as shd

        n = int(x.shape[0])
        dp = shd.data_parallel_size(self.mesh)
        spec = (shd.batch_spec(self.mesh, x.ndim)
                if dp > 1 and n % dp == 0 else P())
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    # compile counters (tests assert decode compiles exactly once). Each
    # is ONE jitted callable's cache size: none sees the scatter of the
    # prefilled pages (kv_cache.ds_scatter_prefill_pages, once a prefill
    # bucket), the page gather, or what eager operations lower. The
    # whole account, by program name, is monitor.compile_account().
    @property
    def decode_compile_count(self) -> int:
        return getattr(self._decode_step, "_cache_size", lambda: -1)()

    @property
    def prefill_compile_count(self) -> int:
        return getattr(self._prefill_step, "_cache_size", lambda: -1)()

    @property
    def chunk_prefill_compile_count(self) -> int:
        return getattr(self._suffix_prefill, "_cache_size", lambda: -1)()

    @property
    def draft_compile_count(self) -> int:
        return self._spec.draft_compile_count if self._spec else -1

    @property
    def verify_compile_count(self) -> int:
        return self._spec.verify_compile_count if self._spec else -1

    def _decode_window(self) -> int:
        return self._spec.K + 1 if self._spec is not None else 1

    def set_drafter_params(self, drafter_params) -> None:
        """Swap the drafter's weights in place (same drafter config —
        shapes must match, so the compiled draft program is reused).
        The lifecycle rollout path: a (target, drafter) version pair
        restarts the engine for the target side but can hot-swap the
        drafter, whose KV is rebuilt lazily. No-op guard when
        speculative decoding is off."""
        if self._spec is None:
            raise RuntimeError(
                "set_drafter_params: speculative decoding is not enabled "
                "on this engine")
        self._spec.set_drafter_params(drafter_params)

    def _pick_token(self, logits_1d, req: Request) -> int:
        """Prefill-time next-token selection (one request, host-driven).
        Greedy path is the same raw argmax make_generator uses; sampling
        keys off (req.seed, token index) exactly like the decode step,
        so a re-prefill after preemption or retry replays the stream."""
        logits_1d = mixers.served_logits(self.cfg, logits_1d)
        if req.temperature <= 0.0:
            return int(jnp.argmax(logits_1d))
        top_k = self.scfg.top_k
        if top_k is not None and top_k >= self.cfg.vocab_size:
            top_k = None
        filtered = prep_sampling_logits(logits_1d[None], req.temperature,
                                        top_k)
        key = request_sample_key(req.seed, len(req.generated))
        return int(jax.random.categorical(key, filtered, axis=-1)[0])

    # -- admission: full, suffix, and chunked prefill ------------------ #

    def _budget_ok(self) -> bool:
        b = self.scfg.prefill_token_budget
        return b is None or self._prefill_spent < b

    def _prefill_phase(self) -> None:
        """Chunk-aware prefill phase: pump in-flight prompt chunks, then
        admit queued requests, all under ``prefill_token_budget`` prompt
        tokens per step (budget is a high-water mark, not a hard cap —
        the launch that crosses it still runs, so progress is guaranteed
        and a prompt longer than the budget cannot starve)."""
        self._prefill_spent = 0
        self._chunk_ran = self._prefill_ran = False
        self._sweep_chunk_states()
        self._pick_deferred()
        for slot in sorted(self._chunking):
            if not self._budget_ok():
                break
            self._pump_slot(slot, self._chunking[slot])
        if self._draining:
            return
        while self._budget_ok() and \
                (adm := self._pop_admissible()) is not None:
            self._admit_one(*adm)

    def _sweep_chunk_states(self) -> None:
        """Drop chunk states whose request no longer holds the slot
        (preempted or expired mid-prefill). Nothing to undo: chunked
        prefill stages into a private dense cache and touches the pool
        only at finalize, so abandoning the state abandons nothing."""
        for slot in list(self._chunking):
            if self.sched.slots[slot] is not self._chunking[slot]["req"]:
                del self._chunking[slot]

    def _admit_one(self, slot: int, req: Request, blocks: List[int]) -> None:
        """Prefill the request's context into its allocated blocks.

        A mixed stack's prompt enters chunk by chunk through
        ``ds_prefill_chunk``, in place (its pool is laid out page by page
        and its state rows are no pages: nothing of it can be staged). A
        stack of attention layers has three paths: (1) no cached prefix,
        prompt within one chunk —
        the original full bucketed prefill; (2) cached prefix — gather
        shared pages into a staging cache, forward only the suffix at
        the matched offset, scatter back the private pages (the matched
        boundary page's re-scatter is the CoW split); (3) long suffix —
        same staging, but forwarded ``prefill_chunk`` tokens per engine
        step so active decodes interleave instead of stalling behind one
        long prompt."""
        ctx = req.context
        L = len(ctx)
        if self._chunk_step is not None:
            C = prefill_chunk_for(self.cfg, self.scfg)
            n = -(-L // C)
            # a cached prefix: the chunks that lie wholly inside its shared
            # pages are not run again (the pages hold every cache layer's
            # keys); the prompt's last chunk always runs, for its logits.
            # A page matched in part is computed again with its chunk
            first = min(req.prefix_shared_blocks * self.scfg.block_size // C,
                        n - 1)
            self.sched.release_prefix_src(req)
            state = {"req": req, "ctx": ctx, "L": L, "chunk": C,
                     "n": n, "next": first, "first": first, "blocks": blocks,
                     "forward": self._forward_chunk,
                     "table": jnp.asarray(self.sched.slot_table_row(slot),
                                          jnp.int32)}
            self._chunking[slot] = state
            self._pump_slot(slot, state)
            return
        plan = (self.scfg.prefill_plan(L, req.prefix_matched)
                if (req.prefix_matched > 0
                    or self.scfg.prefill_chunk is not None) else None)
        if plan is None or (req.prefix_matched == 0 and plan[0] == 1):
            self._prefill_full(slot, req, blocks)
            self._prefill_spent += L
            return
        n_chunks, chunk, cache_len = plan
        m = req.prefix_matched
        bs = self.scfg.block_size
        page_to_block = [NULL_BLOCK] * (cache_len // bs)
        for i in range(req.prefix_shared_blocks):
            page_to_block[i] = blocks[i]
        if req.prefix_src is not None:
            page_to_block[req.prefix_shared_blocks] = req.prefix_src[0]
        k_stage, v_stage = self.kv.gather_pages(page_to_block)
        state = {
            "req": req, "blocks": blocks, "m": m, "L": L,
            "suffix": ctx[m:], "n": n_chunks, "chunk": chunk,
            "cache_len": cache_len, "k": k_stage, "v": v_stage,
            "next": 0, "forward": self._forward_staged,
        }
        self._chunking[slot] = state
        self._pump_slot(slot, state)

    def _pump_slot(self, slot: int, state: dict) -> None:
        """Forward prompt chunks for one slot while the step budget
        allows, each by the route its prompt entered on
        (``state["forward"]``: staged or in place); the final chunk
        yields the request's first token (``_end_prompt``)."""
        while state["next"] < state["n"] and self._budget_ok():
            state["forward"](slot, state)

    def _end_prompt(self, slot: int, state: dict, logits) -> None:
        """A prompt's last chunk is dispatched; ``logits`` (V,) are those
        of its last position. Picking the first token waits that chunk
        out. With a decode step in flight the wait would hold back tokens
        already computed (the step is queued before the chunk, and its
        tokens would be read a chunk late on top of whatever chunk ran
        before IT: two chunks in one token gap), so the pick is then left
        to the next ``step()``, which makes it before anything else
        (``_pick_deferred``). With nothing in flight it is made at once."""
        if self._inflight:
            state["logits"] = logits
        else:
            self._pick_first(slot, state, logits)

    def _pick_first(self, slot: int, state: dict, logits) -> None:
        """The request's first token off its prompt's last logits; the
        slot joins the decode steps."""
        req = state["req"]
        with trace_span("serving/prefill/pick", lane="serving"):
            req.generated.append(self._pick_token(logits, req))
        if "exits" in state:    # the pick waited the chunk out: no new wait
            self.metrics.record_exits(np.asarray(state["exits"]), 1)
        del self._chunking[slot]
        self._record_emitted(req, prefill=True)
        self._gap_turn()

    def _pick_deferred(self) -> None:
        """The first tokens the last step left unpicked (``_end_prompt``).
        Their chunks were queued before the decode step now in flight, so
        this waits for nothing that step's tokens wait for."""
        for slot, state in sorted(self._chunking.items()):
            if "logits" in state:
                with trace_span("serving/prefill", lane="serving",
                                rid=state["req"].rid, slot=slot,
                                ctx_len=state["L"], bucket=state["chunk"]):
                    self._pick_first(slot, state, state.pop("logits"))

    def _forward_staged(self, slot: int, state: dict) -> None:
        """One chunk of a staged suffix through ``ds_suffix_prefill``; the
        final chunk scatters the staging cache into the pool."""
        req, chunk, suffix = state["req"], state["chunk"], state["suffix"]
        c = state["next"]
        lo = c * chunk
        hi = min(lo + chunk, len(suffix))
        final = (c + 1) == state["n"]
        if final:
            cm = trace_span("serving/prefill", lane="serving",
                            rid=req.rid, slot=slot,
                            ctx_len=state["L"],
                            bucket=state["cache_len"])
        else:
            cm = trace_span("serving/prefill_chunk", lane="serving",
                            rid=req.rid, chunk=c, tokens=hi - lo)
        with cm as _sp:
            with trace_span("serving/prefill/pack", lane="serving"):
                toks = np.zeros((1, chunk), np.int32)
                toks[0, :hi - lo] = suffix[lo:hi]
                _pargs = (self.params, jnp.asarray(toks), state["k"],
                          state["v"], state["m"] + lo)
            with trace_span("serving/prefill/dispatch",
                            lane="serving"):
                logits, cache = self._suffix_prefill(*_pargs)
            state["k"], state["v"] = cache["k"], cache["v"]
            if final:
                with trace_span("serving/prefill/scatter",
                                lane="serving"):
                    self._finish_staged(req, state)
                self._end_prompt(slot, state, logits[0, hi - lo - 1])
            tel = self.telemetry
            if tel is not None:
                if tel.cost_index is not None:
                    # one compile per (chunk len, staging len) pair;
                    # the traced offset keeps every chunk position
                    # on the same program
                    tel.cost_index.observe(
                        f"serving/suffix_prefill"
                        f"[s{chunk}c{state['cache_len']}]",
                        self._suffix_prefill, _pargs)
                if tel.memwatch is not None:
                    tel.memwatch.annotate(_sp, "prefill")
        self._prefill_spent += hi - lo
        self._chunk_ran = True
        self.metrics.record_prefill_chunk(hi - lo)
        state["next"] += 1
        if final:
            logger.debug(
                "serving: admitted %s to slot %d (ctx=%d matched=%d "
                "chunks=%d)", req.rid, slot, state["L"], state["m"],
                state["n"])

    def chunk_pages_read(self, offset: int) -> int:
        """Pool pages the sparse layers' selections name in one prompt
        chunk at ``offset`` (0 while the dense rule holds: that read is a
        gather of the slot's first pages, not a selection): every query
        and key head ``topk`` blocks less those of the chunk itself."""
        sp = self.cfg.sparse
        if sp is None or offset < sp.dense_len:
            return 0
        C = prefill_chunk_for(self.cfg, self.scfg)
        n = C // sp.block_size
        return (self.cfg.count("minicpm4") * self.cfg.kv_heads
                * (C * sp.topk - sp.block_size * n * (n + 1) // 2))

    def chunk_pages_listed(self, offset: int) -> int:
        """Of the pages ``chunk_pages_read`` counts, those the rows' LISTS
        name: the chosen ones. The forced pages before the chunk are the
        same for all its queries and are gathered once
        (``kv_cache.sparse_chunk_attend``)."""
        sp = self.cfg.sparse
        if sp is None or offset < sp.dense_len:
            return 0
        bs = sp.block_size
        first = offset // bs
        n = prefill_chunk_for(self.cfg, self.scfg) // bs
        return (self.cfg.count("minicpm4") * self.cfg.kv_heads * bs
                * sum(sp.topk - mixers.forced_count(bt, sp)
                      for bt in range(first, first + n)))

    def _chunk_pages_by_rule(self, offset: int) -> dict:
        """The pages a prompt chunk at ``offset`` lists, rule by rule, as
        its span carries them (a stack of two page rules alone): the
        pages of every key before it, and the ring's that hold a position
        before it."""
        rule = self.scfg.page_rule
        if not rule.ring:
            return {}
        full, ring = rule.counts(offset, self.scfg.block_size)
        n_full, n_ring = (self.cfg.count(k)
                          for k in ("full_attn", "window_attn"))
        return {"full_pages": str(full * n_full),
                "window_pages": str(ring * n_ring)}

    def _forward_chunk(self, slot: int, state: dict) -> None:
        """One chunk of a mixed stack's prompt through ``ds_prefill_chunk``:
        pages, pooled keys and the slot's state row are written in place;
        the last chunk yields the request's first token."""
        req, C, c = state["req"], state["chunk"], state["next"]
        lo = c * C
        hi = min(lo + C, state["L"])
        final = (c + 1) == state["n"]
        named, listed = self.chunk_pages_read(lo), self.chunk_pages_listed(lo)
        # serving/prefill: a request's prompt work inside one step, as for
        # every model; the chunk inside it says where in the prompt it is
        with trace_span("serving/prefill", lane="serving", rid=req.rid,
                        slot=slot, ctx_len=state["L"], bucket=C,
                        **self._chunk_pages_by_rule(lo)), \
                trace_span("serving/prefill_chunk", lane="serving",
                           rid=req.rid, chunk=c, tokens=hi - lo, offset=lo,
                           pages=named, listed_pages=f"{listed}/{named}",
                           **({"attn": self._chunk_attn}
                              if self._chunk_attn else {}),
                           **({"scan": self._kda_scan}
                              if self._kda_scan else {})):
            with trace_span("serving/prefill/pack", lane="serving"):
                toks = np.zeros((1, C), np.int32)
                toks[0, :hi - lo] = state["ctx"][lo:hi]
                kv = self.kv
                _pargs = (self.params, kv.k, kv.v, kv.kc, kv.state,
                          jnp.asarray(toks), state["table"], np.int32(slot),
                          np.int32(lo), np.int32(hi - lo))
            with trace_span("serving/prefill/dispatch", lane="serving"):
                logits, kv.k, kv.v, kv.kc, kv.state = \
                    self._chunk_step(*_pargs)
            if looped(self.cfg):
                # where the prompt's last position would have left
                logits, state["exits"] = logits
            if self._counts_experts:
                logits, counts = logits
                self._chunk_counts.append(counts)
            if final:
                self._end_prompt(slot, state, logits)
        self._prefill_spent += hi - lo
        self._chunk_ran = True
        self.metrics.record_prefill_chunk(
            hi - lo, named, listed, kernel_attn=self._chunk_attn == "kernel",
            kernel_scan=self._kda_scan == "kernel")
        if lo == 0 and self.kv.state is not None:
            # the first chunk entered the slot's state rows as zeros
            self.metrics.record_state_reset()
        if self.scfg.page_rule.window:
            self.metrics.record_chunk_summaries(
                (hi - lo) // self.scfg.page_rule.chunk)
        state["next"] += 1
        if final:
            self.metrics.record_reuse(state["first"] * C, state["L"])
            self._index_prompt(req, state["blocks"])

    def _finish_staged(self, req: Request, state: dict) -> None:
        """Scatter the staged suffix into the slot's private blocks.
        Pages fully covered by shared blocks stay mapped read-only (their
        scatter target is the null block); the matched boundary page —
        gathered shared rows plus freshly forwarded suffix rows — lands
        in a private block, which IS the copy-on-write split. Then index
        the prompt in the radix cache for the next request."""
        bs = self.scfg.block_size
        m, L, blocks = state["m"], state["L"], state["blocks"]
        first = m // bs
        page_to_block = [NULL_BLOCK] * (state["cache_len"] // bs)
        for p in range(first, self.scfg.pages_needed(L)):
            page_to_block[p] = blocks[p]
        self.kv.write_pages(state["k"], state["v"], page_to_block)
        if req.prefix_src is not None:
            trace_instant("kv/cow_split", lane="serving", rid=req.rid,
                          block=blocks[first], rows=req.prefix_src[1])
            self.metrics.record_cow_split()
        self.sched.release_prefix_src(req)
        self.metrics.record_reuse(m, L)
        self._index_prompt(req, blocks)

    def _index_prompt(self, req: Request, blocks: List[int]) -> None:
        if self.sched.prefix_cache is None:
            return
        n = self.scfg.pages_needed(len(req.prompt))
        self.sched.prefix_cache.insert(req.prompt, blocks[:n])

    def _prefill_full(self, slot: int, req: Request,
                      blocks: List[int]) -> None:
        """Length-bucketed prefill of the request's whole context into
        its allocated blocks; emits the request's next token."""
        ctx = req.context
        L = len(ctx)
        bucket = self.scfg.bucket_for(L)
        self._gap_takes_prefill()
        with trace_span("serving/prefill", lane="serving", rid=req.rid,
                        slot=slot, ctx_len=L, bucket=bucket) as _sp:
            with trace_span("serving/prefill/pack", lane="serving"):
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :L] = ctx
                _pargs = (self.params, jnp.asarray(toks))
            with trace_span("serving/prefill/dispatch", lane="serving"):
                logits, cache = self._prefill_step(*_pargs)
            with trace_span("serving/prefill/scatter", lane="serving"):
                # admission allocated headroom for the first decode
                # write; only the context's own pages carry prefill data
                data_blocks = blocks[:self.scfg.pages_needed(L)]
                self.kv.write_prefill(cache["k"], cache["v"],
                                      data_blocks, L)
            with trace_span("serving/prefill/pick", lane="serving"):
                # the argmax is read back: the host waits here for the
                # prefill and its scatter
                tok = self._pick_token(logits[0, L - 1], req)
            req.generated.append(tok)
            tel = self.telemetry
            if tel is not None:
                if tel.cost_index is not None:
                    # per-bucket: the prefill jit legitimately holds one
                    # compile per context-length bucket
                    tel.cost_index.observe(
                        f"serving/prefill_step[b{bucket}]",
                        self._prefill_step, _pargs)
                if tel.memwatch is not None:
                    tel.memwatch.annotate(_sp, "prefill")
        logger.debug("serving: admitted %s to slot %d (ctx=%d bucket=%d)",
                     req.rid, slot, L, bucket)
        self.metrics.record_reuse(0, L)
        self._index_prompt(req, blocks)
        self._record_emitted(req, prefill=True)
        self._gap_turn()

    # -- the gaps between tokens, as spans ------------------------------ #

    def _owed(self) -> bool:
        """Whether any request that holds a slot has a token and is owed
        another (a slot whose prompt is still being chunked in has none
        yet; a request back in the queue after a preemption holds no
        slot, and its wait there is the queue's)."""
        chunking = self._chunking
        return any(req is not None and s not in chunking
                   for s, req in enumerate(self.sched.slots))

    def _gap_turn(self, ended: bool = False,
                  name: Optional[str] = None) -> None:
        """Keep ONE ``gap/`` span open exactly while a token is owed.
        ``ended``: a decode step has just been read, so the gap that was
        open is over; the next begins here if anybody is still owed a
        token. Otherwise (a first token picked, a request cancelled,
        expired or preempted) a span is opened if none is open and
        somebody is owed, closed if one is open and nobody is. A span's
        name is fixed where it is entered, and says what the gap will
        hold by the rule of ``_Launched.held``: the step whose read will
        end it is already in flight and knows what ran before it
        (``gap/decode``, ``gap/chunk``); with nothing in flight the step
        is yet to be launched (``gap/cold``). These spans begin in one
        ``step()`` and end in a later one, so they are entered and left
        by hand, on a lane and in a family of their own: no reader of the
        ``serving/`` spans meets one that crosses its nesting."""
        gap, owed = self._gap, self._owed()
        if gap is not None and (ended or not owed):
            gap.__exit__(None, None, None)
            gap = None
        if gap is None and owed:
            if name is None:
                name = self._inflight[0].held if self._inflight else "cold"
            gap = trace_span(f"gap/{name}", lane="gaps")
            gap.__enter__()
            self._gap_held = name
        self._gap = gap

    def _gap_takes_prefill(self) -> None:
        """A bucketed prefill is about to run, and its pick makes the
        host wait it out. While a token is owed that wait falls into the
        open gap: into the one the step in flight will end (the host
        reads that step only after the pick), else into the one the step
        launched next will end. The gap was entered under another name
        before the prefill was admitted, so the span is cut here: what
        follows is ``gap/prefill``, and the part before it (the loop's
        way from the last read to this admission, a fraction of a
        millisecond) keeps the name it had."""
        if self._gap is None:
            return
        if self._inflight:
            self._inflight[0].held_prefill = True
        else:
            self._prefill_ran = True
        if self._gap_held != "prefill":
            self._gap_turn(ended=True, name="prefill")

    def _active_decodable(self):
        """(slot, request) pairs that get a row in the step launched now.
        Chunk-prefilling slots have no pending token yet: their lane
        stays idle (all-null table, length 0), so the decode programs'
        shapes — and their single compiles — are untouched by
        chunking. A request whose remaining tokens are all in flight
        gets none either: it ends by length when they are read."""
        return [(s, req) for s, req in enumerate(self.sched.slots)
                if req is not None and s not in self._chunking
                and req.remaining > req.in_flight]

    def has_work(self) -> bool:
        return bool(self._inflight) or self.sched.has_work()

    def _has_decodable(self) -> bool:
        return bool(self._inflight) or bool(self._active_decodable())

    def _launch(self, lanes) -> None:
        """Pack and dispatch the plain decode program with ``lanes``
        populated (the rest idle), from what the host knows WITHOUT the
        tokens in flight: a lane whose last token is still on the device
        takes it there (``TAKE_PREV``), and its sampled-token count
        includes the rows in flight, so ``request_sample_key`` sees the
        pairs it would see reading every step. Lengths advance here: the
        row a lane writes counts as cached from now on. The step joins
        ``_inflight`` unread; ``_collect`` reads it. This is both the
        decode phase's launch (speculation off) and, collected at once,
        the fallback program for non-speculating slots (speculation
        on)."""
        with trace_span("serving/decode/pack", lane="serving",
                        placements="1"):
            N = self.scfg.num_slots
            tables = np.zeros((N, self.scfg.blocks_per_slot), np.int32)
            lengths = np.zeros(N, np.int32)
            tokens = np.zeros(N, np.int32)
            temps = np.zeros(N, np.float32)
            seeds = np.zeros(N, np.int32)
            counts = np.zeros(N, np.int32)
            live_pages = selected_pages = 0
            sp, rule = self.cfg.sparse, self.scfg.page_rule
            held, summary_rows, wraps = [0, 0], 0, 0
            listed = [0, 0]     # a cache of two rules: pages a step lists
            for s, req in lanes:
                tables[s] = self.sched.slot_table_row(s)
                lengths[s] = req.cached_len
                # the pages that hold a live position of a live slot,
                # the new token's included: all the pool a step need read
                live = rule.live(req.cached_len + 1, self.scfg.block_size)
                live_pages += live
                if rule.window or rule.ring:
                    for role, n in enumerate(self.sched.slot_roles[s]):
                        held[role] += n
                if rule.window:
                    summary_rows += (req.cached_len + 1) % rule.chunk == 0
                    wraps += req.cached_len % rule.window == 0
                if rule.ring:   # the new key goes over the ring's first row
                    wraps += req.cached_len % rule.ring == 0
                    # the pages of every key, and the ring's WHOLE pages
                    # (the one that takes the new key is read beside them)
                    full, ring = rule.counts(req.cached_len + 1,
                                             self.scfg.block_size)
                    listed[0] += full
                    listed[1] += ring - 1
                if sp is not None:
                    # what one selection of a sparse layer names of them
                    selected_pages += (live if req.cached_len + 1
                                       <= sp.dense_len else sp.topk)
                tokens[s] = (TAKE_PREV if req.in_flight
                             else req.pending_token)
                temps[s] = req.temperature
                seeds[s] = req.seed
                counts[s] = len(req.generated) + req.in_flight
                req.cached_len += 1
                req.in_flight += 1
            # the step's ONE host-to-device placement
            slots = pack_slots(tables, lengths, tokens, temps, seeds,
                               counts)
            slots = (self._place_slot_array(slots)
                     if self.mesh is not None else jnp.asarray(slots))
            _dargs = (self.params, self.kv.k, self.kv.v, slots, self._prev,
                      self.kv.kc, self.kv.state)
        ahead = bool(self._inflight)
        self.metrics.record_decode_placements(1)
        self.metrics.record_kv_pages(live_pages, tables.size,
                                     selected_pages)
        roles = {}
        if rule.window:
            self.metrics.record_window_pages(len(lanes), *held,
                                             summary_rows, wraps)
            roles = {"window_pages": str(held[0]),
                     "summary_pages": str(held[1]),
                     "summary_rows": str(summary_rows), "wraps": str(wraps)}
        if rule.ring:
            self.metrics.record_ring_pages(len(lanes), *held, wraps)
            roles = {"full_pages": str(listed[0]),
                     "window_pages": str(listed[1]), "wraps": str(wraps)}
        if self._kda_scan:
            # the full_attn layers' pages the live slots list, and the
            # state rows the step moves: every slot's, a kda layer each
            self.metrics.record_full_pages(len(lanes), live_pages)
            roles = {"full_pages": str(live_pages),
                     "state_rows": str(self.scfg.num_slots
                                       * self.cfg.count("kda"))}
        elif self._full_alone:
            # the pages the live slots list: each is read by every cache
            # layer, a (pass, layer) pair each
            self.metrics.record_full_pages(len(lanes), live_pages)
            roles = {"full_pages": str(live_pages),
                     "passes": str(self.cfg.loop_steps)}
        with trace_span("serving/decode/dispatch", lane="serving",
                        live_pages=live_pages, view_pages=tables.size,
                        selected_pages=selected_pages,
                        ahead="1" if ahead else "0",
                        slot_rows="1" if self._slot_rows else "0", **roles):
            nxt, self.kv.k, self.kv.v, self.kv.kc, self.kv.state = \
                self._decode_step(*_dargs)
            # the read-back starts when the step ends, not when the host
            # comes to ask for it
            nxt.copy_to_host_async()
        # under a mesh: laid out as the first step's zeros were, so the
        # jit's cache keeps one entry
        self._prev = self._place_slot_array(nxt)
        self._inflight.append(_Launched(
            nxt, lanes, self._chunk_ran, ahead,
            held_prefill=self._prefill_ran and not ahead,
            chunk_counts=self._chunk_counts))
        self._chunk_counts = []
        tel = self.telemetry
        if tel is not None:
            if tel.cost_index is not None:
                # the AOT re-lower never touches the decode jit's cache
                # (one-compile decode stays one-compile)
                tel.cost_index.observe("serving/decode_step",
                                       self._decode_step, _dargs)
            tel.watchdog.observe("serving/decode_step", step=self._step_i)

    def _collect(self) -> _Launched:
        """Read the oldest launched step's tokens and emit them: append,
        finish. A lane whose request no longer holds its slot (it ended
        on the token before this one) ran a row nobody asked for: its
        token is dropped; what it wrote went into a page the slot still
        held, and its state row is the next occupant's to clear."""
        step = self._inflight.popleft()
        with trace_span("serving/decode/wait", lane="serving"):
            nxt = np.asarray(step.nxt)          # device sync
        experts = {}
        N = self.scfg.num_slots
        if looped(self.cfg):
            # last behind the tokens: where the step's tokens would have left
            self.metrics.record_exits(
                np.ascontiguousarray(nxt[-self.cfg.loop_steps:]).view(
                    np.float32), len(step.lanes))
        if self._counts_experts:
            # behind the slots' tokens: what the step's routed experts did
            touched, assigned, most, *away = (
                int(n) for n in nxt[N:N + self._counts_experts])
            self.metrics.record_experts("decode", touched, assigned, most,
                                        self.cfg.n_layer, *away)
            experts = {"experts": str(touched), "assignments": str(assigned),
                       "max_load": str(most)}
            if away:
                experts["away"] = str(away[0])
            if step.chunk_counts:       # finished before the step was
                chunks = np.stack([np.asarray(c) for c in step.chunk_counts])
                for c in chunks:
                    self.metrics.record_experts(
                        "chunk", *(int(n) for n in c[:3]), self.cfg.n_layer,
                        *(int(n) for n in c[3:]))
                experts.update(
                    chunks=str(len(chunks)),
                    chunk_experts=str(int(chunks[:, 0].sum())),
                    chunk_assignments=str(int(chunks[:, 1].sum())))
        with trace_span("serving/decode/emit", lane="serving", **experts):
            for s, req in step.lanes:
                req.in_flight -= 1
                if self.sched.slots[s] is not req:
                    step.discarded += 1
                    continue
                req.generated.append(int(nxt[s]))
                self._record_emitted(req, prefill=False, held=step.held)
        self._gap_turn(ended=True)
        return step

    def _settle(self, keep: int = 0) -> int:
        """Collect the steps in flight, oldest first, until ``keep`` are
        left, and count each as the decode step it was; returns how many
        were read."""
        read = 0
        while len(self._inflight) > keep:
            step = self._collect()
            read += 1
            self.metrics.record_decode_step(
                len(step.lanes), len(self.sched.queue), self.clock(),
                held_chunk=step.held_chunk, ahead=step.ahead,
                discarded=step.discarded, slot_rows=self._slot_rows)
        return read

    def _decode_all(self) -> None:
        """One decode phase over the full slot array: the speculative
        round when enabled, else the plain decode step one step ahead:
        step n+1 is launched, THEN step n is collected (with nothing to
        launch, whatever is in flight)."""
        if self._spec is not None:
            self._spec.decode_round()
            return
        lanes = self._active_decodable()
        # the span's riders: the step launched, else the one read
        riders = lanes or self._inflight[0].lanes
        with trace_span("serving/decode", lane="serving",
                        rids=RID_SEP.join(r.rid for _, r in riders),
                        n_active=len(riders)) as _sp:
            if lanes:
                self._launch(lanes)
            read = self._settle(keep=1 if lanes else 0)
            tel = self.telemetry
            if tel is not None:
                if tel.cost_index is not None and read:
                    # a step was read inside the span: with the loop one
                    # step ahead the span is as long as the device took
                    # for it, the launch of the next included
                    _stats = tel.cost_index.note_step(
                        "serving/decode_step", _sp.elapsed_s())
                    if _stats is not None:
                        _sp.note(mfu=round(_stats["mfu"], 6),
                                 verdict=_stats["verdict"])
                if tel.memwatch is not None:
                    tel.memwatch.annotate(_sp, "decode")


# ------------------------------------------------------------------ #
# pipelined-model bridge
# ------------------------------------------------------------------ #


class PipelineServingBridge(_ServingBase):
    """The same submit/step/run surface for models served through a
    full-prefix logits function — in particular a pipelined model's
    ``PipelineEngine.inference_batch`` (the reference's per-token
    recompute serving mode).

    ``logits_fn(tokens (1, S) int32) -> logits (1, S, V)`` runs once per
    active request per step (pipelined stages can't batch mixed-length
    prefixes without an attention mask), so this path is for
    compatibility, not throughput; the paged ServingEngine is the fast
    path for non-pipelined models.
    """

    def __init__(self, logits_fn,
                 serving_config: Union[ServingConfig, dict, None] = None,
                 clock=time.monotonic, monitor=None, monitor_config=None):
        scfg = (serving_config if isinstance(serving_config, ServingConfig)
                else ServingConfig.from_dict(serving_config))
        self.logits_fn = logits_fn
        # no KV pool: a throwaway allocator sized so block accounting
        # never backpressures — slots are the only admission limit here
        from .kv_cache import BlockAllocator

        alloc = BlockAllocator(1 + scfg.num_slots * scfg.blocks_per_slot)
        super().__init__(scfg, Scheduler(scfg, alloc, clock), clock,
                         monitor, monitor_config)

    @classmethod
    def from_pipeline_engine(cls, engine, serving_config=None, **kw):
        """Serve a PipelineEngine (see runtime/pipe/engine.py
        ``serving_logits_fn``)."""
        return cls(engine.serving_logits_fn(), serving_config, **kw)

    def _pick(self, logits_1d, req: Request) -> int:
        if req.temperature <= 0.0:
            return int(np.asarray(jnp.argmax(logits_1d)))
        top_k = self.scfg.top_k
        filtered = prep_sampling_logits(jnp.asarray(logits_1d)[None],
                                        req.temperature, top_k)
        key = request_sample_key(req.seed, len(req.generated))
        return int(jax.random.categorical(key, filtered, axis=-1)[0])

    def _emit_next(self, req: Request, prefill: bool) -> None:
        ctx = np.asarray(req.context, np.int32)[None]
        logits = self.logits_fn(ctx)
        req.generated.append(self._pick(logits[0, -1], req))
        req.cached_len = ctx.shape[1]   # bookkeeping only (no real cache)
        self._record_emitted(req, prefill=prefill)

    def _admit_one(self, slot: int, req: Request, blocks) -> None:
        with trace_span("serving/prefill", lane="serving", rid=req.rid,
                        slot=slot, ctx_len=len(req.context)):
            self._emit_next(req, prefill=True)

    def _decode_all(self) -> None:
        active = list(self.sched.active)
        with trace_span("serving/decode", lane="serving",
                        rids=RID_SEP.join(r.rid for r in active),
                        n_active=len(active)):
            for req in active:
                self._emit_next(req, prefill=False)
        self.metrics.record_decode_step(len(active),
                                        len(self.sched.queue),
                                        self.clock())
