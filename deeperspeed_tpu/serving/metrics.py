"""Serving metrics: per-request TTFT/TPOT, every gap between two tokens by
what it held, queue depth, slot occupancy, tokens/s.

Collection is host-side and allocation-light (floats appended to lists,
token gaps counted in fixed buckets);
export goes through ``utils/tensorboard.TensorBoardMonitor`` for scalar
series, the surface the training engine uses, so serving shows up in the
dashboards training already feeds. The prefill and decode wall clocks
are the ``serving/prefill`` and ``serving/decode`` spans
(monitor/tracer.py), not timers of this module.
"""

import math
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from ..monitor.metrics import DEFAULT_LATENCY_BUCKETS, MetricsRegistry
from ..monitor.tracer import trace_instant
from ..utils.tensorboard import TensorBoardMonitor


class SLOTracker:
    """Live SLO accounting against an ``SLOConfig`` (serving/config.py).

    Each observed latency is checked against its axis target
    (``ttft``/``tpot``/``e2e`` p99 bounds in ms); a breach emits an
    ``slo/violation`` trace instant and bumps the labeled violation
    counter, and every observation refreshes the burn-rate gauge:
    ``burn_rate = violating_fraction / error_budget``. 1.0 means the
    stream violates exactly as fast as a p99 promise allows; > 1.0
    means the error budget is burning down. A None/empty config makes
    every call a no-op, so both metrics classes embed one
    unconditionally."""

    def __init__(self, slo=None,
                 registry: Optional[MetricsRegistry] = None):
        self.slo = slo
        self.registry = registry
        # axis -> [observations, violations]
        self.counts: Dict[str, List[int]] = {}

    @property
    def enabled(self) -> bool:
        return self.slo is not None and bool(self.slo.targets())

    def observe(self, axis: str, seconds: float) -> bool:
        """Record one latency on ``axis``; returns True on violation."""
        if self.slo is None:
            return False
        target_ms = self.slo.targets().get(axis)
        if target_ms is None:
            return False
        value_ms = seconds * 1e3
        n = self.counts.setdefault(axis, [0, 0])
        n[0] += 1
        violated = value_ms > target_ms
        if violated:
            n[1] += 1
            trace_instant("slo/violation", lane="serving", slo=axis,
                          value_ms=round(value_ms, 3),
                          target_ms=target_ms)
        if self.registry is not None:
            if violated:
                self.registry.counter(
                    "slo_violations_total",
                    "Latency observations over their SLO target.",
                    labels={"slo": axis}).inc()
            self.registry.gauge(
                "slo_burn_rate",
                "Violating fraction / error budget (1.0 = burning "
                "exactly at the p99 promise).",
                labels={"slo": axis}).set(self.burn_rate(axis))
        return violated

    def burn_rate(self, axis: str) -> float:
        n = self.counts.get(axis)
        if not n or not n[0] or self.slo is None:
            return 0.0
        return (n[1] / n[0]) / self.slo.error_budget

    def summary(self) -> Dict[str, Dict]:
        if self.slo is None:
            return {}
        out = {}
        for axis, target_ms in self.slo.targets().items():
            obs, viol = self.counts.get(axis, [0, 0])
            out[axis] = {
                "target_ms": target_ms,
                "observations": obs,
                "violations": viol,
                "violation_rate": viol / obs if obs else 0.0,
                "burn_rate": round(self.burn_rate(axis), 4),
            }
        return out


def record_finish_outcome(registry: Optional[MetricsRegistry],
                          reason: str) -> None:
    """Bump the labeled per-attempt outcome counter. The label space is
    the union of engine finish reasons (``length``/``eos``/``timeout``)
    and router outcomes (``shed``/``retried``/``failed``), so one
    ``serving_finish_total`` series tells the whole admission-to-finish
    story; no-op without a registry."""
    if registry is None:
        return
    registry.counter(
        "serving_finish_total",
        "Per-attempt request outcomes (engine evictions + router "
        "shed/retry/failover), labeled by reason.",
        labels={"reason": str(reason)},
    ).inc()


def _percentiles(xs: List[float]) -> Dict[str, float]:
    if not xs:
        return {"p50": 0.0, "p99": 0.0, "mean": 0.0, "max": 0.0}
    a = np.asarray(xs, np.float64)
    return {
        "p50": float(np.percentile(a, 50)),
        "p99": float(np.percentile(a, 99)),
        "mean": float(a.mean()),
        "max": float(a.max()),
    }


# what a gap between two tokens of one request held beside the decode
# step that ended it: nothing (``decode``), a prompt chunk launched before
# that step and behind the one before (``chunk``), a bucketed prefill whose
# pick the host waited out before it read the step (``prefill``), a launch
# with nothing in flight (``cold``: the loop had nothing to run, or had
# settled), the request's own wait in the queue and re-prefill after a
# preemption (``preempt``), a speculative round (``spec``)
GAP_HELD = ("decode", "chunk", "prefill", "cold", "preempt", "spec")


class GapHistogram:
    """Counts of durations in fixed log-spaced buckets, each 1% wide, from
    10 us to 100 s (1,620 integers and one bucket beyond either end): the
    memory is the same after ten gaps and after ten million, and a
    percentile is right to half a bucket (0.5%)."""

    LO, HI, WIDTH = 1e-5, 100.0, 1.01
    _PER_LOG = 1.0 / math.log(WIDTH)
    SIZE = 2 + math.ceil(math.log(HI / LO) * _PER_LOG)

    def __init__(self):
        self.counts = [0] * self.SIZE

    def add(self, seconds: float, n: int = 1) -> None:
        if seconds <= self.LO:
            i = 0
        elif seconds >= self.HI:
            i = self.SIZE - 1
        else:
            i = 1 + int(math.log(seconds / self.LO) * self._PER_LOG)
        self.counts[i] += n

    @classmethod
    def value(cls, i: int) -> float:
        """What bucket ``i`` reports: its geometric middle."""
        i = min(max(i, 1), cls.SIZE - 2)
        return cls.LO * cls.WIDTH ** (i - 0.5)

    @classmethod
    def percentiles(cls, counts: List[int], qs=(50, 95, 99)) -> Dict:
        """``{n, p50, p95, p99}`` (seconds) of the gaps counted in
        ``counts``, between the closest ranks as numpy does."""
        cum = np.cumsum(counts)
        n = int(cum[-1])
        out = {"n": n}
        for q in qs:
            if not n:
                out[f"p{q}"] = 0.0
                continue
            pos = (n - 1) * q / 100.0
            lo = math.floor(pos)
            a, b = (cls.value(int(np.searchsorted(cum, rank + 1)))
                    for rank in (lo, min(lo + 1, n - 1)))
            out[f"p{q}"] = a + (b - a) * (pos - lo)
        return out


class ServingMetrics:
    def __init__(self, num_slots: int,
                 clock: Callable[[], float] = time.monotonic,
                 monitor: Optional[TensorBoardMonitor] = None,
                 registry: Optional[MetricsRegistry] = None,
                 slo=None):
        self.num_slots = num_slots
        self.clock = clock
        self.monitor = monitor
        self.registry = registry
        self.slo_tracker = SLOTracker(slo, registry)
        self.queue_wait_s: List[float] = []   # admit less arrival
        self.ttft_s: List[float] = []
        self.tpot_s: List[float] = []
        self.queue_depth: List[int] = []
        self.occupancy: List[float] = []
        self.total_generated = 0
        self.decode_steps = 0
        # pages holding live positions of live slots against the pages
        # of every slot's whole view, summed over plain decode steps
        self.kv_live_pages = 0
        self.kv_view_pages = 0
        # of the live pages, those a block-sparse layer's selection names
        self.kv_selected_pages = 0
        # a cache that reuses its pages behind a window: the pages the
        # live slots HELD, summed over plain decode steps (exact keys of
        # the window and summaries apart) and the rows those steps ran
        # for them; summary rows written by decode steps and by prompt
        # chunks; slots whose window's pages started over at a decode step
        self.kv_window_pages = 0
        self.kv_summary_pages = 0
        self.kv_held_rows = 0
        self.summary_rows_decode = 0
        self.summary_rows_chunk = 0
        self.window_wraps = 0
        # a stack of two page rules: the pages of every key a live slot
        # held (its ring's are kv_window_pages)
        self.kv_full_pages = 0
        # routed experts, by the program that ran them ("decode", "chunk"):
        # over its calls, the experts with any assignment (summed over the
        # layers), the assignments, the largest expert's assignments of a
        # call (summed over the calls) and the calls x layers counted
        self.moe_experts_touched = {"decode": 0, "chunk": 0}
        self.moe_assignments = {"decode": 0, "chunk": 0}
        self.moe_max_load = {"decode": 0, "chunk": 0}
        self.moe_layer_calls = {"decode": 0, "chunk": 0}
        self.moe_calls = {"decode": 0, "chunk": 0}
        # ... and, where the program holds a share of the experts, the
        # live assignments that went to experts it does not hold
        self.moe_assignments_away = {"decode": 0, "chunk": 0}
        # prompt chunks whose kda layers ran the chunkwise kernel
        self.kda_chunks_kernel = 0
        # host-to-device placements made for the plain decode program's
        # slot inputs, and the dispatches they were made for
        self.decode_placements = 0
        self.decode_dispatches = 0
        # plain decode steps launched while the step before was still
        # unread (the loop one step ahead), and rows a step ran for a slot
        # whose request had ended before its token was read (EOS is seen
        # a step late): their tokens were dropped
        self.decode_steps_ahead = 0
        self.decode_rows_discarded = 0
        # plain decode steps run by a program whose layers that read one
        # list a slot took the kernel whose row is a slot
        self.decode_steps_slot_rows = 0
        # tokens decoded, and those of them decoded in a step that first
        # ran a prompt chunk (their gap held the chunk, but where the step
        # was launched with nothing in flight: ``token_gaps`` files those
        # under ``cold``)
        self.gaps = 0
        self.chunk_gaps = 0
        # every gap between two tokens of one request, by what it held
        self.token_gaps = {held: GapHistogram() for held in GAP_HELD}
        # bytes of per-slot recurrent state beside the pages (the engine
        # sets it once; 0 for a model that keeps pages only), the bytes of
        # it the plain decode steps read and wrote (every slot's rows,
        # whatever the live count), and the slots whose rows a request's
        # first prompt chunk entered as zeros
        self.state_bytes = 0
        self.state_bytes_moved = 0
        self.state_resets = 0
        # a looped stack (the engine sets the first two once): the passes
        # a token takes over the stack, the bytes one cached position
        # costs in the page pools (from their shapes: a cache layer a
        # (pass, layer) pair), and the exit gate's read-out: over the
        # tokens served, the summed distribution over the pass a token
        # would have left after (nothing acts on it)
        self.loop_steps = 1
        self.kv_bytes_per_position = 0
        self.exit_mass = np.zeros(0)
        self.exit_tokens = 0
        self.prefills = 0
        self.preemptions = 0
        # prefix reuse / chunked prefill: admissions is every context
        # prefilled, prefill_tokens its token total; tokens_saved the
        # part served from the radix cache instead of recomputed
        self.admissions = 0
        self.prefill_tokens = 0
        self.reuse_hits = 0
        self.tokens_saved = 0
        self.cow_splits = 0
        self.prefill_chunks = 0
        self.chunk_tokens = 0
        # pool pages the selections of the sparse layers' prompt chunks
        # named, and those of them the rows' lists named (the rest, forced
        # for every query of a chunk, is gathered once a chunk)
        self.chunk_named_pages = 0
        self.chunk_listed_pages = 0
        # prompt chunks whose attention over the past ran in the kernel
        # (ops/pallas/chunk_past_attn), of a stack of two cache rules or
        # of pages of two roles
        self.prefill_chunks_kernel_attn = 0
        # speculative decoding: per-round draft/accept accounting plus
        # the draft-vs-verify wall split (spec/runtime.decode_round)
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.spec_fallback_lanes = 0
        self.spec_draft_s = 0.0
        self.spec_verify_s = 0.0
        self.spec_drafter_prefills = 0
        self.spec_drafter_prefill_tokens = 0
        self.finished: Dict[str, int] = {}
        self._start_t: Optional[float] = None
        self._end_t: Optional[float] = None
        if registry is not None:
            self._c_tokens = registry.counter(
                "serving_tokens_generated_total",
                "Tokens emitted (prefill first-tokens + decode tokens).")
            self._c_prefills = registry.counter(
                "serving_prefills_total", "Prefill launches (admissions).")
            self._c_decode = registry.counter(
                "serving_decode_steps_total", "Batched decode steps.")
            self._c_preempt = registry.counter(
                "serving_preemptions_total",
                "Requests preempted back to the queue.")
            self._g_queue = registry.gauge(
                "serving_queue_depth", "Requests waiting for admission.")
            self._g_active = registry.gauge(
                "serving_active_slots", "Slots currently running a request.")
            self._g_occ = registry.gauge(
                "serving_slot_occupancy",
                "Active slots / num_slots at the last decode step.")
            self._h_queue_wait = registry.histogram(
                "serving_queue_wait_seconds",
                "Arrival to first admission (the wait in the queue).",
                buckets=DEFAULT_LATENCY_BUCKETS)
            self._h_ttft = registry.histogram(
                "serving_ttft_seconds", "Time to first token.",
                buckets=DEFAULT_LATENCY_BUCKETS)
            self._h_tpot = registry.histogram(
                "serving_tpot_seconds", "Time per output token (per-request "
                "mean, recorded at finish).",
                buckets=DEFAULT_LATENCY_BUCKETS)
            self._h_itl = {
                held: registry.histogram(
                    "serving_itl_seconds", "Gap between two tokens of one "
                    "request, by what it held beside the decode step.",
                    buckets=DEFAULT_LATENCY_BUCKETS, labels={"held": held})
                for held in GAP_HELD}

    # ------------------------------------------------------------ #
    # recording
    # ------------------------------------------------------------ #

    def record_queue_wait(self, wait_s: float) -> None:
        """A request's first admission: how long it stood in the queue."""
        self.queue_wait_s.append(wait_s)
        if self.registry is not None:
            self._h_queue_wait.observe(wait_s)

    def record_prefill(self, now: float,
                       ttft_s: Optional[float] = None) -> None:
        """One prefill (it emits one token). ttft_s is set only for a
        request's FIRST admission — preemption re-prefills don't re-count
        time-to-first-token."""
        if self._start_t is None:
            self._start_t = now
        self.prefills += 1
        self.total_generated += 1
        if ttft_s is not None:
            self.ttft_s.append(ttft_s)
            self.slo_tracker.observe("ttft", ttft_s)
        self._end_t = now
        if self.registry is not None:
            self._c_prefills.inc()
            self._c_tokens.inc()
            if ttft_s is not None:
                self._h_ttft.observe(ttft_s)

    def record_reuse(self, matched: int, ctx_len: int) -> None:
        """One admission's prefix-cache outcome: ``matched`` of the
        ``ctx_len`` context tokens came out of the radix cache (0 on a
        miss — call this for EVERY admission so the saved fraction has
        its denominator)."""
        self.admissions += 1
        self.prefill_tokens += ctx_len
        if matched > 0:
            self.reuse_hits += 1
            self.tokens_saved += matched
            if self.registry is not None:
                self.registry.counter(
                    "serving_prefix_reuse_hits_total",
                    "Admissions that matched a cached prefix.").inc()
                self.registry.counter(
                    "serving_prefill_tokens_saved_total",
                    "Prompt tokens served from the prefix cache instead "
                    "of recomputed.").inc(matched)

    def record_cow_split(self) -> None:
        """A matched boundary page copied into a private block (exactly
        one per admission whose match ends mid-block)."""
        self.cow_splits += 1
        if self.registry is not None:
            self.registry.counter(
                "serving_kv_cow_splits_total",
                "Copy-on-write splits of shared boundary pages.").inc()

    def record_prefill_chunk(self, tokens: int, named_pages: int = 0,
                             listed_pages: int = 0,
                             kernel_attn: bool = False,
                             kernel_scan: bool = False) -> None:
        """One staged prompt-chunk forward (chunked/suffix prefill);
        ``named_pages``, ``listed_pages``: what its sparse layers'
        selections and its rows' lists named of the pool; ``kernel_attn``:
        its program attends over the past in the chunk kernel;
        ``kernel_scan``: its kda layers run the chunkwise kernel."""
        self.prefill_chunks += 1
        self.prefill_chunks_kernel_attn += kernel_attn
        self.kda_chunks_kernel += kernel_scan
        self.chunk_tokens += tokens
        self.chunk_named_pages += named_pages
        self.chunk_listed_pages += listed_pages
        if self.registry is not None:
            self.registry.counter(
                "serving_prefill_chunks_total",
                "Staged prompt-chunk forwards.").inc()

    def record_decode_step(self, n_active: int, queue_depth: int,
                           now: float, held_chunk: bool = False,
                           ahead: bool = False, discarded: int = 0,
                           slot_rows: bool = False) -> None:
        """One decode step over ``n_active`` rows, recorded when its
        tokens are read. ``held_chunk``: a prompt chunk ran before it in
        the ``step()`` that launched it, so each of its tokens came a
        chunk later. ``ahead``: it was launched while the step before it
        was still unread. ``discarded``: of its rows, those whose token
        was dropped (the request had ended meanwhile). ``slot_rows``: its
        program read a slot's shared list through the kernel whose row is
        a slot (``kv_cache.takes_slot_form``)."""
        if self._start_t is None:
            self._start_t = now
        emitted = n_active - discarded
        self.decode_steps += 1
        self.decode_steps_ahead += bool(ahead)
        self.decode_steps_slot_rows += bool(slot_rows)
        self.decode_rows_discarded += discarded
        self.total_generated += emitted
        self.gaps += emitted
        self.chunk_gaps += emitted if held_chunk else 0
        self.state_bytes_moved += 2 * self.state_bytes
        self.queue_depth.append(queue_depth)
        self.occupancy.append(n_active / self.num_slots)
        self._end_t = now
        if self.registry is not None:
            self._c_decode.inc()
            self._c_tokens.inc(emitted)
            self._g_queue.set(queue_depth)
            self._g_active.set(n_active)
            self._g_occ.set(n_active / self.num_slots)

    def record_token_gap(self, seconds: float, held: str,
                         tokens: int = 1) -> None:
        """The gap that ends with a token of a request that had one
        before: ``seconds`` since that one, under what the engine launched
        in between (one of ``GAP_HELD``). A speculative round hands a
        request ``tokens`` at once: the round's length is divided among
        them and counted that often."""
        gap = seconds / tokens
        self.token_gaps[held].add(gap, tokens)
        if self.registry is not None:
            for _ in range(tokens):
                self._h_itl[held].observe(gap)

    def record_state_reset(self) -> None:
        """A request's first prompt chunk took its slot's state rows as
        zeros (a row is cleared by whoever enters it)."""
        self.state_resets += 1

    def record_kv_pages(self, live_pages: int, view_pages: int,
                        selected_pages: int = 0) -> None:
        """``selected_pages``: of the live pages, those one selection of
        a block-sparse layer names (0 for a model that has none)."""
        self.kv_live_pages += live_pages
        self.kv_view_pages += view_pages
        self.kv_selected_pages += selected_pages

    def record_ring_pages(self, rows: int, full_pages: int,
                          window_pages: int, wraps: int) -> None:
        """One launched decode step of a cache of two page rules: its
        ``rows`` live slots held ``full_pages`` pages of every key and
        ``window_pages`` of their rings; ``wraps`` of them write the
        ring's first row."""
        self.kv_held_rows += rows
        self.kv_full_pages += full_pages
        self.kv_window_pages += window_pages
        self.window_wraps += wraps

    def record_experts(self, program: str, touched: int, assignments: int,
                       max_load: int, layers: int, away: int = 0) -> None:
        """What one call of ``program`` ("decode", "chunk") counted of its
        routed experts over its ``layers`` layers; ``away``: assignments
        to experts the program does not hold."""
        self.moe_experts_touched[program] += touched
        self.moe_assignments[program] += assignments
        self.moe_max_load[program] += max_load
        self.moe_layer_calls[program] += layers
        self.moe_calls[program] += 1
        self.moe_assignments_away[program] += away

    def record_exits(self, mass, tokens: int) -> None:
        """The exit distribution of ``tokens`` served tokens of a looped
        stack, summed over them: ``mass`` (loop_steps,)."""
        mass = np.asarray(mass, np.float64)
        self.exit_mass = mass + (self.exit_mass if self.exit_mass.size
                                 else 0.0)
        self.exit_tokens += tokens

    def record_full_pages(self, rows: int, full_pages: int) -> None:
        """One launched decode step of a stack whose full_attn layers
        alone keep pages: its ``rows`` live slots held ``full_pages``."""
        self.kv_held_rows += rows
        self.kv_full_pages += full_pages

    def record_window_pages(self, rows: int, window_pages: int,
                            summary_pages: int, summary_rows: int,
                            wraps: int) -> None:
        """One plain decode step over a cache that reuses pages behind a
        window: its ``rows`` live slots held ``window_pages`` pages of
        exact keys and ``summary_pages`` of summaries; ``summary_rows``
        of them completed a chunk (a summary row written) and ``wraps``
        started their window's pages over."""
        self.kv_held_rows += rows
        self.kv_window_pages += window_pages
        self.kv_summary_pages += summary_pages
        self.summary_rows_decode += summary_rows
        self.window_wraps += wraps

    def record_chunk_summaries(self, rows: int) -> None:
        """Summary rows a prompt chunk wrote (its whole chunks)."""
        self.summary_rows_chunk += rows

    def record_decode_placements(self, placements: int) -> None:
        """One dispatch of the plain decode program, and how many
        host-to-device placements its slot inputs took."""
        self.decode_placements += placements
        self.decode_dispatches += 1

    def record_preemption(self) -> None:
        self.preemptions += 1
        if self.registry is not None:
            self._c_preempt.inc()

    def record_spec_round(self, n_spec: int, n_fallback: int,
                          drafted: int, accepted: int, emitted: int,
                          draft_s: float, verify_s: float) -> None:
        """One speculative decode round. ``record_decode_step`` already
        counted one token per active lane, so only the EXTRA tokens the
        round emitted beyond that (accepted drafts past the first token
        per speculating slot) are added here."""
        self.spec_rounds += 1
        self.spec_drafted += drafted
        self.spec_accepted += accepted
        self.spec_emitted += emitted
        self.spec_fallback_lanes += n_fallback
        self.spec_draft_s += draft_s
        self.spec_verify_s += verify_s
        extra = emitted - n_spec
        self.total_generated += extra
        if self.registry is not None:
            if extra > 0:
                self._c_tokens.inc(extra)
            self.registry.counter(
                "serving_spec_rounds_total",
                "Speculative draft+verify decode rounds.").inc()
            if drafted:
                self.registry.counter(
                    "serving_spec_drafted_total",
                    "Draft tokens proposed to the verify step.",
                ).inc(drafted)
            if accepted:
                self.registry.counter(
                    "serving_spec_accepted_total",
                    "Draft tokens accepted (emitted) by verification.",
                ).inc(accepted)

    def record_drafter_prefill(self, tokens: int) -> None:
        """One drafter-pool suffix prefill (spec slot sync)."""
        self.spec_drafter_prefills += 1
        self.spec_drafter_prefill_tokens += tokens
        if self.registry is not None:
            self.registry.counter(
                "serving_spec_drafter_prefills_total",
                "Drafter-cache suffix prefills (slot syncs).").inc()

    def record_finish(self, req, now: float) -> None:
        self.finished[req.finish_reason] = (
            self.finished.get(req.finish_reason, 0) + 1)
        self._end_t = now
        n = len(req.generated)
        tpot = None
        if n > 1 and req.first_token_t is not None:
            tpot = (now - req.first_token_t) / (n - 1)
            self.tpot_s.append(tpot)
            self.slo_tracker.observe("tpot", tpot)
        if req.first_token_t is not None:
            # engine-side E2E: arrival to terminal (the router tracks
            # its own accept-to-terminal E2E for fleet serving)
            self.slo_tracker.observe("e2e", now - req.arrival_t)
        if self.registry is not None:
            self.registry.counter(
                "serving_requests_finished_total",
                "Finished requests by terminal reason.",
                labels={"reason": str(req.finish_reason)},
            ).inc()
            # one label space shared with the router layer, so engine
            # evictions and router outcomes (shed/retried/failed) land
            # in the same serving_finish_total series
            record_finish_outcome(self.registry, req.finish_reason)
            if tpot is not None:
                self._h_tpot.observe(tpot)

    # ------------------------------------------------------------ #
    # reporting
    # ------------------------------------------------------------ #

    @property
    def elapsed_s(self) -> float:
        if self._start_t is None or self._end_t is None:
            return 0.0
        return max(self._end_t - self._start_t, 1e-9)

    def summary(self) -> Dict:
        occ = np.asarray(self.occupancy, np.float64)
        return {
            "requests_finished": int(sum(self.finished.values())),
            "finish_reasons": dict(self.finished),
            "tokens_generated": int(self.total_generated),
            "decode_steps": int(self.decode_steps),
            "prefills": int(self.prefills),
            "preemptions": int(self.preemptions),
            "elapsed_s": self.elapsed_s,
            "tokens_per_sec": self.total_generated / self.elapsed_s
            if self.elapsed_s else 0.0,
            "queue_wait_s": _percentiles(self.queue_wait_s),
            "ttft_s": _percentiles(self.ttft_s),
            "tpot_s": _percentiles(self.tpot_s),
            "slot_occupancy": float(occ.mean()) if occ.size else 0.0,
            "kv_live_page_frac": (self.kv_live_pages / self.kv_view_pages
                                  if self.kv_view_pages else 0.0),
            "kv_selected_page_frac": (
                self.kv_selected_pages / self.kv_live_pages
                if self.kv_live_pages else 0.0),
            "kv_pages_per_slot": {
                "window": (self.kv_window_pages / self.kv_held_rows
                           if self.kv_held_rows else 0.0),
                "summary": (self.kv_summary_pages / self.kv_held_rows
                            if self.kv_held_rows else 0.0),
                "full": (self.kv_full_pages / self.kv_held_rows
                         if self.kv_held_rows else 0.0)},
            # routed experts by program: a layer's experts with any
            # assignment, and the largest expert's load, both a call
            "moe": {prog: {
                "experts_touched_per_layer": (
                    self.moe_experts_touched[prog] / calls if calls else 0.0),
                "assignments": int(self.moe_assignments[prog]),
                "assignments_away": int(self.moe_assignments_away[prog]),
                "max_load_per_call": (
                    self.moe_max_load[prog] / self.moe_calls[prog]
                    if calls else 0.0)}
                for prog, calls in self.moe_layer_calls.items()},
            "summary_rows": {"decode": int(self.summary_rows_decode),
                             "chunk": int(self.summary_rows_chunk)},
            "window_wraps": int(self.window_wraps),
            "decode_placements_per_step": (
                self.decode_placements / self.decode_dispatches
                if self.decode_dispatches else 0.0),
            "decode_ahead_share": (self.decode_steps_ahead
                                   / self.decode_steps
                                   if self.decode_steps else 0.0),
            "decode_rows_discarded": int(self.decode_rows_discarded),
            "decode_slot_rows_share": (self.decode_steps_slot_rows
                                       / self.decode_steps
                                       if self.decode_steps else 0.0),
            "chunk_gap_share": (self.chunk_gaps / self.gaps
                                if self.gaps else 0.0),
            "itl_ms": self.itl_ms(),
            "chunk_listed_page_share": (
                self.chunk_listed_pages / self.chunk_named_pages
                if self.chunk_named_pages else 0.0),
            "loop_steps": int(self.loop_steps),
            "kv_bytes_per_position": int(self.kv_bytes_per_position),
            # where a looped stack's tokens would have left: the mean
            # distribution over its passes and its mean, counted from 1
            "exit_p": [float(m) / max(self.exit_tokens, 1)
                       for m in self.exit_mass],
            "exit_step_expected": float(
                1.0 + np.dot(np.arange(self.exit_mass.size), self.exit_mass)
                / max(self.exit_tokens, 1)) if self.exit_mass.size else 0.0,
            "state_bytes": int(self.state_bytes),
            "state_bytes_per_step": (self.state_bytes_moved
                                     / self.decode_steps
                                     if self.decode_steps else 0.0),
            "state_resets": int(self.state_resets),
            "kda_chunks_kernel": int(self.kda_chunks_kernel),
            "queue_depth_max": int(max(self.queue_depth, default=0)),
            "slo": self.slo_tracker.summary(),
            "prefix_reuse": {
                "admissions": int(self.admissions),
                "reuse_hits": int(self.reuse_hits),
                "reuse_hit_rate": (self.reuse_hits / self.admissions
                                   if self.admissions else 0.0),
                "prefill_tokens": int(self.prefill_tokens),
                "tokens_saved": int(self.tokens_saved),
                "tokens_saved_frac": (self.tokens_saved
                                      / self.prefill_tokens
                                      if self.prefill_tokens else 0.0),
                "cow_splits": int(self.cow_splits),
                "prefill_chunks": int(self.prefill_chunks),
                "prefill_chunks_kernel_attn": int(
                    self.prefill_chunks_kernel_attn),
                "chunk_tokens": int(self.chunk_tokens),
            },
            "speculative": {
                "rounds": int(self.spec_rounds),
                "drafted": int(self.spec_drafted),
                "accepted": int(self.spec_accepted),
                "accept_rate": (self.spec_accepted / self.spec_drafted
                                if self.spec_drafted else 0.0),
                "emitted": int(self.spec_emitted),
                "tokens_per_round": (self.spec_emitted / self.spec_rounds
                                     if self.spec_rounds else 0.0),
                "fallback_lanes": int(self.spec_fallback_lanes),
                "draft_time_s": float(self.spec_draft_s),
                "verify_time_s": float(self.spec_verify_s),
                "drafter_prefills": int(self.spec_drafter_prefills),
                "drafter_prefill_tokens": int(
                    self.spec_drafter_prefill_tokens),
            },
        }

    def itl_ms(self) -> Dict[str, Dict]:
        """``{"all" | held: {n, p50, p95, p99}}``: the gaps between two
        tokens of one request in ms, all together and by what they held.
        Beside ``tpot_s`` (one number a request, the mean over its life)
        this is what a reader of the stream waits between two tokens."""
        table = np.array([h.counts for h in self.token_gaps.values()])
        by = dict(zip(("all", *self.token_gaps), (table.sum(0), *table)))
        return {held: {k: v if k == "n" else 1e3 * v for k, v in
                       GapHistogram.percentiles(counts).items()}
                for held, counts in by.items()}

    def export(self, step: int) -> None:
        """Push the running summary to the TensorBoard monitor (JSONL
        fallback included — see utils/tensorboard.py)."""
        if self.monitor is None:
            return
        s = self.summary()
        self.monitor.write_scalars(
            {
                "Serving/tokens_per_sec": s["tokens_per_sec"],
                "Serving/ttft_p50_s": s["ttft_s"]["p50"],
                "Serving/ttft_p99_s": s["ttft_s"]["p99"],
                "Serving/tpot_p50_s": s["tpot_s"]["p50"],
                "Serving/tpot_p99_s": s["tpot_s"]["p99"],
                "Serving/itl_p95_s": s["itl_ms"]["all"]["p95"] / 1e3,
                "Serving/itl_p99_s": s["itl_ms"]["all"]["p99"] / 1e3,
                "Serving/slot_occupancy": s["slot_occupancy"],
                "Serving/queue_depth": float(
                    self.queue_depth[-1] if self.queue_depth else 0),
                "Serving/preemptions": float(self.preemptions),
            },
            step,
        )


class FleetMetrics:
    """Router-side accounting: accepted/shed/retried counts, replica
    health transitions, and router-observed TTFT/E2E latencies (clocked
    from router accept to the event arriving back at the router, so a
    retry's re-prefill time is IN the number — this is the latency a
    client actually sees under failure).

    Same split as ServingMetrics: host-side lists for ``summary()``,
    plus registry counters/gauges when a monitor/ registry is present.
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 registry: Optional[MetricsRegistry] = None,
                 slo=None):
        self.clock = clock
        self.registry = registry
        self.slo_tracker = SLOTracker(slo, registry)
        self.accepted = 0
        self.shed = 0
        self.retries = 0
        self.replica_downs: List[Dict] = []
        self.outcomes: Dict[str, int] = {}
        self.ttft_s: List[float] = []
        self.e2e_s: List[float] = []
        if registry is not None:
            self._c_accepted = registry.counter(
                "serving_router_accepted_total",
                "Requests accepted by router admission control.")
            self._c_shed = registry.counter(
                "serving_shed_total",
                "Requests rejected by admission control (overload).")
            self._c_retry = registry.counter(
                "serving_retries_total",
                "Request re-dispatches after replica failures.")
            self._h_ttft = registry.histogram(
                "serving_router_ttft_seconds",
                "Router-observed time to first token (includes retry "
                "re-prefills).", buckets=DEFAULT_LATENCY_BUCKETS)
            self._h_e2e = registry.histogram(
                "serving_router_e2e_seconds",
                "Router-observed accept-to-terminal latency.",
                buckets=DEFAULT_LATENCY_BUCKETS)

    # ------------------------------------------------------------ #

    def record_accept(self) -> None:
        self.accepted += 1
        if self.registry is not None:
            self._c_accepted.inc()

    def record_shed(self) -> None:
        self.shed += 1
        if self.registry is not None:
            self._c_shed.inc()
        record_finish_outcome(self.registry, "shed")

    def record_retry(self) -> None:
        self.retries += 1
        if self.registry is not None:
            self._c_retry.inc()
        record_finish_outcome(self.registry, "retried")

    def record_replica_down(self, name: str, cause: str,
                            inflight: int) -> None:
        self.replica_downs.append(
            {"replica": name, "cause": cause, "inflight": inflight,
             "t": self.clock()})
        if self.registry is not None:
            self.registry.counter(
                "serving_replica_down_total",
                "Replicas marked unhealthy, by cause.",
                labels={"replica": name, "cause": cause},
            ).inc()

    def record_ttft(self, ttft: float) -> None:
        self.ttft_s.append(ttft)
        self.slo_tracker.observe("ttft", ttft)
        if self.registry is not None:
            self._h_ttft.observe(ttft)

    def record_outcome(self, reason: str,
                       e2e_s: Optional[float] = None) -> None:
        """Terminal outcome for an ACCEPTED request (finish reasons plus
        router-level timeout/failed); shed requests were never accepted
        and are counted by record_shed."""
        self.outcomes[reason] = self.outcomes.get(reason, 0) + 1
        if e2e_s is not None:
            self.e2e_s.append(e2e_s)
            self.slo_tracker.observe("e2e", e2e_s)
            if self.registry is not None:
                self._h_e2e.observe(e2e_s)
        record_finish_outcome(self.registry, reason)

    def set_replica_gauges(self, name: str, healthy: bool,
                           inflight: int) -> None:
        if self.registry is None:
            return
        self.registry.gauge(
            "serving_replica_healthy",
            "1 while the replica passes both watchdogs, else 0.",
            labels={"replica": name}).set(1.0 if healthy else 0.0)
        self.registry.gauge(
            "serving_replica_inflight",
            "Requests currently dispatched to the replica.",
            labels={"replica": name}).set(float(inflight))

    def set_load_gauges(self, queue_depth: int,
                        inflight_tokens: int) -> None:
        if self.registry is None:
            return
        self.registry.gauge(
            "serving_fleet_queue_depth",
            "Accepted-but-unfinished requests at the router.",
        ).set(float(queue_depth))
        self.registry.gauge(
            "serving_fleet_inflight_tokens",
            "Token budget in flight (sum of prompt + max_new_tokens).",
        ).set(float(inflight_tokens))

    # ------------------------------------------------------------ #

    def summary(self) -> Dict:
        offered = self.accepted + self.shed
        return {
            "accepted": self.accepted,
            "shed": self.shed,
            "shed_rate": self.shed / offered if offered else 0.0,
            "retries": self.retries,
            "replica_downs": list(self.replica_downs),
            "outcomes": dict(self.outcomes),
            "router_ttft_s": _percentiles(self.ttft_s),
            "router_e2e_s": _percentiles(self.e2e_s),
            "slo": self.slo_tracker.summary(),
        }
