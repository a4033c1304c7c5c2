"""Continuous perf-regression ledger: one schema, one gate.

The repo accumulates benchmark truth as loose ``BENCH_*.json`` files —
every drill writes its own shape and nothing ever compares two runs.
This module gives them a spine:

  * one record schema — ``{metric, value, direction, platform, source,
    git_rev, wall_time, run}`` (run context from runctx) — appended as
    JSON lines to ``BENCH_LEDGER.jsonl`` (git-ignored; ``PERF_LEDGER.jsonl``
    at the repo root belongs to the benchmark driver and is never read
    or written here);
  * a tracked-metric table (:data:`METRIC_SPECS`) mapping each headline
    number in the BENCH corpus to its file, JSON path, direction
    (higher/lower-is-better), and per-metric tolerance;
  * a CLI gate::

        python -m deeperspeed_tpu.monitor.ledger append   # ingest corpus
        python -m deeperspeed_tpu.monitor.ledger check    # regression gate

    ``check`` compares each metric's current value (from the BENCH file,
    or ``--metric/--value`` for a live run) against the rolling baseline
    (median of the last N ledger records on the same platform) and exits
    non-zero when any tracked metric regresses beyond its tolerance —
    the gate every future perf PR (and the sharding refactor) benches
    against.

Design choices that keep the gate honest rather than noisy: tolerances
are per-metric (wall-clock numbers on the 1-core CPU host get wide
bands, counters like ``decode_compiles`` and ``strict_problems`` get
zero), missing BENCH files are *skipped with a note* (BENCH_elastic was
specced but never landed; absence is not a regression), and a first run
against an empty ledger seeds it and passes — the gate compares runs,
it does not invent a baseline.
"""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .runctx import current as current_run

__all__ = [
    "METRIC_SPECS",
    "MetricSpec",
    "PerfLedger",
    "collect_current",
    "main",
]

DEFAULT_LEDGER = "BENCH_LEDGER.jsonl"
DEFAULT_BASELINE_N = 5


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    """One tracked metric: where it lives and how much drift is noise."""

    name: str                 # ledger metric name, dotted
    file: str                 # BENCH file (repo-root-relative)
    path: Tuple[str, ...]     # JSON path inside the file
    direction: str            # "higher" | "lower" (which way is better)
    rel_tol: float = 0.25     # fractional drift allowed past baseline
    abs_tol: float = 0.0      # additive slack (units of the metric)
    note: str = ""

    def regressed(self, value: float, baseline: float) -> bool:
        if self.direction == "higher":
            return value < baseline * (1.0 - self.rel_tol) - self.abs_tol
        return value > baseline * (1.0 + self.rel_tol) + self.abs_tol


# The corpus gate. Wall-clock metrics measured on the 1-core CPU host
# carry wide rel_tol (the BENCH files themselves document the timing
# caveat); structural counters carry zero tolerance — one extra decode
# compile IS the regression.
METRIC_SPECS: Tuple[MetricSpec, ...] = (
    # comm (PR 6/10)
    MetricSpec("comm.int8.reduce_only_x", "BENCH_comm.json",
               ("modes", "int8", "reduce_only_x"), "higher", 0.10),
    MetricSpec("comm.int8.loss_delta_pct", "BENCH_comm.json",
               ("modes", "int8", "loss_delta_pct"), "lower", 0.50, 0.05),
    MetricSpec("comm.fp32.step_ms", "BENCH_comm.json",
               ("modes", "fp32", "step_ms"), "lower", 0.50,
               note="cpu wall clock: wide band"),
    MetricSpec("comm.overlap_fraction", "BENCH_comm.json",
               ("overlap", "overlap_fraction"), "higher", 0.05),
    # serving (PR 2/8)
    MetricSpec("serving.tokens_per_sec", "BENCH_serving.json",
               ("tokens_per_sec",), "higher", 0.30,
               note="cpu wall clock: wide band"),
    MetricSpec("serving.ttft_p99_s", "BENCH_serving.json",
               ("ttft_p99_s",), "lower", 0.50, 0.05),
    MetricSpec("serving.decode_compiles", "BENCH_serving.json",
               ("decode_compiles",), "lower", 0.0,
               note="one-compile decode is the invariant"),
    MetricSpec("serving.prefill_compiles", "BENCH_serving.json",
               ("prefill_compiles",), "lower", 0.0, 2.0,
               note="one compile per length bucket; --slo warms every "
                    "bucket (5) where the old bench warmed 3"),
    # request-path doctor (PR 17): attributed tail latency and unit
    # cost from the bench's --slo breakdown. Wall-clock on the CPU
    # host: wide bands; the attribution itself is gated by the slo CLI
    # in check.sh (residual < 5% is a hard failure there, not here)
    MetricSpec("serving.ttft_p99_ms", "BENCH_serving.json",
               ("slo", "ttft_p99_ms"), "lower", 0.50, 85.0,
               note="cpu wall clock: wide band; basis changed at the "
                    "--shared-prefix bench (slo pass now measures a "
                    "d_model=256 model, was 64) — abs band covers the "
                    "declared re-basis until the rolling median "
                    "catches up"),
    MetricSpec("serving.cost_per_1k_tokens", "BENCH_serving.json",
               ("slo", "cost_per_1k_tokens"), "lower", 0.50, 0.5,
               note="device-seconds per 1k tokens, cpu-host nominal"),
    # prefix-radix KV reuse (PR 18): the --shared-prefix traffic mix
    # must keep finding its system prompts in the radix cache — a
    # regression here means prompts are being re-prefilled fleet-wide
    MetricSpec("serving.prefill_tokens_saved_frac", "BENCH_serving.json",
               ("prefix_reuse", "tokens_saved_frac"), "higher", 0.15,
               note="fraction of prompt tokens served from the radix "
                    "cache under --shared-prefix traffic"),
    MetricSpec("serving.reuse_hit_rate", "BENCH_serving.json",
               ("prefix_reuse", "reuse_hit_rate"), "higher", 0.15),
    # speculative decoding (PR 19): the --speculative dual-pass bench.
    # Acceptance is a model/drafter property (tight band — a drop means
    # the verify contract or the drafter sync broke, not the host);
    # TPOT is cpu wall clock (wide band)
    MetricSpec("serving.spec_accept_rate", "BENCH_serving.json",
               ("speculative", "accept_rate"), "higher", 0.15,
               note="drafted tokens the target verified and kept"),
    MetricSpec("serving.tpot_ms", "BENCH_serving.json",
               ("speculative", "tpot_ms"), "lower", 0.50, 1.0,
               note="cpu wall clock: wide band; speculative pass of "
                    "the dual-pass bench"),
    # fleet (PR 8)
    MetricSpec("fleet.fault.accepted", "BENCH_fleet.json",
               ("failover", "fault", "accepted"), "higher", 0.0,
               note="kill drill must not lose accepted requests"),
    MetricSpec("fleet.fault.retries", "BENCH_fleet.json",
               ("failover", "fault", "retries"), "lower", 0.0, 2.0),
    MetricSpec("fleet.healthy.p99_ttft_s", "BENCH_fleet.json",
               ("failover", "healthy", "p99_ttft_s"), "lower", 0.50, 0.05),
    # observability (PR 9)
    MetricSpec("obs.strict_problems", "BENCH_obs.json",
               ("fleet_merge", "strict_problems"), "lower", 0.0),
    MetricSpec("obs.rids_traceable", "BENCH_obs.json",
               ("fleet_merge", "rids_traceable"), "higher", 0.0),
    MetricSpec("obs.goodput.accounting_error", "BENCH_obs.json",
               ("goodput", "accounting_error"), "lower", 0.0, 0.001),
    # datapipe (PR 5)
    MetricSpec("datapipe.host_blocked_mean_ms", "BENCH_datapipe.json",
               ("prefetch_on", "host_blocked_mean_ms"), "lower", 0.50, 0.5),
    MetricSpec("datapipe.stall_ratio", "BENCH_datapipe.json",
               ("stall_ratio",), "lower", 1.00, 0.10),
    # resilience (PR 4)
    MetricSpec("resilience.blocked_ratio", "BENCH_resilience.json",
               ("blocked_ratio",), "lower", 1.00, 0.01),
    MetricSpec("resilience.resume_latency_s", "BENCH_resilience.json",
               ("resume_latency_s",), "lower", 0.50, 0.2),
    # elastic (PR 7) — drill writes no BENCH file yet; specced so the
    # day it lands it is tracked, skipped-with-a-note until then
    MetricSpec("elastic.max_loss_delta", "BENCH_elastic.json",
               ("max_loss_delta",), "lower", 0.0, 1e-6,
               note="world-size resharding must stay bit-identical"),
    # sharding substrate (PR 13): loss parity across layouts is an
    # exactness gate; step time per layout is wide-band (CPU-host noise)
    MetricSpec("mesh.parity.max_loss_delta", "BENCH_mesh.json",
               ("parity", "max_loss_delta"), "lower", 0.0, 1e-6,
               note="canonical mesh must reproduce the legacy loss curve"),
    MetricSpec("mesh.dp_fsdp.step_ms", "BENCH_mesh.json",
               ("layouts", "dp2_fsdp4", "step_ms"), "lower", 1.00, 5.0),
    MetricSpec("mesh.zero3.sharded_frac", "BENCH_mesh.json",
               ("layouts", "fsdp8_zero3", "param_sharded_frac"),
               "higher", 0.0, 0.01,
               note="ZeRO-3 on fsdp must actually shard the param bytes"),
    # lifecycle (PR 15): zero-downtime train→serve. Losing an accepted
    # request across a weight push, a non-bit-identical live re-mesh,
    # or restart downtime during a pool shrink are exactness gates; the
    # re-mesh stall itself is CPU wall clock and gets a wide band
    MetricSpec("lifecycle.lost_accepted", "BENCH_lifecycle.json",
               ("serving", "lost_accepted"), "lower", 0.0,
               note="weight pushes + pool shrink must not lose accepted "
                    "requests"),
    MetricSpec("lifecycle.max_loss_delta", "BENCH_lifecycle.json",
               ("remesh", "max_loss_delta"), "lower", 0.0, 1e-9,
               note="live re-mesh must match the kill-restart reshard "
                    "losses bit-for-bit"),
    MetricSpec("lifecycle.weight_pushes", "BENCH_lifecycle.json",
               ("weight_pushes",), "higher", 0.0),
    MetricSpec("lifecycle.goodput.restart_s", "BENCH_lifecycle.json",
               ("goodput", "restart_s"), "lower", 0.0, 0.5,
               note="the live path keeps the process up: shrink "
                    "downtime lands in `remesh`, not `restart`"),
    MetricSpec("lifecycle.remesh_stall_s", "BENCH_lifecycle.json",
               ("remesh", "stall_s"), "lower", 1.00, 5.0,
               note="cpu wall clock: wide band"),
    # static analysis (PR 14): the committed baseline findings file —
    # error count is an exactness gate (the CLI already fails CI on
    # errors; the ledger catches a quietly-committed regressed
    # baseline), warnings/suppressions get one entry of slack so a
    # deliberate new waiver doesn't read as a perf regression
    MetricSpec("analysis.errors", "ANALYSIS_BASELINE.json",
               ("counts", "error"), "lower", 0.0,
               note="python -m deeperspeed_tpu.analysis must stay clean"),
    MetricSpec("analysis.warnings", "ANALYSIS_BASELINE.json",
               ("counts", "warning"), "lower", 0.0, 1.0),
    MetricSpec("analysis.suppressed", "ANALYSIS_BASELINE.json",
               ("counts", "suppressed"), "lower", 0.0, 1.0,
               note="every new waiver needs a reason in "
                    "ANALYSIS_SUPPRESSIONS.json"),
    # autotune (PR 16): the cost model's honesty metric is rank
    # correlation between predicted and measured orderings over the
    # confirmed set (the acceptance floor is 0.6, so a baseline near
    # 1.0 minus the absolute band still gates there); the best
    # predicted cost itself is CPU-nominal and wide-band — it exists
    # so a cost-model change that doubles every prediction is seen
    MetricSpec("autotune.rank_correlation", "BENCH_autotune.json",
               ("confirm", "rank_correlation"), "higher", 0.0, 0.40,
               note="predicted order must keep tracking measured order"),
    MetricSpec("autotune.best_predicted_cost", "BENCH_autotune.json",
               ("best", "predicted_step_s"), "lower", 1.00,
               note="cpu-nominal roofline seconds: wide band"),
    # distributed (PR 20): the multi-host fleet drill. Cross-process
    # loss parity is an exactness gate (the canonical-slot reduction
    # must be independent of the device->process mapping AND the world
    # size); the SIGKILL->recovery wall time is CPU wall clock (two
    # jax.distributed rendezvous + recompile) and gets a wide band;
    # the cross-host wire bytes of the hierarchical int8 schedule are
    # a structural count priced by wiremodel.py
    MetricSpec("multihost.max_loss_delta", "BENCH_multihost.json",
               ("parity", "max_loss_delta"), "lower", 0.0, 1e-9,
               note="2-process fleet (and the grown 3-process fleet) "
                    "must match the single-process mesh bit-for-bit"),
    MetricSpec("multihost.crash_restarts_after_growth",
               "BENCH_multihost.json",
               ("growth", "crash_restarts_after_growth"), "lower", 0.0,
               note="pool growth is a planned re-mesh, never a crash "
                    "restart"),
    MetricSpec("multihost.restart_s", "BENCH_multihost.json",
               ("restart", "restart_s"), "lower", 1.00, 30.0,
               note="SIGKILL -> first post-barrier step: cpu wall "
                    "clock, wide band"),
    MetricSpec("multihost.int8_inter_bytes", "BENCH_multihost.json",
               ("wire", "int8", "inter_bytes"), "lower", 0.0,
               note="cross-host hop of the two-level int8 schedule "
                    "(wiremodel pricing, exact)"),
)

_SPECS_BY_NAME = {s.name: s for s in METRIC_SPECS}


# ------------------------------------------------------------------ #
# record plumbing
# ------------------------------------------------------------------ #


def _git_rev(root: str) -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=root,
            capture_output=True, text=True, timeout=10)
        rev = out.stdout.strip()
        return rev if out.returncode == 0 and rev else "unknown"
    except Exception:
        return "unknown"


def _detect_platform() -> str:
    try:
        import jax
        return jax.local_devices()[0].platform
    except Exception:
        return "unknown"


def _dig(obj: Any, path: Sequence[str]) -> Optional[float]:
    for key in path:
        if not isinstance(obj, dict) or key not in obj:
            return None
        obj = obj[key]
    if isinstance(obj, bool) or not isinstance(obj, (int, float)):
        return None
    return float(obj)


def make_record(metric: str, value: float, platform: str, source: str,
                git_rev: str, wall_time: Optional[float] = None) -> Dict:
    rc = current_run()
    return {
        "metric": metric,
        "value": float(value),
        "platform": platform,
        "source": source,
        "git_rev": git_rev,
        "wall_time": time.time() if wall_time is None else wall_time,
        "run": rc.as_args(),
    }


def collect_current(root: str,
                    specs: Sequence[MetricSpec] = METRIC_SPECS,
                    ) -> Tuple[List[Dict], List[str]]:
    """Read every tracked metric's current value from the BENCH corpus
    under ``root``. Returns (records, notes) — notes name skipped files
    and missing paths, which are reported but never fail the gate."""
    records: List[Dict] = []
    notes: List[str] = []
    rev = _git_rev(root)
    cache: Dict[str, Any] = {}
    for spec in specs:
        fpath = os.path.join(root, spec.file)
        if spec.file not in cache:
            if not os.path.exists(fpath):
                cache[spec.file] = None
            else:
                try:
                    with open(fpath) as f:
                        cache[spec.file] = json.load(f)
                except (OSError, json.JSONDecodeError) as e:
                    cache[spec.file] = None
                    notes.append(f"skip {spec.file}: unreadable ({e})")
        blob = cache[spec.file]
        if blob is None:
            if not any(n.startswith(f"skip {spec.file}") for n in notes):
                notes.append(f"skip {spec.file}: missing")
            continue
        value = _dig(blob, spec.path)
        if value is None:
            notes.append(f"skip {spec.name}: no value at "
                         f"{'.'.join(spec.path)} in {spec.file}")
            continue
        platform = blob.get("platform") if isinstance(blob, dict) else None
        records.append(make_record(
            spec.name, value, platform or "cpu", spec.file, rev))
    return records, notes


class PerfLedger:
    """The JSONL file plus baseline/regression arithmetic."""

    def __init__(self, path: str, baseline_n: int = DEFAULT_BASELINE_N):
        self.path = path
        self.baseline_n = baseline_n

    def read(self) -> List[Dict]:
        if not os.path.exists(self.path):
            return []
        out: List[Dict] = []
        with open(self.path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # half-written tail (crash) — records stand alone
                if isinstance(rec, dict) and "metric" in rec:
                    out.append(rec)
        return out

    def append(self, records: Sequence[Dict]) -> int:
        if not records:
            return 0
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        with open(self.path, "a") as f:
            for rec in records:
                f.write(json.dumps(rec, sort_keys=True) + "\n")
        return len(records)

    def baseline(self, metric: str, platform: Optional[str] = None,
                 history: Optional[List[Dict]] = None) -> Optional[float]:
        """Rolling baseline: median of the last N records for ``metric``
        (same platform when given — a TPU number is not a CPU baseline)."""
        if history is None:
            history = self.read()
        vals = [r["value"] for r in history
                if r.get("metric") == metric
                and isinstance(r.get("value"), (int, float))
                and (platform is None or r.get("platform") == platform)]
        if not vals:
            return None
        tail = sorted(vals[-self.baseline_n:])
        mid = len(tail) // 2
        if len(tail) % 2:
            return float(tail[mid])
        return (tail[mid - 1] + tail[mid]) / 2.0

    def check(self, candidates: Sequence[Dict]) -> Tuple[List[str], List[str]]:
        """Compare candidate records against rolling baselines. Returns
        (failures, report_lines)."""
        history = self.read()
        failures: List[str] = []
        report: List[str] = []
        for rec in candidates:
            name = rec["metric"]
            spec = _SPECS_BY_NAME.get(name)
            base = self.baseline(name, rec.get("platform"), history)
            if base is None:
                # same metric, any platform — better a cross-platform
                # note than silence on a first TPU run
                base = self.baseline(name, None, history)
            if base is None:
                report.append(f"  NEW  {name} = {rec['value']:g} "
                              f"(no baseline yet)")
                continue
            if spec is None:
                report.append(f"  ??   {name} = {rec['value']:g} "
                              f"(untracked metric; baseline {base:g})")
                continue
            if spec.regressed(rec["value"], base):
                arrow = "<" if spec.direction == "higher" else ">"
                failures.append(
                    f"{name}: {rec['value']:g} {arrow} baseline {base:g} "
                    f"beyond tol (rel {spec.rel_tol:g}, abs {spec.abs_tol:g})"
                    + (f" — {spec.note}" if spec.note else ""))
                report.append(f"  FAIL {name} = {rec['value']:g} "
                              f"(baseline {base:g}, {spec.direction} is "
                              f"better)")
            else:
                report.append(f"  ok   {name} = {rec['value']:g} "
                              f"(baseline {base:g})")
        return failures, report


# ------------------------------------------------------------------ #
# CLI
# ------------------------------------------------------------------ #


def _live_records(args, root: str) -> List[Dict]:
    """One record from ``--metric/--value`` (a live run reporting in)."""
    if args.metric is None:
        return []
    if args.value is None:
        raise SystemExit("--metric requires --value")
    return [make_record(args.metric, args.value,
                        args.platform or _detect_platform(),
                        "live", _git_rev(root))]


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m deeperspeed_tpu.monitor.ledger",
        description="Perf-regression ledger over the BENCH_*.json corpus.")
    ap.add_argument("command", choices=("append", "check"))
    ap.add_argument("--root", default=".",
                    help="repo root holding the BENCH_*.json corpus")
    ap.add_argument("--ledger", default=None,
                    help=f"ledger path (default <root>/{DEFAULT_LEDGER})")
    ap.add_argument("--baseline-n", type=int, default=DEFAULT_BASELINE_N,
                    help="rolling-baseline window (median of last N)")
    ap.add_argument("--metric", default=None,
                    help="also include one live metric by name")
    ap.add_argument("--value", type=float, default=None,
                    help="value for --metric")
    ap.add_argument("--platform", default=None,
                    help="platform label for --metric (default: detected)")
    args = ap.parse_args(argv)

    root = args.root
    ledger = PerfLedger(args.ledger or os.path.join(root, DEFAULT_LEDGER),
                        baseline_n=args.baseline_n)
    corpus, notes = collect_current(root)
    live = _live_records(args, root)

    if args.command == "append":
        n = ledger.append(corpus + live)
        for note in notes:
            print(f"note: {note}")
        print(f"appended {n} records to {ledger.path}")
        return 0

    # check
    candidates = corpus + live
    if not ledger.read():
        n = ledger.append(candidates)
        for note in notes:
            print(f"note: {note}")
        print(f"ledger was empty: seeded {n} records to {ledger.path}; "
              "nothing to compare yet")
        return 0
    failures, report = ledger.check(candidates)
    print(f"perf ledger check: {len(candidates)} metrics vs {ledger.path}")
    for line in report:
        print(line)
    for note in notes:
        print(f"note: {note}")
    if failures:
        print(f"\n{len(failures)} regression(s):", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print("no regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
