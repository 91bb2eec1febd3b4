"""Telemetry artifact gate: trace-event JSON + trilemma ledger schemas.

    python tools/check_trace.py trace.json [--ledger metrics.jsonl]
        [--summary run.json] [--expect-chunk-traces N]
        [--expect-step-builds N] [--stall-tol 1e-3]
        [--require-spans retry,prefetch_degraded] [--require-device-lane]

Checks, in order:
  1. Trace structure — Chrome trace-event JSON ({traceEvents, otherData});
     every event carries ph/name/pid/tid/ts, complete ("X") events a
     non-negative dur, and the driver's core span names are present
     (chunk, dispatch, chunk_prep, prep_stall, metrics_flush).
  2. Nesting — per host tracer lane (cat "obs"), "X" spans are properly
     nested (contained or disjoint, never partially overlapping): the
     tracer records via nested context managers, so a violation means a
     broken clock. Merged device-op events are exempt (the runtime
     overlaps executions by design). The chunk prep's inner spans
     (`prep_device_wait`, `batch_gen`, `h2d_put`) must each lie inside a
     `ctl_build` or `batch_stage` span on their lane: the prep's host
     work and its device waits are told apart only there.
  3. Stall attribution — the sum of prep_stall (and ckpt_snapshot) span
     durations equals otherData's legacy prep_stall_s/ckpt_stall_s
     counters within --stall-tol seconds (default 1ms): spans are the
     single source of truth, the scalars its derived sums.
  4. Prefetch overlap — when otherData.overlap is true, every kicked
     chunk_prep span for chunk i starts at/after its prefetch_kick
     instant, and that kick fires inside chunk i-1's driver span: the
     pipeline's next-chunk prep really overlaps the current chunk.
  5. Compile watermarks — with --expect-chunk-traces/--expect-step-builds,
     otherData.compile_stats must match exactly (a CI cold run compiles a
     known number of programs; more means a cache-key break).
  6. Ledger (--ledger) — line 1 is the trilemma_ledger/v2 header; every
     row carries the full record schema (v2: k_sync/stale_frac desync
     columns, with 0 <= k_sync <= k_eff and stale_frac their consistent
     ratio); rounds strictly increase and the cumulative columns
     (bits_cum, dp_spent_cum, eps_cum) never decrease. A torn TRAILING
     line (SIGKILL mid-row) is reported as a truncation note, not a
     crash; a torn line anywhere else is corruption and fails.
  7. Required extra spans (--require-spans) — each named span must appear
     at least once (the chaos lane asserts the retry/degradation path
     really fired: retry, prefetch_degraded).
  8. Device lane (--require-device-lane) — the trace carries profiler-
     merged device-op events on a pid distinct from the host spans'
     pid 0, their time window overlaps the host span window (the clocks
     were actually aligned), and otherData.profile records the merge.
  9. Summary cross-check (--summary, needs --ledger) — the final row's
     bits_cum / dp_spent_cum / peak_bytes equal the run summary's
     uplink_bits / privacy_spent / peak_bytes EXACTLY, and the row count
     equals the executed rounds: the ledger and RunResult are one
     accounting, not two.
Exit code 0 on pass; 1 with every violation listed on failure.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict

REQUIRED_SPANS = ("chunk", "dispatch", "chunk_prep", "prep_stall",
                  "metrics_flush")
# the chunk prep's inner spans and the spans they must nest in
PREP_INNER = ("prep_device_wait", "batch_gen", "h2d_put")
PREP_OUTER = ("ctl_build", "batch_stage")
LEDGER_SCHEMA = "trilemma_ledger/v2"
LEDGER_KEYS = ("round", "loss", "k_eff", "k_sync", "stale_frac",
               "bits_round", "bits_cum", "dp_cost", "dp_spent_cum",
               "eps_cum", "peak_bytes", "wall_s")


def _spans(events, name=None):
    return [e for e in events if e.get("ph") == "X"
            and (name is None or e.get("name") == name)]


def check_trace(doc, errors, stall_tol):
    """Checks 1-4 over a parsed trace document; appends to `errors`."""
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        errors.append("trace: not a trace-event document "
                      "(missing traceEvents)")
        return
    events = doc["traceEvents"]
    meta = doc.get("otherData", {})
    if not isinstance(meta, dict):
        errors.append("trace: otherData must be an object")
        meta = {}

    # 1. structure ------------------------------------------------------
    for i, e in enumerate(events):
        # profiler-merged metadata records may omit tid (process_name
        # entries label a whole device pid); host M events carry both
        keys = ("ph", "name", "pid") if e.get("ph") == "M" \
            else ("ph", "name", "pid", "tid", "ts")
        for key in keys:
            if key not in e:
                errors.append(f"trace: event {i} missing {key!r}")
        if e.get("ph") == "X" and not (e.get("dur", -1) >= 0):
            errors.append(f"trace: X event {i} ({e.get('name')}) has no "
                          "non-negative dur")
    names = {e.get("name") for e in events}
    for want in REQUIRED_SPANS:
        if want not in names:
            errors.append(f"trace: required span {want!r} absent")

    # 2. nesting per thread lane — host tracer spans only (cat "obs").
    # Merged device-op events legitimately overlap within a lane: the
    # runtime pipelines executions, and only the context-manager tracer
    # guarantees strict nesting.
    lanes = defaultdict(list)
    for e in _spans(events):
        if e.get("cat") != "obs":
            continue
        lanes[e["tid"]].append((float(e["ts"]), float(e["ts"]) +
                                float(e.get("dur", 0)), e["name"]))
    eps = 1.0  # µs slack for equal perf_counter quanta
    for tid, spans in lanes.items():
        spans.sort(key=lambda s: (s[0], -(s[1] - s[0])))
        stack = []
        for (a, b, nm) in spans:
            while stack and a >= stack[-1][1] - eps:
                stack.pop()
            if stack and b > stack[-1][1] + eps:
                errors.append(
                    f"trace: span {nm!r} [{a:.1f}, {b:.1f}] partially "
                    f"overlaps {stack[-1][2]!r} [{stack[-1][0]:.1f}, "
                    f"{stack[-1][1]:.1f}] on tid {tid}")
                continue
            stack.append((a, b, nm))
        outers = [(a, b) for a, b, nm in spans if nm in PREP_OUTER]
        for a, b, nm in spans:
            if nm in PREP_INNER and not any(
                    oa - eps <= a and b <= ob + eps for oa, ob in outers):
                errors.append(
                    f"trace: {nm!r} span [{a:.1f}, {b:.1f}] on tid {tid} "
                    f"lies in no {' or '.join(PREP_OUTER)} span")

    # 3. stall attribution ---------------------------------------------
    for span_name, scalar in (("prep_stall", "prep_stall_s"),
                              ("ckpt_snapshot", "ckpt_stall_s")):
        if scalar not in meta:
            continue
        total = sum(e["dur"] for e in _spans(events, span_name)) * 1e-6
        want = float(meta[scalar])
        if abs(total - want) > stall_tol:
            errors.append(
                f"trace: Σ {span_name} spans = {total:.6f}s but "
                f"otherData.{scalar} = {want:.6f}s "
                f"(tol {stall_tol}s) — the scalar is no longer the "
                "span-derived sum")

    # 4. prefetch overlap ----------------------------------------------
    if meta.get("overlap"):
        kicks = {e["args"]["chunk"]: float(e["ts"]) for e in events
                 if e.get("ph") == "i" and e.get("name") == "prefetch_kick"}
        chunks = {e["args"]["chunk"]:
                  (float(e["ts"]), float(e["ts"]) + float(e["dur"]))
                  for e in _spans(events, "chunk")}
        kicked = [e for e in _spans(events, "chunk_prep")
                  if e.get("args", {}).get("kicked")]
        if not kicked and len(chunks) > 1:
            errors.append("trace: overlap on but no kicked chunk_prep "
                          "spans recorded")
        for e in kicked:
            i = e["args"]["chunk"]
            ts = float(e["ts"])
            if i in kicks and ts < kicks[i] - eps:
                errors.append(f"trace: chunk_prep {i} starts before its "
                              "prefetch_kick")
            prev = chunks.get(i - 1)
            if i in kicks and prev and not \
                    (prev[0] - eps <= kicks[i] <= prev[1] + eps):
                errors.append(
                    f"trace: prefetch_kick {i} at {kicks[i]:.1f} fired "
                    f"outside chunk {i - 1}'s span {prev} — prep does "
                    "not overlap the previous chunk")
    return meta


def check_compile(meta, args, errors):
    """Check 5: exact compile-count assertions vs otherData."""
    stats = meta.get("compile_stats", {})
    for flag, key in ((args.expect_chunk_traces, "scan_chunk_trace"),
                      (args.expect_step_builds, "zo_step_build")):
        if flag is None:
            continue
        got = int(stats.get(key, 0))
        if got != flag:
            errors.append(f"trace: compile_stats[{key!r}] = {got}, "
                          f"expected exactly {flag} — the step/executor "
                          "memoization keys changed")


def check_device_lane(doc, meta, errors):
    """Check 8: profiler-merged device events share the host timeline.

    Host spans always live on pid 0; `--profile-out` appends device-op
    events on their own pids. Requires: at least one non-host "X" event,
    a window overlap between device and host events (the anchor offset
    really mapped the profiler clock onto the tracer epoch), and the
    otherData.profile meta the exporter records for the merge.
    """
    events = doc.get("traceEvents", []) if isinstance(doc, dict) else []
    host = [(float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0)))
            for e in _spans(events) if e.get("pid") == 0]
    dev = [(float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0)))
           for e in _spans(events) if e.get("pid") != 0]
    if not dev:
        errors.append("trace: no device-lane X events (pid != 0) — "
                      "was the trace exported with --profile-out?")
        return
    profile = meta.get("profile")
    if not isinstance(profile, dict):
        errors.append("trace: otherData.profile missing — exporter did "
                      "not record the profiler merge")
    elif "error" in profile:
        errors.append(f"trace: profiler capture errored: "
                      f"{profile['error']}")
    if host:
        h0, h1 = min(a for a, _ in host), max(b for _, b in host)
        d0, d1 = min(a for a, _ in dev), max(b for _, b in dev)
        if d1 < h0 or d0 > h1:
            errors.append(
                f"trace: device window [{d0:.1f}, {d1:.1f}]µs does not "
                f"overlap host window [{h0:.1f}, {h1:.1f}]µs — clock "
                "alignment failed")


def check_ledger(path, errors, notes):
    """Check 6: schema + monotonicity. Returns (header, rows).

    Tolerates a torn TRAILING line (SIGKILL mid-row append) by dropping
    it and recording a truncation note; a torn line anywhere else is
    corruption and fails the gate.
    """
    try:
        with open(path) as f:
            raw = [ln for ln in f if ln.strip()]
    except OSError as e:
        errors.append(f"ledger: unreadable ({e})")
        return None, []
    lines = []
    for i, ln in enumerate(raw):
        try:
            lines.append(json.loads(ln))
        except json.JSONDecodeError as e:
            if i == len(raw) - 1:
                notes.append(f"ledger: torn trailing record dropped "
                             f"(crash mid-append at line {i + 1})")
            else:
                errors.append(f"ledger: corrupt line {i + 1} ({e}) — "
                              "torn records are only legal at the tail")
    if not lines or lines[0].get("schema") != LEDGER_SCHEMA:
        errors.append(f"ledger: line 1 must carry schema={LEDGER_SCHEMA!r}")
        return None, []
    header, rows = lines[0], lines[1:]
    prev_round, prev = None, {}
    for i, row in enumerate(rows):
        missing = [k for k in LEDGER_KEYS if k not in row]
        if missing:
            errors.append(f"ledger: row {i} missing keys {missing}")
            continue
        if prev_round is not None and row["round"] <= prev_round:
            errors.append(f"ledger: rounds not strictly increasing at "
                          f"row {i}")
        for cum in ("bits_cum", "dp_spent_cum", "eps_cum", "peak_bytes"):
            if prev and row[cum] < prev[cum]:
                errors.append(f"ledger: {cum} decreases at row {i}")
        # v2 desync columns: k_sync is a sub-count of k_eff and
        # stale_frac is exactly their ratio
        if not 0.0 <= row["k_sync"] <= row["k_eff"]:
            errors.append(f"ledger: row {i} k_sync={row['k_sync']} "
                          f"outside [0, k_eff={row['k_eff']}]")
        want_frac = ((row["k_eff"] - row["k_sync"]) / row["k_eff"]
                     if row["k_eff"] > 0 else 0.0)
        if abs(row["stale_frac"] - want_frac) > 1e-9:
            errors.append(f"ledger: row {i} stale_frac={row['stale_frac']}"
                          f" != (k_eff-k_sync)/k_eff = {want_frac}")
        prev_round, prev = row["round"], row
    return header, rows


def check_summary(path, rows, errors):
    """Check 9: the final ledger row equals the run summary exactly."""
    try:
        with open(path) as f:
            summary = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        errors.append(f"summary: unreadable ({e})")
        return
    if not rows:
        errors.append("summary: cross-check requested but ledger has no "
                      "rows")
        return
    final = rows[-1]
    for row_key, sum_key in (("bits_cum", "uplink_bits"),
                             ("dp_spent_cum", "privacy_spent"),
                             ("peak_bytes", "peak_bytes")):
        if sum_key not in summary:
            errors.append(f"summary: missing {sum_key!r}")
        elif final[row_key] != summary[sum_key]:
            errors.append(
                f"summary: ledger {row_key} = {final[row_key]!r} != "
                f"summary {sum_key} = {summary[sum_key]!r} (exact match "
                "required — one accounting, not two)")
    if "rounds" in summary and len(rows) != int(summary["rounds"]):
        errors.append(f"summary: {len(rows)} ledger rows != "
                      f"{summary['rounds']} executed rounds")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace", help="Chrome trace-event JSON (--trace-out)")
    ap.add_argument("--ledger", default=None,
                    help="trilemma JSONL ledger (--metrics-out)")
    ap.add_argument("--summary", default=None,
                    help="run summary JSON (train.py --out); requires "
                         "--ledger — cross-checked exactly")
    ap.add_argument("--expect-chunk-traces", type=int, default=None,
                    help="assert compile_stats.scan_chunk_trace == N")
    ap.add_argument("--expect-step-builds", type=int, default=None,
                    help="assert compile_stats.zo_step_build == N")
    ap.add_argument("--stall-tol", type=float, default=1e-3,
                    help="span-sum vs legacy stall counter tolerance (s)")
    ap.add_argument("--require-spans", default=None,
                    help="comma-separated extra span names that must each "
                         "appear at least once (chaos lane: "
                         "retry,prefetch_degraded)")
    ap.add_argument("--require-device-lane", action="store_true",
                    help="assert profiler-merged device-op events are "
                         "present (pid != 0), window-overlap the host "
                         "spans, and otherData.profile records the merge")
    args = ap.parse_args()
    errors = []
    notes = []

    try:
        with open(args.trace) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"check_trace: FAIL (trace unreadable: {e})")
        sys.exit(1)

    meta = check_trace(doc, errors, args.stall_tol) or {}
    check_compile(meta, args, errors)
    if args.require_spans:
        names = {e.get("name") for e in doc.get("traceEvents", [])}
        for want in args.require_spans.split(","):
            want = want.strip()
            if want and want not in names:
                errors.append(f"trace: required span {want!r} absent "
                              "(--require-spans)")
    if args.require_device_lane:
        check_device_lane(doc, meta, errors)
    rows = []
    if args.ledger:
        _, rows = check_ledger(args.ledger, errors, notes)
    if args.summary:
        check_summary(args.summary, rows, errors)

    if errors:
        print(f"check_trace: FAIL ({len(errors)} violation(s))")
        for e in errors:
            print(f"  {e}")
        sys.exit(1)
    for note in notes:
        print(f"check_trace: note: {note}")
    n_events = len(doc.get("traceEvents", []))
    print(f"check_trace: OK ({n_events} trace events"
          + (f", {len(rows)} ledger rows" if args.ledger else "")
          + (", summary cross-checked" if args.summary else "") + ")")


if __name__ == "__main__":
    main()
