"""Device-op capture merged onto the host span timeline (one Perfetto view).

`jax.profiler` records what the runtime actually executed — XLA executable
launches, buffer awaits, per-op device activity — but on its *own* clock
and in its own TensorBoard-oriented dump format. The PR-8 span tracer
records host-side truth (chunk_prep / dispatch / prep_stall) on a
`perf_counter` epoch. This module joins the two:

  1. `ProfilerSession.start()` begins a `jax.profiler` trace and
     immediately emits a named `TraceAnnotation` **anchor** at a recorded
     `perf_counter` instant. The anchor shows up verbatim as an event in
     the profiler dump, giving an exact affine map between the profiler
     clock and the tracer epoch (no clock guessing).
  2. `device_events(epoch)` loads the newest Chrome-format dump the
     profiler wrote (``plugins/profile/<ts>/*.trace.json.gz``), shifts
     every timestamp by the anchor offset onto the tracer epoch, and
     rebadges pids so device lanes render as their own Perfetto process
     next to the host spans (which always live on pid 0).
  3. `Tracer.export_chrome(..., extra_events=...)` appends them — host
     spans and XLA ops on ONE timeline (`train.py --profile-out`).

The capture itself holds the tracer's spans as well: `trace_annotation`
is the factory a recording tracer mirrors each span through, so inside any
`jax.profiler` capture the spans sit on their threads' host lines, on the
device planes' own clock, with no anchor needed.

Opt-in and strictly additive: the run's numerics are untouched either
way (the profiler observes; it never reschedules).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple


def trace_annotation(name: str, step_num: Optional[int],
                     args: Dict[str, Any]):
    """The annotation factory a recording `Tracer` mirrors its spans
    through: a `jax.profiler.TraceAnnotation` (a `StepTraceAnnotation`
    numbered `step_num` when given), with the span's arguments as its
    stats. Cheap when no capture runs; inside one, the span lands on its
    thread's line of the host plane, on the device planes' clock."""
    import jax
    if step_num is None:
        return jax.profiler.TraceAnnotation(name, **args)
    return jax.profiler.StepTraceAnnotation(name, step_num=step_num, **args)


class ProfilerSession:
    """One opt-in `jax.profiler` capture, alignable to a Tracer epoch.

    Lifecycle: `start()` before the run, `stop()` after, then
    `device_events(tracer.epoch)` for the merged-timeline events. All
    failure modes (profiler unavailable, no dump written) degrade to an
    empty event list with the error recorded in the meta dict — a broken
    profiler must never fail the run it was watching.
    """

    ANCHOR = "obs_profile_anchor"

    def __init__(self, logdir: Optional[str] = None):
        self.logdir = logdir or tempfile.mkdtemp(prefix="obs_profile_")
        self._anchor_host: Optional[float] = None
        self._start_host: Optional[float] = None
        self._active = False
        self._sess = None            # runtime-level session, when available
        self._error: Optional[str] = None

    def start(self) -> None:
        """Begin capture and stamp the clock anchor.

        Prefers a runtime-level session with the python call tracer OFF:
        at python_tracer_level>0 the profiler records every interpreter
        call, flooding its bounded buffer so badly that the actual XLA
        runtime events get dropped mid-run (observed on CPU: device
        events end seconds before the run does). Falls back to the public
        `jax.profiler.start_trace` when the options API is unavailable —
        `device_events` filters the python spam either way.
        """
        import jax
        try:
            try:
                from jax._src import profiler as _jprof
                opts = _jprof.xla_client.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                self._sess = _jprof.xla_client.profiler.ProfilerSession(opts)
            except Exception:
                self._sess = None
                jax.profiler.start_trace(self.logdir)
            self._start_host = time.perf_counter()
            self._anchor_host = time.perf_counter()
            with jax.profiler.TraceAnnotation(self.ANCHOR):
                pass
            self._active = True
        except Exception as exc:  # profiler unavailable on this backend
            self._error = f"{type(exc).__name__}: {exc}"

    def stop(self) -> None:
        """End capture (writes the dump under `logdir`)."""
        if not self._active:
            return
        import jax
        try:
            if self._sess is not None:
                self._sess.export(self._sess.stop(), self.logdir)
                self._sess = None
            else:
                jax.profiler.stop_trace()
        except Exception as exc:
            self._error = f"{type(exc).__name__}: {exc}"
        self._active = False

    def _newest_dump(self) -> Optional[str]:
        pat = os.path.join(self.logdir, "plugins", "profile", "*",
                           "*.trace.json.gz")
        paths = sorted(glob.glob(pat))
        return paths[-1] if paths else None

    def device_events(self, epoch: float
                      ) -> Tuple[List[Dict[str, Any]], Dict[str, Any]]:
        """Profiler events shifted onto a tracer epoch (µs Chrome events).

        Returns ``(events, meta)``: events ready for
        `Tracer.export_chrome(extra_events=...)`; meta records the event
        count, whether the exact anchor was found (vs the min-timestamp
        fallback), the applied offset, and any capture error —
        `check_trace.py --require-device-lane` validates against it.
        """
        meta: Dict[str, Any] = {"events": 0, "anchor": False,
                                "offset_us": 0.0}
        if self._error:
            meta["error"] = self._error
        path = self._newest_dump()
        if path is None or self._start_host is None:
            return [], meta
        try:
            with gzip.open(path, "rt") as f:
                doc = json.load(f)
        except Exception as exc:
            meta["error"] = f"{type(exc).__name__}: {exc}"
            return [], meta
        raw = doc.get("traceEvents", [])
        # keep well-formed metadata + timestamped events; rebadge pid 0
        # (the host tracer's pid) so device lanes stay a separate process
        kept: List[Dict[str, Any]] = []
        anchor_ts: Optional[float] = None
        min_ts: Optional[float] = None
        for e in raw:
            ph = e.get("ph")
            if ph == "M":
                if "pid" in e and "name" in e:
                    kept.append(dict(e))
                continue
            if ph not in ("X", "i", "C"):
                continue
            name = e.get("name")
            if isinstance(name, str) and name.startswith("$"):
                continue             # python call-tracer spam
            ts = e.get("ts")
            if not isinstance(ts, (int, float)):
                continue
            if ph == "X" and not isinstance(e.get("dur"), (int, float)):
                e = dict(e)
                e["dur"] = 0.0
            kept.append(dict(e))
            min_ts = ts if min_ts is None else min(min_ts, ts)
            if e.get("name") == self.ANCHOR and anchor_ts is None:
                anchor_ts = ts
        if anchor_ts is not None:
            offset = (self._anchor_host - epoch) * 1e6 - anchor_ts
            meta["anchor"] = True
        elif min_ts is not None:
            # fallback: align the first captured event to session start
            offset = (self._start_host - epoch) * 1e6 - min_ts
        else:
            offset = 0.0
        meta["offset_us"] = offset
        out: List[Dict[str, Any]] = []
        for e in kept:
            pid = e.get("pid", 1)
            if pid == 0:
                e["pid"] = 1_000_000
            if "ts" in e and isinstance(e["ts"], (int, float)):
                e["ts"] = e["ts"] + offset
            out.append(e)
        meta["events"] = sum(1 for e in out if e.get("ph") != "M")
        meta["source"] = path
        return out, meta
