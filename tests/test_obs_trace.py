"""The program's spans in the profiler's own trace, and its named scopes in
the compiled chunk program.

The contracts under test:

  1. ANNOTATIONS — a recording tracer mirrors each span into a running
     `jax.profiler` capture: on a CPU capture of a tiny traced run the
     spans sit on the host plane, nested per thread lane, the chunk prep's
     `prep_device_wait` inside `ctl_build` / `batch_stage` on the prefetch
     worker's lane, and Σ `prep_stall` there equals `RunResult.prep_stall_s`.
     The null tracer (telemetry off) opens no annotation.
  2. SCOPES — `obs.hlo.op_scopes` finds the round step's six named scopes
     in a compiled chunk program, and every top-level instruction of the
     round loop's body carries one, loop bookkeeping aside.
  3. MEMORY — `obs.memory.device_peak_bytes` reads the fullest device's
     buffers plus its reserved region.
"""
import glob
import subprocess
import sys
from collections import Counter
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import engine as eng
from repro.core import fedsim, pairzero
from repro.models import registry
from repro.obs import hlo as ohlo
from repro.obs import memory as omem

REPO = Path(__file__).resolve().parents[1]


def _pipeline(cfg, pz, make_pipeline):
    return make_pipeline(vocab=cfg.vocab_size, n_clients=pz.n_clients,
                         batch=2, seq=16)


# ---------------------------------------------------------------------------
# 1. Spans as profiler annotations
# ---------------------------------------------------------------------------

def test_tracer_mirrors_spans_through_its_annotation_factory():
    calls = []

    class Ann:
        def __init__(self, name, step, args):
            self.rec = (name, step, dict(args))

        def __enter__(self):
            calls.append(("enter",) + self.rec)

        def __exit__(self, *exc):
            calls.append(("exit", self.rec[0]))
            return False

    tr = obs.Tracer()
    tr.annotate = Ann
    with tr.step_span("chunk", 3, chunk=3):
        with tr.span("dispatch", chunk=3):
            pass
    with tr.annotation("prep_stall", chunk=4):
        pass
    # a span argument named `step` (the checkpoint spans') is no step
    with tr.span("ckpt_write", step=8):
        pass
    assert calls == [("enter", "chunk", 3, {"chunk": 3}),
                     ("enter", "dispatch", None, {"chunk": 3}),
                     ("exit", "dispatch"), ("exit", "chunk"),
                     ("enter", "prep_stall", None, {"chunk": 4}),
                     ("exit", "prep_stall"),
                     ("enter", "ckpt_write", None, {"step": 8}),
                     ("exit", "ckpt_write")]
    # annotation() alone records no span; span/step_span do
    assert [s["name"] for s in tr.spans()] == ["dispatch", "chunk",
                                               "ckpt_write"]
    # the null tracer hands out one shared no-op context
    nt = obs.NULL_TRACER
    assert nt.annotation("a", x=1) is nt.span("b") is nt.step_span("c", 0)
    assert obs.Telemetry.on().tracer.annotate is obs.profile.trace_annotation


@pytest.mark.parametrize("on", [False, True], ids=["null", "recording"])
def test_annotations_open_only_with_a_recording_tracer(
        tiny_model, make_pz, make_pipeline, monkeypatch, on):
    opened = Counter()

    class Counting:
        def __init__(self, name, **kw):
            opened[name] += 1

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Counting)
    pz = make_pz(scheme="solution", rounds=6)
    fedsim.run(tiny_model, pz, _pipeline(tiny_model, pz, make_pipeline),
               rounds=6, engine="scan", chunk_rounds=3,
               telemetry=obs.Telemetry.on() if on else None)
    if on:
        assert opened["chunk"] == 2 and opened["prep_device_wait"] >= 2
    else:
        assert not opened


def _host_lines(path):
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                lines.append([(e.name, int(e.start_ns),
                               int(e.start_ns + e.duration_ns),
                               {str(k): str(v) for k, v in e.stats})
                              for e in line.events])
    return lines


SPAN_NAMES = {"chunk", "dispatch", "prep_stall", "chunk_prep", "ctl_build",
              "batch_stage", "prep_device_wait", "batch_gen", "h2d_put",
              "metrics_flush", "hooks_boundary"}


def _parent(spans, child):
    """The innermost span of the lane that contains `child`."""
    _, a, b, _ = child
    outer = [s for s in spans if s is not child and s[1] <= a and b <= s[2]]
    return min(outer, key=lambda s: s[2] - s[1])[0] if outer else None


def test_profiler_capture_holds_the_program_spans(
        tiny_model, make_pz, make_pipeline, tmp_path):
    pz = make_pz(scheme="solution", rounds=9)
    tel = obs.Telemetry.on()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res = fedsim.run(tiny_model, pz,
                         _pipeline(tiny_model, pz, make_pipeline), rounds=9,
                         engine="scan", chunk_rounds=3, telemetry=tel)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    lanes = [[e for e in line if e[0] in SPAN_NAMES]
             for line in _host_lines(path)]
    lanes = [ln for ln in lanes if ln]
    driver = [ln for ln in lanes if any(e[0] == "chunk" for e in ln)]
    worker = [ln for ln in lanes if any(e[0] == "chunk_prep" for e in ln)
              and not any(e[0] == "chunk" for e in ln)]
    assert len(driver) == 1 and len(worker) == 1
    driver, worker = driver[0], worker[0]

    # one step annotation per chunk, numbered by chunk index
    steps = sorted(int(e[3]["step_num"]) for e in driver if e[0] == "chunk")
    assert steps == [0, 1, 2]
    # spans nest per lane: contained or disjoint, never crossing
    for lane in lanes:
        for x in lane:
            for y in lane:
                crossing = x[1] < y[1] < x[2] < y[2]
                assert not crossing, (x[:3], y[:3])
    # the kicked preps' device waits sit inside ctl_build / batch_stage on
    # the worker's lane, as do the batch generation and the transfers
    waits = [e for e in worker if e[0] == "prep_device_wait"]
    assert {_parent(worker, w) for w in waits} == {"ctl_build",
                                                   "batch_stage"}
    for name in ("batch_gen", "h2d_put"):
        kids = [e for e in worker if e[0] == name]
        assert kids and {_parent(worker, k) for k in kids} <= {
            "ctl_build", "batch_stage"}
    # dispatch nests in its chunk; the driver's stall equals the scalar
    assert {_parent(driver, e) for e in driver if e[0] == "dispatch"} \
        == {"chunk"}
    stall_s = sum(b - a for n, a, b, _ in driver if n == "prep_stall") * 1e-9
    assert stall_s == pytest.approx(res.prep_stall_s, abs=1e-3)
    assert tel.tracer.total_s("prep_stall") == res.prep_stall_s


# ---------------------------------------------------------------------------
# 2. Named scopes in the compiled chunk program
# ---------------------------------------------------------------------------

# top-level instructions that do no work on the device: the loop's carried
# tuple and its constants
NO_WORK = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast"}
# the scan's own ops in the loop body: its counter, the slices of the
# per-round inputs and the updates of the stacked outputs
LOOP_BOOKKEEPING = {"add", "dynamic_slice", "dynamic_update_slice"}


def _chunk_text(cfg, pz, make_pipeline, unroll):
    pipe = _pipeline(cfg, pz, make_pipeline)
    params = registry.init_params(jax.random.key(0), cfg, jnp.float32)
    exp = fedsim.Experiment(cfg, pz, pipe, 4, engine="scan")
    channel = exp.channel_model.realize(pz.seed ^ 0xC4A7, 4, pz.n_clients)
    schedule = exp.transport.make_schedule(channel, pz)
    trace = eng.build_trace(schedule, pz, 0, 4, channel=channel)
    batches = eng.stack_batches(pipe, 0, 4)
    executor = eng.ScanExecutor(pairzero.make_zo_step(cfg, pz),
                                unroll=unroll)
    return executor.aot_compiled(ohlo.specs_of(params),
                                 ohlo.specs_of(trace.ctl),
                                 ohlo.specs_of(batches)).as_text()


def test_op_scopes_cover_the_round_body(tiny_model, make_pz, make_pipeline):
    pz = make_pz(scheme="solution", rounds=4)
    text = _chunk_text(tiny_model, pz, make_pipeline, unroll=1)
    scopes = ohlo.op_scopes(text)
    assert set(scopes.values()) - {None} == set(ohlo.SCOPES)

    comps, entry = ohlo.computations(text)
    rounds = [rhs for _, rhs in comps[entry]
              if ohlo.opcode(rhs) == "while"
              and ohlo.op_name(rhs) == "jit(chunk)/while"]
    assert len(rounds) == 1
    body = rounds[0].split("body=", 1)[1].split(",")[0].lstrip("%")
    unscoped = []
    for name, rhs in comps[body]:
        assert name in scopes
        if scopes[name] is not None:
            continue
        meta = ohlo.op_name(rhs)
        if ohlo.opcode(rhs) in NO_WORK:
            continue
        if meta is None:
            # copy insertion's copies of the carried state carry no
            # metadata; nor do the CPU backend's single-op `wrapped_*`
            # fusions
            if ohlo.opcode(rhs) == "copy" or name.startswith("wrapped_"):
                continue
        else:
            head, _, leaf = meta.rpartition("/")
            if head == "jit(chunk)/while/body" and leaf in LOOP_BOOKKEEPING:
                continue
        unscoped.append((name, meta))
    assert not unscoped


def test_op_scopes_reads_nested_and_wrapped_scopes():
    text = "\n".join([
        "HloModule m",
        "",
        "%fused (p: f32[2]) -> f32[2] {",
        "  %p = f32[2]{0} parameter(0)",
        '  ROOT %n = f32[2]{0} negate(%p), metadata={op_name="x/theta_plus"}',
        "}",
        "",
        "%body (t: (s32[], f32[2])) -> (s32[], f32[2]) {",
        "  %t = (s32[], f32[2]{0}) parameter(0)",
        '  %f = f32[2]{0} fusion(%t), kind=kLoop, calls=%fused, '
        'metadata={op_name="jit(chunk)/forward_blocks/vmap(lm_head_ce)/dot"}',
        "  ROOT %r = (s32[], f32[2]{0}) tuple(%f)",
        "}",
        "",
        "ENTRY %main (a: f32[2]) -> f32[2] {",
        "  %a = f32[2]{0} parameter(0)",
        "  %w = (s32[], f32[2]{0}) while(%a), condition=%body, body=%body, "
        'metadata={op_name="jit(chunk)/theta_update/while"}',
        "  %c = f32[2]{0} copy(%a)",
        "  %d = f32[2]{0} copy(%c)",
        '  %e = f32[2]{0} negate(%d), metadata={op_name="jit(chunk)/'
        'ota_aggregate/neg"}',
        '  ROOT %g = f32[2]{0} add(%a, %a), metadata={op_name="jit(chunk)/add"}',
        "}",
    ])
    scopes = ohlo.op_scopes(text)
    # fusion bodies are not top level; loop bodies are. Copies without
    # metadata take a scope from their neighbours; parameters do not
    assert scopes == {"a": None, "w": "theta_update", "g": None, "t": None,
                      "f": "lm_head_ce", "r": None, "c": "ota_aggregate",
                      "d": "ota_aggregate", "e": "ota_aggregate"}


# ---------------------------------------------------------------------------
# 3. The peak memory reading
# ---------------------------------------------------------------------------

class _Dev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("stats,want", [
    ([{"peak_bytes_in_use": 10, "peak_bytes_reserved": 5},
      {"peak_bytes_in_use": 12, "peak_bytes_reserved": 0}, None], 15),
    ([{"peak_bytes_in_use": 7}], 7),
    ([None, {}], None)])
def test_device_peak_bytes_reads_the_fullest_device(monkeypatch, stats,
                                                    want):
    monkeypatch.setattr(omem.jax, "devices",
                        lambda: [_Dev(st) for st in stats])
    assert omem.device_peak_bytes() == want


@pytest.mark.parametrize("inside", [True, False], ids=["nested", "stray"])
def test_check_trace_places_the_prep_spans(tmp_path, inside):
    tr = obs.Tracer()
    for name in ("chunk", "dispatch", "chunk_prep", "prep_stall",
                 "metrics_flush"):
        with tr.span(name, chunk=0):
            pass
    with tr.span("ctl_build"):
        if inside:
            with tr.span("prep_device_wait"):
                pass
    if not inside:
        with tr.span("prep_device_wait"):
            pass
    path = tmp_path / "trace.json"
    tr.export_chrome(str(path))
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_trace.py"), str(path)],
        capture_output=True, text=True)
    assert (proc.returncode == 0) == inside, proc.stdout
    if not inside:
        assert "lies in no ctl_build or batch_stage span" in proc.stdout
