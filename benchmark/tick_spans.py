"""The host's time around one decode step, from the traced seconds of a
serving run: the scheduler's spans, the runtime's own launch events and the
device's executions of the decode program.

``telemetry/spans.py::span`` holds a ``TraceAnnotation`` while open, so in a
trace every span of the scheduler is an event of the host plane named by its
kind, its fields the event's stats (``benchmark/host_spans.py``).  This file
reads, in one pass over the ``.xplane.pb`` and once a path:

- the spans ``tick``, ``decode_step`` (``step``, ``active``), ``readback``
  and its child ``readback_wait`` (``step``, ``for_step``: the tick whose
  ``decode_step`` this read drains), ``loop_idle`` (the loop asleep) and
  ``prefill`` (``tokens``, ``stalled``, ``padded_tokens``);
- the runtime's host events ``DoEnqueueProgram`` (a program handed to the
  device; stat ``run_id``) and ``CompleteCallbacks`` (the host learns that a
  run is over; the same ``run_id``);
- the events of the line ``XLA Modules`` of the first device plane, one an
  executed program, each with its ``run_id``.

**The device's plane is not on the host's clock.**  In this repository's
traces a module event starts 0.35-2.15 ms BEFORE the host enqueued it, another
offset in every trace (PERF.md, PR 38): the two planes agree on durations and not on instants, so a device
stamp is never subtracted from a host stamp here.  From the device plane a
step takes its DURATION, found by ``run_id``; every instant is the host's:

- a ``decode_step`` span launched the first ``DoEnqueueProgram`` that starts
  at or after the span's start and before the next ``decode_step`` span's;
- the device begins a run when it is enqueued and the run before it is over
  (``begin = max(enqueue end, the previous run's end)``, over every program in
  the order of the enqueues: on the sync path the device is idle and this is
  the enqueue's end; on the async ring a step queues behind the one before)
  and ends it ``duration`` later.  What lies between the enqueue and the
  device's first instruction is thereby counted in the return lag; the
  ``CompleteCallbacks`` event bounds it (``notice``, in the run's ``notes``);
- the ``readback_wait`` / ``readback`` of a step are those whose
  ``for_step`` is the span's ``step``; on the ring they lie in a later tick.

An interval cut by the trace's edge has no event: a step that lacks a part
is dropped and counted.  A CPU trace has neither module nor launch events:
the pairs then hold the spans alone, and the two readers that need the
device return None.

Its own cache, keyed by the path: ``host_spans._by_kind`` holds two entries,
and a second tuple of kinds would make every metric parse the file again.
A hand-made trace (``.json.gz``) lists beside ``"spans"`` the module events
under ``"modules"`` (``[{"name", "run_id", "start_s", "end_s"}, ...]``, on a
clock of their own) and the runtime's under ``"launches"`` and
``"completions"`` (``[{"run_id", "start_s", "end_s"}, ...]``, on the spans'
clock).  Under a program that emits no ``readback_wait``, as a parent commit
does not, every reader returns None: without ``loop_idle`` and ``for_step``
nothing here can be told apart.
"""
import bisect
import gzip
import json
import statistics

from benchmark import trace

KINDS = ("tick", "decode_step", "readback", "readback_wait", "loop_idle", "prefill")
LAUNCH, COMPLETION = "DoEnqueueProgram", "CompleteCallbacks"
MODULE_HINT = "decode"
# the identity the span pairing is held to, and the range it is sound in
IDENTITY, IDENTITY_RANGE = "tick_parts_sum_over_phases", (0.95, 1.05)

_LOADED: dict = {}


def load(path: str) -> dict:
    """``{"spans": {kind: [fields + start_s, end_s]}, "modules": [...],
    "launches": [...], "completions": [...]}`` of the trace at ``path``, each
    list in the order of its starts."""
    if path not in _LOADED:
        _LOADED[path] = _read(path)
    return _LOADED[path]


def _read(path: str) -> dict:
    spans = {kind: [] for kind in KINDS}
    runtime = {LAUNCH: [], COMPLETION: []}
    modules = []
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as fp:
            listed = json.load(fp)
        for kind in KINDS:
            spans[kind] = [dict(s) for s in listed.get("spans", {}).get(kind, [])]
        modules = [dict(m) for m in listed.get("modules", [])]
        runtime[LAUNCH] = [dict(e) for e in listed.get("launches", [])]
        runtime[COMPLETION] = [dict(e) for e in listed.get("completions", [])]
    else:
        from jax.profiler import ProfileData

        planes = list(ProfileData.from_file(path).planes)
        device = min((p.name for p in planes if trace.DEVICE_PLANE.match(p.name)),
                     default=None)
        for plane in planes:
            if plane.name == trace.HOST_PLANE:
                for line in plane.lines:
                    for event in line.events:
                        into = spans.get(event.name, runtime.get(event.name))
                        if into is not None:
                            into.append(_timed(event, event.stats))
            elif plane.name == device:
                for line in plane.lines:
                    if line.name == trace.MODULES_LINE:
                        modules += [
                            _timed(event, {"name": event.name,
                                           "run_id": dict(event.stats).get("run_id")})
                            for event in line.events]
    for found in (*spans.values(), *runtime.values(), modules):
        found.sort(key=lambda s: s["start_s"])
    return {"spans": spans, "modules": modules, "launches": runtime[LAUNCH],
            "completions": runtime[COMPLETION]}


def _timed(event, fields) -> dict:
    start = event.start_ns / 1e9
    return dict(fields, start_s=start, end_s=start + event.duration_ns / 1e9)


def device_runs(found: dict) -> dict:
    """``{run_id: run}`` for every enqueued program whose module event is in
    the trace: ``begin_s`` and ``end_s`` on the HOST's clock (the device takes
    a run up when it is enqueued and the run before it is over), ``name``,
    ``notice_s`` (where the trace has it: the host learns of the end) and
    ``clock_offset_s`` (enqueue end - the module's own start stamp: how far
    the device's plane lies behind the host's, and the launch's latency)."""
    modules = {m["run_id"]: m for m in found["modules"] if m.get("run_id") is not None}
    noticed = {c["run_id"]: c["start_s"] for c in found["completions"]
               if c.get("run_id") is not None}
    runs, free_at = {}, float("-inf")
    for launch in sorted(found["launches"], key=lambda e: e["end_s"]):
        module = modules.get(launch.get("run_id"))
        if module is None:
            continue  # cut by the trace's edge: the device's queue is unknown here
        begin = max(launch["end_s"], free_at)
        free_at = begin + module["end_s"] - module["start_s"]
        runs[launch["run_id"]] = {
            "name": module["name"], "begin_s": begin, "end_s": free_at,
            "launch_start_s": launch["start_s"],
            "notice_s": noticed.get(launch["run_id"]),
            "clock_offset_s": launch["end_s"] - module["start_s"],
        }
    return runs


def ticks(path: str):
    """``(paired steps, dropped)`` or None under a program without
    ``readback_wait``.  A paired step holds, in seconds: ``phases`` (the
    ``decode_step`` span + the ``readback`` span), ``extra_reads``
    (``readback`` end - ``readback_wait`` end) and, where the trace has the
    runtime's launches and the device's modules, ``launch_lag`` (the device
    begins - ``decode_step`` start), ``module`` (the step's duration on the
    device), ``return_lag`` (``readback_wait`` end - the device ends),
    ``notice`` (the host learns of the end - the device ends) and
    ``clock_offset``.  ``dropped``: ``decode_step`` spans that lack a part,
    and decode modules no span launched."""
    found = load(path)
    spans = found["spans"]
    if not spans["readback_wait"]:
        return None
    runs = device_runs(found)
    by_launch = sorted(
        (run["launch_start_s"], run_id) for run_id, run in runs.items())
    starts = [start for start, _ in by_launch]
    waits = {int(s["for_step"]): s for s in reversed(spans["readback_wait"])}
    reads = {int(s["for_step"]): s for s in reversed(spans["readback"])
             if "for_step" in s}
    steps = spans["decode_step"]
    paired, taken = [], set()
    for i, step in enumerate(steps):
        wait, read = waits.get(int(step["step"])), reads.get(int(step["step"]))
        before = steps[i + 1]["start_s"] if i + 1 < len(steps) else float("inf")
        at = bisect.bisect_left(starts, step["start_s"])
        run = runs[by_launch[at][1]] if at < len(starts) and starts[at] < before else None
        if run is not None and MODULE_HINT not in run["name"]:
            run = None  # the span's first launch is no decode step: not paired
        if wait is None or read is None or (runs and run is None):
            continue
        one = {
            "step": int(step["step"]),
            "phases": (step["end_s"] - step["start_s"]
                       + read["end_s"] - read["start_s"]),
            "extra_reads": read["end_s"] - wait["end_s"],
        }
        if run is not None:
            taken.add(by_launch[at][1])
            one.update(
                launch_lag=run["begin_s"] - step["start_s"],
                module=run["end_s"] - run["begin_s"],
                return_lag=wait["end_s"] - run["end_s"],
                clock_offset=run["clock_offset_s"],
            )
            if run["notice_s"] is not None:
                one["notice"] = run["notice_s"] - run["end_s"]
        paired.append(one)
    stray = sum(MODULE_HINT in m["name"] and m.get("run_id") not in taken
                for m in found["modules"])
    return paired, len(steps) - len(paired) + stray


def _median_ms(values):
    return statistics.median(values) * 1e3 if values else None


def part_ms_p50(run, part: str):
    """Median over the run's paired steps of one part, in ms; files the
    count dropped and the identity in the run's ``notes``."""
    path = run.notes.get("xplane")
    found = ticks(path) if path else None
    if found is None:
        return None
    paired, dropped = found
    run.notes["tick_spans"] = dict(
        paired=len(paired), dropped=dropped,
        # a host that holds the tokens, or hears of the end, before the
        # device is through: the pairing or the device's queue is wrong
        unsound=sum(t.get("return_lag", 0.0) < 0 or t.get("notice", 0.0) < 0
                    for t in paired),
        **{f"{key}_ms_p50": _median_ms([t[key] for t in paired if key in t])
           for key in ("notice", "clock_offset")},
    )
    ratio = parts_over_phases(paired)
    if ratio is not None:
        run.notes[IDENTITY] = ratio
    return _median_ms([t[part] for t in paired if part in t])


def parts_over_phases(paired: list):
    """Median of (launch lag + the module + return lag + extra reads) over
    median of (``decode_step`` span + ``readback`` span): 1 but for the few
    microseconds between the two spans when every step met its own reads
    (the sum runs from the span's start to its ``readback``'s end whatever
    run was found, so it holds the SPAN pairing and not the device's: that
    is what ``unsound`` is for); None where no step has a module."""
    whole = [t for t in paired if "module" in t]
    if not whole:
        return None
    parts = statistics.median(
        t["launch_lag"] + t["module"] + t["return_lag"] + t["extra_reads"]
        for t in whole)
    return parts / statistics.median(t["phases"] for t in whole)


def tick_gap_ms_p50(run):
    """Median of ``tick`` start - the previous ``tick``'s end over
    consecutive PRODUCTIVE ticks (a ``decode_step`` or a ``prefill`` under
    each) with no ``loop_idle`` between them: the loop's own overhead
    between two back-to-back ticks."""
    spans = _spans_of(run)
    if spans is None:
        return None
    productive = {int(s["step"]) for kind in ("decode_step", "prefill")
                  for s in spans[kind]}
    idle = [s["start_s"] for s in spans["loop_idle"]]
    gaps = []
    for a, b in zip(spans["tick"], spans["tick"][1:]):
        if int(a["step"]) in productive and int(b["step"]) in productive:
            at = bisect.bisect_left(idle, a["end_s"])
            if at == len(idle) or idle[at] >= b["start_s"]:
                gaps.append(b["start_s"] - a["end_s"])
    return _median_ms(gaps)


def prefill_stalled_gap_pct(run):
    """100 x sum of ``prefill.stalled`` / sum of ``decode_step.active``: of
    the token gaps of the traced seconds, the share with a prefill in it."""
    spans = _spans_of(run)
    if spans is None or any("stalled" not in s for s in spans["prefill"]):
        return None
    gaps = sum(int(s["active"]) for s in spans["decode_step"])
    if not gaps:
        return None
    return 100.0 * sum(int(s["stalled"]) for s in spans["prefill"]) / gaps


def prefill_padding_pct(run):
    """100 x (1 - sum of ``tokens`` / sum of ``padded_tokens``) over the
    traced ``prefill`` spans: the share of the prefills' tokens that is
    padding, rows and positions alike."""
    spans = _spans_of(run)
    if spans is None:
        return None
    calls = [s for s in spans["prefill"] if "padded_tokens" in s]
    padded = sum(int(s["padded_tokens"]) for s in calls)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(int(s["tokens"]) for s in calls) / padded)


def _spans_of(run):
    path = run.notes.get("xplane")
    spans = load(path)["spans"] if path else None
    return spans if spans and spans["readback_wait"] else None
