"""Device profiling: jax-profiler traces and their reading.

The reference profiles with BEAM VM introspection (emqx_vm.erl) and
system monitors (SURVEY §5 "Tracing/profiling"); the TPU equivalent
is the XLA profiler (TensorBoard-format traces of every kernel).
Exposed as:

  - :func:`trace` — context manager writing a profiler trace dir
    (inspect with TensorBoard / xprof);
  - :func:`report` — what an operator needs from such a trace
    without TensorBoard: the device's busy share, and the longest
    device idle gaps, each with the host annotations
    (``emqx/<stage>``, telemetry.py) that overlap it and the share
    of it in which a batch was on the device path (between its
    ``emqx/enqueue`` mark and the end of its ``emqx/fetch``), and
    per batch what the host adds before the chip starts and after
    it is done. The annotations and the device's ``XLA Ops`` line
    sit in one ``xplane.pb``, so they share a clock;
  - ctl integration: ``profile start <dir>`` / ``profile stop`` /
    ``profile report <dir>`` on a live node (registered by Node via
    :func:`register_ctl`).
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
from typing import Dict, List, Optional

from emqx_tpu.telemetry import ENQUEUE_ANN, union_s


#: the in-checkout cache location, resolved from the package (never
#: the working directory — the path is part of the cache's key, so a
#: directory that moves never hits)
_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def compile_cache_dir() -> str:
    """Where the persistent compile cache lives: wherever
    ``JAX_COMPILATION_CACHE_DIR`` places it from outside, else
    ``<repo>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _REPO_CACHE


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    First-compile of a padding bucket costs seconds on the TPU; the
    cache makes it once per machine, not once per process — the
    analogue of the reference shipping precompiled BEAM files. With
    ``JAX_COMPILATION_CACHE_DIR`` set JAX already reads the directory
    from it and no directory is set in code. Called at ``Node``
    start-up and by the benches; safe to call repeatedly."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _REPO_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


@contextlib.contextmanager
def trace(logdir: str):
    """XLA profiler trace over the enclosed block (device + host)."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


#: where the trace keeps host annotations and device operations
HOST_PLANE = "/host:CPU"
DEVICE_PLANE_PREFIX = "/device:"
DEVICE_OP_LINE = "XLA Ops"
#: every annotation this program writes starts with this
ANNOTATION_PREFIX = "emqx/"
#: what a gap's remainder is called: host time in no publish stage
OUTSIDE = "loop outside publish stages"


def read_trace(trace_dir: str) -> tuple:
    """The newest ``*.xplane.pb`` under ``trace_dir`` as ``(ops,
    annotations)``: device operations ``(start_s, end_s, name)`` off
    the ``XLA Ops`` line of every ``/device:`` plane, and this
    program's host annotations ``(start_s, end_s, name, seq)`` off
    the ``/host:CPU`` plane (one line per thread)."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(sorted(found)[-1])
    ops: List[tuple] = []
    anns: List[tuple] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == DEVICE_OP_LINE:
                    ops.extend(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                        for ev in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        a = ev.start_ns * 1e-9
                        anns.append(
                            (a, a + ev.duration_ns * 1e-9, ev.name,
                             next((v for k, v in ev.stats
                                   if k == "seq"), None)))
    return ops, anns


#: the stage whose end closes a batch's stretch on the device path
FETCH_ANN = "emqx/fetch"


def path_stretches(anns: List[tuple]) -> List[tuple]:
    """``(start_s, end_s, seq)`` of every batch the trace holds whole
    on the device path: from the start of its ``emqx/enqueue`` mark
    (telemetry.PublishSpan.enqueue: its first device call) to the end
    of its ``emqx/fetch`` stage, sorted by start. A batch the trace
    cut at either end is left out."""
    enq: Dict[object, float] = {}
    fetched: Dict[object, float] = {}
    for a, b, name, seq in anns:
        if seq is None:
            continue
        if name == ENQUEUE_ANN:
            enq[seq] = min(a, enq.get(seq, a))
        elif name == FETCH_ANN:
            fetched[seq] = max(b, fetched.get(seq, b))
    return sorted((a, fetched[seq], seq) for seq, a in enq.items()
                  if fetched.get(seq, a) > a)


def _spread(xs: List[float]) -> dict:
    """Count, median and p99 (nearest rank), milliseconds."""
    xs = sorted(xs)
    n = len(xs)
    if not n:
        return {"count": 0, "median": None, "p99": None}
    return {"count": n, "median": xs[n // 2] * 1e3,
            "p99": xs[min(n - 1, int(0.99 * n))] * 1e3}


def path_latencies(ops: List[tuple], stretches: List[tuple]) -> dict:
    """What the host adds around the chip's work, per batch on the
    device path: ``enqueue_to_first_op_ms`` (the ``emqx/enqueue``
    mark → the start of the first device operation inside the batch's
    stretch: launch latency) and ``device_done_to_fetch_ms`` (the end
    of the last device operation inside the stretch → the end of
    ``emqx/fetch``: the copy back, ``device_get``'s return, the
    executor thread getting the interpreter back), each as ``{"count",
    "median", "p99"}``. A batch with no device operation inside its
    stretch is counted in ``no_device_op``, never as 0. Where
    stretches overlap (a pipeline more than one deep) an operation
    inside two of them counts for both: the trace does not say whose
    it is."""
    ops = sorted((a, b) for a, b, _n in ops)
    starts = [a for a, _b in ops]
    first, done, empty = [], [], 0
    for lo, hi, _seq in stretches:
        i = bisect.bisect_left(starts, lo)
        last = None
        while i < len(ops) and ops[i][0] < hi:
            if ops[i][1] <= hi:
                if last is None:
                    first.append(ops[i][0] - lo)
                    last = ops[i][1]
                elif ops[i][1] > last:
                    last = ops[i][1]
            i += 1
        if last is None:
            empty += 1
        else:
            done.append(hi - last)
    return {"batches": len(stretches), "no_device_op": empty,
            "enqueue_to_first_op_ms": _spread(first),
            "device_done_to_fetch_ms": _spread(done)}


def attribute(ops: List[tuple], anns: List[tuple],
              top: int = 5) -> dict:
    """Device busy share, the ``top`` longest device idle gaps with
    the host annotations that overlap each, and the device path's
    latencies.

    Returns ``{"window_s", "device_busy_s", "device_busy_share",
    "device_ops", "annotations", "gaps", "device_path"}``; a gap is
    ``{"start_s"`` (from the first event of the trace), ``"seconds",
    "before"`` (the op that ended it), ``"in_flight"`` (the share of
    the gap in which some batch stood between its ``emqx/enqueue``
    mark and the end of its ``emqx/fetch``: a gap inside is launch,
    transfer or wake-up latency, a gap outside is the host giving the
    chip nothing; None where the trace holds no such mark),
    ``"host"``: ``[[name, seq, share of the gap], ...]`` largest
    first, closed by ``[OUTSIDE, None, share]`` — the part of the gap
    no annotation covers``}``. ``device_path`` is
    :func:`path_latencies` (None without a mark). Without a device op
    (the CPU backend has no device plane) the busy share is None."""
    if not ops and not anns:
        raise ValueError("the trace holds neither a device operation "
                         "nor a host annotation of this program")
    t_lo = min(x[0] for x in ops + anns)
    t_hi = max(x[1] for x in ops + anns)
    out = {"window_s": t_hi - t_lo, "device_ops": len(ops),
           "annotations": len(anns), "device_busy_s": None,
           "device_busy_share": None, "gaps": [], "device_path": None}
    if not ops:
        return out
    marked = any(name == ENQUEUE_ANN for _a, _b, name, _s in anns)
    stretches = path_stretches(anns) if marked else []
    if marked:
        out["device_path"] = path_latencies(ops, stretches)
    busy = union_s([(a, b) for a, b, _n in ops])
    out["device_busy_s"] = busy
    out["device_busy_share"] = busy / (t_hi - t_lo)
    gaps = []
    end = None
    for a, b, name in sorted(ops):
        if end is not None and a > end:
            gaps.append((a - end, end, a, name))
        end = b if end is None else max(end, b)
    gaps.sort(reverse=True)
    for length, g0, g1, name in gaps[:top]:
        by: Dict[tuple, float] = {}
        clipped = []
        for a, b, ann, seq in anns:
            lo, hi = max(a, g0), min(b, g1)
            if hi > lo:
                by[(ann, seq)] = by.get((ann, seq), 0.0) + hi - lo
                clipped.append((lo, hi))
        host = [[ann, seq, secs / length]
                for (ann, seq), secs in
                sorted(by.items(), key=lambda kv: -kv[1])]
        host.append([OUTSIDE, None, 1.0 - union_s(clipped) / length])
        out["gaps"].append({
            "start_s": g0 - t_lo, "seconds": length,
            "before": name.partition(" = ")[0].strip()[:80],
            "in_flight": union_s(
                (max(a, g0), min(b, g1)) for a, b, _seq in stretches
                if min(b, g1) > max(a, g0)) / length if marked else None,
            "host": host})
    return out


def report(trace_dir: str, top: int = 5) -> dict:
    """:func:`attribute` over :func:`read_trace`."""
    ops, anns = read_trace(trace_dir)
    return attribute(ops, anns, top)


def render_report(rep: dict, per_gap: int = 8) -> str:
    """:func:`report` as the text ``ctl profile report`` prints."""
    lines = [f"trace: {rep['window_s']:.3f}s, {rep['device_ops']} "
             f"device ops, {rep['annotations']} host annotations"]
    if rep["device_busy_s"] is None:
        lines.append("device: no device plane in this trace")
        return "\n".join(lines)
    lines.append(f"device: busy {rep['device_busy_s']:.6f}s = "
                 f"{100.0 * rep['device_busy_share']:.3f}% of the "
                 f"trace")
    for i, g in enumerate(rep["gaps"], 1):
        fl = g.get("in_flight")
        lines.append(f"gap {i}: {g['seconds'] * 1e3:.3f}ms at "
                     f"+{g['start_s']:.3f}s, ended by {g['before']}"
                     + ("" if fl is None else
                        f", a batch on the device path "
                        f"{100.0 * fl:.1f}% of it"))
        # the largest annotations, then always the uncovered rest
        for name, seq, share in g["host"][:-1][:per_gap - 1] \
                + g["host"][-1:]:
            tag = f" seq={seq}" if seq is not None else ""
            lines.append(f"    {100.0 * share:6.2f}%  {name}{tag}")
    path = rep.get("device_path")
    if path is None:
        lines.append("device path: no emqx/enqueue mark in this trace")
        return "\n".join(lines)
    lines.append(f"device path: {path['batches']} batches from "
                 f"emqx/enqueue to the end of emqx/fetch, "
                 f"{path['no_device_op']} with no device op inside")
    for key, what in (
            ("enqueue_to_first_op_ms", "enqueue -> first device op"),
            ("device_done_to_fetch_ms", "device done -> fetch returned")):
        sp = path[key]
        if sp["count"]:
            lines.append(f"    {what}: {sp['count']} batches, median "
                         f"{sp['median']:.3f}ms, p99 {sp['p99']:.3f}ms")
    return "\n".join(lines)


_active: Dict[str, Optional[str]] = {"dir": None}


def register_ctl(ctl) -> None:
    """``profile start <dir> | stop | report <dir>`` on a live
    node."""

    def _profile_loops(args):
        # the per-loop sampling profiler (tracing.LoopProfiler):
        # collapsed Python stacks over the front-door loop threads,
        # the ingress executor, and the main loop
        trc = getattr(getattr(ctl, "node", None), "tracing", None)
        if trc is None:
            return "loop profiler unavailable (no node)"
        prof = trc.profiler
        if not args or args[0] == "show":
            state = "running" if prof.running else "stopped"
            head = f"loop profiler: {state}, {prof.samples} samples"
            stacks = prof.collapsed(top=20)
            return head + ("\n" + stacks if stacks else "")
        if args[0] == "start":
            if not prof.start():
                return "loop profiler already running"
            return (f"loop profiler sampling every "
                    f"{prof.interval_ms:g}ms (front-door loops + "
                    f"ingress executor + main loop)")
        if args[0] == "stop":
            if not prof.stop():
                return "loop profiler not running"
            return f"loop profiler stopped ({prof.samples} samples)"
        if args[0] == "dump":
            text = prof.collapsed()
            if len(args) > 1:
                with open(args[1], "w") as f:
                    f.write(text + "\n")
                return f"collapsed stacks written to {args[1]}"
            return text or "(no samples)"
        raise ValueError(f"bad subcommand: loops {args[0]}")

    def _rebuild_stages(tel) -> str:
        """A background compaction by stage (telemetry.REBUILD_STAGES):
        one line, empty while none has run."""
        parts = [f"{s} {st['count']} x p50 {st['p50_ms']:.3f}ms "
                 f"sum {st['sum_ms']:.3f}ms"
                 for s, st in tel.rebuild_stats().items() if st["count"]]
        return "\nrebuild stages: " + "; ".join(parts) if parts else ""

    def _profile(args):
        import jax

        if not args:
            trc = getattr(getattr(ctl, "node", None), "tracing", None)
            loops = ("on" if trc is not None and trc.profiler.running
                     else "off")
            out = (f"profiling: "
                   f"{'on -> ' + _active['dir'] if _active['dir'] else 'off'}"
                   f" | loops: {loops}")
            tel = getattr(getattr(ctl, "node", None), "telemetry", None)
            if tel is not None and tel.enabled:
                # automaton rebuilds, the one thing timed here that
                # is no publish stage (telemetry's `rebuild` stage)
                st = tel.stage_stats()["rebuild"]
                out += (f"\nrebuild: {st['count']} "
                        f"p50 {st['p50_ms']:.3f}ms "
                        f"p99 {st['p99_ms']:.3f}ms "
                        f"sum {st['sum_ms']:.3f}ms")
                out += _rebuild_stages(tel)
            return out
        if args[0] == "loops":
            return _profile_loops(args[1:])
        if args[0] == "start":
            if _active["dir"] is not None:
                return f"already tracing to {_active['dir']}"
            logdir = args[1] if len(args) > 1 else "/tmp/emqx_tpu_trace"
            # a trace on a serving node: the Python function tracer
            # off (it would time every call of the busy loop), host
            # tracer at the level that keeps TraceAnnotation events —
            # the emqx/<stage> annotations `report` reads
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            try:
                jax.profiler.start_trace(logdir, profiler_options=opts)
            except Exception as e:
                # an unwritable dir must not strand a half-started
                # trace with _active["dir"] unset (the next `start`
                # would raise "already started" from inside jax with
                # no way out but a restart): best-effort stop any
                # partial trace, keep the registry consistent, and
                # hand the operator the reason as text
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                return f"profile start failed: {e}"
            _active["dir"] = logdir
            return f"tracing to {logdir} (view with TensorBoard)"
        if args[0] == "stop":
            if _active["dir"] is None:
                return "not tracing"
            out = _active["dir"]
            _active["dir"] = None
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                # a stop whose trace jax never actually started (or
                # that died mid-trace) must come back as operator
                # text, not a raised traceback; the registry is
                # already cleared so the next `start` works
                return f"profile stop failed: {e}"
            return f"trace written to {out}"
        if args[0] == "report":
            logdir = args[1] if len(args) > 1 else "/tmp/emqx_tpu_trace"
            try:
                out = render_report(report(logdir))
            except (OSError, ValueError) as e:
                return f"profile report failed: {e}"
            tel = getattr(getattr(ctl, "node", None), "telemetry", None)
            if tel is not None and tel.enabled:
                # what an `emqx/rebuild` stretch of the trace was made of
                out += _rebuild_stages(tel)
            return out
        raise ValueError(f"bad subcommand: {args[0]}")

    ctl.register_command(
        "profile", _profile,
        "start [dir] | stop | report [dir] | "
        "loops start|stop|show|dump [path]")
