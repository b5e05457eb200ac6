"""Device profiling: jax-profiler traces + per-kernel timing.

The reference profiles with BEAM VM introspection (emqx_vm.erl) and
system monitors (SURVEY §5 "Tracing/profiling"); the TPU equivalent
is the XLA profiler (TensorBoard-format traces of every kernel) plus
wall-clock timing of the compiled steps themselves. Exposed as:

  - :func:`trace` — context manager writing a profiler trace dir
    (inspect with TensorBoard / xprof);
  - :class:`KernelTimer` — named wall-clock accumulators with
    block-until-ready semantics (per-kernel timing for bench modes
    and the ``profile`` ctl command);
  - ctl integration: ``profile start <dir>`` / ``profile stop`` on a
    live node (registered by Node via :func:`register_ctl`).
"""

from __future__ import annotations

import contextlib
import os
import time
from collections import deque
from typing import Dict, Optional


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


class KernelTimer:
    """Named wall-clock timing for compiled steps.

    Usage — the span yields a capture function; pass it the step's
    output so the timer can block on it (otherwise only async
    DISPATCH time is measured, microseconds instead of the device
    execution)::

        with timer.span("match") as done:
            done(step(x))

    p50/p99 per name; samples ring-buffered (a long-lived node must
    not grow timing lists without bound).
    """

    MAX_SAMPLES = 4096

    def __init__(self) -> None:
        self._samples: Dict[str, deque] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        holder = {}

        def _block(x):
            holder["out"] = x
            return x

        try:
            yield _block
        finally:
            if "out" in holder:
                jax.block_until_ready(holder["out"])
            self.record(name, (time.perf_counter() - t0) * 1000.0)

    def record(self, name: str, ms: float) -> None:
        self._samples.setdefault(
            name, deque(maxlen=self.MAX_SAMPLES)).append(ms)

    def stats(self) -> Dict[str, Dict[str, float]]:
        import numpy as np

        out = {}
        for name, xs in self._samples.items():
            arr = np.asarray(xs)
            out[name] = {
                "count": int(arr.size),
                "p50_ms": float(np.percentile(arr, 50)),
                "p99_ms": float(np.percentile(arr, 99)),
                "total_ms": float(arr.sum()),
            }
        return out

    def reset(self) -> None:
        self._samples.clear()


_active: Dict[str, Optional[str]] = {"dir": None}


def register_ctl(ctl) -> None:
    """``profile start <dir> | stop | kernels`` on a live node."""
    import json

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

    def _profile(args):
        import jax

        if not args:
            trc = getattr(getattr(ctl, "node", None), "tracing", None)
            loops = ("on" if trc is not None and trc.profiler.running
                     else "off")
            return (f"profiling: "
                    f"{'on -> ' + _active['dir'] if _active['dir'] else 'off'}"
                    f" | loops: {loops}")
        if args[0] == "loops":
            return _profile_loops(args[1:])
        if args[0] == "start":
            if _active["dir"] is not None:
                return f"already tracing to {_active['dir']}"
            logdir = args[1] if len(args) > 1 else "/tmp/emqx_tpu_trace"
            try:
                jax.profiler.start_trace(logdir)
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
        if args[0] == "kernels":
            return json.dumps(timer.stats(), indent=2)
        raise ValueError(f"bad subcommand: {args[0]}")

    ctl.register_command(
        "profile", _profile,
        "start [dir] | stop | kernels | "
        "loops start|stop|show|dump [path]")


#: process-wide timer the router/bench feed (opt-in: spans only
#: recorded where instrumented)
timer = KernelTimer()
