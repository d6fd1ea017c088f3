"""Device-memory / HBM accounting (the ROADMAP "device-side memory/HBM
gauges" headroom).

Three signal sources, all recorded into the shared metrics registry at
the engine seams (engine/executor.py) so a snapshot — or a streaming
JSONL "snap" event — carries memory next to latency:

* **Live-buffer census** (``record_step_memory``): ``jax.live_arrays()``
  after each step, split into *scope-resident* bytes (parameters,
  optimizer moments, BN stats — anything a Scope pins between runs) vs
  *transient* bytes (feeds, fetches, in-flight activations), plus a
  high-watermark gauge. This is the host-visible truth of what the
  process is holding on the device right now.

* **Allocator stats**: ``device.memory_stats()`` where the backend
  reports them (``bytes_in_use`` / ``peak_bytes_in_use`` /
  ``bytes_limit`` on TPU) — the allocator's own view, which also sees
  buffers other frameworks in the process allocated.

* **Compile-time peak estimates** (``record_compile_memory``): the
  jitted executable's ``memory_analysis()`` (argument + output + XLA
  temp bytes), recorded once per cache-miss executable — what the step
  *will* need before it runs, the number that explains an OOM at
  compile time.

When a step's live bytes (allocator view where available, census
otherwise) cross ``PADDLE_TPU_MEMORY_PRESSURE_FRAC`` of device memory, a
``memory_pressure`` instant event lands in the trace/sink (edge
triggered — once per excursion, not per step). Device capacity comes
from ``memory_stats()['bytes_limit']``, overridable via
``PADDLE_TPU_DEVICE_MEMORY_BYTES`` for backends that report none.

Gauges (all bytes): ``hbm.live_bytes``, ``hbm.resident_bytes``,
``hbm.transient_bytes``, ``hbm.live_bytes_peak``,
``hbm.device_bytes_in_use``, ``hbm.device_peak_bytes_in_use``,
``hbm.device_bytes_limit``, ``hbm.compile_arg_bytes``,
``hbm.compile_out_bytes``, ``hbm.compile_temp_bytes``,
``hbm.compile_peak_bytes`` (max over executables) + the per-executable
``hbm.compile_peak_bytes_per_exe`` histogram.
"""

import threading

from paddle_tpu import flags

_lock = threading.Lock()
_state = {"live_peak": 0, "compile_peak": 0, "over_pressure": False}


def _obs():
    # Late import: observability/__init__ imports this module.
    from paddle_tpu import observability

    return observability


def reset_peaks():
    """Zero the watermark state (``observability.reset()`` calls this,
    so ``peak_hbm_bytes()`` attributes to what ran since)."""
    with _lock:
        _state["live_peak"] = 0
        _state["compile_peak"] = 0
        _state["over_pressure"] = False


def peak_hbm_bytes():
    """The high-watermark since the last ``reset_peaks()``: max of the
    live-census peak and the compile-time peak estimate — the "how much
    device memory did this need" number the heartbeat reports
    (observability/health.py)."""
    with _lock:
        return max(_state["live_peak"], _state["compile_peak"])


def device_memory_limit(device=None):
    """Device memory capacity in bytes, or None when unknowable: the
    ``PADDLE_TPU_DEVICE_MEMORY_BYTES`` override wins, else the
    allocator's ``bytes_limit``."""
    override = int(flags.get_flag("device_memory_bytes"))
    if override > 0:
        return override
    try:
        import jax

        device = device or jax.local_devices()[0]
        stats = device.memory_stats()
        if stats:
            limit = stats.get("bytes_limit")
            if limit:
                return int(limit)
    except Exception:
        pass
    return None


# -- compile-time estimates ------------------------------------------------
def record_compile_stats(mem_stats, label=None):
    """Record one executable's CompiledMemoryStats (the object
    ``Compiled.memory_analysis()`` returns). Safe on None/odd shapes —
    backends that report nothing record nothing."""
    if mem_stats is None:
        return None
    obs = _obs()
    try:
        arg = int(getattr(mem_stats, "argument_size_in_bytes", 0) or 0)
        out = int(getattr(mem_stats, "output_size_in_bytes", 0) or 0)
        tmp = int(getattr(mem_stats, "temp_size_in_bytes", 0) or 0)
        alias = int(getattr(mem_stats, "alias_size_in_bytes", 0) or 0)
    except Exception:
        return None
    # Aliased (donated) bytes are counted once: they live in the
    # arguments and the outputs reuse them.
    peak = arg + max(0, out - alias) + tmp
    obs.set_gauge("hbm.compile_arg_bytes", arg)
    obs.set_gauge("hbm.compile_out_bytes", out)
    obs.set_gauge("hbm.compile_temp_bytes", tmp)
    obs.observe("hbm.compile_peak_bytes_per_exe", peak)
    with _lock:
        _state["compile_peak"] = max(_state["compile_peak"], peak)
        obs.set_gauge("hbm.compile_peak_bytes", _state["compile_peak"])
    if label:
        obs.event("compile_memory", label=str(label), arg_bytes=arg,
                  out_bytes=out, temp_bytes=tmp, peak_bytes=peak)
    return peak


def record_compile_memory(jitted, args, label=None):
    """AOT-lower the already-compiled jitted callable to read its
    ``memory_analysis()`` and record it. The lower/compile pair reuses
    jax's caches for an executable the engine just ran (a retrace, not a
    recompile); any backend/tracing failure records nothing — telemetry
    must never take down a step that already succeeded."""
    try:
        import jax

        specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        mem = jitted.lower(*specs).compile().memory_analysis()
    except Exception:
        return None
    return record_compile_stats(mem, label=label)


# -- live-buffer census ----------------------------------------------------
def scope_resident_bytes(scope):
    """Bytes of live jax Arrays pinned by ``scope`` (walking the parent
    chain): the parameter/optimizer/BN state the engine keeps resident
    between runs."""
    import jax

    ids, total = set(), 0
    s = scope
    while s is not None:
        for v in s._vars.values():
            if isinstance(v, jax.Array) and id(v) not in ids:
                ids.add(id(v))
                try:
                    total += int(v.nbytes)
                except Exception:
                    continue
        s = s.parent
    return ids, total


def record_step_memory(scope=None, step=None, device=None):
    """The per-step seam: census live device arrays, split resident vs
    transient, refresh the watermark, mirror allocator stats, and raise
    the edge-triggered ``memory_pressure`` event. Returns the gauge dict
    (also recorded into the registry)."""
    obs = _obs()
    try:
        import jax

        live = jax.live_arrays()
    except Exception:
        return None
    resident_ids, resident = (set(), 0)
    if scope is not None:
        try:
            resident_ids, resident = scope_resident_bytes(scope)
        except Exception:
            pass
    total = 0
    for a in live:
        try:
            n = int(a.nbytes)
        except Exception:
            continue
        total += n
    transient = max(0, total - resident)
    obs.set_gauge("hbm.live_bytes", total)
    obs.set_gauge("hbm.resident_bytes", resident)
    obs.set_gauge("hbm.transient_bytes", transient)
    with _lock:
        _state["live_peak"] = max(_state["live_peak"], total)
        live_peak = _state["live_peak"]
    obs.set_gauge("hbm.live_bytes_peak", live_peak)

    in_use = None
    try:
        dev = device or jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:
        stats = None
    if stats:
        in_use = stats.get("bytes_in_use")
        if in_use is not None:
            obs.set_gauge("hbm.device_bytes_in_use", int(in_use))
        peak_in_use = stats.get("peak_bytes_in_use")
        if peak_in_use is not None:
            obs.set_gauge("hbm.device_peak_bytes_in_use", int(peak_in_use))
            with _lock:
                _state["live_peak"] = max(_state["live_peak"],
                                          int(peak_in_use))

    limit = device_memory_limit(device=device)
    if limit:
        obs.set_gauge("hbm.device_bytes_limit", int(limit))
        frac = float(flags.get_flag("memory_pressure_frac"))
        current = int(in_use) if in_use is not None else total
        over = frac > 0 and current > frac * limit
        with _lock:
            crossed = over and not _state["over_pressure"]
            _state["over_pressure"] = over
        if crossed:
            obs.inc("memory.pressure_events")
            obs.event("memory_pressure", live_bytes=current,
                      limit_bytes=int(limit), frac=frac, step=step)
    return {"live_bytes": total, "resident_bytes": resident,
            "transient_bytes": transient, "live_bytes_peak": live_peak}
