"""What ``run.py`` and the window drivers share: finding the benchmark's
files by name, the compile cache, the device line."""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path):
    """A file of the benchmark as a module, whatever its name holds
    (``metrics/mfu.train.py``)."""
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in os.path.relpath(path, HERE))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def place_compile_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (where ``parallel.place_compile_cache`` puts it when nothing
    else is set), every program kept whatever its compile time or size,
    so that only a checkout's first run of a cell compiles. The size is
    unbounded on purpose: the chip machine's environment caps the cache
    at 192 MiB, less than gpt2_medium's one train step (212 MB)."""
    import jax
    path = os.path.join(ROOT, ".jax-cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_line(devices):
    """The result's ``device`` object, with the peak on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
