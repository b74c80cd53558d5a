"""What every cell shares: files found by name, the device guard, the
compile cache, the compile watch, quantiles and the result line.

Nothing here knows a configuration, a traffic mix, a metric or a model
architecture by name: ``BENCHMARK.json`` and the configuration files name
them, and each lives in a file of its own (``configs/<config>.json``,
``traffic/<mix>.json``, ``metrics/<metric>.py``, ``drivers/<kind>.py``,
``archs/<model_type>.py``, ``reference/<model_type>.py``).
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import pathlib
import sys
from typing import Any, Callable, Dict, List, Optional, Sequence

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SPEC_FILE = ROOT / "BENCHMARK.json"
OUT = BENCH / "out"                     # traces; listed in .gitignore
CACHE_DIR = ROOT / ".jax_cache"         # fixed path inside the checkout


class NoDevice(SystemExit):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


# --- files found by name ------------------------------------------------------

def load_spec() -> Dict[str, Any]:
    return json.loads(SPEC_FILE.read_text())


def find_cell(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    names = ", ".join(c["name"] for c in spec["workloads"])
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                     f"(have: {names})")


def load_config(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    entry = next(c for c in spec["configs"] if c["name"] == name)
    return json.loads((ROOT / entry["file"]).read_text())


def load_traffic(name: str) -> Dict[str, Any]:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def load_module(path: pathlib.Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    return load_module(BENCH / "drivers" / f"{kind}.py", f"driver_{kind}")


def arch(model_type: str):
    """What the serving driver and its readers need of one architecture
    (a configuration file's Hugging Face ``model_type``):
    ``program_config(c)``, ``init_leaf(path, z, c)``,
    ``prefill_flops(c, plen)``, ``decode_flops(c, ctx)``,
    ``page_bytes(c, page_size)``, ``DECODE_KERNELS`` and
    ``small(c, control=False)``."""
    return load_module(BENCH / "archs" / f"{model_type}.py",
                       f"arch_{model_type}")


def reference(model_type: str):
    """The plain reference of one architecture, with its control."""
    return load_module(BENCH / "reference" / f"{model_type}.py",
                       f"reference_{model_type}")


def cell_metrics(spec: Dict[str, Any], cell: str, section: str
                 ) -> List[Dict[str, Any]]:
    """The metrics of ``section`` (``end_to_end`` or ``per_layer``) that
    this cell reports: those listing it, and end-to-end ones with no list
    (``setup_s``).  Every per-layer metric lists its cells."""
    return [m for m in spec[section]
            if cell in m.get("workloads", [cell] if section == "end_to_end"
                             else [])]


def read_per_layer(spec: Dict[str, Any], cell: str, ctx: Dict[str, Any]
                   ) -> Dict[str, Dict[str, Any]]:
    """Run each per-layer metric's reader (``metrics/<name>.py``,
    ``read(ctx) -> float | None``).  A reader that finds nothing to read
    returns None and the metric is left out of the line."""
    out = {}
    for m in cell_metrics(spec, cell, "per_layer"):
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             "metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# --- device ------------------------------------------------------------------

def require_chips(n: int) -> Dict[str, Any]:
    """The device block of the result line; exits non-zero without a
    TPU or with fewer chips than ``n``.  No CPU fallback."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoDevice(f"bench: no TPU: JAX finds {len(devices)} "
                       f"{dev.platform} device(s) and no accelerator")
    if len(devices) < n:
        raise NoDevice(f"bench: the cell needs {n} chips, JAX finds "
                       f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": n}


def memory_peak_bytes(n: int) -> int:
    """Peak bytes in use on the fullest of the first ``n`` devices."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n]]
    return int(max(peaks))


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache/`` at the checkout's root (a fixed path,
    so a later run finds every program again).  Every program is cached,
    however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Counts lowerings (each is a trace and, unless cached, a compile)
    and XLA compile seconds through ``jax.monitoring`` while active."""

    def __init__(self):
        self.lowerings = 0
        self.compile_s = 0.0

    def _on_event(self, event, duration, **_):
        if event == _LOWER_EVENT:
            self.lowerings += 1
        elif event == _COMPILE_EVENT:
            self.compile_s += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


# --- arithmetic over the whole window ----------------------------------------

def quantile(values: Sequence[float], q: float) -> float:
    """The ``q`` quantile (0..1) of all ``values``, linear between order
    statistics (numpy's default).  Raises on an empty sample."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of an empty sample")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# --- the result line -----------------------------------------------------------

def emit(result: Dict[str, Any], checks: Dict[str, Dict[str, float]],
         out=sys.stdout, err=sys.stderr) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result line as the last line of standard
    output, with ``checks`` as its last key."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=err)
    err.flush()
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), file=out)
    out.flush()


def flops_share(flops: float, seconds: float, peak_flops: float
                ) -> Optional[float]:
    """Percent of ``peak_flops`` that ``flops`` in ``seconds`` reach;
    None where nothing was timed."""
    if seconds <= 0 or flops <= 0:
        return None
    return 100.0 * flops / seconds / peak_flops


def bytes_share(nbytes: float, seconds: float, peak_bps: float
                ) -> Optional[float]:
    if seconds <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / seconds / peak_bps


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"bench/peaks.json has no peaks for device kind "
                       f"{device_kind!r}")
    return table[device_kind]


def seed_words(seed: int) -> Callable[[int], int]:
    """Independent 31-bit integers from any whole-number seed (larger
    than 32 bits too): ``words(i)`` is the i-th."""
    import numpy as np
    state = np.random.SeedSequence(int(seed)).generate_state(16)

    def words(i: int) -> int:
        return int(state[i]) & 0x7FFFFFFF
    return words
