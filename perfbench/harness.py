"""What every driver shares: the run's context, the model configuration as
the program's ``LlamaConfig``, the profiler window, the compile counter.

Nothing here knows a cell, a traffic mix or a metric by name.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# everything a run writes (traces, checkpoints' disk side) goes here,
# inside the checkout and git-ignored; the compile cache has its own
# fixed directory (<checkout>/.jax_cache, utils/compile_cache.py)
WORK = os.path.join(ROOT, ".perfbench_work")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def merged(base: dict, override: Optional[dict]) -> dict:
    """``base`` with ``override`` laid over it, one level of nesting deep
    enough for the ``rehearse`` blocks of configuration and traffic files."""
    out = dict(base)
    for k, v in (override or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merged(out[k], v)
        else:
            out[k] = v
    return out


@dataclasses.dataclass
class Context:
    """One run of one cell."""

    cell: dict                 # the entry of BENCHMARK.json "workloads"
    config: dict               # perfbench/configs/<config>.json, as run
    traffic: dict              # perfbench/traffic/<traffic>.json, as run
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float             # perf_counter at process start
    devices: List[Any]
    profiler: "Profiler"
    compiles: "CompileCounter"

    @property
    def chips(self) -> int:
        return len(self.devices)

    @property
    def work_dir(self) -> str:
        path = os.path.join(WORK, self.cell["name"])
        os.makedirs(path, exist_ok=True)
        return path

    def say(self, what: str) -> None:
        """Progress on standard error (never on the result's stream)."""
        import sys

        print(f"perfbench [{time.perf_counter() - self.t_start:7.1f}s] "
              f"{what}", file=sys.stderr, flush=True)

    def span(self, name: str):
        """A host span in the profiler's own trace when this run traces;
        nothing at all when it does not (end-to-end runs pay no tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)


def llama_config(config: dict, max_seq_len: int, scan_layers: bool):
    """The published sizes as the program's ``LlamaConfig`` keywords."""
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    dep = config["deployment"]
    if config.get("tie_word_embeddings") or config.get("sliding_window"):
        raise ValueError("tied embeddings / sliding windows are not what "
                         "perfbench/reference.py computes")
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(dep["compute_dtype"]),
        param_dtype=jnp.dtype(dep["param_dtype"]),
        scan_layers=scan_layers,
        remat=bool(dep.get("remat", False)),
        remat_policy=dep.get("remat_policy", "nothing_saveable"),
        tie_embeddings=False,
    )


class CompileCounter:
    """Counts every backend compile request of this process (a persistent-
    cache hit is still a program that was not warm), by JAX's own
    monitoring event.  ``inside(a, b)`` is how many fell in a window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.stamps: List[tuple] = []     # (perf_counter, program name)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.stamps.append((time.perf_counter(),
                                str(kw.get("fun_name", "?"))))

    def inside(self, t0: float, t1: float) -> List[str]:
        """Names of the programs compiled (or loaded) in a window."""
        return [name for t, name in self.stamps if t0 <= t <= t1]


class Profiler:
    """One traced sub-window per run.  ``start`` opens the profiler and a
    ``bench.window`` span; ``stop`` closes both; ``result`` reduces the trace."""

    def __init__(self, out_dir: str, cpu_rehearsal: bool):
        self.out_dir = out_dir
        self.cpu_rehearsal = cpu_rehearsal
        self.active = False
        self.reduced: Optional[dict] = None
        self._window = None
        self._stopped = False
        self.t_start = self.t_stop = None   # perf_counter, around the span
        self.overhead_s = 0.0      # spent opening and closing the profiler

    def start(self) -> None:
        import shutil

        import jax

        t0 = time.perf_counter()
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans are our own, only
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.t_start = time.perf_counter()
        self.overhead_s += self.t_start - t0
        self.active = True

    def stop(self) -> None:
        """Close the window and the profiler; the reduction waits for
        :meth:`result`, after the measured window."""
        import jax

        self.t_stop = time.perf_counter()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.overhead_s += time.perf_counter() - self.t_stop
        self.active = False
        self._stopped = True

    def result(self) -> Optional[dict]:
        """The reduced trace (None if this run traced nothing)."""
        from perfbench import trace_reduce

        if self.active:
            self.stop()
        if self.reduced is None and self._stopped:
            path = trace_reduce.newest_xplane(self.out_dir)
            events = trace_reduce.extract(
                path, cpu_rehearsal=self.cpu_rehearsal)
            self.reduced = trace_reduce.reduce_events(events)
            self.reduced["xplane"] = path
            self.reduced["host_clock"] = [self.t_start, self.t_stop]
        return self.reduced


def device_report(devices: List[Any], trace: Optional[dict]) -> Dict[str, Any]:
    import jax

    peaks = [((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
             for d in devices]
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": jax.device_count(), "memory_peak_bytes": max(peaks)}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out
