"""Worker stack forensics: WHERE a hung training job is stuck.

Parity target: the reference ships py-spy-style stack dumps from stuck
workers through its diagnosis channel
(dlrover/python/elastic_agent/datacollector/cuda_log_collector.py:20 —
the CUDA-log/py-spy collector feeding the master's InferenceChain).
Hang *detection* (agent/monitor/hang.py) says THAT training stalled;
this module says WHERE.

TPU-native mechanism, no external profiler binary:

- the worker calls :func:`enable_stack_dump` at startup (the elastic
  launch path does it automatically when the agent sets
  ``DLROVER_STACK_DUMP_DIR``): ``faulthandler`` is registered on
  ``SIGUSR1`` to append an all-thread traceback to a per-pid file;
- on hang detection the agent calls :func:`trigger_stack_dumps` with
  the worker pids: signal, brief wait, read the files back;
- the dumps ship as ``data_cls="stack"`` DiagnosisReportData; the
  master's hang operator attaches the frames to its hang conclusion so
  the report names the stuck function.
"""

from __future__ import annotations

import os
import signal
import time
from typing import Dict, Iterable, Optional

from dlrover_tpu.common.constants import job_uid, runtime_dir
from dlrover_tpu.common.log import default_logger as logger

ENV_DUMP_DIR = "DLROVER_STACK_DUMP_DIR"
_registered_file = None  # keep the dump file object alive (faulthandler
#                          holds the fd; a GC'd file would break dumps)


def default_dump_dir() -> str:
    return runtime_dir("stacks", job_uid())


def dump_path(pid: int, dump_dir: Optional[str] = None) -> str:
    return os.path.join(dump_dir or default_dump_dir(), f"stack_{pid}.txt")


def enable_stack_dump(dump_dir: Optional[str] = None) -> str:
    """Worker-side: register SIGUSR1 -> all-thread traceback append.

    Returns the dump file path.  Safe to call more than once (the last
    registration wins).  Called automatically by the elastic trainer
    setup when ``DLROVER_STACK_DUMP_DIR`` is set.
    """
    global _registered_file
    import faulthandler

    dump_dir = dump_dir or os.environ.get(ENV_DUMP_DIR) \
        or default_dump_dir()
    os.makedirs(dump_dir, exist_ok=True)
    path = dump_path(os.getpid(), dump_dir)
    f = open(path, "a")
    faulthandler.register(signal.SIGUSR1, file=f, all_threads=True,
                          chain=False)
    if _registered_file is not None:
        try:
            _registered_file.close()
        except OSError:
            pass
    _registered_file = f
    return path


def trigger_stack_dumps(
    pids: Iterable[int],
    dump_dir: Optional[str] = None,
    wait: float = 1.0,
    max_bytes: int = 32768,
) -> Dict[int, str]:
    """Agent-side: SIGUSR1 each pid, wait for the handler to write,
    read back the per-pid dump tails.  Missing/silent pids yield an
    explanatory placeholder instead of being dropped — a worker too
    wedged to handle a signal is itself evidence.

    Only pids whose dump file exists are signaled: the file is created
    by :func:`enable_stack_dump`, so its absence means the worker never
    registered a handler and SIGUSR1's default disposition would KILL
    the process the collector is merely inspecting.
    """
    dump_dir = dump_dir or os.environ.get(ENV_DUMP_DIR) \
        or default_dump_dir()
    marks: Dict[int, int] = {}
    unregistered: list = []
    for pid in pids:
        path = dump_path(pid, dump_dir)
        try:
            marks[pid] = os.path.getsize(path)
        except OSError:
            unregistered.append(pid)
            continue
        try:
            os.kill(pid, signal.SIGUSR1)
        except OSError as e:
            logger.warning("signaling worker %s failed: %s", pid, e)
    deadline = time.time() + wait
    out: Dict[int, str] = {}
    pending = set(marks)
    while pending and time.time() < deadline:
        for pid in list(pending):
            path = dump_path(pid, dump_dir)
            try:
                if os.path.getsize(path) > marks[pid]:
                    pending.discard(pid)
            except OSError:
                pass
        if pending:
            time.sleep(0.05)
    for pid in marks:
        path = dump_path(pid, dump_dir)
        try:
            size = os.path.getsize(path)
            with open(path, "rb") as f:
                f.seek(max(marks[pid], size - max_bytes))
                content = f.read().decode("utf-8", errors="replace")
        except OSError:
            content = ""
        if not content.strip():
            content = (
                f"<no stack dump from pid {pid}: worker did not handle "
                f"SIGUSR1 within {wait}s — process wedged in native "
                f"code>"
            )
        out[pid] = content
    for pid in unregistered:
        out[pid] = (
            f"<no stack dump from pid {pid}: stack dumping not enabled "
            f"in this worker (no dump file; not signaled — SIGUSR1 "
            f"would kill an unregistered process)>"
        )
    return out


def format_stack_report(dumps: Dict[int, str]) -> str:
    parts = []
    for pid, content in sorted(dumps.items()):
        parts.append(f"===== worker pid {pid} =====\n{content.rstrip()}")
    return "\n".join(parts)


def summarize_stacks(dumps: Dict[int, str]) -> str:
    """One line per worker naming the innermost frame of the current
    thread — what goes into the failure REASON (the full dumps travel
    via the diagnosis channel).

    faulthandler format: ``Current thread 0x... (most recent call
    first):`` followed by ``  File "path", line N in func`` frames.
    """
    lines = []
    for pid, content in sorted(dumps.items()):
        frame = ""
        in_current = False
        for raw in content.splitlines():
            line = raw.strip()
            if line.startswith("Current thread"):
                in_current = True
                continue
            if in_current and line.startswith("File "):
                try:
                    path_part, func = line.split(" in ", 1)
                    fname = path_part.split('"')[1].rsplit("/", 1)[-1]
                    lineno = path_part.rsplit("line ", 1)[-1].rstrip(",")
                    frame = f"{func.strip()} ({fname}:{lineno})"
                except (IndexError, ValueError):
                    frame = line
                break
        if not frame:
            # fall back to the first frame of ANY thread / placeholder
            for raw in content.splitlines():
                line = raw.strip()
                if line.startswith("File "):
                    frame = line
                    break
            else:
                frame = "no frames"
        lines.append(f"pid {pid}: {frame}")
    return "; ".join(lines)
