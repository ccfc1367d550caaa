"""Agent-side training monitor: runtime-metrics file -> master SpeedMonitor.

Parity target: reference dlrover/python/elastic_agent/monitor/
training.py:77-134 (``TorchTrainingMonitor`` — the trainer process writes a
metrics file; the agent tails it and reports the global step to the
master, which feeds the SpeedMonitor and straggler logic).  The file
crosses the trainer->agent process boundary without any RPC inside the
training loop.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

from dlrover_tpu.common.constants import ConfigPath
from dlrover_tpu.common.log import default_logger as logger


def metrics_path() -> str:
    return ConfigPath.runtime_metrics()


def write_runtime_metrics(
    step: int,
    timestamp: Optional[float] = None,
    elapsed_per_step: float = 0.0,
    path: Optional[str] = None,
) -> None:
    """Called by the trainer each step (cheap, atomic via rename)."""
    path = path or metrics_path()
    payload = {
        "step": int(step),
        "timestamp": timestamp or time.time(),
        "elapsed_time_per_step": float(elapsed_per_step),
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
    except OSError as e:  # never break the training loop over metrics
        logger.warning("runtime-metrics write failed: %s", e)


def read_runtime_metrics(path: Optional[str] = None) -> Optional[dict]:
    path = path or metrics_path()
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


class TrainingMonitor:
    """Tails the runtime-metrics file and reports global steps upstream.

    Also the data source for hang detection: ``last_progress_time`` is the
    wall-clock time the global step last advanced.
    """

    def __init__(
        self,
        client,
        interval: Optional[float] = None,
        path: Optional[str] = None,
    ):
        self._client = client
        if interval is None:
            interval = float(os.getenv("DLROVER_MONITOR_INTERVAL", "15"))
        self._interval = interval
        self._path = path or metrics_path()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.last_step = -1
        self.last_progress_time = time.time()

    def start(self) -> None:
        if self._thread is not None:
            return
        # a fresh monitor must not inherit a stale file from a previous run
        try:
            os.remove(self._path)
        except OSError:
            pass
        self.last_progress_time = time.time()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="training-monitor"
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None
            # the file is this job's (its name carries the job id):
            # nobody else would ever overwrite or remove it
            try:
                os.remove(self._path)
            except OSError:
                pass

    def check_once(self) -> Optional[int]:
        data = read_runtime_metrics(self._path)
        if not data:
            return None
        step = int(data.get("step", -1))
        if step > self.last_step:
            self.last_step = step
            self.last_progress_time = time.time()
            try:
                self._client.report_global_step(
                    step,
                    timestamp=data.get("timestamp", 0.0),
                    elapsed=data.get("elapsed_time_per_step", 0.0),
                )
            except Exception as e:
                logger.warning("global-step report failed: %s", e)
        return step

    def seconds_without_progress(self) -> float:
        return time.time() - self.last_progress_time

    def reset_progress_clock(self) -> None:
        """Re-arm after a worker restart (new compile isn't a hang).

        Also drops the pre-restart step high-water mark and the stale
        metrics file: a checkpoint-resumed trainer legitimately starts
        below the pre-crash step, and its first write must count as
        progress (not be masked by ``step > last_step``).
        """
        try:
            os.remove(self._path)
        except OSError:
            pass
        self.last_step = -1
        self.last_progress_time = time.time()

    def _loop(self) -> None:
        while not self._stop.wait(self._interval):
            self.check_once()
