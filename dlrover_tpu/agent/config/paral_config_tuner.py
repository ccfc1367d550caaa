"""ParalConfigTuner: master ParallelConfig -> trainer hot-reload file.

Parity target: reference dlrover/python/elastic_agent/config/
paral_config_tuner.py:30-80 — the agent polls the master's mutable
``ParallelConfig`` (dataloader workers / batch size, optimizer lr, and —
TPU addition — a mesh re-plan hint) and writes it to a JSON file the
trainer re-reads between steps (ElasticDataLoader.load_config).  RPC
stays out of the training loop; the file is the hot-reload boundary.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Optional

from dlrover_tpu.common import comm
from dlrover_tpu.common.constants import ConfigPath
from dlrover_tpu.common.log import default_logger as logger


def paral_config_path() -> str:
    return ConfigPath.paral_config()


def write_paral_config(config: comm.ParallelConfig,
                       path: Optional[str] = None) -> None:
    path = path or paral_config_path()
    payload = {
        "dataloader": dataclasses.asdict(config.dataloader),
        "optimizer": dataclasses.asdict(config.optimizer),
        "mesh_shape": dict(config.mesh_shape),
        "restart": bool(config.restart),
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def read_paral_config(path: Optional[str] = None) -> Optional[dict]:
    path = path or paral_config_path()
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


class ParalConfigTuner:
    """Polls the master and refreshes the config file on version bumps."""

    def __init__(self, client, interval: float = 30.0,
                 path: Optional[str] = None):
        self._client = client
        self._interval = interval
        self._path = path or paral_config_path()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_versions = (-1, -1)

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="paral-config-tuner"
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

    def check_once(self) -> Optional[comm.ParallelConfig]:
        """Fetch the config; write the file when a version advanced."""
        try:
            config = self._client.get_paral_config()
        except Exception as e:
            logger.warning("paral config poll failed: %s", e)
            return None
        if config is None:
            return None
        versions = (config.dataloader.version, config.optimizer.version)
        if versions == self._last_versions:
            return config
        self._last_versions = versions
        write_paral_config(config, self._path)
        logger.info(
            "paral config updated: dataloader v%s batch_size=%s workers=%s",
            config.dataloader.version, config.dataloader.batch_size,
            config.dataloader.num_workers,
        )
        return config

    def _loop(self) -> None:
        while not self._stop.wait(self._interval):
            self.check_once()
