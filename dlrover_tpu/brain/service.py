"""Standalone Brain service: cross-job optimization over gRPC.

Parity target: the reference's Brain deployment
(dlrover/go/brain/cmd/brain/main.go + pkg/server/ — a SEPARATE service
that masters query for resource plans, backed by the job-history
datastore; the processor/optimizer registry lives behind one RPC
surface).

TPU-native shape: the same get/report envelope every other service here
uses (common/rpc.py — msgpack bodies, no new proto).  Endpoints:

- ``optimize``   — job meta + current speed samples -> a resource plan
  (worker count), combining the live curve with the persistent history
  (the LocalOptimizer heuristics running on the Brain side);
- ``suggest`` / ``observe`` — per-job hyperparameter search sessions
  (GP + EI, warm-started from the job's prior trials);
- ``record_*``  — masters push speeds/trials/outcomes for future jobs.

Run standalone::

    python -m dlrover_tpu.brain.service --port 23500 \
        --db /shared/history.db

Masters keep working without a Brain (their in-process optimizer is the
same code); pointing them at one upgrades decisions from single-job to
fleet-level history.
"""

from __future__ import annotations

import argparse
import os
import tempfile
import threading
from typing import Any, Dict, Optional

from dlrover_tpu.brain.datastore import JobHistoryStore
from dlrover_tpu.brain.hpsearch import BayesianOptimizer, Param
from dlrover_tpu.brain.serving import ServingScalePolicy, ServingSignal
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.rpc import RpcStub, bind_server_port, build_server
from dlrover_tpu.common.serialize import dumps, loads
from dlrover_tpu.master.resource.local_optimizer import LocalOptimizer
from dlrover_tpu.master.resource.optimizer import SpeedSample


class BrainService:
    """Serve optimization queries over the shared history store."""

    def __init__(self, store: JobHistoryStore, port: int = 0):
        self._store = store
        self._searches: Dict[str, BayesianOptimizer] = {}
        self._lock = threading.Lock()
        self._server = build_server(self._handle_get, self._handle_report)
        # one copy of the race-free-bind policy: rpc.bind_server_port
        # (atomic pick/bind, raises on grpc's silent-failure 0)
        self.port = bind_server_port(self._server, port)

    def start(self) -> None:
        self._server.start()
        logger.info("Brain service on port %s", self.port)

    def stop(self, close_store: bool = False) -> None:
        """``close_store`` only when this service owns the store (the
        CLI does); an embedder sharing the store keeps it usable."""
        self._server.stop(grace=1.0)
        if close_store:
            self._store.close()

    # -- dispatch ---------------------------------------------------------
    def _handle_get(self, request: bytes, context) -> bytes:
        msg = loads(request)
        kind = msg.get("kind")
        if kind == "optimize":
            return dumps(self._optimize(msg))
        if kind == "suggest":
            return dumps(self._suggest(msg))
        if kind == "speed_history":
            return dumps(self._store.speed_history(msg.get("job_name")))
        if kind == "serving_plan":
            return dumps(self._serving_plan(msg))
        raise ValueError(f"unknown brain query {kind!r}")

    def _handle_report(self, request: bytes, context) -> bytes:
        msg = loads(request)
        kind = msg.get("kind")
        if kind == "record_job":
            self._store.record_job(
                msg["job_uuid"], msg.get("job_name", ""),
                msg.get("config") or {},
            )
        elif kind == "record_speed":
            self._store.record_speed(
                msg["job_uuid"], int(msg["worker_num"]),
                float(msg["speed"]),
            )
        elif kind == "observe":
            self._observe(msg)
        elif kind == "record_serving":
            self._store.ensure_job(msg["job_uuid"], msg.get("job_name", ""))
            self._store.record_serving(
                msg["job_uuid"], int(msg.get("replicas", 1)),
                float(msg.get("queue_depth", 0.0)),
                float(msg.get("ttft_seconds", 0.0)),
                float(msg.get("tokens_per_sec", 0.0)),
            )
        elif kind == "finish_job":
            self._store.finish_job(msg["job_uuid"], msg.get("status", ""))
        else:
            raise ValueError(f"unknown brain report {kind!r}")
        return dumps({"ok": True})

    # -- optimize ---------------------------------------------------------
    def _optimize(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """The reference's ProcessOptimizeJobs: plan worker resources
        from the live samples + fleet history."""
        samples = [
            SpeedSample(worker_num=int(s["worker_num"]),
                        speed=float(s["speed"]))
            for s in msg.get("samples", [])
        ]
        opt = LocalOptimizer(
            node_unit=int(msg.get("node_unit", 1)),
            min_workers=int(msg.get("min_workers", 1)),
            max_workers=int(msg.get("max_workers", 0)),
            history_store=self._store,
            job_name=msg.get("job_name", ""),
        )
        plan = opt.generate_opt_plan(
            samples, int(msg.get("current_workers", 1))
        )
        workers = None
        group = plan.node_group_resources.get("worker")
        if group is not None:
            workers = group.count
        return {"worker_count": workers}

    # -- serving scale plans ----------------------------------------------
    def _serving_plan(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Replica-count plan from router load samples (the serving twin
        of ``optimize``; policy: brain/serving.ServingScalePolicy)."""
        policy = ServingScalePolicy(
            min_replicas=int(msg.get("min_replicas", 1)),
            max_replicas=int(msg.get("max_replicas", 8)),
            queue_high=float(msg.get("queue_high", 4.0)),
            queue_low=float(msg.get("queue_low", 0.5)),
            ttft_high=msg.get("ttft_high"),
        )
        samples = [
            ServingSignal.from_dict(s) for s in msg.get("samples", [])
        ]
        return {
            "replica_count": policy.decide(
                samples, int(msg.get("current_replicas", 1))
            )
        }

    # -- hyperparameter search sessions ----------------------------------
    def _session_locked(self, msg: Dict[str, Any]) -> BayesianOptimizer:
        """Get/create the per-job optimizer. Caller holds ``self._lock``."""
        job_uuid = msg["job_uuid"]
        bo = self._searches.get(job_uuid)
        if bo is None:
            space = [
                Param(
                    name=p["name"],
                    low=float(p.get("low", 0.0)),
                    high=float(p.get("high", 1.0)),
                    choices=p.get("choices"),
                    integer=bool(p.get("integer", False)),
                )
                for p in msg.get("space", [])
            ]
            bo = BayesianOptimizer(space, seed=int(msg.get("seed", 0)))
            bo.warm_start(
                self._store.prior_trials(msg.get("job_name") or None)
            )
            self._searches[job_uuid] = bo
        return bo

    def _suggest(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        # the lock must span the optimizer call itself: concurrent
        # observe() mutates the trial history suggest() fits over
        with self._lock:
            return {"params": self._session_locked(msg).suggest()}

    def _observe(self, msg: Dict[str, Any]) -> None:
        with self._lock:
            bo = self._searches.get(msg["job_uuid"])
            if bo is not None:
                # dlint: disable=DL007 bo is the in-process search session built by _session_locked, not a BrainClient (the duck-typed fan-out smears the two); no RPC runs here, and the lock MUST span observe() — it mutates the trial history suggest() fits over
                bo.observe(msg["params"], float(msg["value"]))
        # an unregistered session's trials must still be reachable by
        # NAMED warm starts later (prior_trials joins the jobs table)
        self._store.ensure_job(msg["job_uuid"], msg.get("job_name", ""))
        self._store.record_trial(
            msg["job_uuid"], dict(msg["params"]), float(msg["value"])
        )


class BrainClient:
    """Master-side client (reference BrainClient, brain/client.py)."""

    def __init__(self, addr: str, timeout: float = 30.0):
        self._stub = RpcStub(addr, timeout=timeout)

    def optimize(self, **query) -> Optional[int]:
        out = loads(self._stub.get(dumps({"kind": "optimize", **query})))
        return out.get("worker_count")

    def speed_history(self, job_name: str = "") -> Dict[int, float]:
        return {
            int(k): v for k, v in loads(self._stub.get(dumps(
                {"kind": "speed_history", "job_name": job_name or None}
            ))).items()
        }

    def serving_plan(self, **query) -> Optional[int]:
        out = loads(
            self._stub.get(dumps({"kind": "serving_plan", **query}))
        )
        return out.get("replica_count")

    def record_serving(self, **report) -> None:
        self._stub.report(dumps({"kind": "record_serving", **report}))

    def suggest(self, **query) -> Dict[str, float]:
        return loads(
            self._stub.get(dumps({"kind": "suggest", **query}))
        )["params"]

    def observe(self, **report) -> None:
        self._stub.report(dumps({"kind": "observe", **report}))

    def record_job(self, **report) -> None:
        self._stub.report(dumps({"kind": "record_job", **report}))

    def record_speed(self, **report) -> None:
        self._stub.report(dumps({"kind": "record_speed", **report}))

    def finish_job(self, **report) -> None:
        self._stub.report(dumps({"kind": "finish_job", **report}))

    def close(self) -> None:
        self._stub.close()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--port", type=int, default=23500)
    p.add_argument("--db", default=os.path.join(
        tempfile.gettempdir(), "dlrover_tpu_brain.db"))
    args = p.parse_args(argv)
    service = BrainService(JobHistoryStore(args.db), port=args.port)
    service.start()
    try:
        threading.Event().wait()
    except KeyboardInterrupt:  # pragma: no cover
        pass
    finally:
        service.stop(close_store=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
