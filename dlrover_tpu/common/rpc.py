"""Control-plane RPC transport: a 2-method generic gRPC service.

The master exposes exactly two unary RPCs, ``get`` and ``report`` (the
reference's envelope — reference: dlrover/proto/elastic_training.proto:26-29),
carrying msgpack-encoded typed messages (common/comm.py). We register them
as generic bytes->bytes handlers, so no protoc code generation is required.
"""

import socket
from concurrent import futures
from typing import Callable

import grpc

from dlrover_tpu.common.constants import GRPC

SERVICE_NAME = "dlrover_tpu.Master"


# dlint: disable=DL001 sanctioned helper of the tests and of the rendezvous join; every server of the package binds through bind_server_port / the worker announce idiom, and DL001 blocks new callers
def find_free_port(port: int = 0) -> int:
    """Pick a currently-free port — bind-then-close, i.e. RACY.

    Between this function returning and the caller re-binding, any
    other process can grab the port (the classic TOCTOU port race).
    For tests, and for ONE in-package caller: every server of this
    package binds port 0 ITSELF and reports the kernel-assigned port,
    either via :func:`bind_server_port` (gRPC) or the serving worker's
    announce handshake (serving/remote/worker.py, master/main.py).  The
    exception is ``MasterClient.join_rendezvous``, which offers a port
    for a round's ``jax.distributed`` service: that binder is a worker
    process that cannot announce before its peers dial it.  dlint's
    DL001 checker (``python -m tools.dlint dlrover_tpu``) rejects any
    other in-package call to this function."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("", port))
        return s.getsockname()[1]


def bind_server_port(
    server: "grpc.Server", port: int = 0, host: str = "[::]"
) -> int:
    """Race-free gRPC port binding: ``add_insecure_port`` binds inside
    the server and returns the kernel-assigned port, so ``port=0`` never
    round-trips through a closed socket (the ``find_free_port`` TOCTOU
    race).  Raises instead of returning grpc's silent-failure 0 — a
    master that "started" on an unbound port is the worst failure mode
    (every worker retries against nothing)."""
    bound = server.add_insecure_port(f"{host}:{int(port)}")
    if not bound:
        raise OSError(
            f"could not bind gRPC server to {host}:{port} "
            "(port in use or permission denied)"
        )
    return bound


def addr_connectable(addr: str, timeout: float = 3.0) -> bool:
    if not addr or ":" not in addr:
        return False
    host, port = addr.rsplit(":", 1)
    try:
        with socket.create_connection((host, int(port)), timeout=timeout):
            return True
    except OSError:
        return False


def build_server(
    get_handler: Callable[[bytes, object], bytes],
    report_handler: Callable[[bytes, object], bytes],
    max_workers: int = 32,
) -> grpc.Server:
    """Create a gRPC server with generic get/report bytes handlers."""

    rpc_methods = {
        "get": grpc.unary_unary_rpc_method_handler(
            get_handler,
            request_deserializer=None,
            response_serializer=None,
        ),
        "report": grpc.unary_unary_rpc_method_handler(
            report_handler,
            request_deserializer=None,
            response_serializer=None,
        ),
    }
    handler = grpc.method_handlers_generic_handler(SERVICE_NAME, rpc_methods)
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers),
        options=[
            ("grpc.max_send_message_length", GRPC.MAX_SEND_MESSAGE_LENGTH),
            (
                "grpc.max_receive_message_length",
                GRPC.MAX_RECEIVE_MESSAGE_LENGTH,
            ),
        ],
    )
    server.add_generic_rpc_handlers((handler,))
    return server


class RpcStub:
    """Client stub for the get/report envelope."""

    def __init__(self, addr: str, timeout: float = 30.0,
                 wait_for_ready: bool = False):
        self._addr = addr
        self._timeout = timeout
        self._wait_for_ready = bool(wait_for_ready)
        self._closed = False
        self._channel = grpc.insecure_channel(
            addr,
            options=[
                ("grpc.max_send_message_length", GRPC.MAX_SEND_MESSAGE_LENGTH),
                (
                    "grpc.max_receive_message_length",
                    GRPC.MAX_RECEIVE_MESSAGE_LENGTH,
                ),
                ("grpc.enable_retries", 1),
                # bound the channel's own reconnect backoff well below
                # RetryPolicy's total deadline (default 30s): grpc's
                # 120s default max means a channel that raced through a
                # few refused dials early in an outage would not re-dial
                # again inside the whole retry budget — every app-level
                # retry just replays the cached UNAVAILABLE and a master
                # restart is never observed (seen live: master back up
                # 20s before retry_rpc gave up, all attempts "connection
                # refused")
                ("grpc.initial_reconnect_backoff_ms", 1000),
                ("grpc.max_reconnect_backoff_ms", 5000),
            ],
        )
        self._get = self._channel.unary_unary(
            f"/{SERVICE_NAME}/get",
            request_serializer=None,
            response_deserializer=None,
        )
        self._report = self._channel.unary_unary(
            f"/{SERVICE_NAME}/report",
            request_serializer=None,
            response_deserializer=None,
        )

    def get(self, payload: bytes, timeout: float = 0) -> bytes:
        # wait_for_ready (opt-in): a call issued while the server is
        # down WAITS (bounded by the per-RPC deadline) for the channel
        # to reconnect instead of instantly bouncing UNAVAILABLE off
        # the broken channel — fail-fast calls never re-dial, so an
        # app-level retry loop can exhaust its whole deadline replaying
        # one cached refusal while a restarted master sits reachable.
        # It stays OFF by default: callers with a fallback (the router
        # pump's Brain-backed autoscale, coworker data-path stubs)
        # need the millisecond UNAVAILABLE, not a stall to the full
        # RPC deadline
        return self._get(payload, timeout=timeout or self._timeout,
                         wait_for_ready=self._wait_for_ready)

    def report(self, payload: bytes, timeout: float = 0) -> bytes:
        return self._report(payload, timeout=timeout or self._timeout,
                            wait_for_ready=self._wait_for_ready)

    @property
    def closed(self) -> bool:
        return self._closed

    def close(self) -> None:
        """Close the stub and its gRPC channel — idempotent (a double
        close must not touch the already-closed channel).  The channel
        owns real resources (sockets, poller threads), so releasing it
        here is load-bearing; the fd-hygiene regression test in
        tests/test_common.py pins that behavior."""
        if self._closed:
            return
        self._closed = True
        self._channel.close()
