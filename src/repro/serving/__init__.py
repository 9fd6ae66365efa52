"""Serving subsystem — the request loop above ``InferenceEngine``.

``Server`` accepts single-image requests for many networks out of one
process; ``MicroBatcher`` coalesces concurrent requests into one
padded-batch dispatch with **mid-flight admission** (a new request joins
the forming batch whenever its padded power-of-two shape still fits;
batch-1 traffic keeps the paper's single-image fast path); a shared
``DeviceScheduler`` interleaves every network's dispatches onto the
accelerator oldest-deadline-first, so a slow network cannot head-of-line
block a fast one. ``EngineCache`` LRU-caches built engines keyed by
(network, input_size, device, dtype) and reuses tuned plans across
variants; ``StreamSession`` (``Server.open_stream``) serves fixed-rate
frame streams over per-stream engine leases.

The wire tier puts a socket in front of the same surface:
``ServerEndpoint`` speaks a length-prefixed binary framing
(``protocol.py``), ``AsyncClient`` is the asyncio caller —
``await client.classify(net, image)`` returns logits bitwise-equal to
``engine.run``, and typed rejections re-raise client-side.

Public API: configure with frozen ``ServingOptions`` (server-wide) and
``RequestOptions`` (per call); every submit path returns a ``Ticket``
(``.result(timeout)`` / ``.cancel()`` / ``.done()`` + latency stamps).
The typed-exception hierarchy (``Rejected`` > ``Overloaded`` /
``DeadlineExceeded`` / ``CircuitOpen``, plus the wire-tier
``ProtocolError`` / ``BadRequest`` / ``RemoteError``) is exported here —
clients never import from ``resilience``/``request`` internals. See
docs/serving.md ("Front door", "Overload & failure semantics"). ``spans``
records spans on the served request path (docs/serving.md,
"Observability").
"""
from repro.serving import spans  # noqa: F401
from repro.serving.batcher import MicroBatcher, bucket  # noqa: F401
from repro.serving.client import AsyncClient  # noqa: F401
from repro.serving.engine_cache import (  # noqa: F401
    EngineCache,
    EngineLease,
    engine_key,
    plan_key,
)
from repro.serving.faults import Fault, FaultInjector  # noqa: F401
from repro.serving.protocol import (  # noqa: F401
    MAX_FRAME_BYTES,
    PROTOCOL_VERSION,
    BadRequest,
    ProtocolError,
    RemoteError,
    ServerEndpoint,
    decode_request,
    decode_response,
    encode_request,
    encode_response,
    pack_frame,
    read_frame,
    unpack_body,
)
from repro.serving.request import (  # noqa: F401
    Request,
    RequestOptions,
    Ticket,
)
from repro.serving.resilience import (  # noqa: F401
    CircuitBreaker,
    CircuitOpen,
    DeadlineExceeded,
    Overloaded,
    Rejected,
    RetryPolicy,
    TransientFailure,
)
from repro.serving.scheduler import DeviceScheduler  # noqa: F401
from repro.serving.server import Server, ServingOptions  # noqa: F401
from repro.serving.streaming import (  # noqa: F401
    Frame,
    FrameDropped,
    StreamScheduler,
    StreamSession,
)

__all__ = [
    "AsyncClient",
    "BadRequest",
    "CircuitBreaker",
    "CircuitOpen",
    "DeadlineExceeded",
    "DeviceScheduler",
    "EngineCache",
    "EngineLease",
    "Fault",
    "FaultInjector",
    "Frame",
    "FrameDropped",
    "MAX_FRAME_BYTES",
    "MicroBatcher",
    "Overloaded",
    "PROTOCOL_VERSION",
    "ProtocolError",
    "Rejected",
    "RemoteError",
    "Request",
    "RequestOptions",
    "RetryPolicy",
    "Server",
    "ServerEndpoint",
    "ServingOptions",
    "StreamScheduler",
    "StreamSession",
    "Ticket",
    "TransientFailure",
    "bucket",
    "decode_request",
    "decode_response",
    "encode_request",
    "encode_response",
    "engine_key",
    "pack_frame",
    "plan_key",
    "read_frame",
    "spans",
    "unpack_body",
]
