"""asyncio HTTP/SSE gateway: the fleet's network front door.

Stdlib only (``asyncio.start_server`` + a hand-rolled HTTP/1.1 parser —
no aiohttp, no new deps).  One endpoint does the work:

``POST /v1/generate``
    JSON body ``{"prompt": [token ids], "max_new_tokens": N,
    "greedy": true, "priority_class": "interactive",
    "deadline_s": 2.0, ...}``; the response is a
    ``text/event-stream`` of ``token`` events (``{"pos": p,
    "token": t}``), terminated by one ``done`` event (finish reason,
    usage, TTFT) or one typed ``error`` event (``deadline`` /
    ``quarantined`` / ``replay_budget`` / ... — the fleet's
    defense-in-depth verdicts, surfaced to the client instead of a
    hung stream).

Edge semantics, all riding the existing machinery rather than
duplicating it:

* **auth + quota** — ``Authorization: Bearer <key>`` maps to a tenant
  (``api_keys``); the router's :class:`TenantQuota` then bounds the
  tenant's in-flight work (``QuotaExceededError`` → HTTP 429).
* **overload** — :class:`~deepspeed_tpu.fleet.defense.AdmissionBudget`
  sheds surface as HTTP 429 with a ``Retry-After`` header derived from
  ``OverloadShedError.retry_after_s`` (body carries the float + shed
  class).
* **deadlines** — the client's ``deadline_s`` propagates into the
  scheduler, whose ``_expire_deadlines`` fails the request mid-stream;
  the gateway turns that into the ``error`` event typed ``deadline``.
* **tracing** — the ``trace_id`` is minted AT THE EDGE and returned as
  the ``X-Trace-Id`` response header; the gateway opens a
  ``http/request`` span under it on the fleet's tracer (tid
  ``gateway``), and the scheduler's ``request/submit`` instant and its
  ``request/queued`` / ``request/prefill`` / ``request/decode`` spans
  continue the same id —
  one Perfetto timeline from HTTP accept to the emitting tick.
* **exactly-once streaming** — tokens cross from the fleet's
  synchronous ``on_token`` callbacks into the SSE writer through a
  :class:`~deepspeed_tpu.gateway.bridge.StreamBridge`, deduplicated by
  ``(uid, position)``: a kill→replay never duplicates or drops a
  position on the wire.

The gateway also owns the fleet pump: an event-loop task steps the
backend whenever work is pending, so SSE writes interleave with
scheduler ticks on one loop (no threads, no locks).
"""

from __future__ import annotations

import asyncio
import inspect
import json
import math
import time
from typing import Dict, Optional

from deepspeed_tpu.fleet.defense import OverloadShedError
from deepspeed_tpu.gateway.bridge import StreamBridge
from deepspeed_tpu.gateway.metrics import GatewayMetrics
from deepspeed_tpu.observability.tracer import Tracer, mint_trace_id
from deepspeed_tpu.serving.request import SamplingParams
from deepspeed_tpu.serving.router import (AdmissionRejectedError,
                                          QuotaExceededError)
from deepspeed_tpu.serving.scheduler import QueueFullError
from deepspeed_tpu.utils.logging import logger

#: request-body knobs forwarded into SamplingParams when present
_SAMPLING_KEYS = ("greedy", "temperature", "top_k", "max_new_tokens",
                  "eos_token_id", "seed")


def _sse(event: str, payload: dict) -> bytes:
    return (f"event: {event}\ndata: {json.dumps(payload)}\n\n"
            ).encode("utf-8")


def _state(handle) -> str:
    """'live' | 'finished' | 'failed' for FleetRequest or Request."""
    s = handle.state
    return getattr(s, "value", s)


class GatewayServer:
    """See module doc.  ``backend`` is a :class:`ServingFleet` (or
    anything fleet-shaped: ``submit(prompt, tenant=..., ...)``,
    ``step()``, ``num_pending``)."""

    def __init__(self, backend, *, api_keys: Optional[Dict[str, str]] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 tracer: Optional[Tracer] = None,
                 registry=None, step_backend: bool = True,
                 poll_s: float = 0.001, max_body_bytes: int = 1 << 20,
                 max_stream_s: float = 120.0, trace_tid: str = "gateway"):
        self.backend = backend
        #: api key -> tenant; None = open gateway (tenant from the
        #: X-Tenant header, default "default")
        self.api_keys = api_keys
        self.host = host
        self._want_port = port
        self.port: Optional[int] = None
        #: edge spans land on the FLEET's tracer by default, so one
        #: export already holds the whole accept→tick→emit timeline
        self.tracer = tracer if tracer is not None \
            else getattr(backend, "tracer", None)
        self.trace_tid = trace_tid
        self.step_backend = step_backend
        self.poll_s = poll_s
        self.max_body_bytes = max_body_bytes
        self.max_stream_s = max_stream_s
        self.metrics = GatewayMetrics()
        if registry is not None:
            registry.register_provider("gateway", self.metrics.telemetry)
        #: kwargs the backend's submit actually accepts (FleetFrontEnd's
        #: is narrower than ServingFleet's — degrade, don't crash)
        try:
            self._submit_kwargs = frozenset(
                inspect.signature(backend.submit).parameters)
        except (TypeError, ValueError):
            self._submit_kwargs = frozenset()
        self._server: Optional[asyncio.AbstractServer] = None
        self._pump_task: Optional[asyncio.Task] = None
        self._closed = False

    # ------------------------------------------------------------------ #
    # Lifecycle
    # ------------------------------------------------------------------ #
    async def start(self) -> "GatewayServer":
        self._server = await asyncio.start_server(
            self._handle_conn, self.host, self._want_port)
        self.port = self._server.sockets[0].getsockname()[1]
        if self.step_backend:
            self._pump_task = asyncio.ensure_future(self._pump())
        return self

    async def stop(self) -> None:
        self._closed = True
        if self._pump_task is not None:
            self._pump_task.cancel()
            try:
                await self._pump_task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            self._pump_task = None
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None

    async def _pump(self) -> None:
        """Step the backend whenever it has pending work; otherwise idle
        at ``poll_s``.  Runs on the gateway's loop, so a scheduler tick
        and an SSE write never race — they interleave."""
        while not self._closed:
            if self.backend.num_pending:
                try:
                    self.backend.step()
                except Exception:  # noqa: BLE001 — the fleet survives its
                    # own replica deaths; anything escaping here is a bug,
                    # but the pump dying would hang every open stream
                    logger.exception("gateway: backend step raised")
                await asyncio.sleep(0)
            else:
                await asyncio.sleep(self.poll_s)

    # ------------------------------------------------------------------ #
    # HTTP plumbing
    # ------------------------------------------------------------------ #
    async def _read_request(self, reader):
        line = await reader.readline()
        if not line:
            return None
        try:
            method, target, _version = line.decode("latin-1").split()
        except ValueError:
            return None
        headers: Dict[str, str] = {}
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            k, _, v = h.decode("latin-1").partition(":")
            headers[k.strip().lower()] = v.strip()
        n = int(headers.get("content-length", "0") or 0)
        if n > self.max_body_bytes:
            return method, target, headers, None    # 413 upstream
        body = await reader.readexactly(n) if n else b""
        return method, target, headers, body

    @staticmethod
    async def _respond_json(writer, status: int, reason: str, obj: dict,
                            extra_headers: Optional[Dict[str, str]] = None
                            ) -> None:
        body = json.dumps(obj).encode("utf-8")
        head = [f"HTTP/1.1 {status} {reason}",
                "Content-Type: application/json",
                f"Content-Length: {len(body)}",
                "Connection: close"]
        for k, v in (extra_headers or {}).items():
            head.append(f"{k}: {v}")
        writer.write(("\r\n".join(head) + "\r\n\r\n").encode("latin-1")
                     + body)
        await writer.drain()

    async def _handle_conn(self, reader, writer) -> None:
        try:
            req = await self._read_request(reader)
            if req is None:
                return
            method, target, headers, body = req
            self.metrics.requests += 1
            if body is None:
                self.metrics.bad_requests += 1
                await self._respond_json(writer, 413, "Payload Too Large",
                                         {"error": "body too large"})
            elif method == "GET" and target in ("/healthz", "/health"):
                await self._respond_json(
                    writer, 200, "OK",
                    {"ok": True,
                     "pending": int(self.backend.num_pending),
                     "open_streams": self.metrics.open_streams})
            elif method == "POST" and target == "/v1/generate":
                await self._handle_generate(headers, body, writer)
            else:
                self.metrics.bad_requests += 1
                await self._respond_json(
                    writer, 404, "Not Found",
                    {"error": f"no route {method} {target}"})
        except (ConnectionResetError, BrokenPipeError,
                asyncio.IncompleteReadError):
            pass                      # client went away; nothing to say
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass

    # ------------------------------------------------------------------ #
    # POST /v1/generate
    # ------------------------------------------------------------------ #
    def _authenticate(self, headers) -> Optional[str]:
        """Tenant for this request, or None for a 401."""
        if self.api_keys is None:
            return headers.get("x-tenant", "default")
        auth = headers.get("authorization", "")
        if auth.lower().startswith("bearer "):
            return self.api_keys.get(auth[7:].strip())
        return None

    def _parse_generate(self, body: bytes) -> dict:
        spec = json.loads(body.decode("utf-8"))
        prompt = spec.get("prompt")
        if not isinstance(prompt, list) or not prompt \
                or not all(isinstance(t, int) for t in prompt):
            raise ValueError("'prompt' must be a non-empty list of "
                             "token ids")
        kw = {k: spec[k] for k in _SAMPLING_KEYS if k in spec}
        spec["_sampling"] = SamplingParams(**kw)
        return spec

    def _submit(self, spec: dict, tenant: str, trace_id: str,
                on_token) -> object:
        kw = {"tenant": tenant, "sampling": spec["_sampling"],
              "on_token": on_token, "trace_id": trace_id,
              "priority_class": spec.get("priority_class"),
              "deadline_s": spec.get("deadline_s")}
        kw = {k: v for k, v in kw.items() if k in self._submit_kwargs}
        return self.backend.submit(spec["prompt"], **kw)

    async def _handle_generate(self, headers, body: bytes, writer) -> None:
        tenant = self._authenticate(headers)
        if tenant is None:
            self.metrics.rejected_auth += 1
            await self._respond_json(writer, 401, "Unauthorized",
                                     {"error": "unknown or missing "
                                               "API key"})
            return
        try:
            spec = self._parse_generate(body)
        except (ValueError, UnicodeDecodeError) as e:
            self.metrics.bad_requests += 1
            await self._respond_json(writer, 400, "Bad Request",
                                     {"error": str(e)})
            return
        # the edge mints the trace id: one Perfetto timeline from HTTP
        # accept through scheduler tick to token emit
        trace_id = mint_trace_id()
        tr = self.tracer
        span = tr.start("http/request", trace_id=trace_id,
                        tid=self.trace_tid,
                        attrs={"tenant": tenant,
                               "prompt_tokens": len(spec["prompt"]),
                               "priority_class":
                                   spec.get("priority_class") or "",
                               }) if tr is not None and tr.enabled \
            else None
        outcome = "error"
        try:
            bridge = StreamBridge()
            try:
                fr = self._submit(spec, tenant, trace_id, bridge.on_token)
            except OverloadShedError as e:
                self.metrics.sheds_429 += 1
                outcome = "shed"
                await self._respond_json(
                    writer, 429, "Too Many Requests",
                    {"error": "overloaded", "message": str(e),
                     "retry_after_s": e.retry_after_s,
                     "shed_class": e.shed_class, "trace_id": trace_id},
                    extra_headers={
                        "Retry-After":
                            str(max(1, math.ceil(e.retry_after_s))),
                        "X-Trace-Id": trace_id})
                return
            except QuotaExceededError as e:
                self.metrics.rejected_quota += 1
                outcome = "quota"
                await self._respond_json(
                    writer, 429, "Too Many Requests",
                    {"error": "quota", "message": str(e),
                     "trace_id": trace_id},
                    extra_headers={"X-Trace-Id": trace_id})
                return
            except (AdmissionRejectedError, QueueFullError) as e:
                self.metrics.bad_requests += 1
                outcome = "rejected"
                await self._respond_json(
                    writer, 503, "Service Unavailable",
                    {"error": "admission", "message": str(e),
                     "trace_id": trace_id},
                    extra_headers={"X-Trace-Id": trace_id})
                return
            outcome = await self._stream(fr, bridge, trace_id, writer)
        finally:
            if span is not None:
                tr.finish(span, attrs={"outcome": outcome})

    async def _stream(self, fr, bridge: StreamBridge, trace_id: str,
                      writer) -> str:
        """Write the SSE stream for one admitted request; returns the
        outcome string for the edge span."""
        uid = getattr(fr, "uid", -1)
        writer.write((
            "HTTP/1.1 200 OK\r\n"
            "Content-Type: text/event-stream\r\n"
            "Cache-Control: no-cache\r\n"
            "Connection: close\r\n"
            f"X-Trace-Id: {trace_id}\r\n"
            f"X-Request-Uid: {uid}\r\n\r\n").encode("latin-1"))
        await writer.drain()
        self.metrics.streams_started += 1
        self.metrics.open_streams += 1
        deadline = time.monotonic() + self.max_stream_s
        try:
            while True:
                for pos, tok in bridge.drain():
                    writer.write(_sse("token", {"pos": pos, "token": tok}))
                    self.metrics.tokens_streamed += 1
                await writer.drain()
                if _state(fr) != "live" and not bridge.pending:
                    break
                if time.monotonic() > deadline:
                    writer.write(_sse("error", {
                        "type": "gateway_timeout",
                        "message": f"stream exceeded max_stream_s="
                                   f"{self.max_stream_s}"}))
                    await writer.drain()
                    self.metrics.streams_failed += 1
                    return "gateway_timeout"
                await asyncio.sleep(self.poll_s)
            self.metrics.duplicates_suppressed += \
                bridge.duplicates_suppressed
            if _state(fr) == "finished":
                ttft = getattr(fr, "ttft", None)
                writer.write(_sse("done", {
                    "finish_reason": fr.finish_reason or "stop",
                    "tokens": bridge.next_pos,
                    "ttft_s": round(ttft, 6) if ttft is not None else None,
                    "trace_id": trace_id}))
                await writer.drain()
                self.metrics.streams_finished += 1
                return "finished"
            # failed: surface the fleet's typed verdict on the stream
            reason = getattr(fr, "finish_reason", None) or "failed"
            if reason == "deadline":
                self.metrics.deadline_expired += 1
            writer.write(_sse("error", {
                "type": reason,
                "message": getattr(fr, "error", None)
                or f"request {uid} failed: {reason}",
                "tokens": bridge.next_pos, "trace_id": trace_id}))
            await writer.drain()
            self.metrics.streams_failed += 1
            return reason
        except (ConnectionResetError, BrokenPipeError):
            self.metrics.streams_failed += 1
            return "client_disconnect"
        finally:
            self.metrics.open_streams -= 1
