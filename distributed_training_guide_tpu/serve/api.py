"""Serving front-ends over the engine: an offline batch API and a minimal
stdlib HTTP endpoint with per-token streaming. Both emit per-request
latency + TTFT/ITL and aggregate tokens/sec.

``generate_many`` is synchronous continuous batching: all requests enter
the scheduler queue up front and the engine iterates until the queue
drains — requests of different lengths still interleave at iteration
granularity (an early finisher's slot is re-admitted mid-flight).

``serve_http`` is ONLINE continuous batching: a single background engine
thread owns all device work and loops over ``engine.step()``; HTTP handler
threads only enqueue requests and wait on a per-request event (or, with
``"stream": true``, on a per-request token queue). Concurrent clients
therefore genuinely co-batch — two requests in flight share decode steps,
which is the throughput story of iteration-level scheduling.

The streaming response is SSE over chunked transfer-encoding: one
``data: {"token_id": ...}`` event per generated token AS the engine
produces it (tapped from ``engine.partial_tokens()`` after every
iteration), closed by a ``data: {"done": true, ...}`` event carrying the
full result + latency/TTFT metrics. The first token therefore reaches the
client while generation is still running — TTFT < total latency is the
pinned property, and the per-request ``deadline_s`` / ``priority`` fields
are honored by the scheduler underneath (an expired request's stream ends
with ``finish_reason: "deadline"``).

Refusals are structured end to end: the scheduler's RefusalError maps to
HTTP 429 (backpressure — full queue) or 400 (a request that could never
run), and the body carries the machine-readable ``reason`` plus the
current ``queue_depth`` instead of an opaque status; ``/healthz`` serves
the engine's lock-free ``stats()`` snapshot, so it answers even while a
decode iteration holds the engine thread.

Works unchanged over the monolithic :class:`~.engine.ServeEngine` and the
disaggregated :class:`~.disagg.DisaggEngine` — both implement the same
``submit / step / has_work / partial_tokens / stats`` surface.
"""
from __future__ import annotations

import json
import logging
import queue as queue_mod
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from .scheduler import RefusalError, Request, RequestResult

LOGGER = logging.getLogger(__name__)


def generate_many(engine, requests: list[Request],
                  max_iterations: Optional[int] = None) -> list[RequestResult]:
    """Run a batch of requests to completion; results in submit order.

    ``max_iterations`` bounds the loop for tests; the natural bound is
    total decode steps ~= sum(max_new_tokens) + admission stalls.
    """
    ids = [engine.submit(r) for r in requests]
    done: dict[int, RequestResult] = {}
    iters = 0
    while engine.has_work:
        for res in engine.step():
            done[res.request_id] = res
        iters += 1
        if max_iterations is not None and iters > max_iterations:
            raise RuntimeError(
                f"generate_many exceeded {max_iterations} iterations with "
                f"{len(ids) - len(done)} requests unfinished — scheduler "
                f"stall (this is a bug, not load)")
    missing = [i for i in ids if i not in done]
    assert not missing, f"engine drained but requests {missing} never finished"
    return [done[i] for i in ids]


def throughput_stats(results: list[RequestResult],
                     wall_s: float, engine) -> dict:
    """Aggregate serving metrics for a completed batch."""
    gen = sum(len(r.generated_ids) for r in results)
    lat = sorted(r.latency_s for r in results)
    ttft = sorted(r.ttft_s for r in results if r.first_token_at)
    es = engine.stats()
    # goodput (DistServe's serving metric — serve/loadgen.py owns the
    # open-loop harness around it): completions that met their deadline
    # per wall second. A completed request met its deadline by
    # construction — past-deadline work is evicted at every iteration
    # boundary with finish_reason="deadline", never finished.
    met = sum(1 for r in results if r.finish_reason in ("eos", "length"))
    return {
        "n_requests": len(results),
        "generated_tokens": gen,
        "wall_s": round(wall_s, 4),
        "tokens_per_s": round(gen / wall_s, 2) if wall_s else 0.0,
        "goodput_rps": round(met / wall_s, 3) if wall_s else 0.0,
        "deadline_met": met,
        "deadline_missed_queued": es.get("deadline_missed_queued", 0),
        "deadline_missed_running": es.get("deadline_missed_running", 0),
        "decode_steps": engine.decode_steps,
        # slot occupancy of the decode program: 1.0 = every lane of every
        # step carried a live request (continuous batching's win over
        # static batching shows up here)
        "decode_occupancy": es["decode_occupancy"],
        "latency_s_p50": round(lat[len(lat) // 2], 4) if lat else 0.0,
        "latency_s_max": round(lat[-1], 4) if lat else 0.0,
        "ttft_s_p50": round(ttft[len(ttft) // 2], 4) if ttft else 0.0,
        "admission_blocked": es["admission_blocked"],
        # PagedAttention second-half counters: recompute preemptions,
        # prefix-cache reuse, and copy-on-write forks (serve/scheduler.py)
        "preempted": es["preempted"],
        "prefix_hits": es["prefix_hits"],
        "prefix_tokens_shared": es["prefix_tokens_shared"],
        "cow_forks": es["cow_forks"],
        "deadline_expired": es["deadline_expired"],
        "refused": es["refused"],
        # speculative decoding (serve/spec.py): acceptance and the
        # achieved weight-read amortization (tokens per decode iteration)
        "spec_steps": es["spec_steps"],
        "spec_tokens_drafted": es["spec_tokens_drafted"],
        "spec_tokens_accepted": es["spec_tokens_accepted"],
        # absent (not 0.0) when nothing was drafted: a zero here reads
        # as "0% acceptance" on a dashboard that never speculated
        **({"spec_acceptance_rate": es["spec_acceptance_rate"]}
           if "spec_acceptance_rate" in es else {}),
        "decode_tokens_per_step": es["decode_tokens_per_step"],
        # fused-horizon amortization (engine.derived_pool_metrics):
        # host round-trips per emitted token is THE serve-plane CPU wall
        "decode_horizon": es.get("decode_horizon", 1),
        "host_dispatches": es.get("host_dispatches", 0),
        "tokens_per_dispatch": es.get("tokens_per_dispatch", 0.0),
        "horizon_effective": es.get("horizon_effective", 0.0),
        # steps that completed a prefill, and those of them that dispatched
        # the decode behind the chunk program before reading the first
        # token (ServeEngine.step; the disaggregated pair has neither)
        "chunk_steps": es.get("chunk_steps", 0),
        "chunk_steps_overlapped": es.get("chunk_steps_overlapped", 0),
    }


class _EngineWorker(threading.Thread):
    """The single thread that touches the device. Handlers enqueue via
    ``submit`` (engine + futures under one lock) and wait on an event —
    or, for streaming requests, consume a per-request token queue the
    run loop feeds from ``engine.partial_tokens()`` after every
    iteration."""

    def __init__(self, engine):
        super().__init__(daemon=True, name="serve-engine")
        self.engine = engine
        self.lock = threading.Lock()
        self.wakeup = threading.Event()
        self.futures: dict[int, dict] = {}
        self.dead: Optional[BaseException] = None
        self._stop = False
        # the loop's heartbeat: stamped every pass, read lock-free by
        # /readyz — a wedged iteration (stuck device op) leaves it stale
        # while /healthz keeps answering, which is exactly the
        # liveness-vs-readiness split
        self.last_loop_at = time.monotonic()

    def submit(self, request: Request, stream: bool = False) -> dict:
        fut = {"event": threading.Event(), "result": None, "error": None,
               "submitted": time.monotonic(), "stream": stream,
               "queue": queue_mod.SimpleQueue() if stream else None,
               "sent": 0}
        with self.lock:
            if self.dead is not None:
                raise RuntimeError(f"engine thread died: {self.dead!r}")
            rid = self.engine.submit(request)  # raises -> handler 400/429
            self.futures[rid] = fut
        self.wakeup.set()
        return fut

    def _fail_all(self, exc: BaseException) -> None:
        self.dead = exc
        for fut in self.futures.values():
            fut["error"] = exc
            if fut["stream"]:
                fut["queue"].put(("error", exc))
            fut["event"].set()
        self.futures.clear()

    def _push_tokens(self) -> None:
        """Feed per-token deltas to streaming waiters. Dedup is by count:
        ``partial_tokens`` lists only grow (replay rewrites k/v, not
        tokens), so slicing past ``sent`` is exact across preemption.
        Pay-for-use: the tap (which copies every live slot's token list)
        is skipped entirely while no streaming request is in flight."""
        if not any(f["stream"] for f in self.futures.values()):
            return
        for rid, toks in self.engine.partial_tokens().items():
            fut = self.futures.get(rid)
            if fut is None or not fut["stream"]:
                continue
            for tok in toks[fut["sent"]:]:
                fut["queue"].put(("token", int(tok)))
            fut["sent"] = max(fut["sent"], len(toks))

    def run(self) -> None:
        while not self._stop:
            self.last_loop_at = time.monotonic()
            try:
                with self.lock:
                    busy = self.engine.has_work
                    finished = self.engine.step() if busy else []
                    if busy:
                        self._push_tokens()
                    for res in finished:
                        fut = self.futures.pop(res.request_id, None)
                        if fut is not None:
                            fut["result"] = res
                            if fut["stream"]:
                                for tok in \
                                        res.generated_ids[fut["sent"]:]:
                                    fut["queue"].put(("token", int(tok)))
                                fut["queue"].put(("done", res))
                            fut["event"].set()
            except Exception as exc:
                # an engine error must fail every waiter LOUDLY — a silent
                # thread death would hang all pending requests forever while
                # /healthz kept answering ok
                LOGGER.exception("serve engine thread died")
                with self.lock:
                    self._fail_all(exc)
                return
            if not busy:
                self.wakeup.wait(timeout=0.05)
                self.wakeup.clear()
        # clean stop: anything still in flight must fail its waiter — a
        # handler thread blocked on fut["event"] with no timeout would
        # otherwise hang (with its client) past server.shutdown()
        with self.lock:
            if self.futures:
                self._fail_all(RuntimeError("server shutting down"))

    def stop(self, drain: bool = False, timeout_s: float = 30.0) -> None:
        """Stop the engine thread. ``drain=True`` is the graceful half
        (SIGTERM): the engine stops ADMITTING (refusing new submits with
        a structured 503) but keeps stepping until every in-flight
        future has its result — clients connected before the signal get
        answers, not reset connections — bounded by ``timeout_s``;
        whatever is still pending after the bound fails loudly through
        the existing clean-stop path."""
        if drain and self.dead is None:
            drain_fn = getattr(self.engine, "drain", None)
            if drain_fn is not None:
                drain_fn()
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                with self.lock:
                    if not self.futures:
                        break
                time.sleep(0.01)
        self._stop = True
        self.wakeup.set()

    def stats(self) -> dict:
        """Worker + engine snapshot WITHOUT the engine lock: the run loop
        holds that lock for a whole iteration, and /healthz must answer
        while a decode iteration is in flight. Every field is a host-side
        read (atomic enough under the GIL for a health probe)."""
        return {
            "ok": self.dead is None,
            **({"error": repr(self.dead)} if self.dead is not None else {}),
            "pending_requests": len(self.futures),
            "loop_age_s": round(time.monotonic() - self.last_loop_at, 4),
            **self.engine.stats(),
        }


def serve_http(engine, host: str = "127.0.0.1", port: int = 8000,
               tokenizer=None):
    """Start the HTTP endpoint; returns (server, worker) — call
    ``server.shutdown()`` + ``worker.stop()`` to tear down.

    POST /generate  {"prompt_ids": [...]} or {"prompt": "..."} (needs a
                    tokenizer), plus optional max_new_tokens / temperature /
                    top_k / top_p / seed / eos_id / priority / deadline_s.
                    With ``"stream": true`` the response is SSE over
                    chunked transfer-encoding: one ``data:`` event per
                    token as it is generated, then a final ``done`` event
                    with the full result + latency/TTFT metrics.
    GET  /healthz   LIVENESS + the engine's full lock-free metrics
                    snapshot (queue depth, pool occupancy, prefix-cache
                    hit rate, TTFT/ITL, refusals by reason)
    GET  /readyz    READINESS: 200 only when a router should send
                    traffic here — not draining, queue depth and pool
                    headroom inside their watermarks, engine loop
                    heartbeat fresh (serve/router.py ``readiness``);
                    503 with the failing reasons otherwise

    429/503 refusals carry a ``Retry-After`` header derived from queue
    depth and decode occupancy (the scheduler's ``retry_after_hint``).
    ``worker.stop(drain=True)`` is the graceful SIGTERM half: refuse new
    work, finish everything in flight, then exit.

    Works over a single engine or a :class:`~.router.Router` fleet —
    both implement the same driving surface.
    """
    worker = _EngineWorker(engine)

    class Handler(BaseHTTPRequestHandler):
        # HTTP/1.1 for chunked transfer-encoding (the streaming path);
        # non-streaming replies keep explicit Content-Length framing
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # route to logging, not stderr
            LOGGER.debug("http: " + fmt, *args)

        def _reply(self, code: int, payload: dict,
                   headers: Optional[dict] = None) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for name, value in (headers or {}).items():
                self.send_header(name, value)
            self.end_headers()
            self.wfile.write(body)

        def _chunk(self, data: bytes) -> None:
            self.wfile.write(f"{len(data):X}\r\n".encode() + data + b"\r\n")

        def _sse(self, payload: dict) -> None:
            self._chunk(b"data: " + json.dumps(payload).encode() + b"\n\n")

        def do_GET(self):
            if self.path == "/healthz":
                # LIVENESS: "is the process up and the engine thread not
                # dead" — deliberately NOT under worker.lock: the engine
                # thread holds it for a full iteration, and a health
                # probe that blocks on in-flight device work defeats its
                # purpose
                return self._reply(200, worker.stats())
            if self.path == "/readyz":
                # READINESS: "should a router send traffic here" — the
                # same lock-free snapshot run through the fleet's gates
                # (serve/router.py readiness): draining, queue depth,
                # pool headroom, and the engine LOOP's heartbeat age
                # (a wedged-but-alive iteration answers /healthz fine
                # and must fail here)
                from .router import readiness

                stats = worker.stats()
                ready, reasons = readiness(
                    stats, loop_age_s=stats.get("loop_age_s"))
                return self._reply(200 if ready else 503,
                                   {"ready": ready, "reasons": reasons})
            return self._reply(404, {"error": "unknown path"})

        def _result_payload(self, res: RequestResult) -> dict:
            payload = {
                "token_ids": res.token_ids,
                "generated_ids": res.generated_ids,
                "finish_reason": res.finish_reason,
                "latency_s": round(res.latency_s, 4),
                "queue_s": round(res.queue_s, 4),
                "ttft_s": round(res.ttft_s, 4),
                "itl_s": round(res.itl_s, 6),
            }
            if tokenizer is not None:
                payload["text"] = tokenizer.decode(res.token_ids)
            return payload

        def do_POST(self):
            if self.path != "/generate":
                return self._reply(404, {"error": "unknown path"})
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                prompt_ids = body.get("prompt_ids")
                if prompt_ids is None and body.get("prompt") is not None:
                    if tokenizer is None:
                        raise ValueError(
                            "text 'prompt' needs a tokenizer; pass "
                            "'prompt_ids' for the hermetic path")
                    prompt_ids = tokenizer(body["prompt"])["input_ids"]
                    if prompt_ids and isinstance(prompt_ids[0], list):
                        prompt_ids = prompt_ids[0]
                stream = bool(body.get("stream", False))
                req = Request(
                    prompt_ids=[int(t) for t in (prompt_ids or [])],
                    max_new_tokens=int(body.get("max_new_tokens", 32)),
                    temperature=float(body.get("temperature", 0.0)),
                    top_k=int(body.get("top_k", 0)),
                    top_p=float(body.get("top_p", 1.0)),
                    seed=int(body.get("seed", 0)),
                    eos_id=(int(body["eos_id"])
                            if body.get("eos_id") is not None else None),
                    priority=int(body.get("priority", 0)),
                    deadline_s=(float(body["deadline_s"])
                                if body.get("deadline_s") is not None
                                else None))
                fut = worker.submit(req, stream=stream)
            except RefusalError as exc:
                # the scheduler's refusal verbatim: machine-readable
                # reason + current load, not an opaque status code. A
                # backpressure refusal additionally carries the
                # load-derived retry hint as a real Retry-After header
                # (integer seconds per RFC 9110 — the precise float
                # rides in the JSON body; router spillover uses that)
                headers = None
                if exc.retry_after_s is not None:
                    headers = {"Retry-After":
                               str(max(1, int(-(-exc.retry_after_s // 1))))}
                return self._reply(exc.http_status, {
                    "error": str(exc), "reason": exc.reason, **exc.detail},
                    headers)
            except (ValueError, KeyError, json.JSONDecodeError) as exc:
                return self._reply(400, {"error": str(exc)})
            except RuntimeError as exc:     # engine thread already dead
                return self._reply(503, {"error": str(exc)})
            if stream:
                return self._stream_response(fut)
            fut["event"].wait()
            if fut["error"] is not None:
                return self._reply(500, {"error": repr(fut["error"])})
            self._reply(200, self._result_payload(fut["result"]))

        def _stream_response(self, fut: dict) -> None:
            """SSE over chunked transfer-encoding, one event per token.
            The headers go out immediately — the client owns a live
            stream while the engine is still decoding (TTFT << total
            latency, the pinned property)."""
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-store")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            index = 0
            while True:
                kind, item = fut["queue"].get()
                if kind == "token":
                    self._sse({"token_id": item, "index": index})
                    index += 1
                elif kind == "done":
                    self._sse({"done": True,
                               **self._result_payload(item)})
                    break
                else:           # error
                    self._sse({"error": repr(item)})
                    break
            self._chunk(b"")    # terminating zero-length chunk
            self.close_connection = True

    server = ThreadingHTTPServer((host, port), Handler)
    worker.start()
    threading.Thread(target=server.serve_forever, daemon=True,
                     name="serve-http").start()
    LOGGER.info(f"serving on http://{host}:{server.server_address[1]} "
                f"(n_slots={engine.n_slots})")
    return server, worker
