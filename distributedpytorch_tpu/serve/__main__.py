"""HTTP front end: ``python -m distributedpytorch_tpu.serve --run-dir RUN``.

A thin, dependency-free (stdlib ``http.server``) shell around
:class:`service.InferenceService`: each HTTP request thread submits into
the shared bounded queue and blocks on its future, so concurrent clients
feed the micro-batcher exactly like in-process threads do.  The endpoints:

    POST /v1/predict   {"image": <wire array>, "points": [[x,y]*4],
                        "deadline_ms": optional}
                    -> {"mask": <wire array>, "latency_ms": ...}
                       429 shed (queue full) | 504 deadline | 400 bad input
    GET  /healthz   -> 200/503 liveness: service state + an in-process
                       device-op probe (backend_health.device_op_alive,
                       TTL-cached so probes stay cheap)
    GET  /stats     -> metrics snapshot (counters, p50/p99, buckets)
    GET  /metrics   -> Prometheus text exposition of the process-wide
                       telemetry registry (serve counters, span
                       percentiles, goodput gauges when co-hosted)
    POST /debug/trace?steps=N
                    -> arm a bounded on-demand jax.profiler capture of
                       the next N batches (202 + target dir; 409 when a
                       capture is already armed/active).  SIGUSR2 arms
                       the same default capture.

Wire arrays are ``{"shape", "dtype", "b64"}`` (client.py) — no pickle.
Graceful stop: SIGTERM/SIGINT land the in-flight batch, fail the queued
remainder loudly, and exit 0 (the same manners as the trainer's
preemption path).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time
from concurrent.futures import TimeoutError as FuturesTimeoutError
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from ..telemetry import get_registry, prometheus
from ..telemetry.trace import query_steps
from .client import HealthCache, decode_array, encode_array
from .service import (
    DeadlineExceededError,
    InferenceService,
    QueueFullError,
    ServiceUnhealthyError,
    SessionLaneFullError,
    warmup_buckets,  # noqa: F401  re-export; pre-consolidation import site
)

#: back-compat alias (the cache moved to client.py so the in-process
#: ServeClient path shares it)
_HealthCache = HealthCache


class _Server(ThreadingHTTPServer):
    """ThreadingHTTPServer with NON-daemon handler threads: a graceful
    stop must let handlers woken by ``service.stop()`` (their futures just
    resolved to 503s) finish WRITING those replies — daemon threads would
    be killed at interpreter exit mid-write and the queued clients would
    see a connection reset instead of the promised loud failure.
    ``server_close`` (ThreadingMixIn, block_on_close) joins them."""
    daemon_threads = False


def make_handler(service: InferenceService, health_cache: _HealthCache,
                 request_timeout_s: float = 120.0) -> type:
    """Build the request-handler class closed over the shared service.

    ``request_timeout_s`` bounds how long a handler thread waits on its
    future when the request carries no deadline: with a wedged backend the
    worker never resolves anything, and an unbounded ``result()`` would
    accumulate blocked HTTP threads forever while /healthz correctly
    reports the backend dead."""

    class Handler(BaseHTTPRequestHandler):
        # per-request threads come from ThreadingHTTPServer
        protocol_version = "HTTP/1.1"
        # headers and body flush as two unbuffered writes; on a
        # keep-alive connection (the fleet proxy pools these) Nagle
        # holds the body segment behind the peer's delayed ACK —
        # a flat ~40ms tax on every proxied reply
        disable_nagle_algorithm = True
        # buffer the reply so headers + body leave as ONE segment —
        # handle_one_request() flushes after every request, so this
        # only coalesces writes, it never delays them
        wbufsize = 64 * 1024
        # idle keep-alive bound: handler threads are NON-daemon (_Server),
        # so a connection-reusing client parked between requests would
        # otherwise block server_close()'s join forever at shutdown —
        # the socket read times out, close_connection ends the thread
        timeout = 10.0

        def log_message(self, fmt, *args):  # quiet: metrics are the log
            pass

        def _reply(self, code: int, payload: dict) -> None:
            self._reply_text(code, json.dumps(payload), "application/json")

        def _reply_text(self, code: int, text: str,
                        content_type: str) -> None:
            body = text.encode("utf-8")
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            if code == 429:
                self.send_header("Retry-After", "1")
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self) -> None:  # noqa: N802 — http.server's contract
            if self.path == "/metrics":
                # the one telemetry surface: serve counters AND any train
                # goodput/span metrics living in this process's registry
                self._reply_text(200, prometheus.render_text(get_registry()),
                                 prometheus.CONTENT_TYPE)
            elif self.path == "/healthz":
                alive, why = health_cache.probe()
                health = service.health()
                health["backend_alive"] = alive
                if not alive:
                    health["ok"] = False
                    health["unhealthy_reason"] = (
                        health.get("unhealthy_reason") or why)
                self._reply(200 if health["ok"] else 503, health)
            elif self.path == "/stats":
                self._reply(200, service.metrics.snapshot())
            else:
                self._reply(404, {"error": f"no such path {self.path!r}"})

        def do_POST(self) -> None:  # noqa: N802
            # body read isolated from the predict phase: a client stalling
            # mid-body raises the socket timeout (builtin TimeoutError on
            # 3.11+, where concurrent.futures.TimeoutError is the SAME
            # class — it must not masquerade as a 503 'backend wedged'),
            # and the desynced keep-alive stream can only be dropped
            try:
                raw = self.rfile.read(
                    int(self.headers.get("Content-Length", 0)))
            except (TimeoutError, OSError):
                self.close_connection = True
                return
            base, _, query = self.path.partition("?")
            if base == "/debug/trace":
                trig = service.trace
                if trig is None:
                    self._reply(503, {"error": "trace capture not armed "
                                               "for this service"})
                    return
                target = trig.request(query_steps(query))
                if target is None:
                    self._reply(409, {"error": "a trace capture is "
                                               "already armed or active"})
                else:
                    self._reply(202, {"trace_dir": target,
                                      "note": "starts at the next batch; "
                                              "bounded by steps and a "
                                              "wall-clock backstop"})
                return
            if base != "/v1/predict":
                # body already drained: on a keep-alive (HTTP/1.1)
                # connection unread bytes would be parsed as the client's
                # NEXT request line
                self._reply(404, {"error": f"no such path {self.path!r}"})
                return
            try:
                body = json.loads(raw.decode("utf-8"))
                image = decode_array(body["image"])
                points = np.asarray(body["points"], np.float64)
                deadline_ms = body.get("deadline_ms")
                deadline_s = None if deadline_ms is None \
                    else float(deadline_ms) / 1e3
                # session-affine serving: absent session_id (the
                # pre-session wire) stays the stateless path
                session_id = body.get("session_id")
                if session_id is not None:
                    session_id = str(session_id)
                t0 = time.perf_counter()
                fut = service.submit(image, points, deadline_s=deadline_s,
                                     session_id=session_id)
                # a request with a deadline can't legitimately outwait it
                # (+grace for the drain-side check to answer first), and
                # nobody outwaits the server-side cap — a huge client
                # deadline must not park this thread on a wedged backend
                mask = fut.result(timeout=request_timeout_s
                                  if deadline_s is None
                                  else min(deadline_s + 5.0,
                                           request_timeout_s))
                self._reply(200, {
                    "mask": encode_array(mask),
                    "latency_ms": round(
                        (time.perf_counter() - t0) * 1e3, 3)})
            except SessionLaneFullError as e:
                # same 429 + Retry-After as a queue-full shed, but a
                # distinct `code` so the client round-trips the type:
                # only the offending session should back off
                self._reply(429, {"error": str(e), "code": "session_lane"})
            except QueueFullError as e:
                self._reply(429, {"error": str(e)})
            except DeadlineExceededError as e:
                self._reply(504, {"error": str(e)})
            except FuturesTimeoutError:
                self._reply(503, {"error": (
                    "no result within the server-side wait bound — the "
                    "backend may be wedged; check /healthz")})
            except ServiceUnhealthyError as e:
                self._reply(503, {"error": str(e)})
            except (KeyError, TypeError, ValueError) as e:
                self._reply(400, {"error": f"bad request: {e}"})

    return Handler


def build_predictor(args):
    """Predictor from a run dir or a torch checkpoint — the same two
    sources the --predict CLI serves, minus the per-call restore cost.

    Quantization (``serve/quantize``): ``--quantize int8`` — or, when
    the flag is absent, the run config's ``model.quantization`` knob —
    rebuilds the restored weights as per-channel int8 + scales before
    any program compiles (``--quantize none`` overrides a config knob
    off).  Shared with ``dptpu-aot`` so the pre-compiled ladder is the
    exact ladder this boot serves."""
    from ..predict import Predictor, load_run_config

    quantize = getattr(args, "quantize", None)
    if args.run_dir:
        cfg = load_run_config(args.run_dir)
        if quantize is None:
            quantize = getattr(cfg.model, "quantization", "") or None
        predictor = Predictor.from_run(args.run_dir, cfg=cfg)
    elif getattr(args, "fresh_init", None):
        predictor = build_fresh_predictor(args.fresh_init)
    else:
        predictor = Predictor.from_torch(args.torch)
    from .quantize import quant_policy, quantize_predictor

    policy = quant_policy(quantize)
    if policy is not None:
        predictor = quantize_predictor(predictor, policy)
    return predictor


def build_fresh_predictor(spec: str):
    """Fresh-init predictor from a ``SIZE[:BACKBONE[:INJECT]]`` spec
    (default ``64:resnet18:head``) — a replica with no checkpoint at
    all, for the fleet's chaos scenarios and dev loops where the test
    is the SERVING MACHINERY (routing, membership, failover), not the
    weights."""
    import jax
    import optax

    from ..models import build_model
    from ..parallel import create_train_state
    from ..predict import Predictor

    parts = (spec or "64").split(":")
    size = int(parts[0] or 64)
    backbone = parts[1] if len(parts) > 1 and parts[1] else "resnet18"
    inject = parts[2] if len(parts) > 2 and parts[2] else "head"
    model = build_model("danet", nclass=1, backbone=backbone,
                        output_stride=8, guidance_inject=inject)
    state = create_train_state(jax.random.PRNGKey(0), model,
                               optax.sgd(1e-3), (1, size, size, 4))
    return Predictor(model, state.params, state.batch_stats,
                     resolution=(size, size), relax=10)


def main(argv: list[str] | None = None) -> int:
    from ..backend_health import enable_compile_cache

    # a replica restarted run after run pays its bucket ladder's compiles
    # once
    enable_compile_cache()
    parser = argparse.ArgumentParser(
        prog="distributedpytorch_tpu.serve",
        description="TPU-native batched inference service for click-guided "
                    "segmentation")
    src = parser.add_mutually_exclusive_group(required=True)
    src.add_argument("--run-dir",
                     help="training run dir (config.json + checkpoints/)")
    src.add_argument("--torch", metavar="PTH",
                     help="torch state_dict checkpoint (reference "
                          "architecture) instead of a run dir")
    src.add_argument("--fresh-init", metavar="SPEC", nargs="?",
                     const="64",
                     help="serve FRESH-INIT weights (no checkpoint): "
                          "SIZE[:BACKBONE[:INJECT]], default "
                          "64:resnet18:head — dev/chaos only (the "
                          "fleet's replica_kill_under_load scenario "
                          "boots its replicas this way; the masks are "
                          "noise, the serving machinery is real)")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8801)
    parser.add_argument("--max-batch", type=int, default=8,
                        help="top micro-batch bucket (power of two); "
                             "buckets are 1/2/4/.../max-batch")
    parser.add_argument("--queue-depth", type=int, default=64,
                        help="bounded request queue; a full queue sheds "
                             "(HTTP 429) instead of growing latency")
    parser.add_argument("--max-wait-ms", type=float, default=5.0,
                        help="batcher hold time waiting to fill a bucket")
    parser.add_argument("--deadline-ms", type=float, default=None,
                        help="default per-request deadline (none = wait)")
    parser.add_argument("--warmup", action="store_true",
                        help="ready every bucket before accepting "
                             "traffic (first clicks pay no compile); "
                             "with --aot-cache, loads pre-compiled "
                             "executables instead of compiling")
    parser.add_argument("--aot-cache", default=None, metavar="DIR",
                        help="AOT executable cache built by dptpu-aot: "
                             "--warmup loads instead of compiling "
                             "(near-zero cold start), falling back "
                             "loudly to fresh compiles on any "
                             "mismatch/corruption")
    parser.add_argument("--quantize", choices=("int8", "none"),
                        default=None,
                        help="post-training weight quantization of the "
                             "serve forward (serve/quantize); default: "
                             "the run config's model.quantization")
    parser.add_argument("--session-budget-mb", type=float, default=256.0,
                        help="HBM byte budget for the per-session encoder "
                             "cache (split predictors only); LRU evicts "
                             "past it")
    parser.add_argument("--session-ttl-s", type=float, default=600.0,
                        help="idle seconds before an abandoned session's "
                             "cached encoding is reaped")
    parser.add_argument("--session-lane-depth", type=int, default=4,
                        help="max queued requests ONE session may hold "
                             "(fairness: excess sheds 429/session_lane)")
    parser.add_argument("--trace-dir", default=None,
                        help="where POST /debug/trace and SIGUSR2 write "
                             "bounded XPlane captures (default: "
                             "<run-dir>/serve_trace, or ./serve_trace)")
    parser.add_argument("--session-log", default=None, metavar="DIR",
                        help="opt-in flywheel sink: append accepted "
                             "(crop, clicks, mask) examples as packed "
                             "records under DIR (crash-safe, deduped, "
                             "budgeted) — the log dptpu-flywheel fine-"
                             "tunes from (docs/DESIGN.md 'The click "
                             "flywheel')")
    args = parser.parse_args(argv)

    from ..telemetry import TraceCapture

    predictor = build_predictor(args)
    trace = TraceCapture(args.trace_dir or os.path.join(
        args.run_dir or ".", "serve_trace"))
    service = InferenceService(
        predictor, max_batch=args.max_batch, queue_depth=args.queue_depth,
        max_wait_s=args.max_wait_ms / 1e3,
        default_deadline_s=None if args.deadline_ms is None
        else args.deadline_ms / 1e3,
        session_budget_bytes=int(args.session_budget_mb * 2**20),
        session_ttl_s=args.session_ttl_s,
        session_lane_depth=args.session_lane_depth,
        aot_cache=args.aot_cache,
        session_log=args.session_log,
        trace=trace)
    if args.warmup:
        # service.warmup (not bare warmup_buckets): it also registers the
        # warmed shapes with the retrace tripwire, keeping its budget
        # exact — and threads through the AOT cache when one is
        # configured (per-bucket compile-vs-load millis land on stderr)
        service.warmup()
    service.start()
    httpd = _Server((args.host, args.port),
                    make_handler(service, _HealthCache()))

    def on_signal(signum, frame):
        # shutdown() must come from another thread than serve_forever's
        threading.Thread(target=httpd.shutdown, daemon=True).start()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    # SIGUSR2 arms the same bounded capture POST /debug/trace does
    uninstall_trace_signal = trace.install_signal()
    from .quantize import quantization_block

    warm = service.last_warmup
    print(json.dumps({"serving": f"http://{args.host}:{args.port}",
                      "buckets": list(service.buckets),
                      "queue_depth": args.queue_depth,
                      "resolution": list(predictor.resolution),
                      "sessions": service.sessions_enabled,
                      "quantization": quantization_block(
                          getattr(predictor, "quant_policy", None)),
                      "cold_start": None if warm is None else {
                          "warmup_seconds": warm["warmup_seconds"],
                          "programs_compiled": warm["programs_compiled"],
                          "programs_loaded": warm["programs_loaded"],
                          "aot_cache": warm["aot_cache"]}}),
          flush=True)
    try:
        httpd.serve_forever()
    finally:
        # ORDER MATTERS: stopping the service resolves every in-flight and
        # queued future (503s for the queued remainder), which is what the
        # blocked handler threads are waiting on; only then can
        # server_close() join them (non-daemon handlers, see _Server) so
        # each client actually receives its reply before the process exits.
        service.stop()
        httpd.server_close()
        uninstall_trace_signal()
        print(json.dumps({"stopped": True,
                          "stats": service.metrics.snapshot()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
