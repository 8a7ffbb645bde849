"""UI REST server (stdlib http.server).

Endpoint parity with `UiServer.run():75-87`:

- POST /api/coords            upload 2-D coords            (ApiResource.java)
- GET  /api/coords            fetch them
- POST /tsne/upload           upload high-dim vectors + labels
- POST /tsne/generate         run t-SNE on the upload      (TsneResource)
- GET  /tsne/coords           fetch generated coords
- POST /nearestneighbors/upload   upload labelled vectors
- POST /nearestneighbors          {"word"|"vector", "k"} → knn via VPTree
                                  (NearestNeighborsResource.java:177)
- POST /weights               training listener posts model-and-gradient
                              histograms (HistogramIterationListener)
- GET  /weights               latest + history summary     (WeightResource)
- GET  /activations           activation grid as nested lists
- POST /activations           upload an activation grid    (ActivationsResource)
- POST /lm/generate           LM generation for the model registered via
                              UiServer.serve_lm(cfg, params): greedy /
                              plain-temperature requests ride the
                              continuous slot-decode pool
                              (serving.ContinuousLMServer); top-k/top-p/
                              beam take the whole-sequence KV path
                              (beyond the reference: LM serving).
                              `"stream": true` answers
                              `text/event-stream` — one SSE event per
                              committed token (speculative rounds emit
                              several) and a final `done` event carrying
                              the full ids; a client that disconnects
                              mid-stream abandons the request, freeing
                              its slot and KV pages.  An optional
                              `"session_id"` feeds sticky-session
                              affinity accounting on every front
- POST /lm/prefill            disaggregated serving, prefill half
                              (ISSUE-14): run the prompt through normal
                              admission but stop at prefill completion
                              and answer the lane's KV page shipment
                              (application/octet-stream,
                              serving/transfer.py wire format) for a
                              decode worker to admit
- POST /lm/admit_pages        disaggregated serving, decode half: admit
                              a shipped lane (binary body), install its
                              pages, decode to completion — answers
                              {"ids": ...} byte-identical to a local
                              /lm/generate; a failed integrity check is
                              a typed 422 the router answers by
                              recomputing locally
- POST /model/predict         batched classifier/regressor inference for
                              the model registered via
                              UiServer.serve_model(net) — concurrent
                              requests coalesce in the serving engine's
                              dynamic micro-batcher
- GET  /serving/stats         serving metrics: queue depth, batch
                              occupancy, p50/p95/p99 latency, requests/s,
                              tokens/s, compiled program counts, plus the
                              resilience ledger (rejected/shed/
                              deadline_missed/poison_isolated/
                              breaker_state)
- GET  /healthz               liveness: 200 while the process serves HTTP
- GET  /readyz                readiness: 200 only while every registered
                              serving plane is accepting admissions and
                              no circuit breaker is open; 503 otherwise
                              (drain flips this before traffic stops)
- GET  /metrics               Prometheus text exposition of every
                              registered serving plane's metric cells
                              (requests/dispatches, the resilience
                              ledger, breaker state, KV page-pool
                              gauges, latency histograms split into
                              queue-wait vs compute, compiles_total)
                              — the observability plane (ISSUE-8,
                              docs/observability.md)
- GET  /trace/recent          recent request traces (bounded ring):
                              queue_wait -> dispatch -> respond spans
                              per request, xla_compile spans attached
                              to the request that paid for a compile;
                              ?format=chrome returns Chrome trace-event
                              JSON loadable in Perfetto.  Requests may
                              carry an X-Request-Id header (echoed on
                              the response; minted when absent)

Serving-plane failures are mapped to transport-correct statuses
(ISSUE-4): ServingOverloadError/CircuitOpenError -> 503 with a
Retry-After hint, ServingUnavailableError (stopped/draining) -> 503,
DeadlineExceededError -> 504.  Requests may carry a deadline via the
`deadline_ms` body field or `X-Deadline-Ms` header; expired work is
shed before it reaches the device on the queued paths — the
micro-batched /model/predict and the continuous /lm/generate pool.
The whole-sequence LM legs (top-k/top-p/beam, or continuous=False)
decode in one uninterruptible jitted scan: a deadline sent there is
validated but not enforced mid-flight — the response simply arrives
late.  Deadline-sensitive clients should use the greedy/temperature
continuous path.

All payloads are JSON. `port=0` picks a free port (tests).
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler
from typing import Any, List, Optional

import numpy as np

from deeplearning4j_tpu.obs.compilewatch import compile_watcher
from deeplearning4j_tpu.obs.registry import (
    EXPOSITION_CONTENT_TYPE,
    MetricsRegistry,
)
from deeplearning4j_tpu.obs.trace import TraceRecorder, chrome_trace
from deeplearning4j_tpu.serving.resilience import (
    ServingHTTPMixin,
    ServingHTTPServer,
    ServingUnavailableError,
)


class _UiHTTPServer(ServingHTTPServer):
    """Restart-after-drain socket semantics (SO_REUSEADDR + daemon
    handler threads) live on the shared `ServingHTTPServer`
    (serving/resilience.py), one copy for both serving fronts."""


# Human-viewable dashboard (the reference served FreeMarker pages from the
# Dropwizard app — UiServer.java view bundles). One self-contained page:
# polls the JSON endpoints and renders score curve, weight histograms and
# t-SNE scatter with inline SVG. No external assets (zero-egress friendly).
_DASHBOARD = """<!DOCTYPE html>
<html><head><meta charset="utf-8"><title>deeplearning4j_tpu</title>
<style>
 body{font-family:system-ui,sans-serif;margin:1.5rem;background:#fafafa}
 h1{font-size:1.2rem} h2{font-size:1rem;margin:1.2rem 0 .3rem}
 .card{background:#fff;border:1px solid #ddd;border-radius:6px;
       padding:.8rem;margin-bottom:1rem;max-width:720px}
 svg{width:100%;height:220px;background:#fcfcfc;border:1px solid #eee}
 .muted{color:#777;font-size:.85rem}
</style></head><body>
<h1>deeplearning4j_tpu — training dashboard</h1>
<div class="card"><h2>Training score (from /weights posts)</h2>
 <svg id="score" viewBox="0 0 600 220" preserveAspectRatio="none"></svg>
 <div class="muted" id="scoreinfo">waiting for HistogramIterationListener
 posts…</div></div>
<div class="card"><h2>Latest weight histogram</h2>
 <svg id="hist" viewBox="0 0 600 220" preserveAspectRatio="none"></svg>
 <div class="muted" id="histinfo"></div></div>
<div class="card"><h2>t-SNE coords (from /tsne/generate)</h2>
 <svg id="tsne" viewBox="0 0 600 220"></svg></div>
<script>
function poly(el, pts, color){
  el.innerHTML = pts.length >= 2
    ? '<polyline fill="none" stroke="'+color+'" stroke-width="2" points="'
      + pts.map(p=>p.join(',')).join(' ') + '"/>' : '';
}
function scale(vals, lo, hi){
  const mn=Math.min(...vals), mx=Math.max(...vals), r=(mx-mn)||1;
  return vals.map(v=> lo + (v-mn)/r*(hi-lo));
}
async function tick(){
  try{
    const w = await (await fetch('/weights')).json();
    if(w.count){
      document.getElementById('scoreinfo').textContent =
        w.count+' posts; last iteration '+(w.last.iteration??'?')
        +', score '+(w.last.score??'?');
      const scores=(w.history||[]).map(h=>h.score);
      if(scores.length){
        const ys=scale(scores.map(v=>-v),10,210);
        const xs=scale(scores.map((_,i)=>i),10,590);
        poly(document.getElementById('score'), xs.map((x,i)=>[x,ys[i]]),
             '#1669c1');
      }
      try{
        const h = w.last.histograms && Object.entries(w.last.histograms)[0];
        const bins = h && (Array.isArray(h[1].counts)?h[1].counts
                          :(Array.isArray(h[1])?h[1]:null));
        if(bins && bins.length){
          document.getElementById('histinfo').textContent=h[0];
          const bw=580/bins.length, mx=Math.max(...bins)||1;
          document.getElementById('hist').innerHTML = bins.map((c,i)=>
            '<rect x="'+(10+i*bw)+'" y="'+(210-200*c/mx)+'" width="'
            +(bw-1)+'" height="'+(200*c/mx)+'" fill="#52a447"/>').join('');
        }
      }catch(e){/* malformed histogram post must not block t-SNE */}
    }
    const t = await (await fetch('/tsne/coords')).json();
    if(t.coords && t.coords.length){
      const xs=scale(t.coords.map(c=>c[0]),10,590);
      const ys=scale(t.coords.map(c=>c[1]),10,210);
      document.getElementById('tsne').innerHTML = xs.map((x,i)=>
        '<circle cx="'+x+'" cy="'+ys[i]+'" r="3" fill="#c14a16"/>'
      ).join('');
    }
  }catch(e){/* server may not have data yet */}
  setTimeout(tick, 2000);
}
tick();
</script></body></html>
"""


class _UiState:
    def __init__(self):
        self.lock = threading.Lock()
        # observability plane (ISSUE-8): every serving plane registered
        # on this server publishes its metric cells here (GET /metrics)
        # and records request traces here (GET /trace/recent)
        self.registry = MetricsRegistry()
        self.tracer = TraceRecorder()
        self.registry.gauge(
            "server_uptime_seconds", "seconds since server construction",
            fn=lambda: self.registry.uptime_s)
        self.registry.register_collector(
            compile_watcher().collector_samples)
        self.coords: List[List[float]] = []
        self.tsne_vectors: Optional[np.ndarray] = None
        self.tsne_labels: List[str] = []
        self.tsne_coords: List[List[float]] = []
        self.nn_vectors: Optional[np.ndarray] = None
        self.nn_labels: List[str] = []
        self.nn_tree = None
        self.weights_history: List[dict] = []
        self.activations: Optional[List] = None
        self.lm = None  # (TransformerConfig, params) via serve_lm
        self.lm_server = None  # serving.ContinuousLMServer via serve_lm
        self.engine = None     # serving.ServingEngine via serve_model
        self.draining = False  # set by UiServer.begin_drain (SIGTERM path)

    def serving_stats(self) -> dict:
        """THE /serving/stats payload — one builder for the HTTP
        endpoint and the host-side drain snapshot, so a field added to
        one cannot silently miss the other.  `uptime_s` + monotonic
        `snapshot_at` let scrapers compute rates without client-side
        clocks (ISSUE-8 satellite)."""
        import time as _time

        with self.lock:
            engine, lm_server = self.engine, self.lm_server
        return {"classifier": engine.stats() if engine else None,
                "lm": lm_server.stats() if lm_server else None,
                "uptime_s": round(self.registry.uptime_s, 3),
                "snapshot_at": _time.monotonic()}


class _Handler(ServingHTTPMixin, BaseHTTPRequestHandler):
    # _send/_json/_body/_deadline_s + the typed-failure -> status
    # mapping come from ServingHTTPMixin (serving/resilience.py), shared
    # with the fleet front so the two HTTP contracts cannot drift.

    @property
    def state(self) -> _UiState:
        return self.server.ui_state  # type: ignore[attr-defined]

    def _html(self, body: str) -> None:
        self._send(200, "text/html; charset=utf-8", body.encode())

    # ---- GET --------------------------------------------------------------
    def do_GET(self) -> None:  # noqa: N802
        s = self.state
        path, _, query = self.path.partition("?")
        if path in ("/", "/index.html"):
            self._html(_DASHBOARD)
            return
        if path == "/metrics":
            # Prometheus text exposition of everything registered on
            # this server (serving planes, breaker, page pool, compile
            # counter, uptime) — ISSUE-8
            self._send(200, EXPOSITION_CONTENT_TYPE,
                       s.registry.exposition().encode())
            return
        if path == "/trace/recent":
            # recent request traces (bounded ring); ?format=chrome
            # returns Chrome trace-event JSON (Perfetto-loadable)
            traces = s.tracer.recent()
            if "format=chrome" in query:
                self._json(200, chrome_trace(traces))
            else:
                self._json(200, {"traces": traces,
                                 "recorded": s.tracer.recorded})
            return
        if path == "/serving/stats":
            self._json(200, s.serving_stats())
            return
        if self.path == "/healthz":
            # liveness: answering at all is the signal
            self._json(200, {"ok": True})
            return
        if self.path == "/readyz":
            # readiness: every registered serving plane must be
            # accepting admissions with its breaker not open; a drain
            # flips this to 503 before traffic actually stops
            with s.lock:
                engine, lm_server = s.engine, s.lm_server
                draining = s.draining
            reasons = []
            if draining:
                reasons.append("draining")
            if engine is not None and not engine.ready():
                reasons.append("classifier engine not ready")
            if lm_server is not None and not lm_server.ready():
                reasons.append("lm server not ready")
            if reasons:
                self._json(503, {"ready": False, "reasons": reasons},
                           headers={"Retry-After": 1})
            else:
                self._json(200, {"ready": True})
            return
        with s.lock:
            if self.path == "/api/coords":
                self._json(200, {"coords": s.coords})
            elif self.path == "/tsne/coords":
                self._json(200, {"coords": s.tsne_coords,
                                 "labels": s.tsne_labels})
            elif self.path == "/weights":
                hist = [{"iteration": h.get("iteration"),
                         "score": h.get("score")}
                        for h in s.weights_history[-200:]
                        if isinstance(h, dict) and h.get("score") is not None]
                self._json(200, {
                    "count": len(s.weights_history),
                    "history": hist,
                    "last": s.weights_history[-1] if s.weights_history
                    else None})
            elif self.path == "/activations":
                self._json(200, {"activations": s.activations})
            else:
                self._json(404, {"error": f"unknown path {self.path}"})

    # ---- POST -------------------------------------------------------------
    def do_POST(self) -> None:  # noqa: N802
        if self.path == "/lm/admit_pages":
            # binary body (a KV page shipment) — must not go through the
            # JSON parse below
            self._lm_admit_pages()
            return
        try:
            body = self._body()
        except (ValueError, json.JSONDecodeError) as e:
            self._json(400, {"error": str(e)})
            return
        try:
            self._route_post(body)
        except Exception as e:  # noqa: BLE001 — surface as 400, keep serving
            # typed serving failures (UnservableShapeError -> 400,
            # DeadlineExceededError -> 504, overload/unavailable -> 503
            # + Retry-After) map via the shared mixin; anything else is
            # surfaced as 400 so the UI server keeps serving
            if not self.respond_typed_failure(e):
                self._json(400, {"error": repr(e)})

    def _route_post(self, body: Any) -> None:
        s = self.state
        if self.path == "/api/coords":
            with s.lock:
                s.coords = body["coords"]
            self._json(200, {"count": len(s.coords)})
        elif self.path == "/tsne/upload":
            with s.lock:
                s.tsne_vectors = np.asarray(body["vectors"], np.float32)
                s.tsne_labels = body.get("labels",
                                         [str(i) for i in
                                          range(len(s.tsne_vectors))])
            self._json(200, {"count": len(s.tsne_vectors)})
        elif self.path == "/tsne/generate":
            from deeplearning4j_tpu.plot import Tsne

            with s.lock:
                vectors = s.tsne_vectors
            if vectors is None:
                self._json(400, {"error": "upload vectors first"})
                return
            tsne = Tsne(
                perplexity=float(body.get("perplexity", 30.0)),
                n_iter=int(body.get("iterations", 300)),
                learning_rate=float(body.get("learning_rate", 100.0)))
            coords = tsne.calculate(vectors).tolist()
            with s.lock:
                s.tsne_coords = coords
            self._json(200, {"coords": coords, "labels": s.tsne_labels})
        elif self.path == "/nearestneighbors/upload":
            from deeplearning4j_tpu.clustering import VPTree

            with s.lock:
                s.nn_vectors = np.asarray(body["vectors"], np.float32)
                s.nn_labels = body.get(
                    "labels", [str(i) for i in range(len(s.nn_vectors))])
                s.nn_tree = VPTree(s.nn_vectors, labels=s.nn_labels,
                                   distance=body.get("distance", "euclidean"))
            self._json(200, {"count": len(s.nn_vectors)})
        elif self.path == "/nearestneighbors":
            with s.lock:
                tree, labels, vectors = s.nn_tree, s.nn_labels, s.nn_vectors
            if tree is None:
                self._json(400, {"error": "upload vectors first"})
                return
            k = int(body.get("k", 5))
            if "word" in body:
                if body["word"] not in labels:
                    self._json(404, {"error": f"unknown word {body['word']}"})
                    return
                query = vectors[labels.index(body["word"])]
            else:
                query = np.asarray(body["vector"], np.float32)
            hits = tree.knn(query, k)
            self._json(200, {"neighbors": [
                {"label": lbl, "distance": float(d)} for d, lbl in hits]})
        elif self.path == "/weights":
            with s.lock:
                s.weights_history.append(body)
                if len(s.weights_history) > 1000:
                    s.weights_history = s.weights_history[-1000:]
            self._json(200, {"count": len(s.weights_history)})
        elif self.path == "/activations":
            with s.lock:
                s.activations = body["activations"]
            self._json(200, {"ok": True})
        elif self.path == "/lm/generate":
            self._lm_generate(body)
        elif self.path == "/lm/prefill":
            self._lm_prefill(body)
        elif self.path == "/model/predict":
            # Batched classifier inference (UiServer.serve_model): the
            # request's rows ride whatever coalesced dispatch the
            # micro-batcher forms with concurrently-arriving requests.
            with s.lock:
                engine, stopping = s.engine, s.draining
            if engine is None:
                if stopping:
                    # the model WAS here — the server is draining or
                    # mid-stop (stop() nulls the engine while handler
                    # threads may still be running).  503, never 400: a
                    # fleet router must fail this request over, not
                    # blame the payload
                    raise ServingUnavailableError(
                        "server stopped: model unregistered")
                self._json(400, {"error": "no model registered: call "
                                          "UiServer.serve_model(net)"})
                return
            feats = body.get("features")
            if not feats:
                self._json(400, {"error": "features required"})
                return
            try:
                deadline_s = self._deadline_s(body)
                tenant = self._tenant(body)
                x = np.asarray(feats, np.float32)
                # an unknown tenant raises ValueError from the
                # batcher's registry normalize -> 400 here; an
                # over-quota tenant raises TenantQuotaError -> the
                # typed 429 + Retry-After mapping in do_POST
                probs = engine.predict_proba(x, deadline_s=deadline_s,
                                             request_id=self.request_id(),
                                             tenant=tenant)
            except (ValueError, TypeError) as e:
                self._json(400, {"error": str(e)})
                return
            self._json(200, {
                "predictions": np.argmax(probs, axis=-1).tolist(),
                "outputs": np.asarray(probs).tolist()})
        else:
            self._json(404, {"error": f"unknown path {self.path}"})

    def _lm_generate(self, body: Any) -> None:
        """POST /lm/generate — LM serving the 2015 reference never had.
        Greedy / plain-temperature requests go through the continuous
        slot-decode pool; top-k/top-p/beam take the whole-sequence
        KV-cached path.  Oversized requests are client errors (400 with
        the limit), never a silently-clipped cache write."""
        s = self.state
        with s.lock:
            lm, lm_server = s.lm, s.lm_server
            stopping = s.draining
        if lm is None:
            if stopping:
                # same stop-race rule as /model/predict: a draining or
                # stopped server answers 503 (fail over), never 400
                raise ServingUnavailableError(
                    "server stopped: LM unregistered")
            self._json(400, {"error": "no LM registered: call "
                                      "UiServer.serve_lm(cfg, params)"})
            return
        cfg, params = lm
        prompt = body.get("prompt_ids")
        if not prompt:
            self._json(400, {"error": "prompt_ids required"})
            return
        from deeplearning4j_tpu.serving.lm import validate_request

        # Validate BEFORE anything touches the fixed-size KV cache, via
        # the ONE shared request contract (serving.lm.validate_request):
        # an oversized request must become a 400 naming the limit, not a
        # dynamic_update_slice running past the cache, and out-of-vocab
        # ids must 400 on EVERY decode path (the whole-sequence legs
        # would otherwise index-clamp them into garbage 200s).
        try:
            max_new = int(body.get("max_new_tokens", 32))
            beams = int(body.get("beam_size", 0))
            temperature = float(body.get("temperature", 0.0))
            top_k = int(body.get("top_k", 0))
            top_p = float(body.get("top_p", 1.0))
            # fold into int32 range: PRNGKey/device seed dtype
            seed = int(body.get("seed", 0)) & 0x7FFFFFFF
            deadline_s = self._deadline_s(body)
            session_id = self._session_id(body)
            stream = bool(body.get("stream", False))
            # admission class (ISSUE-15): validated HERE so an unknown
            # class is a 400 naming the vocabulary, never a silent
            # default; accepted on every front — fleet or bare serve
            from deeplearning4j_tpu.serving.pressure import (
                normalize_priority,
            )

            priority = normalize_priority(body.get("priority"))
            # billing identity (ISSUE-16): validated HERE against the
            # pool's registry so an unknown tenant is a 400 naming the
            # registered vocabulary on EVERY decode path — including
            # the whole-sequence beam/top-k legs that never reach the
            # continuous pool's own normalize
            tenant = self._tenant(body)
            if tenant is not None:
                reg = (lm_server.tenants if lm_server is not None
                       else None)
                if reg is not None:
                    tenant = reg.normalize(tenant)
                elif tenant != "default":
                    raise ValueError(
                        f"unknown tenant {tenant!r}: no tenant "
                        f"registry is installed (serve -tenants)")
            ids_list = validate_request(cfg, prompt, max_new)
            if temperature < 0:
                raise ValueError(f"temperature must be >= 0, "
                                 f"got {temperature}")
            if top_k < 0:
                raise ValueError(f"top_k must be >= 0, got {top_k}")
            if not 0.0 < top_p <= 1.0:
                raise ValueError(f"top_p must be in (0, 1], got {top_p}")
            # unsupported-combo validation at ADMISSION (ISSUE-13): a
            # client explicitly asking for speculative decode on a pool
            # that cannot provide it (speculation off, or no
            # continuous pool at all) gets a typed 400 naming why, not
            # a silently different execution plan.  Sampling lanes on a
            # speculating pool are NOT an error: they ride the same
            # dispatches and fall back to 1-token decode per round.
            if bool(body.get("speculate", False)):
                if lm_server is None:
                    raise ValueError(
                        "speculate requested but no continuous LM pool "
                        "is registered (continuous=False)")
                if lm_server.speculate == "off":
                    raise ValueError(
                        "speculate requested but the pool was started "
                        "with speculation off (serve with -lm-speculate "
                        "ngram|model)")
            if stream:
                # SSE rides the continuous pool's per-token commits; the
                # whole-sequence legs decode in one uninterruptible scan
                # and have nothing to stream — a typed 400 naming why,
                # not a silently-buffered fake stream
                if lm_server is None:
                    raise ValueError(
                        "stream requested but no continuous LM pool is "
                        "registered (continuous=False)")
                if beams > 1 or top_k > 0 or top_p < 1.0:
                    raise ValueError(
                        "stream requires the continuous greedy/"
                        "temperature path: top-k/top-p/beam decode "
                        "whole-sequence and cannot stream")
        except (ValueError, TypeError) as e:
            # bad prompt/params (incl. null/list-valued knobs) -> 400
            payload = {"error": str(e)}
            if "max_len" in payload["error"]:
                payload["max_len"] = cfg.max_len
            self._json(400, payload)
            return
        try:
            if beams > 1:
                from deeplearning4j_tpu.parallel import beam_search

                out, scores = beam_search(
                    cfg, params, np.asarray([ids_list], np.int32),
                    max_new_tokens=max_new, beam_size=beams)
                self._json(200, {"ids": np.asarray(out)[0].tolist(),
                                 "score": float(scores[0])})
                return
            if stream:
                # SSE: admission (and its typed failures) happens HERE,
                # before any response byte commits; tokens then flow as
                # events from the worker's per-commit pushes
                gen = lm_server.generate_stream(
                    ids_list, max_new, temperature=temperature,
                    seed=seed, deadline_s=deadline_s,
                    request_id=self.request_id(), session_id=session_id,
                    priority=priority, tenant=tenant)
                self._sse_stream(gen, ids_list)
                return
            if (lm_server is not None and top_k == 0 and top_p >= 1.0):
                # continuous path: the request shares the slot pool with
                # whatever else is decoding right now
                ids = lm_server.generate(ids_list, max_new,
                                         temperature=temperature,
                                         seed=seed, deadline_s=deadline_s,
                                         request_id=self.request_id(),
                                         session_id=session_id,
                                         priority=priority,
                                         tenant=tenant)
                self._json(200, {"ids": ids})
                return
            import jax

            from deeplearning4j_tpu.parallel import generate

            out = generate(
                cfg, params, np.asarray([ids_list], np.int32),
                max_new_tokens=max_new, temperature=temperature,
                top_k=top_k, top_p=top_p, rng=jax.random.PRNGKey(seed))
        except (ValueError, TypeError) as e:
            self._json(400, {"error": str(e)})
            return
        self._json(200, {"ids": np.asarray(out)[0].tolist()})

    def _session_id(self, body: Any) -> Optional[str]:
        """Per-request `"session_id"` (ISSUE-14 satellite): accepted on
        every front — fleet or bare `serve` — so clients write ONE
        payload shape; a non-scalar value is the client's 400."""
        sid = body.get("session_id")
        if sid is None:
            return None
        if not isinstance(sid, (str, int)):
            raise ValueError(
                f"session_id must be a string or int, got "
                f"{type(sid).__name__}")
        sid = str(sid)
        if not 0 < len(sid) <= 128:
            raise ValueError("session_id must be 1..128 characters")
        return sid

    # _tenant (the JSON-field / X-Tenant extraction) lives on
    # ServingHTTPMixin, shared with the fleet front so the two HTTP
    # tenant contracts cannot drift (ISSUE-16)

    def _sse_stream(self, gen, prompt_ids: List[int]) -> None:
        """Relay one token stream as Server-Sent Events: one `data:`
        event per committed token, a final `done` event with the full
        ids (so `concat(token events)` and the non-streamed body are
        mutually checkable), an `error` event if the decode fails
        mid-stream.  The response is close-delimited (no
        Content-Length).  A client that disconnects mid-stream raises
        on the write; closing the generator (finally) abandons the
        request so its slot and pages free at the next admit round."""
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        rid = getattr(self, "_request_id", None)
        if rid is not None:
            self.send_header("X-Request-Id", rid)
        self.end_headers()
        toks: List[int] = []
        try:
            try:
                for tok in gen:
                    toks.append(int(tok))
                    self.wfile.write(
                        b"data: " + json.dumps(
                            {"token": int(tok),
                             "index": len(toks) - 1}).encode() + b"\n\n")
                    self.wfile.flush()
                self.wfile.write(
                    b"event: done\ndata: " + json.dumps(
                        {"ids": list(prompt_ids) + toks}).encode()
                    + b"\n\n")
                self.wfile.flush()
            except (BrokenPipeError, ConnectionResetError):
                # mid-stream disconnect: nothing to answer; the finally
                # below closes the generator, which abandons the request
                pass
            except Exception as e:  # noqa: BLE001 — headers already sent; the error must ride the stream
                try:
                    self.wfile.write(
                        b"event: error\ndata: " + json.dumps(
                            {"error": str(e)}).encode() + b"\n\n")
                    self.wfile.flush()
                except OSError:
                    pass
        finally:
            gen.close()

    def _lm_prefill(self, body: Any) -> None:
        """POST /lm/prefill — the disaggregated prefill half: normal
        admission and chunked prefill, but the answer is the lane's KV
        page shipment (binary, serving/transfer.py wire format) instead
        of a decoded sequence."""
        s = self.state
        with s.lock:
            lm_server = s.lm_server
            stopping = s.draining
        if lm_server is None:
            if stopping:
                raise ServingUnavailableError(
                    "server stopped: LM unregistered")
            self._json(400, {"error": "no continuous LM pool registered: "
                                      "call UiServer.serve_lm(cfg, "
                                      "params)"})
            return
        prompt = body.get("prompt_ids")
        if not prompt:
            self._json(400, {"error": "prompt_ids required"})
            return
        if not lm_server.ship:
            # typed on the WIRE (the same kind the admit leg's 422
            # carries): "this worker cannot ship" must be machine-
            # distinguishable from "this request is bad everywhere" —
            # the router recomputes on the former and propagates the
            # latter, and substring-matching error text would rot
            self._json(422, {"error": "this worker does not ship KV "
                                      "pages (started without -lm-ship "
                                      "or with dense KV)",
                             "kind": "page_ship"})
            return
        from deeplearning4j_tpu.serving.transfer import serialize_export

        try:
            export = lm_server.prefill_export(
                prompt, int(body.get("max_new_tokens", 32)),
                temperature=float(body.get("temperature", 0.0)),
                seed=int(body.get("seed", 0)) & 0x7FFFFFFF,
                deadline_s=self._deadline_s(body),
                request_id=self.request_id(),
                session_id=self._session_id(body),
                priority=body.get("priority"),
                tenant=self._tenant(body))
        except (ValueError, TypeError) as e:
            self._json(400, {"error": str(e)})
            return
        self._send(200, "application/octet-stream",
                   serialize_export(export))

    def _lm_admit_pages(self) -> None:
        """POST /lm/admit_pages — the disaggregated decode half: a
        binary KV page shipment in, `{"ids": [...]}` out.  Integrity or
        geometry failures are a typed 422 (`kind: "page_ship"`) — the
        router's signal to recompute locally, distinct from the 4xx
        family that means the REQUEST is bad everywhere."""
        from deeplearning4j_tpu.serving.transfer import (
            PageShipError,
            deserialize_export,
        )

        s = self.state
        with s.lock:
            lm_server = s.lm_server
            stopping = s.draining
        try:
            if lm_server is None:
                if stopping:
                    raise ServingUnavailableError(
                        "server stopped: LM unregistered")
                self._json(400, {"error": "no continuous LM pool "
                                          "registered"})
                return
            length = int(self.headers.get("Content-Length", 0))
            data = self.rfile.read(length) if length else b""
            export = deserialize_export(data)
            ids = lm_server.admit_with_pages(
                export, deadline_s=self._deadline_s({}),
                request_id=self.request_id())
            self._json(200, {"ids": ids})
        except PageShipError as e:
            self._json(422, {"error": str(e), "kind": "page_ship"})
        except Exception as e:  # noqa: BLE001 — binary leg bypasses do_POST's mapper; same policy applied here
            if not self.respond_typed_failure(e):
                if isinstance(e, (ValueError, TypeError)):
                    self._json(400, {"error": str(e)})
                else:
                    self._json(500, {"error": repr(e)})


class UiServer:
    """`UiServer(port=0).start()`; `.url` for clients; `.stop()` to halt."""

    def __init__(self, host: str = "127.0.0.1", port: int = 8080):
        self._server = _UiHTTPServer((host, port), _Handler)
        self._server.ui_state = _UiState()  # type: ignore[attr-defined]
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True)

    @property
    def url(self) -> str:
        host, port = self._server.server_address[:2]
        return f"http://{host}:{port}"

    @property
    def state(self) -> _UiState:
        return self._server.ui_state  # type: ignore[attr-defined]

    @property
    def registry(self) -> MetricsRegistry:
        """The server's metrics registry (rendered at GET /metrics)."""
        return self.state.registry

    @property
    def tracer(self) -> TraceRecorder:
        """The server's trace ring (served at GET /trace/recent)."""
        return self.state.tracer

    def serve_lm(self, cfg, params, slots: int = 4,
                 continuous: bool = True,
                 max_queue_depth: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 breaker_threshold: Optional[int] = 5,
                 breaker_cooldown_s: float = 1.0,
                 page_size: int = 16, pages: Optional[int] = None,
                 prefill_chunk: int = 8, speculate: str = "off",
                 draft_len: int = 4, ship: bool = False,
                 preempt: bool = False, swap_bytes: int = 64 << 20,
                 brownout=None, tenants=None,
                 hibernate_idle_s: Optional[float] = None,
                 state_dir: Optional[str] = None,
                 state_disk_bytes: int = 1 << 30,
                 swap_quantize: bool = True,
                 state_rows: Optional[int] = None,
                 snapshot_every: Optional[int] = None,
                 denoise_steps: Optional[int] = None,
                 unmask: Optional[str] = None,
                 tau: Optional[float] = None) -> "UiServer":
        """Register a TransformerLM for POST /lm/generate.  With
        `continuous` (default) greedy/temperature requests decode in a
        `slots`-lane continuous batching pool; `continuous=False` keeps
        every request on the whole-sequence path.  `max_queue_depth`,
        `default_deadline_s` and the breaker knobs configure the
        serving-plane resilience layer (docs/robustness.md).
        `page_size`, `pages` and `prefill_chunk` configure the paged KV
        pool with radix prefix reuse (docs/performance.md "The KV
        memory cost model"); on a TPU its steps attend through the fused
        paged-attention kernel (docs/performance.md "The
        paged-attention kernel cost model").  `speculate`
        ("ngram"/"model") turns on speculative multi-token decode for
        greedy lanes with up to `draft_len` drafts per round (sampling
        lanes fall back to 1-token decode — docs/performance.md "The
        speculative decode cost model").  `preempt`/`swap_bytes` turn
        on priority preemption with host KV swap-out and `brownout`
        (True or a `PressureConfig`) the degradation ladder — the
        overload-survival plane (docs/robustness.md "The degradation
        ladder").  `tenants` (a `TenantRegistry`, spec mapping, or the
        `-tenants` JSON text) installs the multi-tenant traffic-shaping
        plane: per-tenant WFQ ordering, token-bucket quotas (429 +
        Retry-After), and SLO burn-rate accounting (docs/robustness.md
        "Tenancy & SLOs").  `hibernate_idle_s`/`state_dir`/
        `state_disk_bytes` configure the tiered KV state hierarchy
        (ISSUE-19): idle sticky sessions hibernate to the host tier and
        spill to an integrity-checked disk tier, resuming
        byte-identically — even after a process restart over the same
        `state_dir`; `swap_quantize=False` keeps swap/hibernate frames
        exact instead of per-page int8 (docs/robustness.md "The state
        hierarchy").  `state_rows` and `snapshot_every` size the state
        pool of a model with recurrent layers (live lanes and the radix
        tree's snapshots share its rows; a prompt leaves a snapshot every
        `snapshot_every` tokens) and are a `ValueError` for any other
        model; such a model is refused `speculate`, `ship`, `preempt` and
        hibernation (`UnsupportedLayerKind`).  `denoise_steps`, `unmask`
        ("static" | "dynamic") and `tau` set the unmasking schedule of a
        block-diffusion model (`cfg.block_length` B > 1): B / steps
        positions a denoise round, or every position whose confidence
        passes `tau`; a stream then yields committed blocks, at most B
        tokens at once; such a model is refused `speculate` and `ship`
        and a request's temperature, and the options are a `ValueError`
        for a causal model (docs/performance.md "The block round")."""
        lm_server = None
        if continuous:
            from deeplearning4j_tpu.serving import (
                CircuitBreaker,
                ContinuousLMServer,
            )

            breaker = (CircuitBreaker(failure_threshold=breaker_threshold,
                                      cooldown_s=breaker_cooldown_s)
                       if breaker_threshold else None)
            lm_server = ContinuousLMServer(
                cfg, params, slots=slots, max_queue_depth=max_queue_depth,
                default_deadline_s=default_deadline_s, breaker=breaker,
                page_size=page_size, pages=pages,
                prefill_chunk=prefill_chunk, speculate=speculate,
                draft_len=draft_len, ship=ship, preempt=preempt,
                swap_bytes=swap_bytes, brownout=brownout,
                tenants=tenants,
                hibernate_idle_s=hibernate_idle_s, state_dir=state_dir,
                state_disk_bytes=state_disk_bytes,
                swap_quantize=swap_quantize,
                state_rows=state_rows, snapshot_every=snapshot_every,
                denoise_steps=denoise_steps, unmask=unmask, tau=tau,
                tracer=self.state.tracer,
                registry=self.state.registry)
        with self.state.lock:
            self.state.lm = (cfg, params)
            old = self.state.lm_server
            self.state.lm_server = lm_server
        if old is not None:
            old.stop()
        return self

    def serve_model(self, net, max_batch: int = 32,
                    max_wait_ms: float = 2.0, ladder=None,
                    warmup_example=None,
                    max_queue_depth: Optional[int] = None,
                    default_deadline_s: Optional[float] = None,
                    breaker_threshold: Optional[int] = 5,
                    breaker_cooldown_s: float = 1.0,
                    quantize: Optional[str] = None,
                    tenants=None) -> "UiServer":
        """Register a MultiLayerNetwork behind the dynamic micro-batcher
        for POST /model/predict.  `warmup_example` (one example row) pre-
        compiles every bucket-ladder shape before traffic.
        `max_queue_depth`, `default_deadline_s` and the breaker knobs
        configure the serving-plane resilience layer; `quantize="int8"`
        serves per-channel int8 weights (precision plane,
        docs/performance.md); `tenants` installs the per-tenant quota
        gate on the micro-batcher (ISSUE-16, docs/robustness.md
        "Tenancy & SLOs")."""
        from deeplearning4j_tpu.serving import ServingEngine

        engine = ServingEngine(net, ladder=ladder, max_batch=max_batch,
                               max_wait_ms=max_wait_ms,
                               max_queue_depth=max_queue_depth,
                               default_deadline_s=default_deadline_s,
                               breaker_threshold=breaker_threshold,
                               breaker_cooldown_s=breaker_cooldown_s,
                               quantize=quantize,
                               tracer=self.state.tracer,
                               registry=self.state.registry,
                               tenants=tenants)
        if warmup_example is not None:
            engine.warmup(warmup_example)
        with self.state.lock:
            old = self.state.engine
            self.state.engine = engine
        if old is not None:
            old.stop()
        return self

    def start(self) -> "UiServer":
        self._thread.start()
        return self

    # ---- drain lifecycle (the `dl4j serve` SIGTERM path) ------------------

    def serving_stats(self) -> dict:
        """The /serving/stats payload, host-side (drain snapshots it) —
        the same builder the HTTP endpoint serves."""
        return self.state.serving_stats()

    def begin_drain(self) -> None:
        """Stop admission on every registered serving plane: new
        requests 503 and /readyz flips to not-ready, while queued and
        in-flight work keeps running."""
        with self.state.lock:
            self.state.draining = True
            engine, lm_server = self.state.engine, self.state.lm_server
        if engine is not None:
            engine.begin_drain()
        if lm_server is not None:
            lm_server.begin_drain()

    def drain(self, grace_s: float = 5.0) -> bool:
        """Graceful drain: stop admission, then give in-flight work up
        to `grace_s` (total) to finish.  Returns True when every plane
        fully drained.  The HTTP server keeps answering /healthz,
        /readyz and /serving/stats throughout; call `stop()` after."""
        self.begin_drain()
        with self.state.lock:
            engine, lm_server = self.state.engine, self.state.lm_server
        import time as _time

        deadline = _time.perf_counter() + max(0.0, grace_s)
        drained = True
        if engine is not None:
            drained &= engine.drain(
                max(0.0, deadline - _time.perf_counter()))
        if lm_server is not None:
            drained &= lm_server.drain(
                max(0.0, deadline - _time.perf_counter()))
        return drained

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        with self.state.lock:
            # handler threads that got in before the close may read the
            # nulled planes: `draining` makes them answer 503 (so a
            # fleet router fails over), not 400.  `lm` must null too —
            # a non-None (cfg, params) would route a stop-racing
            # /lm/generate down the unmanaged whole-sequence fallback
            # (fresh compile, no admission) instead of the 503
            self.state.draining = True
            engine, lm_server = self.state.engine, self.state.lm_server
            self.state.engine = None
            self.state.lm = None
            self.state.lm_server = None
        if engine is not None:
            engine.stop()
        if lm_server is not None:
            lm_server.stop()
