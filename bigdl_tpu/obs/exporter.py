"""Live operational endpoint — /metrics, /healthz, /statusz over stdlib http.

One daemon ``ThreadingHTTPServer`` (no third-party deps) turns the passive
in-process telemetry rails into a scrapeable plane:

- ``/metrics`` — Prometheus text exposition rendered from ONE
  :meth:`MetricRegistry.snapshot` (counters as ``_total``, histograms as
  summaries with p50/p95/p99 ``quantile`` labels + ``_sum``/``_count``,
  gauges as-is), plus per-tenant serving gauges labelled
  ``{tenant="<engine name>"}`` fed live from each registered
  ``ServingEngine.stats()`` — the feed the fleet router dispatches off —
  and, per registered :class:`~bigdl_tpu.serving.fleet.FleetRouter`,
  router counters ``{fleet=...}`` plus per-replica load/health gauges
  ``{fleet=...,replica=...}``.
- ``/healthz`` — the serving health state machine per engine, watchdog arm
  state (armed / disarmed, dump count), SLO breach state, and per-fleet
  replica health. HTTP 503 when any engine is ``dead``, 200 otherwise —
  load-balancer-pollable. A dead REPLICA whose fleet still has a healthy
  peer degrades the fleet instead of 503ing the process: the router is
  routing around it, which is the design working, not an outage.
- ``/statusz`` — JSON status: the latest run report (published by the
  trainer at end of run), MFU accounting, the process's compile phases and
  the programs that took longest to compile, full engine ledgers, SLO state,
  the device-memory picture, and — under ``BIGDL_OBS_SPOOL_DIR`` — a
  per-host table merged from the cluster spools (``obs/cluster.py``).
- ``/profilez?seconds=N`` — on-demand ``jax.profiler.trace`` capture into
  ``BIGDL_TRACE_DIR``; responds with the artifact path when the capture
  completes, 409 while another capture runs (``bigdl-tpu prof`` is the
  CLI form).

Under ``BIGDL_OBS_SPOOL_DIR`` the ``/metrics`` body additionally carries
every spooled host's snapshot with a ``{host="<id>"}`` label — one scrape
of process 0 sees the whole job (stale hosts are stamped
``bigdl_obs_host_up 0``, never dropped).

The exporter is strictly opt-in: :func:`start_from_env` returns ``None``
without allocating ANYTHING when ``BIGDL_METRICS_PORT`` is unset — the
zero-alloc pin is :data:`_SERVERS_CREATED`, mirroring the tracer's
``_SPANS_CREATED``. Port ``0`` binds an ephemeral port (tests).

Engines register themselves (``register_engine`` on start, ``unregister``
on supervisor exit); ``SnapshotServer`` registers all its tenants up front
so the per-tenant rows exist before first traffic. Registration holds weak
references only — a dropped engine disappears from the endpoint.
"""

from __future__ import annotations

import json
import os
import re
import tempfile
import threading
import time
import urllib.parse
import weakref
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from bigdl_tpu.obs import cluster, mfu
from bigdl_tpu.obs import compile as obs_compile
from bigdl_tpu.obs import watchdog as obs_watchdog
from bigdl_tpu.obs.registry import registry

#: exporter instances ever constructed — pins the zero-alloc disabled path
#: (start_from_env with no BIGDL_METRICS_PORT must leave this untouched)
_SERVERS_CREATED = 0

_ENGINES: "weakref.WeakSet" = weakref.WeakSet()
_FLEETS: "weakref.WeakSet" = weakref.WeakSet()
_STATUS: dict = {}
_STATUS_LOCK = threading.Lock()
_ACTIVE: Optional["MetricsExporter"] = None
_ACTIVE_LOCK = threading.Lock()

#: mirror of the serving health state machine (obs must not import serving)
_HEALTH_CODE = {"starting": 0, "ready": 1, "degraded": 2, "draining": 3,
                "dead": 4}

#: numeric ServingEngine.stats() fields exported per tenant
_TENANT_FIELDS = ("backlog", "queued", "active_slots", "submitted",
                  "completed", "timeouts", "shed", "respawns",
                  "poisoned_slots", "slot_recycles", "decode_tps",
                  "queue_depth", "decode_rate", "est_wait_ms",
                  "prefix_hits", "prefix_tokens_saved", "prefix_bytes",
                  "spec_acceptance", "model_version", "pages_used",
                  "pages_free", "free_page_ratio", "page_evictions")

#: numeric per-replica fields exported under {fleet=...,replica=...} — the
#: router's own dispatch signal, scrapeable by external load balancers
_REPLICA_FIELDS = ("queue_depth", "active_slots", "est_wait_ms",
                   "decode_rate", "completed", "shed", "pages_free",
                   "free_page_ratio", "prefill_inflight")

#: numeric FleetRouter.stats() counters exported under {fleet=...}
_FLEET_FIELDS = ("healthy_replicas", "dispatched", "retries",
                 "replica_downs", "rejected", "handoffs",
                 "handoff_failures")


def register_engine(engine) -> None:
    """Expose an engine's stats() on /metrics and /healthz (weakly held)."""
    _ENGINES.add(engine)


def unregister_engine(engine) -> None:
    _ENGINES.discard(engine)


def engines() -> list:
    return list(_ENGINES)


def register_fleet(fleet) -> None:
    """Expose a FleetRouter's stats() — router counters and per-replica
    gauges — on /metrics, /healthz, /statusz (weakly held)."""
    _FLEETS.add(fleet)


def unregister_fleet(fleet) -> None:
    _FLEETS.discard(fleet)


def fleets() -> list:
    return list(_FLEETS)


def publish_status(key: str, value) -> None:
    """Publish a JSON-able blob under /statusz (e.g. the end-of-run report)."""
    with _STATUS_LOCK:
        _STATUS[key] = value


def _san(name: str) -> str:
    """Registry name → Prometheus metric name: train/step_wall →
    bigdl_train_step_wall."""
    return "bigdl_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)


def _fmt(v) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    return repr(float(v))


def render_metrics() -> str:
    """The /metrics body: one registry snapshot + per-tenant engine gauges."""
    snap = registry.snapshot()
    lines = []
    for name, v in sorted(snap["counters"].items()):
        m = _san(name) + "_total"
        lines.append("# TYPE %s counter" % m)
        lines.append("%s %s" % (m, _fmt(v)))
    for name, v in sorted(snap["gauges"].items()):
        m = _san(name)
        lines.append("# TYPE %s gauge" % m)
        lines.append("%s %s" % (m, _fmt(v)))
    for name, h in sorted(snap["histograms"].items()):
        m = _san(name)
        lines.append("# TYPE %s summary" % m)
        for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            if h.get(key) is not None:
                lines.append('%s{quantile="%s"} %s' % (m, q, _fmt(h[key])))
        lines.append("%s_sum %s" % (m, _fmt(h["total"])))
        lines.append("%s_count %s" % (m, _fmt(h["count"])))
    # per-tenant serving gauges: group by field so each metric name carries
    # exactly one TYPE line with all tenant label rows under it
    per_field: dict = {}
    health_rows = []
    for eng in engines():
        try:
            st = eng.stats()
        except Exception:
            continue
        tenant = str(st.get("name", "?"))
        for field in _TENANT_FIELDS:
            v = st.get(field)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                per_field.setdefault(field, []).append((tenant, v))
        health_rows.append((tenant, _HEALTH_CODE.get(st.get("health"), -1),
                            bool(st.get("slo_degraded"))))
    for field in sorted(per_field):
        m = "bigdl_serving_tenant_" + field
        lines.append("# TYPE %s gauge" % m)
        for tenant, v in per_field[field]:
            lines.append('%s{tenant="%s"} %s' % (m, tenant, _fmt(v)))
    if health_rows:
        lines.append("# TYPE bigdl_serving_tenant_health gauge")
        for tenant, code, _ in health_rows:
            lines.append('bigdl_serving_tenant_health{tenant="%s"} %d'
                         % (tenant, code))
        lines.append("# TYPE bigdl_serving_tenant_slo_degraded gauge")
        for tenant, _, slo in health_rows:
            lines.append('bigdl_serving_tenant_slo_degraded{tenant="%s"} %d'
                         % (tenant, 1 if slo else 0))
    # fleet router counters {fleet=...} + per-replica gauges
    # {fleet=...,replica=...}: same group-by-field layout as tenants
    fleet_rows: dict = {}
    rep_rows: dict = {}
    rep_health: list = []
    for fl in fleets():
        try:
            st = fl.stats()
        except Exception:
            continue
        fname = str(st.get("name", "?"))
        for field in _FLEET_FIELDS:
            v = st.get(field)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                fleet_rows.setdefault(field, []).append((fname, v))
        for rname, rst in sorted(st.get("replicas", {}).items()):
            for field in _REPLICA_FIELDS:
                v = rst.get(field)
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    rep_rows.setdefault(field, []).append((fname, rname, v))
            rep_health.append(
                (fname, rname, _HEALTH_CODE.get(rst.get("health"), -1)))
    for field in sorted(fleet_rows):
        m = "bigdl_fleet_" + field
        lines.append("# TYPE %s gauge" % m)
        for fname, v in fleet_rows[field]:
            lines.append('%s{fleet="%s"} %s' % (m, fname, _fmt(v)))
    for field in sorted(rep_rows):
        m = "bigdl_fleet_replica_" + field
        lines.append("# TYPE %s gauge" % m)
        for fname, rname, v in rep_rows[field]:
            lines.append('%s{fleet="%s",replica="%s"} %s'
                         % (m, fname, rname, _fmt(v)))
    if rep_health:
        lines.append("# TYPE bigdl_fleet_replica_health gauge")
        for fname, rname, code in rep_health:
            lines.append('bigdl_fleet_replica_health{fleet="%s",'
                         'replica="%s"} %d' % (fname, rname, code))
    # cluster merge: every spooled host's snapshot rides the same scrape
    # with a {host=} label ([] when BIGDL_OBS_SPOOL_DIR is unset — and a
    # corrupt/stale spool degrades to a stamped row, never a failed scrape)
    try:
        lines.extend(cluster.render_host_lines())
    except Exception:
        pass
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------- profiler capture
class ProfilerBusy(RuntimeError):
    """A /profilez capture is already running (HTTP 409)."""


_PROFILE_LOCK = threading.Lock()
_PROFILE_BUSY = False
_PROFILE_SEQ = 0
#: upper bound on one capture (a typo'd ?seconds= must not wedge the server
#: thread pool for an hour)
_PROFILE_MAX_S = 120.0


def profilez_capture(seconds: float) -> str:
    """Run one ``jax.profiler.trace`` capture of ``seconds`` and return the
    artifact directory (under ``BIGDL_TRACE_DIR``, else a tmpdir). Raises
    :class:`ProfilerBusy` while another capture runs — captures serialize,
    they never stack."""
    global _PROFILE_BUSY, _PROFILE_SEQ
    seconds = min(max(float(seconds), 0.01), _PROFILE_MAX_S)
    with _PROFILE_LOCK:
        if _PROFILE_BUSY:
            raise ProfilerBusy("a profiler capture is already running")
        _PROFILE_BUSY = True
        _PROFILE_SEQ += 1
        seq = _PROFILE_SEQ
    try:
        from bigdl_tpu.utils.faults import SITE_PROFILEZ_CAPTURE, fault_point
        fault_point(SITE_PROFILEZ_CAPTURE)
        base = os.environ.get("BIGDL_TRACE_DIR", "").strip() or os.path.join(
            tempfile.gettempdir(), "bigdl-profilez")
        out = os.path.join(base, "profilez-%d-%d" % (os.getpid(), seq))
        os.makedirs(out, exist_ok=True)
        import jax
        jax.profiler.start_trace(out)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        registry.counter("obs/profilez_captures").inc()
        return out
    finally:
        with _PROFILE_LOCK:
            _PROFILE_BUSY = False


def _render_profilez(path: str) -> "tuple[int, bytes, str]":
    """(status, body, content-type) for GET /profilez?seconds=N."""
    query = urllib.parse.parse_qs(urllib.parse.urlparse(path).query)
    try:
        seconds = float(query.get("seconds", ["1"])[0])
    except ValueError:
        return (400, b'{"error": "seconds must be a number"}\n',
                "application/json")
    try:
        artifact = profilez_capture(seconds)
    except ProfilerBusy as exc:
        return (409, json.dumps({"error": str(exc)}).encode() + b"\n",
                "application/json")
    except Exception as exc:
        # fault-injected or real capture failure: loud, but the endpoint
        # (and the process it observes) keeps serving
        registry.counter("obs/profilez_failures").inc()
        return (503, json.dumps(
            {"error": "profiler capture failed: %s" % exc}).encode() + b"\n",
            "application/json")
    body = json.dumps({"artifact": artifact,
                       "seconds": min(max(seconds, 0.01), _PROFILE_MAX_S)})
    return 200, body.encode() + b"\n", "application/json"


def parse_metrics(text: str) -> dict:
    """Prometheus text → ``{"name" or 'name{labels}': float}``. The inverse
    of :func:`render_metrics` for the round-trip test and ``cli top``."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        out[key] = float(val)
    return out


def render_healthz() -> "tuple[int, dict]":
    """(http status, payload) for /healthz. A dead engine 503s the process
    UNLESS it is a fleet replica with a healthy peer — the router is
    routing around it (the fleet block below shows which), so the process
    still serves."""
    engs = {}
    for eng in engines():
        try:
            st = eng.stats()
        except Exception:
            continue
        engs[str(st.get("name", "?"))] = {
            "health": st.get("health"),
            "backlog": st.get("backlog"),
            "active_slots": st.get("active_slots"),
            "slo_degraded": bool(st.get("slo_degraded")),
        }
    fleet_block = {}
    covered: set = set()   # replica names whose fleet still has a healthy peer
    for fl in fleets():
        try:
            st = fl.stats()
        except Exception:
            continue
        reps = {rn: rs.get("health")
                for rn, rs in st.get("replicas", {}).items()}
        healthy = int(st.get("healthy_replicas", 0))
        fleet_block[str(st.get("name", "?"))] = {
            "replicas": reps, "healthy_replicas": healthy}
        if healthy > 0:
            covered.update(reps)
    states = [(name, e["health"]) for name, e in engs.items()]
    # fleet replicas count even when the engine never started (lazy start
    # means it never self-registered) — the fleet block is the only place
    # such a replica's death is visible
    fleet_states = [(rn, h) for fb in fleet_block.values()
                    for rn, h in fb["replicas"].items()]
    status = "ok"
    code = 200
    if any(s == "dead" and name not in covered for name, s in states):
        status, code = "dead", 503
    elif any(s in ("dead", "degraded", "draining")
             for _, s in states + fleet_states):
        status = "degraded"
    watchdogs = [{"armed": wd.armed, "dumps": wd.dumps, "hard_s": wd.hard_s}
                 for wd in obs_watchdog.active_watchdogs()]
    with _STATUS_LOCK:
        slo = _STATUS.get("slo")
    return code, {"status": status, "engines": engs, "fleets": fleet_block,
                  "watchdogs": watchdogs, "slo": slo, "pid": os.getpid()}


def render_statusz() -> dict:
    """The /statusz payload: run report + MFU + compile phases + engine
    ledgers + SLO."""
    with _STATUS_LOCK:
        status = dict(_STATUS)
    engs = {}
    for eng in engines():
        try:
            st = eng.stats()
        except Exception:
            continue
        engs[str(st.get("name", "?"))] = st
    fls = {}
    for fl in fleets():
        try:
            st = fl.stats()
        except Exception:
            continue
        fls[str(st.get("name", "?"))] = st
    hosts = {}
    try:
        hosts = cluster.host_table()
    except Exception:
        pass
    device_block = None
    try:
        from bigdl_tpu.obs import device as obs_device
        if obs_device.monitor() is not None or obs_device.last_sample():
            device_block = obs_device.stats()
    except Exception:
        pass
    return {"run_report": status.get("run_report"),
            "slo": status.get("slo"),
            "status": status,
            "mfu": mfu.stats(),
            "compile": obs_compile.summary(),
            "device_memory": device_block,
            "hosts": hosts,
            "engines": engs,
            "fleets": fls}


class _Handler(BaseHTTPRequestHandler):
    def do_GET(self):  # noqa: N802 (http.server API)
        try:
            if self.path.startswith("/metrics"):
                code = 200
                body = render_metrics().encode("utf-8")
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            elif self.path.startswith("/healthz"):
                code, payload = render_healthz()
                body = json.dumps(payload, default=str).encode("utf-8")
                ctype = "application/json"
            elif self.path.startswith("/statusz"):
                code = 200
                body = json.dumps(render_statusz(),
                                  default=str).encode("utf-8")
                ctype = "application/json"
            elif self.path.startswith("/profilez"):
                code, body, ctype = _render_profilez(self.path)
            else:
                code, body = 404, b"not found\n"
                ctype = "text/plain"
        except Exception as exc:  # render must never kill the server thread
            code = 500
            body = ("exporter error: %s\n" % exc).encode("utf-8")
            ctype = "text/plain"
        try:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass  # scraper hung up mid-response

    def log_message(self, fmt, *args):  # silence per-request stderr lines
        pass


class MetricsExporter:
    """The endpoint server. ``port=0`` binds an ephemeral port (read back
    from :attr:`port` after :meth:`start`)."""

    def __init__(self, port: int, host: str = "0.0.0.0"):
        global _SERVERS_CREATED
        _SERVERS_CREATED += 1
        self.port = int(port)
        self.host = host
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "MetricsExporter":
        if self._httpd is not None:
            return self
        self._httpd = ThreadingHTTPServer((self.host, self.port), _Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="bigdl-metrics",
            kwargs={"poll_interval": 0.2}, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._httpd is None:
            return
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
        self._httpd = None
        self._thread = None

    @property
    def url(self) -> str:
        return "http://127.0.0.1:%d" % self.port


def start_from_env() -> Optional[MetricsExporter]:
    """Start (once per process) the endpoint when ``BIGDL_METRICS_PORT`` is
    set; return ``None`` — allocating nothing — when it is not. Safe to call
    from every entry point (trainer, engine start, cli): idempotent."""
    raw = os.environ.get("BIGDL_METRICS_PORT", "").strip()
    if not raw:
        return None
    global _ACTIVE
    with _ACTIVE_LOCK:
        if _ACTIVE is not None:
            return _ACTIVE
        try:
            port = int(raw)
        except ValueError:
            raise ValueError(
                "BIGDL_METRICS_PORT=%r is not an integer port" % raw)
        _ACTIVE = MetricsExporter(port).start()
        return _ACTIVE


def active() -> Optional[MetricsExporter]:
    return _ACTIVE


def reset() -> None:
    """Test isolation: stop the active server, drop registrations/status."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        if _ACTIVE is not None:
            _ACTIVE.stop()
        _ACTIVE = None
    _ENGINES.clear()
    _FLEETS.clear()
    with _STATUS_LOCK:
        _STATUS.clear()
