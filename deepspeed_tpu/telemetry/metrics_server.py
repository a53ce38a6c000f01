"""Pull-based metrics endpoint: Prometheus text format over stdlib HTTP.

The monitor backends PUSH events to files/SDKs; external observers (a
prober, a fleet dashboard, ``curl`` during an incident) want to PULL live
state instead. :class:`MetricsServer` serves the
TelemetryHub's counters and gauges — ``Reliability/*`` and ``Anomaly/*``
counts, ``Serving/*`` gauges (prefix-cache counters, latency SLO
percentiles), per-program ``Compile/*`` counters and MFU-attribution gauges
(``program=`` labels), and the flight-recorder occupancy — as Prometheus
exposition text on ``GET /metrics``, plus a trivial ``GET /healthz``.

stdlib-only (`http.server` on a daemon thread); binds 127.0.0.1 by default
and ``port=0`` picks a free port (tests, multi-job hosts). Any object with a
``metrics_snapshot() -> [(event_name, value, kind[, labels])]`` works as the
source; the optional 4th element is a ``{label: value}`` dict rendered as
``name{label="value"}`` with spec-compliant escaping — the fleet
observability plane uses it for ``replica=`` and ``tenant=`` labels
(hostile tenant names escape, never corrupt the exposition).

With a :class:`~.tsdb.TimeSeriesStore` attached (``tsdb=``), ``GET
/series?name=<event name>&last=<seconds>`` answers range queries as JSON
``{"name", "retention_s", "points": [{t,count,mean,min,max,last}...],
"summary"}`` — the live-process window the JSONL log can't serve.
"""

from __future__ import annotations

import http.server
import json
import re
import threading
import urllib.parse
from typing import Dict, List, Optional, Tuple

__all__ = ["MetricsServer", "prometheus_name", "escape_label_value",
           "render_prometheus"]

_SANITIZE = re.compile(r"[^a-zA-Z0-9_]")


def prometheus_name(event_name: str) -> str:
    """``Serving/latency/ttft_ms_p50`` → ``dstpu_serving_latency_ttft_ms_p50``
    (the hub's ``Group/.../metric`` names mapped onto the Prometheus
    ``[a-zA-Z_][a-zA-Z0-9_]*`` grammar)."""
    return "dstpu_" + _SANITIZE.sub("_", event_name).lower().strip("_")


def escape_label_value(value: str) -> str:
    """Prometheus label-value escaping: backslash, double-quote, and
    newline must be escaped or a hostile value (a program name, a path)
    silently corrupts the whole exposition."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _escape_help(text: str) -> str:
    """HELP-line escaping (backslash and newline per the text format)."""
    return str(text).replace("\\", "\\\\").replace("\n", "\\n")


def _render_labels(labels: Optional[Dict[str, str]]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{_SANITIZE.sub("_", str(k))}="{escape_label_value(v)}"'
        for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def render_prometheus(snapshot: List[Tuple]) -> str:
    """Prometheus text exposition (v0.0.4) from ``(name, value, kind)`` or
    ``(name, value, kind, labels)`` rows; kind is ``counter`` or
    ``gauge``."""
    lines: List[str] = []
    seen_type = set()
    for row in snapshot:
        name, value, kind = row[0], row[1], row[2]
        labels = row[3] if len(row) > 3 else None
        pname = prometheus_name(name)
        if pname not in seen_type:
            seen_type.add(pname)
            lines.append(f"# HELP {pname} {_escape_help(name)}")
            lines.append(f"# TYPE {pname} "
                         f"{'counter' if kind == 'counter' else 'gauge'}")
        lines.append(f"{pname}{_render_labels(labels)} {float(value):g}")
    lines.append("")
    return "\n".join(lines)


class MetricsServer:
    """Serve ``source.metrics_snapshot()`` on a background daemon thread.

    >>> srv = MetricsServer(hub, port=0)
    >>> port = srv.start()          # scrape http://127.0.0.1:<port>/metrics
    >>> srv.stop()
    """

    def __init__(self, source, host: str = "127.0.0.1", port: int = 0,
                 tsdb=None):
        self.source = source
        self.host = host
        self.port = port
        self.tsdb = tsdb
        self._httpd: Optional[http.server.ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------ #
    def render(self) -> str:
        snap = self.source.metrics_snapshot() \
            if hasattr(self.source, "metrics_snapshot") else []
        return render_prometheus(list(snap))

    def render_series(self, query: str) -> Tuple[int, bytes]:
        """``/series`` response for a raw query string → (status, JSON
        body). 404 without a tsdb attached, 400 without ``name=``."""
        if self.tsdb is None:
            return 404, json.dumps(
                {"error": "no time-series store attached"}).encode()
        q = urllib.parse.parse_qs(query)
        name = (q.get("name") or [""])[0]
        if not name:
            return 400, json.dumps(
                {"error": "missing required query param: name"}).encode()
        last_s: Optional[float] = None
        raw = (q.get("last") or [""])[0]
        if raw:
            try:
                last_s = float(raw)
            except ValueError:
                return 400, json.dumps(
                    {"error": f"bad last= value: {raw!r}"}).encode()
        body = {"name": name,
                "retention_s": self.tsdb.retention_s(),
                "points": self.tsdb.query(name, last_s=last_s),
                "summary": self.tsdb.summary(name, last_s=last_s)}
        return 200, json.dumps(body).encode()

    def start(self) -> int:
        """Bind and serve; returns the bound port (resolves ``port=0``)."""
        if self._httpd is not None:
            return self.port
        outer = self

        class _Handler(http.server.BaseHTTPRequestHandler):
            server_version = "dstpu-metrics/1.0"

            def do_GET(self):  # noqa: N802 (stdlib API name)
                route, _, query = self.path.partition("?")
                status = 200
                if route in ("/metrics", "/"):
                    body = outer.render().encode()
                    ctype = "text/plain; version=0.0.4; charset=utf-8"
                elif route == "/healthz":
                    body, ctype = b"ok\n", "text/plain"
                elif route == "/series":
                    status, body = outer.render_series(query)
                    ctype = "application/json"
                else:
                    self.send_error(404)
                    return
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # scrapes must not spam the log
                pass

        self._httpd = http.server.ThreadingHTTPServer(
            (self.host, self.port), _Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="dstpu-metrics",
            daemon=True)
        self._thread.start()
        return self.port

    def stop(self) -> None:
        if self._httpd is None:
            return
        self._httpd.shutdown()
        self._httpd.server_close()
        self._httpd = None
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
