"""Live telemetry endpoint: stdlib-HTTP ``/metrics`` + ``/healthz``.

One daemon thread per process (flag ``obs_port``; 0 disables), zero
dependencies: ``GET /metrics`` returns the Prometheus text exposition
of the process registry plus any extra provider pages (the fleet's
per-version/per-replica groups, a Supervisor's merged worker
snapshots), ``GET /healthz`` returns a small JSON liveness document.
The handler thread never touches the hot path — a scrape costs the
scraped, not the server.

Explicit ``port=0`` in the constructor binds an ephemeral port (tests,
multi-process fleets on one host) — the bound port is on ``.port``.
The flag value 0 means *disabled*; pick a real port (or -1 for
ephemeral) to serve.
"""

from __future__ import annotations

import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Iterable, Optional

__all__ = ["TelemetryServer", "start_telemetry_from_flags"]


class TelemetryServer:
    """Serve ``/metrics`` and ``/healthz`` from a daemon thread.

    Parameters
    ----------
    port : TCP port; 0 binds an ephemeral one (read ``.port``).
    registry : the :class:`~paddle1_tpu.obs.registry.MetricsRegistry`
        whose page leads /metrics; defaults to the process registry.
        Pass ``registry=False`` to serve providers only.
    providers : callables returning extra exposition text appended to
        the page (fleet groups, merged child snapshots...). A provider
        raising is reported as a comment line, never a dead endpoint.
    healthz : callable returning the ``/healthz`` JSON dict; default
        ``{"ok": true, "pid": ..., "uptime_s": ...}``. Either way the
        document gains SLO verdicts (``obs.slo``) when the process has
        objectives configured.

    A provider that raises is served from its LAST GOOD page with a
    staleness comment (a scrape racing ``drain()``/teardown gets
    yesterday's numbers labeled as such, never a dead page); only a
    provider that has never succeeded degrades to an error comment.
    ``GET /debug/flight`` returns the flight recorder's current bundle
    (and writes the on-demand dump) when ``obs_flight_steps`` arms it.
    """

    def __init__(self, port: int = 0, registry=None,
                 providers: Iterable[Callable[[], str]] = (),
                 healthz: Optional[Callable[[], dict]] = None,
                 host: str = "127.0.0.1"):
        self._registry = registry
        # [fn, last_good_text, last_good_monotonic] per provider — the
        # scrape-vs-drain stale cache (ISSUE 13 satellite)
        self._providers = [[p, None, 0.0] for p in providers]
        self._healthz = healthz
        self._started = time.monotonic()
        srv_self = self

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # a scrape is not console news
                pass

            def _send(self, code, body: bytes, ctype: str):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path = self.path.split("?", 1)[0]
                if path == "/metrics":
                    self._send(200, srv_self._metrics_page().encode(),
                               "text/plain; version=0.0.4")
                elif path == "/healthz":
                    self._send(200,
                               json.dumps(srv_self._health()).encode(),
                               "application/json")
                elif path == "/debug/flight":
                    body, code = srv_self._flight_page()
                    self._send(code, body.encode(),
                               "application/jsonl")
                else:
                    self._send(404, b"not found\n", "text/plain")

        self._httpd = ThreadingHTTPServer((host, max(int(port), 0)),
                                          _Handler)
        self._httpd.daemon_threads = True
        self._thread: Optional[threading.Thread] = None

    # -- pages -------------------------------------------------------------

    def _metrics_page(self) -> str:
        parts = []
        reg = self._registry
        if reg is None:
            from .registry import process_registry, render_process_groups
            parts.append(process_registry().render_text())
            parts.append(render_process_groups())
        elif reg is not False:
            parts.append(reg.render_text())
        for slot in self._providers:
            try:
                text = slot[0]()
            except Exception as e:  # noqa: broad-except — one broken
                # provider (a replica scrape racing a deploy/drain)
                # must not kill the whole page
                if slot[1] is not None:
                    # serve the last good page, labeled stale: a
                    # scrape racing drain()/teardown reads yesterday's
                    # numbers, never a provider-error hole
                    age = time.monotonic() - slot[2]
                    parts.append(slot[1])
                    parts.append(
                        f"# provider stale ({age:.1f}s old): {e!r}\n")
                else:
                    parts.append(f"# provider error: {e!r}\n")
                continue
            slot[1], slot[2] = text, time.monotonic()
            parts.append(text)
        return "".join(parts)

    def _health(self) -> dict:
        if self._healthz is not None:
            try:
                base = dict(self._healthz())
            except Exception as e:  # noqa: broad-except — a liveness
                # probe must answer even when the probed is sick
                base = {"ok": False, "error": repr(e),
                        "pid": os.getpid()}
        else:
            base = {"ok": True, "pid": os.getpid(),
                    "uptime_s": round(
                        time.monotonic() - self._started, 3)}
        try:
            from . import slo
            base.update(slo.healthz_fields(
                self._registry if self._registry not in (None, False)
                else None))
        except Exception as e:  # noqa: broad-except — a broken SLO
            # spec must degrade the verdict, not the liveness probe
            base["slo_error"] = repr(e)
        return base

    def _flight_page(self):
        from . import flight
        r = flight.recorder()
        if r is None:
            return ("flight recorder disarmed "
                    "(set FLAGS_obs_flight_steps > 0)\n", 404)
        # ONE ring snapshot: the disk dump and the HTTP body are the
        # same bytes (a step landing between two snapshots would make
        # the route disagree with the file)
        _path, text = r.dump_bundle(reason="debug_route")
        return (text, 200)

    # -- lifecycle ---------------------------------------------------------

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def url(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}"

    def start(self) -> "TelemetryServer":
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._httpd.serve_forever,
                kwargs={"poll_interval": 0.2},
                daemon=True, name="p1t-obs-http")
            self._thread.start()
        return self

    def stop(self) -> None:
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except OSError:  # pragma: no cover - teardown race
            pass
        if self._thread is not None:
            self._thread.join(timeout=5.0)


def resolve_port_flag(port: Optional[int]) -> Optional[int]:
    """THE ``obs_port`` semantics, shared by every start_telemetry
    surface: explicit ``port`` wins; None reads the flag; flag 0 means
    disabled (returns None); negative means ephemeral (bind port 0)."""
    if port is None:
        from ..core import flags as core_flags
        port = int(core_flags.flag("obs_port"))
        if port == 0:
            return None
    return 0 if port < 0 else int(port)


def start_telemetry_from_flags(providers: Iterable[Callable[[], str]] = (),
                               healthz: Optional[Callable[[], dict]] = None
                               ) -> Optional[TelemetryServer]:
    """Start the endpoint when the ``obs_port`` flag asks for one
    (0 = disabled, -1 = ephemeral, else the port). Returns the handle
    or None."""
    port = resolve_port_flag(None)
    if port is None:
        return None
    return TelemetryServer(port=port, providers=providers,
                           healthz=healthz).start()
