"""Stdlib threaded HTTP runner for the WSGI app + service entry point.

Stands in for the reference's Flask/WSGI server start (SURVEY.md §3.2): load
config, compile or load tiles, construct the matcher once (device tables
staged to HBM), serve threaded on PORT.

Run:  python -m reporter_tpu.service.server --tiles path/to/tiles.npz
"""

from __future__ import annotations

import argparse
import logging
from wsgiref.simple_server import WSGIRequestHandler, WSGIServer, make_server

from socketserver import ThreadingMixIn

from reporter_tpu.config import Config
from reporter_tpu.service.app import ReporterApp, make_app
from reporter_tpu.tiles.tileset import TileSet


class ThreadedWSGIServer(ThreadingMixIn, WSGIServer):
    daemon_threads = True
    # listen backlog: socketserver's default of 5 drops the SYNs of a
    # fleet's concurrent connects (wsgiref speaks HTTP/1.0, so every
    # request opens a connection) into the kernel's 1 s / 3 s
    # retransmits; the kernel clamps this to net.core.somaxconn
    request_queue_size = 1024


class _QuietHandler(WSGIRequestHandler):
    def log_message(self, fmt, *args):      # route through logging, not stderr
        logging.getLogger("reporter_tpu.http").info(fmt, *args)


def serve(app, host: str = "0.0.0.0", port: int | None = None):
    """Serve forever (threaded). Returns the server for tests to shut down.
    ``app`` is a ReporterApp or a MetroRouter (any WSGI callable with a
    ``config``-bearing app when port is omitted)."""
    if port is None:
        cfg = getattr(app, "config", None)
        if cfg is None:          # MetroRouter: take any member app's config
            cfg = next(iter(app.apps.values())).config
        port = cfg.service.port
    server = make_server(host, port, app, server_class=ThreadedWSGIServer,
                         handler_class=_QuietHandler)
    return server


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description="reporter_tpu report service")
    ap.add_argument("--tiles", nargs="*", default=None,
                    help="compiled TileSet .npz path(s); several start the "
                         "multi-metro router (default: synthetic 'sf')")
    ap.add_argument("--config", help="JSON config path")
    ap.add_argument("--mode", choices=("auto", "bicycle", "foot"),
                    help="serve this transport mode: applies the mode's "
                         "matcher preset and tags/validates requests "
                         "(pair with a tileset compiled via "
                         "`tiles build --mode ...`)")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int)
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    from reporter_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    config = Config.load(args.config)
    if args.mode:
        import dataclasses

        from reporter_tpu.config import MatcherParams
        # An explicit --config wins on matcher tuning (operators mount
        # tuned params; clobbering them with the preset would silently
        # change serving behavior) — --mode then only tags/validates.
        matcher = (config.matcher if args.config
                   else MatcherParams.preset(args.mode))
        if args.config:
            logging.info("--mode %s: matcher params come from --config; "
                         "preset not applied", args.mode)
        config = dataclasses.replace(
            config, matcher=matcher,
            service=dataclasses.replace(config.service, mode=args.mode))
    if args.tiles:
        tilesets = [TileSet.load(p) for p in args.tiles]
    else:
        from reporter_tpu.netgen.synthetic import generate_city
        from reporter_tpu.tiles.compiler import compile_network

        logging.info("no --tiles given; compiling synthetic 'sf'")
        tilesets = [compile_network(generate_city("sf"), config.compiler)]

    if len(tilesets) == 1:
        app = make_app(tilesets[0], config)
        desc = f"{tilesets[0].name} ({tilesets[0].num_edges} edges)"
    else:
        from reporter_tpu.service.router import make_router

        app = make_router(tilesets, config)
        desc = "router[" + ", ".join(ts.name for ts in tilesets) + "]"
    server = serve(app, args.host, args.port)
    logging.info("serving %s on :%d", desc, server.server_address[1])
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        # Graceful drain: in-flight + admitted batches finish (their
        # clients get responses), new admissions get 503, then the
        # publisher flushes. MetroRouter drains every metro's scheduler.
        logging.info("shutting down: draining scheduler + publisher")
        app.close()
        server.server_close()


if __name__ == "__main__":
    main()
