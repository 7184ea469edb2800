"""The system under test: the only file of the benchmark that imports the
program.

``start()`` builds the ``FlowServer`` that ``python -m raft_tpu.cli -m serve
<serve_args>`` builds (``cli.parse_args`` -> ``cli._make_config`` ->
``serving.server.build_server``, the construction ``chip_smoke.py`` proved on
the chip in PR 21), hands it the benchmark's seeded weights in place of a
checkpoint, binds an ephemeral port and warms every executable.  From then on
the benchmark talks to it over HTTP only.
"""

from __future__ import annotations

import http.client
import os
import re


class System:
    def __init__(self, server, config, argv):
        self.server, self.config, self.argv = server, config, argv
        self.host, port = server.url.split("//", 1)[1].split(":")
        self.port = int(port)

    @property
    def executables(self) -> int:
        return self.server.engine.executables

    @property
    def max_batch(self) -> int:
        return self.server.sconfig.max_batch

    def engine_cache_stats(self):
        cache = getattr(self.server, "engine_cache", None)
        st = getattr(cache, "stats", None)
        return None if st is None else {"loaded": st.hits, "compiled": st.misses}

    def scrape(self) -> dict:
        conn = http.client.HTTPConnection(self.host, self.port, timeout=30)
        try:
            conn.request("GET", "/metrics")
            return parse_prom(conn.getresponse().read().decode())
        finally:
            conn.close()

    def stop(self) -> None:
        self.server.stop()


def parse_prom(text: str) -> dict:
    """Prometheus text exposition -> {'name{labels}': value}."""
    out = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        m = re.match(r"^(\S+?)(\{[^}]*\})?\s+(\S+)$", ln)
        if m:
            out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
    return out


def diff_prom(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def start(config: dict, weights: dict, cache_dir: str, name: str) -> System:
    """Start the server of configuration ``config`` (a configuration file's
    contents) with ``weights``; engine cache and work files under
    ``cache_dir``."""
    import jax

    from raft_tpu import cli
    from raft_tpu.models import init_raft
    from raft_tpu.serving.server import build_server

    argv = ["-m", "serve"] + [str(a) for a in config["serve_args"]] + [
        "--engine-cache-dir", os.path.join(cache_dir, "engine", name),
        "--out", os.path.join(cache_dir, "out", name),
        "--port", "0"]
    args = cli.parse_args(argv)
    rconfig = cli._make_config(args)
    want = jax.eval_shape(lambda: init_raft(jax.random.PRNGKey(0), rconfig))
    if (jax.tree.structure(want) != jax.tree.structure(weights)
            or any(a.shape != b.shape for a, b in
                   zip(jax.tree.leaves(want), jax.tree.leaves(weights)))):
        raise SystemExit("the benchmark's weights do not have the shape of "
                         "the program's parameters for this configuration")
    declared = config.get("program", {})
    for k, v in declared.items():
        if getattr(rconfig, k) != v:
            raise SystemExit(f"configuration says {k}={v!r}, the program's "
                             f"serve arguments give {getattr(rconfig, k)!r}")
    cli._start_run_log(args, rconfig)
    server = build_server(args, rconfig, lambda _a, _c: weights)
    server.start()
    return System(server, rconfig, argv)
