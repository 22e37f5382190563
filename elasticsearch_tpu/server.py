"""Server bootstrap: `python -m elasticsearch_tpu.server [--port N] [--data DIR]`.

The CLI/bootstrap layer (reference: `bootstrap/Elasticsearch.main:75` →
`Bootstrap.init:334` → `Node.start:682`): builds the node, registers REST
handlers, binds HTTP, installs signal handlers, runs until stopped.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import signal
import sys


def _http_ssl_context(settings):
    """http.ssl.* -> server SSLContext (xpack.security.http.ssl analog):
    client certificates optional by default; plaintext on a TLS port
    fails the handshake."""
    from elasticsearch_tpu.transport.tls import TlsConfig
    cfg = TlsConfig.from_settings(settings or {}, prefix="http.ssl",
                                  default_client_auth="none")
    return cfg.server_context() if cfg is not None else None


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="elasticsearch-tpu")
    parser.add_argument("--port", type=int, default=9200)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--data", default="./data")
    parser.add_argument("--name", default="node-0")
    parser.add_argument("--cluster-name", default="tpu-search")
    parser.add_argument("-E", action="append", default=[], metavar="KEY=VALUE",
                        help="setting override, e.g. -E xpack.security.enabled=true")
    args = parser.parse_args(argv)
    settings = {}
    for kv in args.E:
        key, _, value = kv.partition("=")
        settings[key] = {"true": True, "false": False}.get(value.lower(), value)

    # the platform is JAX's own to choose (it honours JAX_PLATFORMS);
    # the compile cache is configured here, before the first backend touch
    from elasticsearch_tpu.ops import dispatch
    cache_dir = dispatch.configure_compile_cache()
    print(f"compile cache: {cache_dir}", file=sys.stderr)

    from elasticsearch_tpu import bootstrap
    from elasticsearch_tpu.rest.controller import RestController
    from elasticsearch_tpu.rest.http_server import HttpServer

    # bootstrap checks + native hardening BEFORE the node exists
    # (reference: Bootstrap.init → initializeNatives → BootstrapChecks) —
    # both the single-node and the clustered deployment path run them
    check_settings = dict(settings)
    check_settings.setdefault("path.data", args.data)
    enforce = args.host not in ("127.0.0.1", "localhost", "::1")
    try:
        warnings = bootstrap.run_bootstrap_checks(check_settings,
                                                  enforce=enforce)
    except bootstrap.BootstrapCheckFailure as e:
        print(f"bootstrap checks failed: {e}", file=sys.stderr)
        return 78  # EX_CONFIG
    for w in warnings:
        print(f"warning: {w}", file=sys.stderr)
    natives = bootstrap.initialize_natives(check_settings)
    for err in natives.errors:
        print(f"warning: {err}", file=sys.stderr)

    def _csv(value):
        if value is None:
            return []
        if isinstance(value, (list, tuple)):
            return list(value)
        return [v.strip() for v in str(value).split(",") if v.strip()]

    seed_hosts = _csv(settings.get("discovery.seed_hosts"))
    seed_providers_configured = bool(settings.get("discovery.seed_providers"))
    if seed_providers_configured:
        # dynamic seed discovery (discovery-ec2/gce + the file provider)
        # appends to any static list; provider outages log, never block
        # boot — the discovery loop re-resolves, so peers that were
        # unreachable at boot are found later
        from elasticsearch_tpu.cluster.seed_providers import (
            resolve_seed_hosts,
        )
        seed_hosts = list(dict.fromkeys(
            seed_hosts + resolve_seed_hosts(settings, args.data)))
    initial_masters = _csv(settings.get("cluster.initial_master_nodes"))
    # a configured provider makes this a CLUSTER node even when its first
    # resolution came back empty (a cloud-API blip must not silently boot
    # an independent single-node cluster on the shared data dir)
    cluster_mode = bool(seed_hosts or initial_masters
                        or seed_providers_configured)

    if cluster_mode:
        return _run_clustered(args, settings, seed_hosts, initial_masters,
                              bootstrap)

    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.rest.actions import register_all

    node = Node(args.data, node_name=args.name, cluster_name=args.cluster_name,
                settings=settings)
    node.natives = natives
    controller = RestController()
    register_all(controller, node)
    server = HttpServer(controller, host=args.host, port=args.port,
                        thread_pool=node.thread_pool,
                        ssl_context=_http_ssl_context(settings))

    async def run():
        # even a single-node deployment binds the binary transport when
        # transport.port is set: that's the endpoint OTHER clusters dial
        # for CCS/CCR (reference: every node binds 9300)
        transport = None
        if settings.get("transport.port") is not None:
            from elasticsearch_tpu.transport.tcp import TcpTransportService
            from elasticsearch_tpu.xpack.remote_cluster import (
                register_remote_handlers,
            )
            transport = TcpTransportService(
                args.name, host=args.host,
                port=int(settings["transport.port"]),
                loop=asyncio.get_running_loop())
            host, port = await transport.bind()
            register_remote_handlers(transport, node)
            print(f"[{args.name}] transport bound on {host}:{port}",
                  flush=True)
        await server.start()
        print(f"[{args.name}] listening on http://{args.host}:{server.port} "
              f"(data: {args.data})", flush=True)
        bootstrap.sd_notify("READY=1")  # systemd readiness, if supervised
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, stop.set)
            except NotImplementedError:
                pass
        await stop.wait()
        if transport is not None:
            await transport.close()
        await server.stop()
        node.close()

    asyncio.run(run())
    return 0


def _run_clustered(args, settings, seed_hosts, initial_masters, bootstrap) -> int:
    """Boot a clustered node: transport bind → coordinator initial join →
    HTTP last (reference start order: `node/Node.java:682`)."""
    from elasticsearch_tpu.cluster.cluster_node import ClusterNode
    from elasticsearch_tpu.cluster.coordination import bootstrap_state
    from elasticsearch_tpu.cluster.rest_node import ClusterAwareNode
    from elasticsearch_tpu.rest.actions import register_all
    from elasticsearch_tpu.rest.cluster_actions import (
        ClusterRestAdapter, register_cluster_overrides,
    )
    from elasticsearch_tpu.rest.controller import RestController
    from elasticsearch_tpu.rest.http_server import HttpServer
    from elasticsearch_tpu.transport.tcp import (
        AsyncioScheduler, TcpTransportService,
    )

    node_id = args.name
    transport_port = int(settings.get("transport.port", 9300))
    if not initial_masters:
        print("cluster.initial_master_nodes is required with "
              "discovery.seed_hosts", file=sys.stderr)
        return 78

    # transport TLS + inter-node auth from settings/keystore
    # (xpack.security.transport.ssl analog; key material is secure settings)
    from elasticsearch_tpu.transport.tls import TlsConfig, TransportAuth
    try:
        tls = TlsConfig.from_settings(settings)
    except Exception as e:
        print(f"transport TLS misconfigured: {e}", file=sys.stderr)
        return 78
    auth = None
    auth_key = settings.get("cluster.auth.key")
    if not auth_key:
        # fail CLOSED on keystore errors: a wrong password must not boot
        # the node with transport auth silently disabled
        from elasticsearch_tpu.common.keystore import load_node_keystore
        try:
            ks = load_node_keystore(settings, args.data)
        except Exception as e:
            print(f"keystore load failed: {e}", file=sys.stderr)
            return 78
        if ks is not None:
            auth_key = ks.get("cluster.auth.key")
    if auth_key:
        auth = TransportAuth(str(auth_key).encode("utf-8"))

    async def run():
        loop = asyncio.get_running_loop()
        scheduler = AsyncioScheduler(loop)
        transport = TcpTransportService(node_id, host=args.host,
                                        port=transport_port,
                                        tls=tls, auth=auth)
        host, port = await transport.bind()
        address = f"{host}:{port}"
        print(f"[{node_id}] transport bound on {address}", flush=True)

        initial = bootstrap_state(initial_masters,
                                  cluster_name=args.cluster_name)
        cluster_node = ClusterNode(
            node_id, args.data, transport, scheduler,
            seed_peers=[m for m in initial_masters if m != node_id],
            initial_state=initial, address=address)
        cluster_node.start()

        # seed-host discovery loop (PeerFinder analog): keep probing the
        # configured addresses until every one resolves to a node id, and
        # keep re-probing slowly afterwards so restarted peers re-resolve.
        # Configured providers re-resolve every pass (the reference's
        # FileBasedSeedHostsProvider / cloud providers are live lists:
        # autoscaling additions and unicast_hosts.txt edits take effect
        # without a restart).
        async def discover():
            use_providers = bool(settings.get("discovery.seed_providers"))
            targets = list(seed_hosts)
            while True:
                if use_providers:
                    from elasticsearch_tpu.cluster.seed_providers import (
                        resolve_seed_hosts,
                    )
                    dynamic = await asyncio.to_thread(
                        resolve_seed_hosts, settings, args.data)
                    static = settings.get("discovery.seed_hosts") or ""
                    static_list = ([s.strip() for s in str(static).split(",")
                                    if s.strip()]
                                   if not isinstance(static, (list, tuple))
                                   else list(static))
                    targets = list(dict.fromkeys(static_list + dynamic))
                all_known = True
                for hp in targets:
                    h, _, p = hp.rpartition(":")
                    h = h.strip("[]")  # bracketed IPv6
                    if not h or not p.isdigit():
                        continue
                    try:
                        await transport.probe_address(h, int(p))
                    except Exception:
                        all_known = False
                await asyncio.sleep(1.0 if not all_known else 5.0)

        discovery_task = loop.create_task(discover())

        controller = RestController()
        # ONE feature surface for both deployment shapes: the full Node
        # route set backed by distributed data-path overrides, with the
        # cluster-authoritative routes (health/state/index admin) layered
        # on top (last registration wins)
        import os as _os
        aware = ClusterAwareNode(
            _os.path.join(args.data, "_node_local"), cluster_node, loop,
            node_name=node_id, cluster_name=args.cluster_name,
            settings=settings)
        register_all(controller, aware)
        adapter = ClusterRestAdapter(cluster_node, loop)
        register_cluster_overrides(controller, adapter, aware=aware)
        # remote-cluster (CCS/CCR) server actions ride the same transport
        # the cluster uses internally (reference: one 9300 endpoint)
        from elasticsearch_tpu.xpack.remote_cluster import (
            register_remote_handlers,
        )
        register_remote_handlers(transport, aware)
        server = HttpServer(controller, host=args.host, port=args.port,
                            thread_pool=aware.thread_pool,
                            ssl_context=_http_ssl_context(settings))
        await server.start()
        aware.register_builtin_persistent_tasks()
        print(f"[{node_id}] listening on http://{args.host}:{server.port} "
              f"(data: {args.data}, cluster: {args.cluster_name})", flush=True)
        bootstrap.sd_notify("READY=1")

        stop = asyncio.Event()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, stop.set)
            except NotImplementedError:
                pass
        await stop.wait()
        discovery_task.cancel()
        await server.stop()
        cluster_node.stop()
        await transport.close()

    asyncio.run(run())
    return 0


if __name__ == "__main__":
    code = main()
    # The node is closed and everything durable is on disk, but daemon
    # threads (warmup compiles, the agg column resync a refresh starts)
    # may still be inside XLA. Finalizing the interpreter under them
    # aborts the process (SIGABRT, "FATAL: exception not rethrown" — seen
    # on the chip when SIGTERM followed a flush): leave without it.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
