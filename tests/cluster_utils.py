"""In-process cluster fixtures: N quorum servers + M storage nodes +
clients on the loopback transport — the reference's tier-3 pattern of
running every server in one process (reference: protocol/test_utils.go:24-82,
topology from scripts/setup.sh)."""

from __future__ import annotations

from dataclasses import dataclass, field

import contextlib
import itertools
import os

from bftkv_tpu import topology
from bftkv_tpu.protocol.client import Client
from bftkv_tpu.protocol.server import Server
from bftkv_tpu.storage.memkv import MemStorage
from bftkv_tpu.transport.http import TrHTTP
from bftkv_tpu.transport.loopback import LoopbackNet, TrLoopback

# Each HTTP cluster gets a disjoint port range so tests never collide —
# within a process by counting blocks of 100, across xdist workers
# (every worker process counts from its own start) by a per-worker
# range of ten blocks: gw0 10001…, gw1 11001…, up to 16999.  The test
# files with ports of their own (test_cmd, test_visual, test_sidecar*,
# test_byzantine_fullstack, test_device_plane) sit in 17001…19999 and
# chip_smoke.py asks the kernel above 22100.
_worker = int(os.environ.get("PYTEST_XDIST_WORKER", "gw0").lstrip("gw") or 0)
_port_block = itertools.count(10001 + 1000 * (_worker % 7), 100)


@dataclass
class Cluster:
    universe: topology.Universe
    net: LoopbackNet | None
    servers: list[Server] = field(default_factory=list)  # quorum (a*)
    storage_servers: list[Server] = field(default_factory=list)  # rw*
    clients: list[Client] = field(default_factory=list)
    gateways: list = field(default_factory=list)  # bftkv_tpu.gateway
    gateway_addrs: dict[str, str] = field(default_factory=dict)

    @property
    def all_servers(self) -> list[Server]:
        return self.servers + self.storage_servers

    def gateway_client(self, i: int = 0, *, verify: bool = True):
        """A front-door client riding user ``i``'s identity against
        every gateway of the cluster: the client's own keyring copies
        of the (unaddressed) gateway certificates, paired with the
        cluster's configured dial addresses."""
        from bftkv_tpu.gateway import GatewayClient, GatewayPeer

        client = self.clients[i]
        peers = [
            GatewayPeer(
                client.crypt.keyring.get(gw.self_node.get_self_id()),
                self.gateway_addrs[gw.self_node.name],
            )
            for gw in self.gateways
        ]
        return GatewayClient(client, peers, verify=verify)

    def stop(self) -> None:
        for gw in self.gateways:
            gw.stop()
        for s in self.all_servers:
            s.tr.stop()
        if self.universe.regions:
            # The region map is process-global: a labeled cluster must
            # not leak its geography into the next test's fleet.
            from bftkv_tpu import regions

            regions.clear()

    def server_named(self, name: str) -> Server:
        idents = self.universe.servers + self.universe.storage_nodes
        for ident, srv in zip(idents, self.all_servers):
            if ident.name == name:
                return srv
        raise KeyError(name)


def start_cluster(
    n_servers: int = 4,
    n_users: int = 1,
    n_rw: int = 4,
    *,
    bits: int = 2048,
    unsigned_users: int = 0,
    storage_factory=MemStorage,
    server_cls=Server,
    client_cls=Client,
    transport_cls=TrLoopback,
    transport: str = "loop",
    alg: str = "rsa",
    n_shards: int = 1,
    n_gateways: int = 0,
    n_regions: int = 0,
) -> Cluster:
    """``transport="loop"`` wires the in-process loopback net;
    ``transport="http"`` starts every server on a real localhost HTTP
    port — the reference's tier-3 shape (protocol/test_utils.go:24-82,
    one process, loopback sockets).  ``n_shards`` builds that many
    disjoint server cliques (``n_servers``/``n_rw`` become per-shard
    counts — see topology.build_universe).  ``n_regions`` labels every
    principal round-robin and installs the process-global region map
    (cleared again by :meth:`Cluster.stop`)."""
    if transport == "http":
        http_cls = TrHTTP if transport_cls is TrLoopback else transport_cls
        if not (isinstance(http_cls, type) and issubclass(http_cls, TrHTTP)):
            raise ValueError(
                f"transport='http' needs a TrHTTP subclass, got {transport_cls}"
            )
        base = next(_port_block)
        uni = topology.build_universe(
            n_servers, n_users, n_rw, scheme="http", bits=bits,
            base_port=base, rw_base_port=base + 50,
            unsigned_users=unsigned_users, alg=alg, n_shards=n_shards,
            n_gateways=n_gateways, gw_base_port=base + 80,
            n_regions=n_regions,
        )
        net = None
        make_tr = lambda crypt: http_cls(crypt)
    else:
        uni = topology.build_universe(
            n_servers, n_users, n_rw, scheme="loop", bits=bits,
            unsigned_users=unsigned_users, alg=alg, n_shards=n_shards,
            n_gateways=n_gateways, n_regions=n_regions,
        )
        net = LoopbackNet()
        make_tr = lambda crypt: transport_cls(crypt, net)
    if uni.regions:
        from bftkv_tpu import regions

        regions.install(uni.regions)
    cluster = Cluster(universe=uni, net=net)
    for ident in uni.servers + uni.storage_nodes:
        graph, crypt, qs = topology.make_node(ident, uni.view_of(ident))
        srv = server_cls(graph, qs, make_tr(crypt), crypt, storage_factory())
        srv.start()
        if ident in uni.servers:
            cluster.servers.append(srv)
        else:
            cluster.storage_servers.append(srv)
    for ident in uni.users:
        graph, crypt, qs = topology.make_node(ident, uni.view_of(ident))
        tr = make_tr(crypt)
        # Clients are partitionable links too (the chaos-harness
        # idiom): without a link id the failpoint ctx posts src="" and
        # a region-keyed rule (WAN delay, region cut) can never match
        # client-originated traffic.
        tr.link_id = ident.name
        cluster.clients.append(client_cls(graph, qs, tr, crypt))
    for ident in uni.gateways:
        from bftkv_tpu.gateway import Gateway

        graph, crypt, qs = topology.make_node(ident, uni.view_of(ident))
        gw = Gateway(graph, qs, make_tr(crypt), crypt)
        dial = uni.gateway_addrs[ident.name]
        gw.start(dial.split("://", 1)[-1])
        cluster.gateways.append(gw)
        cluster.gateway_addrs[ident.name] = dial
    return cluster


@contextlib.contextmanager
def modexp_route(route: str, sock_dir):
    """The way a server's ``BatchModExp`` requests leave: ``"local"``
    (no domain installed: this process's own engine) or ``"sidecar"``
    (what a daemon started with ``--sidecar`` installs: a collector
    over a ``RemoteModexpDomain`` on a key-carrying unix socket, here
    against an in-process sidecar service)."""
    if route == "local":
        yield None
        return
    from bftkv_tpu.cmd import verify_sidecar as vs
    from bftkv_tpu.crypto.remote_verify import RemoteModexpDomain
    from bftkv_tpu.ops import dispatch

    addr = f"unix:{sock_dir}/modexp.sock"
    srv, _t = vs.serve(addr)
    domain = RemoteModexpDomain(addr)
    dispatch.install_modexp(
        dispatch.ModexpDispatcher(
            remote=domain, calibrate=False, max_wait=0.002
        )
    )
    try:
        yield domain
    finally:
        dispatch.uninstall_modexp()
        domain.channel.close()
        srv.service.stop()
        srv.shutdown()
        srv.server_close()
