"""A socket cluster inside the test process: mgmtd + 3 storage services
over real TCP (Python transport, mem engine), the socket-mode twin of the
fabric — the same shape as the reference running its UnitTestFabric
against live transports. Helper of test_readpath, test_stubs,
test_writepath and test_node_loss (which stops a node hard, has mgmtd
declare it dead, brings it back empty and drives the rebuild); not a
test module."""

import time

from tpu3fs.client.storage_client import StorageClient
from tpu3fs.kv.mem import MemKVEngine
from tpu3fs.mgmtd.service import Mgmtd
from tpu3fs.mgmtd.types import LocalTargetState, NodeType
from tpu3fs.rpc.net import RpcClient, RpcServer
from tpu3fs.rpc.services import (
    MgmtdRpcClient,
    RpcMessenger,
    bind_mgmtd_service,
    bind_storage_service,
)
from tpu3fs.storage.craq import StorageService
from tpu3fs.storage.target import StorageTarget

FILE_ID = 4242


class RpcCluster:
    def __init__(self, *, replicas: int, chains: int, size: int,
                 ec: tuple = (), nodes: int = 0, usrbio: bool = False):
        """ec=(k, m) makes every chain an RS(k, m) group of k+m targets
        (target i holds shard i; `replicas` is then ignored) whose engine
        chunk size is the shard size. `nodes` overrides how many storage
        services there are (shard j of chain c on node (c + j) % nodes).
        `usrbio` has every storage service host the USRBIO control service
        and ring agent, so a client's messenger rides shm rings."""
        self.mgmtd = Mgmtd(1, MemKVEngine())
        self.mgmtd.extend_lease()
        mgmtd_server = RpcServer()
        bind_mgmtd_service(mgmtd_server, self.mgmtd)
        mgmtd_server.start()
        self.servers = [mgmtd_server]
        self.mgmtd_addr = mgmtd_server.address
        self.shared_client = RpcClient()

        if ec:
            from tpu3fs.ops.stripe import shard_size_of

            replicas = sum(ec)
            target_size = shard_size_of(size, ec[0])
        else:
            target_size = size
        num_nodes = nodes or (3 if ec else max(3, replicas))
        self.target_size = target_size
        node_ids = [10 + i for i in range(num_nodes)]
        self.chain_ids = [900_001 + i for i in range(chains)]
        node_states: dict = {n: {} for n in node_ids}
        self.services = []
        self.usrbio_hosts = []
        self.node_server = {}
        self.mclis = {}
        svc_by_node = self.svc_by_node = {}
        for node_id in node_ids:
            # the held snapshot: this cluster's routing is static, and
            # retries invalidate it anyway
            mcli = self.mclis[node_id] = MgmtdRpcClient(
                self.mgmtd_addr, self.shared_client)
            svc = StorageService(node_id, mcli.cached_routing)
            svc.set_messenger(RpcMessenger(mcli.cached_routing,
                                           self.shared_client))
            server = RpcServer()
            bind_storage_service(server, svc)
            if usrbio:
                from tpu3fs.usrbio.server import (
                    UsrbioRpcHost,
                    bind_usrbio_service,
                )

                host = UsrbioRpcHost(server)
                bind_usrbio_service(server, host)
                self.usrbio_hosts.append(host)
            server.start()
            self.mgmtd.register_node(node_id, NodeType.STORAGE,
                                     host=server.host, port=server.port)
            self.servers.append(server)
            self.services.append(svc)
            self.node_server[node_id] = server
            svc_by_node[node_id] = svc
        for ci, chain_id in enumerate(self.chain_ids):
            targets = []
            for r in range(replicas):
                node_id = node_ids[(ci + r) % num_nodes]
                target_id = 1000 + ci * 16 + r
                svc_by_node[node_id].add_target(
                    StorageTarget(target_id, chain_id,
                                  chunk_size=target_size, engine="mem"))
                self.mgmtd.create_target(target_id, node_id=node_id)
                node_states[node_id][target_id] = LocalTargetState.UPTODATE
                targets.append(target_id)
            self.mgmtd.upload_chain(chain_id, targets,
                                    ec_k=ec[0] if ec else 0,
                                    ec_m=ec[1] if ec else 0)
        self.mgmtd.upload_chain_table(1, self.chain_ids)
        for node_id in node_ids:
            self.mgmtd.heartbeat(node_id, 1, node_states[node_id])
        self._client_seq = 0
        self._hb = {}
        self.resync_workers = {}    # id(service) -> its EcResyncWorker

    def storage_client(self, **kw) -> StorageClient:
        self._client_seq += 1
        mcli = MgmtdRpcClient(self.mgmtd_addr, self.shared_client)
        messenger = RpcMessenger(mcli.cached_routing, self.shared_client)
        return StorageClient(f"test-rpc-{self._client_seq}",
                             mcli.cached_routing, messenger, **kw)

    # -- a node lost and replaced (test_node_loss) ---------------------------
    def stop_node(self, node_id: int) -> None:
        """The node's process is gone: nothing listens at its address.
        mgmtd does not know yet."""
        self.svc_by_node[node_id].stopped = True
        self.node_server[node_id].stop()

    def declare_dead(self, node_id: int) -> None:
        """What mgmtd's tick does once heartbeat_timeout_s has passed."""
        self.mgmtd._routing.nodes[node_id].last_heartbeat = 0.0
        assert self.mgmtd.check_heartbeats() == [node_id]
        self.beat()

    def restart_empty(self, node_id: int) -> StorageService:
        """A new process under the node's id on empty disks: a new service
        on a new port, registered, its targets opened empty and reported
        ONLINE (what storage_main.scan_targets does past chain version 1)."""
        mcli = self.mclis[node_id] = MgmtdRpcClient(
            self.mgmtd_addr, self.shared_client)
        svc = StorageService(node_id, mcli.cached_routing)
        svc.set_messenger(RpcMessenger(mcli.cached_routing,
                                       self.shared_client))
        server = RpcServer()
        bind_storage_service(server, svc)
        server.start()
        self.mgmtd.register_node(node_id, NodeType.STORAGE,
                                 host=server.host, port=server.port)
        routing = self.mgmtd.get_routing_info()
        for info in routing.targets.values():
            if info.node_id == node_id and info.chain_id:
                target = StorageTarget(info.target_id, info.chain_id,
                                       chunk_size=self.target_size,
                                       engine="mem")
                target.local_state = LocalTargetState.ONLINE
                svc.add_target(target)
        self.servers.append(server)
        self.services[self.services.index(self.svc_by_node[node_id])] = svc
        self.node_server[node_id] = server
        self.svc_by_node[node_id] = svc
        self._hb[node_id] = self._hb.get(node_id, 1) + 1000
        return svc

    def beat(self) -> None:
        """Every live node's heartbeat with its targets' local states,
        mgmtd's chain update, and the nodes' routing refresh that a
        heartbeat reply ahead of their snapshot sets off."""
        live = [n for n, svc in self.svc_by_node.items() if not svc.stopped]
        for node_id in live:
            self._hb[node_id] = self._hb.get(node_id, 1) + 1
            self.mgmtd.heartbeat(
                node_id, self._hb[node_id],
                {t.target_id: t.local_state
                 for t in self.svc_by_node[node_id].targets()})
        self.mgmtd.update_chains()
        for node_id in live:
            self.mclis[node_id].refresh_routing()

    def recover(self, budget_s: float = 60.0, each_round=None) -> int:
        """Heartbeats, chain updates and every node's EC resync worker,
        round after round, until every target of every chain is SERVING.
        -> rounds it took."""
        from tpu3fs.mgmtd.types import PublicTargetState
        from tpu3fs.storage.ec_resync import EcResyncWorker

        workers = self.resync_workers
        deadline = time.time() + budget_s
        rounds = 0
        while True:
            self.beat()
            routing = self.mgmtd.get_routing_info()
            if each_round is not None:
                each_round(routing)
            if all(t.public_state == PublicTargetState.SERVING
                   for c in routing.chains.values() for t in c.targets):
                return rounds
            assert time.time() < deadline, "the chain never recovered"
            rounds += 1
            for svc in self.services:
                if svc.stopped:
                    continue
                if id(svc) not in workers:
                    workers[id(svc)] = EcResyncWorker(svc, svc._messenger)
                workers[id(svc)].run_once()

    def close(self) -> None:
        for host in self.usrbio_hosts:
            host.stop()
        for svc in self.services:
            # chain-forward messengers grew rings of their own: unlink
            # now, not at interpreter exit
            svc._messenger.close_rings()
        self.shared_client.close()
        for s in self.servers:
            s.stop()
