"""A socket cluster inside the test process: mgmtd + 3 storage services
over real TCP (Python transport, mem engine), the socket-mode twin of the
fabric — the same shape as the reference running its UnitTestFabric
against live transports. Helper of test_readpath, test_stubs and
test_writepath; not a test module."""

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
                 ec: tuple = ()):
        """ec=(k, m) makes every chain an RS(k, m) group of k+m targets
        (target i holds shard i; `replicas` is then ignored) whose engine
        chunk size is the shard size."""
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
        num_nodes = 3 if ec else max(3, replicas)
        node_ids = [10 + i for i in range(num_nodes)]
        self.chain_ids = [900_001 + i for i in range(chains)]
        node_states: dict = {n: {} for n in node_ids}
        self.services = []
        svc_by_node = {}
        for node_id in node_ids:
            # the held snapshot: this cluster's routing is static, and
            # retries invalidate it anyway
            mcli = MgmtdRpcClient(self.mgmtd_addr, self.shared_client)
            svc = StorageService(node_id, mcli.cached_routing)
            svc.set_messenger(RpcMessenger(mcli.cached_routing,
                                           self.shared_client))
            server = RpcServer()
            bind_storage_service(server, svc)
            server.start()
            self.mgmtd.register_node(node_id, NodeType.STORAGE,
                                     host=server.host, port=server.port)
            self.servers.append(server)
            self.services.append(svc)
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

    def storage_client(self, **kw) -> StorageClient:
        self._client_seq += 1
        mcli = MgmtdRpcClient(self.mgmtd_addr, self.shared_client)
        messenger = RpcMessenger(mcli.cached_routing, self.shared_client)
        return StorageClient(f"test-rpc-{self._client_seq}",
                             mcli.cached_routing, messenger, **kw)

    def close(self) -> None:
        self.shared_client.close()
        for s in self.servers:
            s.stop()
