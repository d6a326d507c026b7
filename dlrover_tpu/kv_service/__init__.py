"""Distributed KvVariable: a sharded embedding service over the
single-node C++ store (``dlrover_tpu/native``) and the 2-RPC transport
(``dlrover_tpu/rpc``).

Reference parity: DLRover's parameter-server sparse path — the tfplus
``KvVariable`` lives on PS nodes and every worker gathers/applies over
the wire (``tfplus/kv_variable/kernels/hashmap.h``, PAPER.md §tfplus).
Here the "PS nodes" are :class:`~dlrover_tpu.kv_service.server
.KvShardServer` processes, each wrapping one host-RAM
:class:`~dlrover_tpu.native.kv_variable.KvVariable`, and routing is
client-side consistent hashing, so aggregate gather throughput scales
with shard count instead of being capped by one host.

Layout:

* ``routing``  — consistent-hash ring over *named* shard owners; stable
  under membership change (replacing the process behind a name moves
  zero keys; adding/removing a name moves ~1/N).
* ``server``   — one shard: KvVariable + gRPC servicer + delta-chain
  durability (``checkpoint/kv_checkpoint.py``) + serving-time HTTP
  lookup endpoint.
* ``client``   — :class:`ShardedKvClient`: shard-groups every batch
  (one pipelined RPC per owner, never per key), coalesces concurrent
  duplicate-key gathers, keeps a bounded hot-row cache with
  write-through invalidation, and short-circuits to the local table
  when the owner is this process.
* ``reshard``  — elastic membership changes reusing the reform
  protocol's shape: replace a dead owner (restore base + deltas from
  its chain), or rebalance rows after scale events.
* ``replication`` — chain-replicated follower replicas fed by the
  delta export as a digest-verified stream (:class:`ChainReplicator`),
  lease-fenced promotion + health polling (:class:`KvHaManager`), and
  the anti-entropy digest scan — always-on serving for the keyspace
  (docs/KV_SERVICE.md §Replication).
* ``__main__`` — real-process shard entrypoint for the CPU harness
  and the chaos/HA drills.

The client is duck-type compatible with :class:`KvVariable` for the
surfaces training uses (``dim``/``slots``/``gather_or_init``/
``apply_*``), so ``native/embedding_ops.py`` and the io_callback bridge
in ``native/kv_variable.py`` work transparently against the sharded
service — see docs/KV_SERVICE.md.
"""

from dlrover_tpu.kv_service.routing import HashRing
from dlrover_tpu.kv_service.client import (
    ShardedKvClient,
    KvShardUnavailable,
    KvStaleEpoch,
)
from dlrover_tpu.kv_service.replication import ChainReplicator, KvHaManager
from dlrover_tpu.kv_service.server import KvShardServer
from dlrover_tpu.kv_service.reshard import KvReshardManager, owners_from_addrs

__all__ = [
    "HashRing",
    "ShardedKvClient",
    "KvShardUnavailable",
    "KvStaleEpoch",
    "ChainReplicator",
    "KvHaManager",
    "KvShardServer",
    "KvReshardManager",
    "owners_from_addrs",
]
