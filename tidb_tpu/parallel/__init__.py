"""Distributed execution over a device mesh.

Reference parity: the MPP engine — fragment cutting at exchange boundaries
(pkg/planner/core/fragment.go), exchange types Hash/Broadcast/PassThrough
(tipb.ExchangeType), executed by exchange senders/receivers (unistore
cophandler/mpp_exec.go:609 exchSenderExec streaming to peer tasks).

TPU-native mapping (SURVEY §7.7):
- one table shard ("region group") per device along mesh axis ``dp``;
- Hash exchange   → ``jax.lax.all_to_all`` on hash-bucketed rows/groups;
- Broadcast       → ``jax.lax.all_gather``;
- PassThrough     → gather-to-root (all_gather + root read);
- scalar merges   → ``jax.lax.psum``.

The coordinator stays host-side Python (ref: local_mpp_coordinator.go); the
data plane never leaves the ICI once shards are device-resident.
"""

from tidb_tpu.parallel.mesh import make_mesh

__all__ = ["make_mesh"]
