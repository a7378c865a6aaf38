"""The paper's explicit trees executed ON DEVICES through the
``backend="ppermute"`` Communicator: one ``lax.ppermute`` per tree round.

Shows the faithful §3.2 port: every host deterministically constructs the
same multilevel tree from the mesh's coordinate table, then one
collective-permute per tree round moves the data — one DCN crossing total.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
     PYTHONPATH=src python examples/tree_collectives_on_mesh.py
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core import Communicator
from repro.core.topology import tpu_v5e_multipod

# A 2-pod, 2-board-per-pod, 2-chip-per-board fleet (8 devices emulated).
topo = tpu_v5e_multipod(pods=2, boards=2, chips_per_board=2)
comm = Communicator(topo, policy="paper", backend="ppermute", axis="all")

plan = comm.plan("bcast", root=3)
print("tree rounds (src,dst per collective-permute):")
for r, edges in enumerate(plan.rounds):
    lv = [topo.levels[topo.comm_level(s, d)].name for s, d in edges]
    print(f"  round {r}: {edges}  links={lv}")

mesh = make_mesh((8,), ("all",))
x = jnp.arange(8.0)

bcast = jax.jit(shard_map(lambda v: comm.bcast(v, root=3),
                          mesh=mesh, in_specs=P("all"), out_specs=P("all")))
print("bcast from rank 3:", np.asarray(bcast(x)))

red = jax.jit(shard_map(lambda v: comm.reduce(v, root=3), mesh=mesh,
                        in_specs=P("all"), out_specs=P("all")))
print("reduce to rank 3:", np.asarray(red(x)), "(expect 28 at index 3)")

allred = jax.jit(shard_map(lambda v: comm.allreduce(v), mesh=mesh,
                           in_specs=P("all"), out_specs=P("all")))
print("allreduce:", np.asarray(allred(x)), "(expect 28 everywhere)")

# Count DCN crossings in the schedule — the paper's metric.  The plan is
# cached: these reads re-run zero tree constructions.
dcn = sum(1 for edges in plan.rounds
          for s, d in edges if topo.comm_level(s, d) == 0)
print(f"DCN crossings in the whole broadcast: {dcn}")
print(f"plan cache: {comm.cache_info()}")
