"""Device-mesh construction and sharded assignment kernels.

The reference scales its control plane with tokio fan-out concurrency
(SURVEY.md §2.9); the O(providers x tasks) matching itself never scales.
Here the matching is SPMD over a 1-D provider mesh: each device owns a
contiguous shard of providers (cost rows), and the auction's combine step
rides ICI collectives (all_gather of per-shard top-2 candidates, max-combine
of replicated state).
"""

# the jit-cache witness must wrap jax.jit BEFORE any kernel module's
# decorators execute (scripts/analysis/staging.py is the static twin)
from protocol_tpu.utils import jitwitness as _jitwitness

_jitwitness.install()

from protocol_tpu.parallel.mesh import make_mesh, pad_to_multiple
from protocol_tpu.parallel.auction import assign_auction_sharded
from protocol_tpu.parallel.jax_arena import JaxSolveArena
from protocol_tpu.parallel.sinkhorn import sinkhorn_potentials_sharded
from protocol_tpu.parallel.sparse import candidates_topk_bidir_sharded

__all__ = [
    "JaxSolveArena",
    "assign_auction_sharded",
    "candidates_topk_bidir_sharded",
    "make_mesh",
    "pad_to_multiple",
    "sinkhorn_potentials_sharded",
]
