"""Fused hashed-embedding gather-and-combine kernel family.

ROBE-style compositional embeddings (arxiv 2207.10731): a row is never
stored — it is *materialized* from a shared ``(S, Z)`` parameter chunk
pool.  Row ``r``'s chunk ``c`` is the signed sum of ``num_hashes`` pool
rows picked by a universal hash of ``(r, c, j)``; memory is bounded by
the pool size ``S * Z``, independent of the vocabulary.

``ref``      jnp oracles + the hash family (``hash_slots``)
``kernel``   the forward on the ``dequant_bag`` kernel
             (``hashed_gather_pallas``)
``ops``      dispatch + block resolution (``hashed_gather``)
``autodiff`` the ``custom_vjp`` training twins
             (``hashed_bag_lookup_train`` / ``hashed_lookup_train``)
"""

from repro.kernels.hashed_gather.autodiff import (
    hashed_bag_lookup_train,
    hashed_lookup_train,
)
from repro.kernels.hashed_gather.kernel import hashed_gather_pallas
from repro.kernels.hashed_gather.ops import (
    hashed_gather,
    slot_plan,
)
from repro.kernels.hashed_gather.ref import hash_slots, hashed_gather_ref

__all__ = [
    "hash_slots",
    "hashed_bag_lookup_train",
    "hashed_gather",
    "hashed_gather_pallas",
    "hashed_gather_ref",
    "hashed_lookup_train",
    "slot_plan",
]
