"""Operations and bytes of one extroversion-field evaluation, from its
shapes, and the least time the chip could take for it.

The bytes are the field's compulsory HBM traffic: each input read once and
each output written once, in float32 and int32.  Gathers, scatters and
intermediates that a real schedule adds are left out, so the count is a
lower bound and the roofline share it gives can only err low.  The
operations are those of the recurrence per (edge, trie node of depth >= 2):
the product of parent state, conditional probability and inverse label
count (2 multiplies), the label mask and the locality mask (2 multiplies),
and the adds into the edge mass and the destination state (2 adds).
"""
from __future__ import annotations

from typing import Dict


def field_cost(shapes: Dict) -> Dict[str, float]:
    """``shapes``: ``n`` vertices, ``m`` directed edges, ``k`` parts,
    ``n_labels``, ``n_nodes`` trie nodes including the root, and
    ``depth_nodes[d]`` trie nodes at depth d.  Returns ``bytes`` and
    ``flops`` of one evaluation."""
    n, m, k = shapes["n"], shapes["m"], shapes["k"]
    L, N = shapes["n_labels"], shapes["n_nodes"]
    steps = sum(shapes["depth_nodes"][2:])
    inputs = (2 * m           # src, dst
              + n             # vertex labels
              + n * L         # neighbour label counts
              + L             # vertices per label
              + n             # partition
              + 2 * N)        # p, cond_p
    outputs = (n * N          # alpha
               + n            # pr
               + m            # edge mass
               + n            # external mass
               + n            # extroversion
               + n * k)       # external mass per destination part
    return {"bytes": 4.0 * (inputs + outputs), "flops": 6.0 * m * steps}


def least_time(cost: Dict[str, float], peak: Dict) -> Dict[str, object]:
    """The larger of operations over peak FLOP/s and bytes over peak
    bytes/s, and which of the two bounds it."""
    t_mem = cost["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = cost["flops"] / peak["flops_per_s"]
    return {"seconds": max(t_mem, t_ops),
            "bound": "memory" if t_mem >= t_ops else "compute"}
