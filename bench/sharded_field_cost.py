"""Operations and bytes one chip moves in one sharded extroversion-field
evaluation, from the shard shapes, for the least time of the sharded
field program (``least_time`` of ``bench/field_cost.py``).

Per chip, the compulsory HBM traffic of its shard: its packed per-edge
arrays (source map, source and destination ids, destination offset and
label, 1/count, raw edge id: seven 4-byte words an edge), its owned
vertices' labels, the replicated partition and trie (probabilities and
the transition tensor), the halo rows it receives (per DP depth step,
the parent-depth trie columns of each halo row it reads), and its share
of the outputs.  Padding slots, the padded exchange rounds, the gathers
and the replicated aggregates of the real schedule are left out, so the
count is a lower bound and the roofline share it gives can only err low,
as ``field_cost.py``'s.  The operations are ``field_cost.py``'s, over the
chip's own edges.  The slowest chip bounds the program.
"""
from __future__ import annotations

from typing import Dict


def sharded_field_cost(shapes: Dict) -> Dict[str, float]:
    """``shapes``: ``n``, ``k``, ``n_labels``, ``n_nodes``, ``depth_nodes``
    as ``field_cost`` takes them, and ``shards``: per shard its real
    ``edges``, owned ``vertices`` and the ``halo_rows`` it reads.  Returns
    the ``bytes`` and ``flops`` of the chip that moves the most."""
    n, k = shapes["n"], shapes["k"]
    L, N = shapes["n_labels"], shapes["n_nodes"]
    dn = shapes["depth_nodes"]
    steps = sum(dn[2:])
    # a depth-d step reads the depth-(d - 1) columns of each halo row
    halo_cols = sum(dn[1:-1])
    replicated = (n               # partition
                  + 2 * N         # p, cond_p
                  + L * N * N     # transition tensor
                  + L)            # vertices per label
    worst = {"bytes": 0.0, "flops": 0.0}
    for sh in shapes["shards"]:
        e, v, h = sh["edges"], sh["vertices"], sh["halo_rows"]
        inputs = 7 * e + v + replicated + h * halo_cols
        outputs = (v * N          # alpha rows
                   + 3 * v        # pr, external mass, extroversion
                   + e            # edge mass
                   + v * k)       # external mass per destination part
        worst["bytes"] = max(worst["bytes"], 4.0 * (inputs + outputs))
        worst["flops"] = max(worst["flops"], 6.0 * e * steps)
    return worst
