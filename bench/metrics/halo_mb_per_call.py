"""Bytes one shard receives in the sharded field's halo exchange per field
call (per-depth exchange bytes of the packing times the DP depth steps):
the ``halo_bytes`` of ``invocation.field`` spans inside the window, in MB
(1e6 bytes).  An exact count."""


def read(view):
    b = [s["attrs"]["halo_bytes"] for s in view.spans("invocation.field")
         if "halo_bytes" in s["attrs"]]
    return sum(b) / len(b) / 1e6 if b else None
