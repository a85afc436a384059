"""Least bytes a device program must move, from what it was asked to do."""

KEY_HALVES = 8          # a 64-bit key as two uint32 halves


def fused_probe_least_bytes(pairs: int) -> float:
    """HBM bytes of a fused probe program that probed `pairs` (key,
    filter) pairs: each pair's key halves, read from the key column the
    data plane keeps in HBM. Each filter of a vertex probes its own key
    column, so no pair shares its read with another. Filter blocks are
    left out: XLA may stage a small filter in VMEM and read it once."""
    return pairs * KEY_HALVES
