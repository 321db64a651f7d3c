"""The bytes and operations the dot (SDDMM) path's roofline share is
taken against: counts of the MATHEMATICS of one collaborative-filtering
sweep, the same whatever implements it.  Kept with the benchmark so
that no later PR can change the yardstick."""


def least_bytes_per_iteration(nv: int, stored_edges: int, k: int) -> int:
    """The least one sweep must move through HBM: every stored edge's
    4-byte source id and 4-byte rating are read once, and every
    vertex's K float32 factors are read and written once.  The
    gathered source and destination rows, lane offsets, masks and the
    pair rows' padding are left out: a lower bound."""
    return 8 * int(stored_edges) + 8 * int(k) * int(nv)


def least_flops_per_iteration(stored_edges: int, k: int) -> int:
    """The least arithmetic of one sweep: per stored edge the inner
    product <src, dst> (2K) and the scaled accumulate err * src into
    the destination's sum (2K).  The dense [128, 128] blocks an MXU
    formulation computes and discards, and the passes a float32
    contraction takes, are left out: a lower bound."""
    return 4 * int(k) * int(stored_edges)
