"""The bytes and operations a kernel's roofline share is taken
against.  Kept with the benchmark so that no later PR can change the
yardstick."""


def least_bytes_per_iteration(nv: int, stored_edges: int) -> int:
    """The least one engine iteration must move through HBM: every
    stored edge's 4-byte source id is read once, and every vertex's
    4-byte state is read and written once.  Gathered state values,
    destination ids, masks and the pair rows' padding are left out:
    a lower bound, so a share of the roofline taken against it cannot
    pass 100%."""
    return 4 * int(stored_edges) + 8 * int(nv)
