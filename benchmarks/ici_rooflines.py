"""The bytes an exchange's share of the inter-chip interconnect is
taken against.  Kept with the benchmark so that no later PR can change
the yardstick (as ``rooflines.py`` keeps the HBM bytes)."""


def least_owner_exchange_bytes_per_chip(nv: int, chips: int) -> int:
    """The least one chip must SEND in one dense iteration of the owner
    exchange: every chip generates a 4-byte candidate for each of the
    ``nv`` vertices, keeps those of the ``nv / chips`` it owns, and
    each of the others crosses the interconnect once.  The pair rows'
    label ``all_gather``, the sparse iterations' queues, the loop's
    scalars and the padding of the parts are left out: a lower bound,
    so a share of the interconnect's peak taken against it cannot pass
    100%.  On one chip nothing crosses."""
    return 4 * int(nv) * (int(chips) - 1) // int(chips)
