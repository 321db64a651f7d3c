"""Analytic mesh-scaling model priced from measured chip constants.

This environment exposes ONE physical TPU chip, so multi-chip GTEPS
cannot be *measured* here; the multi-chip path is correctness-tested
on virtual meshes (``__graft_entry__.dryrun_multichip``,
tests/test_multidevice.py) but its economics would otherwise be a
hope.  This module prices a mesh run of the pull engine from
constants measured on the real chip (PERF_NOTES.md), so the scaling
claim is an auditable calculation:

- compute is per-edge work measured at the owner-exchange slot rate
  (the scan keeps every shard at the small-table gather rate
  regardless of total state size -- the whole point of the owner
  layout, PERF_NOTES "scale-25 decomposition"), and it divides by the
  chip count because parts that a single chip must scan SEQUENTIALLY
  run on their own chips on a mesh;
- communication is the owner exchange's ``psum_scatter`` (plus the
  pair rows' state ``all_gather`` when composed), a fixed
  O(state-table) byte volume per chip per iteration that does NOT
  grow with the mesh -- so efficiency is compute-bound until the
  per-chip edge share gets small.

The model is CALIBRATED: tests/test_scalemodel.py reproduces the
recorded single-chip configurations (RMAT25/26 owner and pair+owner
runs, PERF_NOTES round 3/4) from their recorded layout stats.
``project_table`` renders the markdown mesh-projection table; the
PERF_NOTES "per-chip ceiling" section records its output for the
flagship configurations.

Reference anchor: Lux scales by adding GPUs/nodes to the same
binaries (/root/reference/README.md:33-38); this is the TPU-native
pricing of the same move over ICI instead of GASNet.
"""

from __future__ import annotations

from dataclasses import dataclass

# Measured v5e constants (PERF_NOTES.md).  ns figures are per unit of
# the named work on ONE chip; they are flat across the scales measured
# (scale 21-26) because the owner layout pins the gather to the
# small-shard regime and pair rows are row-granular.
OWNER_SLOT_NS = 9.92     # scan gather + pallas partials + combine,
                         # per padded owner slot (PERF_NOTES.md round 3)
GATHER_SMALL_NS = 8.96   # per-edge gather, state table <= ~64 MB
GATHER_BIG_NS = 14.6     # per-edge gather past the emitter step
BIG_TABLE_BYTES = 96e6   # auto-exchange threshold (engine/pull.py)
PAIR_ROW_NS = 150.0      # per delivered 128-lane pair row
# K-dim (SDDMM) pair rows: a delivery row additionally fetches TWO
# [128, K] tile blocks (row-granular, cheap) and runs two 128x128xK
# MXU contractions (D = S @ T^T and the one-hot gradient matmul) plus
# the [128, 128] lane select.  2 x 2*128*128*K flops at the f32 MXU
# rate (~half the 24 TFLOP/s bf16 figure) ~= 5.5 ns per K — MODELED
# from the measured primitive costs, not yet swept on-device
# (PERF_NOTES round 8); the scalar row's 150 ns stays as the fixed
# per-row machinery term.
PAIR_DOT_ROW_K_NS = 5.5
# K-dim residual edges (the chunked dot path) pay the ~9 ns/row src
# gather plus per-edge MXU work that also scales with K.
RESIDUAL_EDGE_NS = 9.92
RESIDUAL_DOT_K_NS = 0.11


def pair_row_ns(kdim: int = 1) -> float:
    """Modeled cost of ONE delivered pair row: the measured 150 ns for
    scalar programs; + PAIR_DOT_ROW_K_NS per K for the SDDMM (K-dim)
    delivery (ops/pairs.pair_partial_dot*)."""
    if kdim <= 1:
        return PAIR_ROW_NS
    return PAIR_ROW_NS + PAIR_DOT_ROW_K_NS * kdim


def residual_edge_ns(kdim: int = 1) -> float:
    """Modeled per-edge cost of the residual (gather) path serving the
    same program: ~9.92 ns scalar, + per-K MXU work on the dot path."""
    if kdim <= 1:
        return RESIDUAL_EDGE_NS
    return RESIDUAL_EDGE_NS + RESIDUAL_DOT_K_NS * kdim


def break_even_fill(kdim: int = 1,
                    residual_ns: float | None = None) -> int:
    """min_fill break-even: live lanes a pair row must deliver to beat
    sending its edges down the residual path — row_cost / residual
    per-edge cost, rounded up.  Scalar: 150 / 9.92 ~= 16 (the measured
    RMAT21 optimum basin is F=12..32, PERF_NOTES round 5).  K=20
    (colfilter): 260 / 12.1 ~= 22 — K-dim rows must be FULLER to pay,
    because row cost grows with K faster than residual cost."""
    if residual_ns is None:
        residual_ns = residual_edge_ns(kdim)
    import math
    return max(1, math.ceil(pair_row_ns(kdim) / residual_ns))


# Paged two-level gather (ops/pagegather.py, round 15): the measured
# primitive costs of its stages (PERF_NOTES round 2).  Static row
# movement is cheap — `jnp.take` of [*, 128] rows = 24 ns/row — and
# the Pallas lane shuffle (`take_along_axis` axis=1 ->
# tpu.dynamic_gather dim 1) is the one fast dynamic primitive.
PAGE_ROW_FETCH_NS = 24.0       # one [*, 128] row fetch (0.19 ns/elem)
LANE_SHUFFLE_NS = 0.38         # per element, 128-wide lane shuffle
# Modeled cost of ONE paged delivery row: the pair row's measured
# 150 ns fetch + compare-reduce machinery (same row shape, same
# combine) PLUS the 128-lane shuffle the paged row adds.  MODELED
# from measured primitive costs, not yet measured end-to-end on
# device (PERF.md section 7, "paged-gather-ab").
PAGED_ROW_NS = PAIR_ROW_NS + 128 * LANE_SHUFFLE_NS     # = 198.64
# K-dim (SDDMM) paged rows run THREE 128x128xK MXU contractions
# (one-hot lane shuffle + D = S @ T^T + the gradient matmul) where
# pair rows run two — 1.5x the pair per-K term.
PAGED_DOT_ROW_K_NS = 1.5 * PAIR_DOT_ROW_K_NS


def paged_row_ns(kdim: int = 1) -> float:
    """Modeled cost of one delivered 128-lane paged row."""
    if kdim <= 1:
        return PAGED_ROW_NS
    return PAGED_ROW_NS + PAGED_DOT_ROW_K_NS * kdim


def flat_gather_ns(table_bytes: float) -> float:
    """The flat per-edge gather rate for a state table of this size:
    the measured small-table 8.96 ns/elem, stepping to 14.6 past the
    ~96 MB emitter cliff (PERF_NOTES rounds 2-3)."""
    return GATHER_BIG_NS if table_bytes > BIG_TABLE_BYTES \
        else GATHER_SMALL_NS


def page_gather_ns(page_ratio: float, fill: float,
                   kdim: int = 1) -> float:
    """Modeled delivered ns/edge of the paged two-level gather
    (ops/pagegather.py) from the plan's MEASURED stats:

      page_ratio  unique fetched page elements per edge
                  (unique_pages * 128 / ne — the dedup'd page fetch's
                  share, at the 0.19 ns/elem static row-fetch rate)
      fill        average live lanes per delivery row (ne / rows —
                  the per-row machinery amortizes over this)

    Both are graph-structure dependent (R-MAT tails vs real-graph
    clustering), which is why ``gather="auto"`` resolves from the
    plan's recorded stats rather than a fixed constant."""
    if fill <= 0:
        raise ValueError(f"fill must be > 0, got {fill}")
    if page_ratio < 0:
        raise ValueError(f"page_ratio must be >= 0, got {page_ratio}")
    fetch = page_ratio * (PAGE_ROW_FETCH_NS / 128.0) * max(1, kdim)
    return fetch + paged_row_ns(kdim) / fill


def page_break_even_fill(page_ratio: float = 1.0,
                         table_bytes: float = 0.0,
                         kdim: int = 1) -> int:
    """Row fill above which the paged path beats the flat gather (at
    a given unique-page ratio): rows under this live-lane count pay
    more in row machinery than the 9/14.6 ns flat rate.  The modeled
    small-table scalar threshold — fill >= 23 at page_ratio 1 — is
    the recorded break-even of round 15 (pinned in
    tests/test_pagegather.py)."""
    import math
    rate = flat_gather_ns(table_bytes)
    if kdim > 1:
        rate = residual_edge_ns(kdim)
    margin = rate - page_ratio * (PAGE_ROW_FETCH_NS / 128.0) \
        * max(1, kdim)
    if margin <= 0:
        return 1 << 30          # flat always wins at this page ratio
    return max(1, math.ceil(paged_row_ns(kdim) / margin))


def page_break_even_ratio(fill: float, table_bytes: float = 0.0,
                          kdim: int = 1) -> float:
    """Largest unique-page ratio at which the paged path still beats
    the flat gather for rows of the given fill (negative = paged can
    never win at this fill)."""
    rate = flat_gather_ns(table_bytes)
    if kdim > 1:
        rate = residual_edge_ns(kdim)
    return (rate - paged_row_ns(kdim) / fill) \
        / ((PAGE_ROW_FETCH_NS / 128.0) * max(1, kdim))


# Page-major split (round 16, ops/pagegather.py mode="pagemajor"):
# the PAIR_ROW_NS = 150 per-row machinery decomposes as one 24 ns
# static row fetch + the compare-reduce/class-combine remainder; the
# page-major layout pays fetch+shuffle per FULL gather row and the
# remainder per (low-fill) virtual row, plus one extra 24 ns take
# binding each virtual row to its gather row's delivered values.
# MODELED from the measured primitive costs like PAGED_ROW_NS
# (PERF.md section 7, "pagemajor-route-ab").
VROW_REDUCE_NS = PAIR_ROW_NS - PAGE_ROW_FETCH_NS       # = 126.0


def pagemajor_gather_ns(page_ratio: float, g_fill: float,
                        v_fill: float, kdim: int = 1,
                        routed: bool = False,
                        itemsize: int = 4) -> float:
    """Modeled delivered ns/edge of the PAGE-MAJOR two-level layout
    from the plan's measured stats: the dedup'd page fetch
    (``page_ratio``) + row fetch and lane shuffle amortized over the
    near-full GATHER rows (``g_fill``) + the compare-reduce machinery
    amortized over the VIRTUAL rows (``v_fill`` — the same joint
    (tile, page) density the plain paged fill measures) + the routing
    hop when the rows cross the mesh (``routed``, the owner plan's
    all_to_all — priced per shipped lane over ICI,
    ``pagemajor_route_ns``).  K-dim (SDDMM) programs are not served
    by this mode (typed refusal, matching ops/pagegather)."""
    if kdim > 1:
        raise ValueError("page-major does not serve K-dim (SDDMM) "
                         "programs; use page_gather_ns")
    if g_fill <= 0 or v_fill <= 0:
        raise ValueError(f"fills must be > 0, got g_fill={g_fill} "
                         f"v_fill={v_fill}")
    if page_ratio < 0:
        raise ValueError(f"page_ratio must be >= 0, got {page_ratio}")
    fetch = page_ratio * (PAGE_ROW_FETCH_NS / 128.0)
    gather = (PAGE_ROW_FETCH_NS + 128 * LANE_SHUFFLE_NS) / g_fill
    reduce = (PAGE_ROW_FETCH_NS + VROW_REDUCE_NS) / v_fill
    route = pagemajor_route_ns(g_fill, itemsize) if routed else 0.0
    return fetch + gather + reduce + route


def pagemajor_route_ns(g_fill: float, itemsize: int = 4) -> float:
    """The routing hop's per-edge price: every (padded) lane of a
    routed 128-lane row ships ``itemsize`` bytes over ICI once, so an
    edge pays itemsize * 128 / g_fill bytes at the link rate — ~0.1
    ns/edge at full rows, which is why trading the hop for full rows
    can pay (the comm-is-permille-of-compute relation the mesh model
    rests on, ICI_BYTES_PER_S)."""
    if g_fill <= 0:
        raise ValueError(f"g_fill must be > 0, got {g_fill}")
    return itemsize * (128.0 / g_fill) / (ICI_BYTES_PER_S * 1e-9)


def pagemajor_break_even_vfill(page_ratio: float = 1.0,
                               g_fill: float = 128.0,
                               table_bytes: float = 0.0,
                               routed: bool = False,
                               itemsize: int = 4) -> int:
    """Virtual-row fill above which page-major beats the flat gather
    (at a given page ratio and gather fill) — the page-major
    counterpart of ``page_break_even_fill``.  The modeled small-table
    threshold at full gather rows — v_fill >= 19 — undercuts the
    plain paged break-even of 23 because the shuffle rides the full
    rows (pinned in tests/test_pagegather.py)."""
    import math
    rate = flat_gather_ns(table_bytes)
    margin = rate - page_ratio * (PAGE_ROW_FETCH_NS / 128.0) \
        - (PAGE_ROW_FETCH_NS + 128 * LANE_SHUFFLE_NS) / g_fill
    if routed:
        margin -= pagemajor_route_ns(g_fill, itemsize)
    if margin <= 0:
        return 1 << 30
    return max(1, math.ceil((PAGE_ROW_FETCH_NS + VROW_REDUCE_NS)
                            / margin))


# MXU compute core (round 23, ops/tiled.chunk_partials use_mxu): the
# per-chunk reduce as one-hot contractions.  The VPU masked reduce
# FUSES (no [C, E, W] intermediate, tiled.py) but runs its
# compare-select machinery once per PAYLOAD SLICE — the wide (K x B)
# payload multiplies the whole row cost.  The MXU path pays a fixed
# per-row toll to MATERIALIZE the [E, W] int8 one-hot (the pair row's
# fetch-shaped cost — modeled at the measured 150 ns pair-row
# machinery + its 0.19 ns/B int8 store, NOT yet measured on device:
# PERF.md section 7, "mxu-core-ab"), after which each payload slice
# is one 128x128 int8 systolic pass (~2 ns at the MXU int8 rate).  min/max
# replay that contraction 2x per ORDER BIT (vote + candidacy
# route-back, tiled._mxu_compare_reduce), which is why compare kinds
# essentially never auto-engage — the resolver is deliberately
# honest about that.
ONEHOT_TILE_NS = 160.0   # materialize + load one [128, W] int8 one-hot
MXU_TILE_NS = 2.0        # one 128x128 int8 contraction, per wide slice


def mxu_reduce_rounds(kind: str, nbits: int = 32) -> int:
    """Contractions per chunk row for a reduce kind: sum is ONE
    one-hot matmul; min/max run the bit-serial tournament — one vote
    + one route-back contraction per bit of the order encoding."""
    if kind == "sum":
        return 1
    if kind in ("min", "max"):
        return 2 * nbits
    raise ValueError(f"unknown reduce kind {kind!r}")


def vpu_reduce_row_ns(wide: int = 1) -> float:
    """Modeled VPU masked-reduce cost of one 128-lane chunk row: the
    measured VROW_REDUCE_NS compare-reduce machinery, once per payload
    slice (the broadcast-select-reduce runs over every K x B lane)."""
    if wide < 1:
        raise ValueError(f"wide must be >= 1, got {wide}")
    return VROW_REDUCE_NS * wide


def mxu_reduce_row_ns(wide: int = 1, kind: str = "sum",
                      nbits: int = 32) -> float:
    """Modeled MXU one-hot cost of one 128-lane chunk row: the fixed
    one-hot materialization + one int8 contraction per payload slice
    per tournament round.  The wide (K x B) payload rides as a free
    MXU minor dimension — only the ~2 ns systolic term scales with
    it, not the 160 ns toll."""
    if wide < 1:
        raise ValueError(f"wide must be >= 1, got {wide}")
    return ONEHOT_TILE_NS + MXU_TILE_NS * wide * mxu_reduce_rounds(
        kind, nbits)


def mxu_break_even_wide(kind: str = "sum", nbits: int = 32) -> int:
    """Smallest K x B payload width at which the MXU one-hot reduce
    beats the fused VPU masked reduce for a kind.  sum: width 2 (the
    one-hot toll needs one extra payload slice to amortize — scalar
    sum stays VPU, so f32 scalar flagships keep their bitwise
    behavior).  min/max: the 2 x nbits tournament rounds outrun the
    VPU's per-slice saving at every width (1 << 30 = never) — those
    paths exist for the measured A/B and the pull-kind revalidators,
    not the auto default."""
    import math
    per_slice_margin = VROW_REDUCE_NS \
        - MXU_TILE_NS * mxu_reduce_rounds(kind, nbits)
    if per_slice_margin <= 0:
        return 1 << 30
    return max(1, math.ceil(ONEHOT_TILE_NS / per_slice_margin))


def resolve_use_mxu(kind: str, wide: int = 1, nbits: int = 32) -> bool:
    """The ``use_mxu="auto"`` resolution: engage the MXU reduce when
    the payload is wide enough to amortize the one-hot toll.  wide is
    the product of the program's vector K and query batch B (both are
    free minor dims of the contraction)."""
    return wide >= mxu_break_even_wide(kind, nbits)


# Query batching (ROADMAP item 2, engine/program.py ``batch``): the
# dense iteration's ONE table gather fetches a [B]-wide CONTIGUOUS
# state row per edge instead of one element — the fetch is
# latency-bound, so the extra lanes ride at roughly the wide-row rate
# (modeled from the measured 150 ns / 128-lane pair-row fetch, NOT
# yet swept on-device: PERF.md section 7, "batch-sweep-on-device").
BATCH_LANE_NS = PAIR_ROW_NS / 128.0      # ~1.17 ns per extra lane


def batched_edge_ns(B: int, rate: float = GATHER_SMALL_NS) -> float:
    """Modeled per-edge cost of ONE batched dense iteration serving B
    queries: the scalar gather latency + (B-1) ride-along lanes."""
    if B < 1:
        raise ValueError(f"B must be >= 1, got {B}")
    return rate + BATCH_LANE_NS * (B - 1)


def per_query_edge_ns(B: int, rate: float = GATHER_SMALL_NS) -> float:
    """Modeled DELIVERED cost per edge per query at batch width B —
    the ~9/B amortization claim, priced honestly: exactly rate/B only
    if extra lanes were free; the wide-row lane term floors it at
    ~BATCH_LANE_NS (~1.2 ns) for large B.  The bench batch-sweep's
    measured 1/query_gteps is the number this predicts."""
    return batched_edge_ns(B, rate) / B


def batch_sweep_table(widths=(1, 2, 4, 8, 16, 32, 64),
                      rate: float = GATHER_SMALL_NS) -> str:
    """Markdown modeled ~9/B table for PERF_NOTES."""
    lines = ["| B | edge ns (batched iter) | ns/edge/query "
             "| vs B=1 |",
             "|---|---|---|---|"]
    base = per_query_edge_ns(1, rate)
    for b in widths:
        pq = per_query_edge_ns(b, rate)
        lines.append(f"| {b} | {batched_edge_ns(b, rate):.2f} | "
                     f"{pq:.2f} | {base / pq:.1f}x |")
    return "\n".join(lines)


STATE_NS_PER_VERTEX = 6.0  # apply + epilogues, per padded vertex
                           # (the ~0.2 s/iter residual in the RMAT25
                           # np=4 decomposition)
# ICI: one v5e link direction (public scaling-book figure).  The
# conclusions are insensitive to 2-4x error here -- comm is permille
# of compute at the scales this engine targets.  Round 19: when the
# communication observatory has MEASURED a link rate on a canonical
# session (observe.calibrate_links -> set_measured_link), the
# projections price from the measurement instead of this figure.
ICI_BYTES_PER_S = 4.5e10
# DCN: inter-slice links are 10-100x thinner than ICI (ROADMAP item
# 3); no canonical figure exists yet, so the model carries the
# midpoint thinness: no multi-slice session has measured it.
DCN_THINNESS_MODEL = 30.0

# Quantized-exchange wire factors (EQuARX-style in-collective block
# quantization, PAPERS.md): owner messages (pagerank partials,
# min-distances) tolerate block-scaled low precision with
# exact-identity padding.  int8 ships 1 payload byte + one f32 scale
# per 32-element block; bf16 halves the word.  These price the
# item-3 target; the quantized exchange itself is not built yet.
QUANT_FACTORS = {"f32": 1.0, "bf16": 0.5,
                 "int8": (32 + 4) / (32 * 4)}

# tier -> measured bytes/s, fed by observe.calibrate_links on
# canonical sessions only (a CPU-mesh "link" rate must never price a
# pod projection; CPU figures stay in the perf ledger, labeled)
_MEASURED_LINKS: dict = {}


def set_measured_link(tier: str, bytes_per_s: float) -> None:
    """Record a MEASURED link rate (observe.calibrate_links).  The
    projections prefer it over the canonical constant from then on."""
    if tier not in ("ici", "dcn"):
        raise ValueError(f"unknown link tier {tier!r}")
    if not bytes_per_s > 0:
        raise ValueError(f"link rate must be > 0, got {bytes_per_s}")
    _MEASURED_LINKS[tier] = float(bytes_per_s)


def measured_link(tier: str) -> float | None:
    """The measured rate for ``tier``, or None when never calibrated."""
    return _MEASURED_LINKS.get(tier)


def link_bytes_per_s(tier: str = "ici") -> float:
    """Link rate of record for a tier: the session's measured figure
    when one exists, else the canonical model (ICI figure; DCN =
    ICI / DCN_THINNESS_MODEL — flagged as model until the
    multi-slice debt is collected).  "local" (single device) has no
    link; pricing comm there is a caller bug."""
    if tier == "local":
        raise ValueError("tier 'local' has no link — single-device "
                         "placements ship zero bytes")
    got = _MEASURED_LINKS.get(tier)
    if got is not None:
        return got
    if tier == "ici":
        return ICI_BYTES_PER_S
    if tier == "dcn":
        return _MEASURED_LINKS.get("ici", ICI_BYTES_PER_S) \
            / DCN_THINNESS_MODEL
    raise ValueError(f"unknown link tier {tier!r}")


@dataclass
class Projection:
    chips: int
    compute_s: float       # per chip, per iteration
    comm_s: float          # per chip, per iteration
    iter_s: float          # compute + comm (no overlap assumed)
    gteps: float           # aggregate: ne / iter_s
    gteps_per_chip: float  # driver metric: aggregate / chips
    efficiency: float      # vs perfect linear scaling of 1 chip

    def row(self) -> str:
        return (f"| {self.chips} | {self.compute_s:.3f} | "
                f"{self.comm_s * 1e3:.1f} | {self.gteps:.3f} | "
                f"{self.gteps_per_chip:.4f} | "
                f"{self.efficiency * 100:.0f}% |")


def project_pull(ne: int, nv: int, chips: int, *,
                 exchange: str = "owner",
                 chunk_inflation: float = 1.2,
                 pair_coverage: float = 0.0,
                 pair_row_inflation: float = 1.0,
                 state_bytes_per_vertex: int = 4,
                 ici_bytes_per_s: float | None = None) -> Projection:
    """Price one pull-engine iteration on a ``chips``-device mesh.

    ``chunk_inflation``/``pair_coverage``/``pair_row_inflation`` come
    from the layout stats the engines already report
    (OwnerLayout.stats; StackedPairPlan.stats "coverage"/"inflation");
    pass a measured configuration's stats to price its mesh run.
    ``ici_bytes_per_s=None`` (default) prices from the link rate of
    record — this session's MEASURED figure when the comm observatory
    calibrated one (set_measured_link), the canonical constant
    otherwise.
    """
    if ici_bytes_per_s is None:
        ici_bytes_per_s = link_bytes_per_s("ici")
    if exchange not in ("owner", "gather"):
        raise ValueError(f"unknown exchange {exchange!r}")
    if not 0.0 <= pair_coverage <= 1.0:
        raise ValueError(f"pair_coverage must be in [0, 1], "
                         f"got {pair_coverage}")
    if chunk_inflation < 1.0:
        raise ValueError(f"chunk_inflation is padded/real slots and "
                         f"cannot be < 1, got {chunk_inflation}")
    if pair_row_inflation < 1.0:
        raise ValueError(f"pair_row_inflation is delivered/ideal rows "
                         f"and cannot be < 1, got {pair_row_inflation}")
    cov = pair_coverage
    pair_rows = ne * cov * pair_row_inflation / 128.0
    residual_ne = ne * (1.0 - cov)
    state_bytes = nv * state_bytes_per_vertex

    if exchange == "owner":
        # every shard stays at the small-table rate; padded slots are
        # the unit of residual work
        edge_ns = residual_ne * chunk_inflation * OWNER_SLOT_NS
        # psum_scatter of per-dst-part partials: each chip ships
        # (P-1)/P of one state table per iteration
        comm_bytes = state_bytes * (chips - 1) / chips
    else:
        per_chip_table = state_bytes  # all_gather materializes it all
        rate = (GATHER_BIG_NS if per_chip_table > BIG_TABLE_BYTES
                else GATHER_SMALL_NS)
        edge_ns = residual_ne * rate
        comm_bytes = state_bytes * (chips - 1) / chips
    if cov > 0.0 and exchange == "owner":
        # pair rows read 128-wide state rows from an all_gather kept
        # only for them (row fetches do not pay the big-table step);
        # the gather path feeds pairs from its one existing all_gather
        comm_bytes += state_bytes * (chips - 1) / chips

    compute_ns = (edge_ns + pair_rows * PAIR_ROW_NS) / chips \
        + nv * STATE_NS_PER_VERTEX / chips
    compute_s = compute_ns * 1e-9
    comm_s = comm_bytes / ici_bytes_per_s
    iter_s = compute_s + comm_s
    gteps = ne / iter_s / 1e9

    one = (edge_ns + pair_rows * PAIR_ROW_NS
           + nv * STATE_NS_PER_VERTEX) * 1e-9
    eff = (gteps / chips) / (ne / one / 1e9)
    return Projection(chips=chips, compute_s=compute_s, comm_s=comm_s,
                      iter_s=iter_s, gteps=gteps,
                      gteps_per_chip=gteps / chips, efficiency=eff)


def phase_model(*, engine: str, exchange: str, ne: int, nv: int,
                kdim: int = 1, pair_coverage: float = 0.0,
                pair_row_inflation: float = 1.0,
                chunk_inflation: float = 1.2,
                state_bytes_per_vertex: int = 4,
                dot: bool = False, scale: float = 1.0,
                paged: bool = False, page_ratio: float = 0.0,
                page_fill: float = 128.0,
                page_scale: float | None = None,
                page_mode: str = "paged",
                page_g_fill: float = 128.0,
                use_mxu: bool = False,
                mxu_wide: int = 1,
                reduce_kind: str = "sum",
                state_nbits: int = 32) -> dict:
    """Per-PHASE predicted nanoseconds for ONE engine iteration
    (``bench.py``'s ``model_ns`` through ``observe._engine_model``).
    Keys are the step's phases (exchange / gather / reduce / apply,
    owner ``gen_exchange``, push relax / update, ``dot_reduce``); a
    value of None means the phase has no measured constant to price
    it — honesty over coverage, per the round-3 rule that un-measured
    figures are flagged models.

    ``scale`` rescales every priced constant by the session
    calibration factor (observe.session_scale: this session's measured
    gather rate over the canonical figure), so predictions are in THIS
    session's nanoseconds — that is what makes a CPU or off-canon
    session's comparison meaningful at all.

    Phase attribution of the project_pull aggregate:
    - gather/relax       per-edge delivery (the ~90%% term): residual
                         edges at the gather rate + pair rows at the
                         150+5.5K ns row cost
    - gen_exchange       owner path: the whole per-slot scan
                         (gather+partials+combine folded, per padded
                         slot) + the pair-row term
    - gather_reduce /    streamed single-phase delivery: same total as
      relax_reduce /     gather+reduce (the fused block loop)
      dot_reduce
    - apply/update       per-vertex epilogue (STATE_NS_PER_VERTEX)
    - exchange           all_gather materialization: free on one chip
                         (a reshape), ICI-priced per mesh chip
    - reduce             VPU: no isolated measured constant (None);
                         with ``use_mxu`` the one-hot contraction IS
                         modeled (mxu_reduce_row_ns over the chunk
                         rows at ``mxu_wide`` = K x B payload slices)
    """
    if engine not in ("pull", "push"):
        raise ValueError(f"unknown engine {engine!r}")
    cov = pair_coverage
    pair_rows = ne * cov * pair_row_inflation / 128.0
    pair_ns = pair_rows * pair_row_ns(kdim) * scale
    residual_ne = ne * (1.0 - cov)
    state_bytes = nv * state_bytes_per_vertex

    if paged:
        # paged two-level delivery (ops/pagegather.py): priced from
        # the plan's recorded unique-page ratio and row fill — total
        # coverage, so no pair/residual split.  ``page_scale`` is the
        # session's measured page-row probe over its canon (the
        # observe.calibrate page_gather probe) — the paged pipeline's
        # platform factor differs from the flat gather's, so it gets
        # its own scale when the caller has one.  The PAGE-MAJOR mode
        # prices its split gather/virtual rates + the routing hop
        # instead (pagemajor_gather_ns).
        if page_mode == "pagemajor":
            per_edge = pagemajor_gather_ns(
                page_ratio, page_g_fill, page_fill,
                routed=exchange == "owner")
        else:
            per_edge = page_gather_ns(page_ratio, page_fill, kdim)
        deliver = ne * per_edge \
            * (scale if page_scale is None else page_scale)
    elif exchange == "owner":
        deliver = residual_ne * chunk_inflation * OWNER_SLOT_NS * scale
    else:
        rate = (GATHER_BIG_NS if state_bytes > BIG_TABLE_BYTES
                else GATHER_SMALL_NS)
        if dot:
            rate = residual_edge_ns(kdim)
        deliver = residual_ne * rate * scale
    apply_ns = nv * STATE_NS_PER_VERTEX * scale
    if paged:
        pair_ns = 0.0

    model: dict[str, float | None] = {}
    if exchange == "owner":
        model["gen_exchange"] = deliver + pair_ns
    else:
        # single-chip all_gather is a reshape; comm pricing only
        # applies on a mesh (project_pull) — unmodeled here
        model["exchange"] = None
        if dot:
            model["dot_reduce"] = deliver + pair_ns
        else:
            key = "relax" if engine == "push" else "gather"
            model[key] = deliver + pair_ns
            if use_mxu:
                rows = ne * chunk_inflation / 128.0
                reduce_ns = rows * mxu_reduce_row_ns(
                    mxu_wide, reduce_kind, state_nbits) * scale
                model["reduce"] = reduce_ns
                model[f"{key}_reduce"] = deliver + pair_ns + reduce_ns
            else:
                model["reduce"] = None
                model[f"{key}_reduce"] = deliver + pair_ns
    model["update" if engine == "push" else "apply"] = apply_ns
    return model


def project_table(ne: int, nv: int, chip_counts=(1, 4, 8, 16, 64),
                  **kw) -> str:
    """Markdown projection table for PERF_NOTES."""
    lines = ["| chips | compute s/iter | comm ms/iter | GTEPS "
             "| GTEPS/chip | efficiency |",
             "|---|---|---|---|---|---|"]
    lines += [project_pull(ne, nv, c, **kw).row() for c in chip_counts]
    return "\n".join(lines)
