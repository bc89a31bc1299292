"""The paper's §6 alpha-beta communication model (Table 1 / Eq. 2).

Counts are 64-bit words per *entire search*, matching the paper's units.
The distributed implementation threads live counters through every
collective; benchmarks compare measured "useful words" against these
closed forms.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


def topdown_words(n: int, m: int, pr: int, pc: int) -> float:
    """w_t ~= 4m + n*pr  (undirected: each edge examined from both sides,
    2 words per edge endpoint pair; expand replicates n along columns)."""
    return 4.0 * m + float(n) * pr


def bottomup_words(n: int, pr: int, pc: int, s_b: float = 4.0) -> float:
    """w_b ~= n * (s_b*(pr+pc+1)/64 + 2)   (Table 1 total)."""
    return n * (s_b * (pr + pc + 1) / 64.0 + 2.0)


def ratio_eq2(k: float, pc: int, s_b: float = 4.0) -> float:
    """Eq. (2), square grid pr=pc: (pc + 4k) / (s_b(2pc+1)/64 + 2)."""
    return (pc + 4.0 * k) / (s_b * (2.0 * pc + 1.0) / 64.0 + 2.0)


def fold_bitmap_level_words(nr: int, pc: int, cap_w: int) -> float:
    """Per-level, per-device wire of the bitmap fold (steps._fold_bitmap):
    the exchange is exactly 2 bitmap all_to_all rounds (candidate
    presence out, winner bits back — nr bits = nr/64 words each) plus
    2 id all_to_alls (winner parent values + local offsets, pc*cap_w
    ids each, 1 id = 1 word):

        2 * nr/64  +  2 * pc * cap_w

    This is the ONE place the formula lives: the live ``wire_fold``
    counter multiplies it by p, and tests pin the counter against it —
    docstring, counter, and model cannot drift."""
    return 2.0 * nr / 64.0 + 2.0 * pc * cap_w


def level_collective_budget(decomposition: str, mode: str, pc: int = 1,
                            fold_mode: str = "alltoall",
                            compact_updates: bool = False,
                            codec: str = "none",
                            expand_chunks: int = 1) -> int:
    """Per-level collective-op budget of the ``instrument=False`` fast
    path, counted as collective ops in the LOWERED level body (both
    branches of a lax.cond count — StableHLO keeps them in the text
    even though only one executes).  ``tests/test_perf_guard.py``
    asserts the compiled programs stay within these, so future PRs
    cannot silently re-bloat the schedule; the shared ``_search_loop``
    adds exactly one fused vector psum per level on top (plus one pmax
    when searches are pod-batched).

      2d top-down : transpose ppermute + allgather + fold
                    (alltoall: 1 op; ring reduce: pc-1 ppermutes;
                    bitmap: 4 all_to_alls — 2 bitmap rounds + winner
                    values + offsets — and the runtime-fallback variant
                    adds its overflow pmax + dense all_to_all branch)
      2d bottom-up: transpose ppermute + allgather + (pc-1) hoisted
                    rotation ppermutes + ONE batched update all_to_all
                    (compact updates add 1 pmax + the dense-fallback
                    all_to_all in the other cond branch).  With
                    ``expand_chunks > 1`` the systolic rotation is
                    SOFTWARE-PIPELINED: the carried bitmap splits into a
                    pure-rotation R chain (pre-level completed, issued
                    ahead of the local scan with no data dependency on
                    it) and a G chain of accumulated finds (consumed
                    only at scan end for the exactness post-filter) —
                    2(pc-1) ppermutes instead of pc-1, buying overlap
                    with an extra latency-cheap permute per sub-step.
      1d          : one bitmap allgather per level; ``expand_chunks=C``
                    splits it into C pipelined sub-chunk allgathers
                    (budget C), each consumed while the next is in
                    flight — same total bytes
                    (``chunked_expand_1d_level_words``).
      1ds td      : sparse/dense allgather pair (one cond, 2 in text;
                    1 executes) — the overflow predicate rides the
                    previous level's fused reduction.  The packed codec
                    (codec="packed") changes the BYTES on the wire, not
                    the op count: the count word rides inside the same
                    allgathered bucket buffer, so the budget is
                    identical by construction and the guard pins that.
                    ``expand_chunks=C`` runs C sub-bucket exchanges per
                    branch: budget 2C in text, C execute.
    """
    if codec not in ("none", "packed"):
        raise ValueError(f"no collective budget modeled for "
                         f"codec={codec!r}")
    if expand_chunks < 1:
        raise ValueError(f"no collective budget modeled for "
                         f"expand_chunks={expand_chunks!r}")
    if decomposition == "2d":
        if mode == "td":
            folds = {"alltoall": 1, "reduce": max(pc - 1, 1),
                     "bitmap_pure": 4, "bitmap": 6}
            if fold_mode not in folds:
                raise ValueError(f"no collective budget modeled for "
                                 f"fold_mode={fold_mode!r}")
            return 2 + folds[fold_mode]
        if mode == "bu":
            rot = (2 if expand_chunks > 1 else 1) * (pc - 1)
            return rot + 3 + (2 if compact_updates else 0)
    if decomposition in ("1d", "1ds") and mode in ("td", "bu"):
        if decomposition == "1ds" and mode == "td":
            return 2 * expand_chunks
        if decomposition == "1d" and mode == "td":
            return expand_chunks
        return 1     # bottom-up always exchanges the one dense bitmap
    raise ValueError(f"no collective budget modeled for "
                     f"decomposition={decomposition!r} mode={mode!r}")


def level_budgets_for(decomposition: str, *, pc: int, p: int,
                      fold_mode: str = "alltoall",
                      compact_updates: bool = False,
                      frontier_codec: str = "none",
                      expand_chunks: int = 1) -> Dict[str, int]:
    """Both per-level budgets for one registry-enumerated schedule case
    (``repro.analysis.registry.budget_cases``): the keyword names match
    the BFSConfig fields a Decomposition entry lists in its
    ``schedule_dims``, so the enumeration needs no per-entry adapter.
    The grid size the budget scales with is the fold/ring extent ``pc``
    for the 2d checkerboard and the strip count ``p`` for 1d/1ds."""
    grid = pc if decomposition == "2d" else p
    return {mode: level_collective_budget(
        decomposition, mode, grid, fold_mode=fold_mode,
        compact_updates=compact_updates, codec=frontier_codec,
        expand_chunks=expand_chunks) for mode in ("td", "bu")}


# ---------------------------------------------------------------------------
# 1D row decomposition (the paper's comparison baseline, Alg. 1/2)
# ---------------------------------------------------------------------------


def expand_1d_level_words(n, p):
    """Per-level wire of the DENSE 1D frontier exchange: one n-bit bitmap
    per level, every chunk replicated to the other p-1 processors ->
    (p-1) * n/64 global 64-bit words.  Pure arithmetic, so it is the ONE
    place the word-size conversion lives: the live ``wire_expand``
    counter (core/steps_1d.py, traced values) and the host-side closed
    forms both call it and cannot drift."""
    return (p - 1) * (n / 64.0)


def chunked_expand_1d_level_words(n, p, n_chunks: int):
    """Per-level wire of the CHUNKED (software-pipelined) dense 1D
    expand: the one bitmap allgather splits into ``n_chunks`` sub-chunk
    allgathers — each owner ships chunk/n_chunks bits per step, all
    steps together exactly the chunk — so the total is IDENTICAL to the
    single-gather schedule.  Chunking moves latency (overlap with the
    per-sub-chunk SpMSV), not bytes; this form exists so the measured
    ``wire_expand`` counter and the overlap artifact pin that invariant
    rather than assume it.  ``n_chunks`` must divide the per-strip
    bitmap extent (chunk/32 packed words) — the same constraint
    ``plan_bfs`` validates."""
    if n_chunks < 1:
        raise ValueError(f"expand_chunks must be >= 1, got {n_chunks}")
    chunk_words = (n // max(p, 1)) // 32
    if chunk_words % n_chunks:
        raise ValueError(
            f"expand_chunks={n_chunks} does not divide the per-strip "
            f"bitmap extent ({chunk_words} packed words)")
    return expand_1d_level_words(n, p)


def expand_1d_words(n: int, p: int, n_levels: int) -> float:
    """Exact wire volume of the allgather-based ``"1d"`` implementation
    over a whole search: ``n_levels`` dense bitmap exchanges.  This is
    the closed form the 1D ``wire_expand`` counter must reproduce (there
    is no fold/transpose/rotate wire in 1D)."""
    return float(n_levels) * expand_1d_level_words(n, p)


def sparse_expand_1d_words(n_f, p):
    """Per-level wire of the SPARSE owner-directed 1D frontier exchange
    (``"1ds"``): each of the ``n_f`` global frontier ids is shipped by
    its owner to the other p-1 processors, 1 id = 1 word.  Works on
    traced values (the live counter) and on host floats (the model)."""
    return n_f * (p - 1.0)


def codec_bits(chunk: int) -> int:
    """Fixed offset width of the packed ``"1ds"`` frontier codec: local
    offsets live in [0, chunk), so ceil(log2(chunk)) bits each.  Static
    — chunk is a partition constant — which is what lets encode/decode
    be pure gathers (kernels/frontier_codec)."""
    return max(1, int(chunk - 1).bit_length())


def codec_packed_words(cap_x: int, bits: int) -> int:
    """u32 words holding ``cap_x`` offsets bit-packed at ``bits`` each."""
    return -((-cap_x * bits) // 32)


def codec_bucket_words(cap_x: int, bits: int) -> int:
    """Physical u32 words of one encoded bucket: 1 count word + the
    packed payload.  The tiled allgather moves p of these per level."""
    return 1 + codec_packed_words(cap_x, bits)


def compressed_expand_1d_words(n_f, p, bits: int, n_chunks: int = 1):
    """Per-level wire of the PACKED sparse 1D exchange in the paper's
    64-bit-word units: each of the ``n_f`` frontier ids costs ``bits``
    bits instead of a 64-bit word, plus one u32 count word per bucket
    from each of the p owners.  Everything is replicated to the other
    p-1 processors.  Works on traced values (the live counter) and on
    host floats (the model); the raw-id counterpart is
    ``sparse_expand_1d_words``.

    ``n_chunks > 1`` models the software-pipelined exchange: each owner
    ships ``n_chunks`` sub-range buckets per level (one count word
    each), with offsets packed at ``codec_bits(chunk / n_chunks)`` bits
    — callers pass the narrower width.  Id bytes shrink, count-word
    bytes grow n_chunks-fold; the raw codec and the dense fallback are
    byte-identical to the unchunked schedule."""
    return (p - 1.0) * (n_f * bits + 32.0 * p * n_chunks) / 64.0


def compressed_expand_padded_words(cap_x: int, p: int, bits: int) -> float:
    """Physical buffer volume of the packed static-shape exchange, in
    64-bit words: p owners x (p-1) peers x the full encoded bucket
    (``codec_bucket_words`` u32 = half that many paper words), sentinel
    slots included.  Compare against ``sparse_expand_padded_words``
    (whose i32 ids are likewise 1/2 paper word each, reported in id
    units there) and the dense ``expand_1d_level_words``."""
    return float(p) * (p - 1.0) * codec_bucket_words(cap_x, bits) / 2.0


def hybrid_expand_1d_level_words(n_f_local_max: float, n_f: float, n: int,
                                 p: int, cap_x: int,
                                 bits: int = 0) -> float:
    """Overflow model for one ``"1ds"`` level: the sparse exchange ships
    ids while every per-processor bucket fits ``cap_x``; any overflow
    falls back to the dense bitmap for that level (the per-level hybrid,
    mirroring the direction-optimizing switch).  ``bits > 0`` models the
    packed codec on the sparse branch; 0 keeps raw 1-id-=-1-word ids."""
    if n_f_local_max > cap_x:
        return expand_1d_level_words(n, p)
    if bits > 0:
        return compressed_expand_1d_words(n_f, p, bits)
    return sparse_expand_1d_words(n_f, p)


def sparse_expand_padded_words(cap_x: int, p) -> float:
    """Physical buffer volume of the STATIC-SHAPE sparse exchange: the
    tiled allgather always moves the full cap_x-slot bucket — sentinels
    included — from each of the p owners to its p-1 peers, whatever the
    live frontier size.  Reported in the same 1-id-=-1-word units as
    ``sparse_expand_1d_words`` so the two are directly comparable; note
    ids are i32 on the wire, so at the planned crossover capacity
    (cap_x ~ n/(64p)) the padded buckets cost the same BYTES as the
    n-bit dense bitmap — the id counter measures the alltoallv volume
    of the sparse formulation the exchange models, not the padding."""
    return float(p) * (p - 1.0) * cap_x


def plan_cap_x(n: int, p: int, m: int, align: int = 32,
               bits: int = 64) -> int:
    """Plan the ``"1ds"`` per-destination send-bucket capacity from the
    graph degree stats.  The dense bitmap costs (p-1)*n/64 words a level
    while the sparse exchange costs n_f*bits/64*(p-1) (``bits`` = 64 for
    raw ids, ``codec_bits(chunk)`` for the packed codec), so sparse only
    wins while the global frontier is under n/bits ids — n/(bits*p) per
    processor.  The bucket cap bounds the PER-PROCESSOR frontier, so the
    degree-stat headroom is the expected per-bucket level-1 load,
    (2m/n)/p on a symmetrized graph (a whole level-1 frontier spreads
    over all p owners); the ``align`` floor absorbs skew.  Capping at
    the crossover keeps the planned hybrid within bucket granularity of
    the per-level optimum: a fitting level ships at most the dense
    bitmap volume, and levels the sparse path cannot win overflow to the
    bitmap.  ``m`` is required: planning without edge stats silently
    collapses the headroom term, which is exactly the call-site bug this
    signature exists to refuse."""
    if m <= 0:
        raise ValueError(
            f"plan_cap_x needs the real edge count to size the level-1 "
            f"headroom (got m={m}); thread PlanStatics.n_real_edges or "
            f"graph.m from the call site")
    chunk = max(n // max(p, 1), 1)
    d_avg = int(2.0 * m / n) if n else 0
    cap = max(n // (max(bits, 1) * max(p, 1)), d_avg // max(p, 1) + 1,
              align)
    cap = ((cap + align - 1) // align) * align
    return min(cap, ((chunk + align - 1) // align) * align)


def topdown_1d_words(m: int, p: int) -> float:
    """Classic sparse 1D top-down volume (Buluc & Madduri): every
    cross-processor edge endpoint is shipped once as a vertex id, and a
    random partition leaves a (p-1)/p fraction of the 2m directed
    endpoints remote.  The measured counterpart is the ``"1ds"``
    ``wire_expand`` counter with overflow disabled (cap_x = chunk)."""
    return 2.0 * m * (p - 1) / p


def strip_csr_pointer_words(n: int, p: int) -> float:
    """§5.1 storage charge against 1D compressed formats: an uncompressed
    strip CSC needs n+1 column pointers on EVERY processor — O(n*p)
    aggregate words, growing with the machine at fixed n."""
    return float(p) * (n + 1)


def strip_dcsc_pointer_words(nzc_total: float, p: int) -> float:
    """Strip DCSC answer: (jc, cp) pairs over non-empty columns only,
    2*nzc + 2 words per strip — O(min(n, m)) aggregate, independent of n
    per processor.  ``nzc_total`` = sum of per-strip non-empty column
    counts (<= m, and <= the 2*ef*n distinct sources for R-MAT)."""
    return 2.0 * float(nzc_total) + 2.0 * p


# ---------------------------------------------------------------------------
# Build-phase (distributed graph construction) closed forms
# ---------------------------------------------------------------------------


def build_route_1d_words(m_input: int, p: int) -> float:
    """Expected owner-routing volume of the 1D distributed build: every
    generated edge is emitted in both directions (symmetrization happens
    before routing, 2*m_input records), each record is one 64-bit word
    (two i32 endpoints), and a uniformly partitioned destination leaves
    a (p-1)/p fraction remote.  One all_to_all round."""
    return 2.0 * m_input * (p - 1) / p


def build_route_2d_words(m_input: int, pr: int, pc: int) -> float:
    """Expected two-hop routing volume of the 2D build: hop 1 moves each
    record to its block COLUMN owner along the pc-sized axis, hop 2 to
    its block ROW owner along the pr-sized axis — the same record count
    as 1D, charged per hop."""
    return 2.0 * m_input * ((pc - 1) / pc + (pr - 1) / pr)


def build_route_padded_words(p: int, cap_route: int) -> float:
    """Actual shipped volume of one capped all_to_all routing round:
    every device ships its full (p, cap_route) record buckets minus the
    diagonal, regardless of fill — the static-shape tax the expected
    forms above are compared against."""
    return float(p) * (p - 1) * cap_route


def relabeled_route_share(p: int, scale: int, a: float = 0.57,
                          b: float = 0.19) -> float:
    """Expected fraction of the records the heaviest of p buckets takes
    once the vertex ids are relabeled (graph/rmat.py ``VertexRelabel``,
    which every build over more than one shard applies): the relabel
    scatters R-MAT's low-id hubs, so a bucket takes a uniform 1/p plus
    at most the heaviest vertex's share (a+b)**scale of the endpoints,
    which no relabel can split.  One bucket takes every record."""
    if p <= 1:
        return 1.0
    return min(1.0, 1.0 / p + (a + b) ** scale)


def plan_cap_route(records: int, p: int, share: float,
                   slack: float = 1.5, pad: int = 32) -> int:
    """Static per-destination bucket capacity for one routing round:
    ``records`` locally generated records spread over p buckets whose
    heaviest takes ``share`` of them (``relabeled_route_share``),
    inflated by ``slack`` for sampling noise.  Overflow is detected on
    device and raised loudly — the build never silently drops an edge."""
    frac = max(share, 1.0 / max(p, 1))
    cap = int(slack * frac * records) + pad
    return ((cap + pad - 1) // pad) * pad


@dataclass(frozen=True)
class AlphaBeta:
    """Machine terms for the latency/bandwidth model. Defaults are TPU v5e
    ICI-flavored stand-ins (used for *relative* predictions only)."""
    alpha_n: float = 1e-6        # network latency (s)
    beta_n: float = 1.0 / 50e9   # s per byte per link

    def expand_cost(self, n: int, pr: int, pc: int, word_bytes: int = 8) -> float:
        return pr * self.alpha_n + (n / pc) * word_bytes * self.beta_n

    def fold_cost(self, m: int, pr: int, pc: int, word_bytes: int = 8) -> float:
        p = pr * pc
        return pc * self.alpha_n + (m / p) * word_bytes * self.beta_n

    def bottomup_level_cost(self, n: int, pr: int, pc: int) -> float:
        # pc sub-steps of rotation + updates, bitmap-compressed
        rotate = pc * (self.alpha_n + (n / (pr * pc) / 8) * self.beta_n)
        gather = pr * self.alpha_n + (n / pc / 8) * self.beta_n
        updates = pc * self.alpha_n + (n / (pr * pc)) * 8 * self.beta_n
        return rotate + gather + updates


# ---------------------------------------------------------------------------
# Graph500 validator collective budget (core/validate.py)
# ---------------------------------------------------------------------------


def validate_collective_budget(decomposition: str) -> Dict[str, int]:
    """Whole-program collective budget for the sharded parent-tree
    validator, per decomposition (pinned in tests/test_perf_guard.py).

    The validator spends exactly: one tiled all_gather per mesh axis to
    replicate the candidate parents (1 for the strip entries, 2 for
    2d), one psum to OR the per-shard tree-edge-existence marks, and
    one psum for the final (6,) verdict vector.  Everything else —
    pointer-doubling depth resolution, per-edge level/reachability
    checks — is shard-local.
    """
    if decomposition == "2d":
        gathers = 2
    elif decomposition in ("1d", "1ds"):
        gathers = 1
    else:
        raise ValueError(
            f"no validator collective budget for {decomposition!r}; "
            "extend validate_collective_budget alongside the new "
            "decomposition's local_edges hook")
    return {"all-gather": gathers, "all-reduce": 2,
            "total": gathers + 2}
