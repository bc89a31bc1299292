"""Config system: typed arch configs, shape sets, and a registry.

Every assigned architecture is a selectable config (``--arch <id>``); each
arch carries its own input-shape set so every (arch x shape) cell is
well-defined.  BFS (the paper's own workload) registers its configs here
too, so the launcher treats it uniformly.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

# --------------------------------------------------------------------------
# Shape specs
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class LMShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


LM_SHAPES: Tuple[LMShape, ...] = (
    LMShape("train_4k", 4096, 256, "train"),
    LMShape("prefill_32k", 32768, 32, "prefill"),
    LMShape("decode_32k", 32768, 128, "decode"),
    LMShape("long_500k", 524288, 1, "decode"),
)


@dataclass(frozen=True)
class GNNShape:
    name: str
    n_nodes: int
    n_edges: int
    d_feat: int = 0
    batch_nodes: int = 0            # sampled-training seed batch
    fanout: Tuple[int, ...] = ()    # neighbor-sampler fanouts
    batch_graphs: int = 0           # batched-small-graphs
    kind: str = "full"              # "full" | "sampled" | "batched"


GNN_SHAPES: Tuple[GNNShape, ...] = (
    GNNShape("full_graph_sm", 2708, 10556, d_feat=1433, kind="full"),
    GNNShape("minibatch_lg", 232965, 114615892, batch_nodes=1024,
             fanout=(15, 10), kind="sampled"),
    GNNShape("ogb_products", 2449029, 61859140, d_feat=100, kind="full"),
    GNNShape("molecule", 30, 64, batch_graphs=128, kind="batched"),
)


@dataclass(frozen=True)
class RecsysShape:
    name: str
    batch: int
    n_candidates: int = 0
    kind: str = "train"  # "train" | "serve" | "retrieval"


RECSYS_SHAPES: Tuple[RecsysShape, ...] = (
    RecsysShape("train_batch", 65536, kind="train"),
    RecsysShape("serve_p99", 512, kind="serve"),
    RecsysShape("serve_bulk", 262144, kind="serve"),
    RecsysShape("retrieval_cand", 1, n_candidates=1_000_000, kind="retrieval"),
)


@dataclass(frozen=True)
class BFSShape:
    name: str
    scale: int           # 2**scale vertices (Graph500 convention)
    degree: int = 16
    n_roots: int = 1     # batched roots (pod axis)
    kind: str = "bfs"


BFS_SHAPES: Tuple[BFSShape, ...] = (
    BFSShape("scale22", 22),
    BFSShape("scale26", 26),
    BFSShape("scale30", 30),
)

# --------------------------------------------------------------------------
# Arch configs
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25


@dataclass(frozen=True)
class LMConfig:
    arch: str
    family: str            # "dense" | "moe"
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0
    rope_theta: float = 10000.0
    swa_window: Optional[int] = None      # sliding-window attention
    moe: Optional[MoEConfig] = None
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat_policy: str = "full"         # "none" | "full" | "dots"
    opt_state_dtype: str = "float32"
    loss_bf16: bool = False            # bf16 logits matmul, f32 accumulate
    fsdp: bool = False                 # shard dense weights over dp too
    shapes: Tuple[LMShape, ...] = LM_SHAPES

    def __post_init__(self):
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)

    @property
    def kind(self) -> str:
        return "lm"

    def n_params(self) -> int:
        """Total parameter count (embedding + blocks)."""
        d, L = self.d_model, self.n_layers
        attn = d * (self.n_heads * self.d_head) + 2 * d * (self.n_kv_heads * self.d_head) \
            + (self.n_heads * self.d_head) * d
        if self.moe is not None:
            ff = self.moe.n_experts * 3 * d * self.moe.d_ff_expert + d * self.moe.n_experts
        else:
            ff = 3 * d * self.d_ff
        norms = 2 * d
        return L * (attn + ff + norms) + self.vocab * d + d

    def n_active_params(self) -> int:
        d, L = self.d_model, self.n_layers
        attn = d * (self.n_heads * self.d_head) + 2 * d * (self.n_kv_heads * self.d_head) \
            + (self.n_heads * self.d_head) * d
        if self.moe is not None:
            ff = self.moe.top_k * 3 * d * self.moe.d_ff_expert + d * self.moe.n_experts
        else:
            ff = 3 * d * self.d_ff
        return L * (attn + ff + 2 * d) + self.vocab * d + d


@dataclass(frozen=True)
class GNNConfig:
    arch: str
    model: str              # "gin" | "gat" | "meshgraphnet" | "mace"
    n_layers: int
    d_hidden: int
    n_heads: int = 1
    aggregator: str = "sum"
    l_max: int = 0                   # MACE
    correlation_order: int = 0       # MACE
    n_rbf: int = 0                   # MACE
    eps_learnable: bool = False      # GIN
    mlp_layers: int = 2              # MeshGraphNet
    n_classes: int = 16
    dtype: str = "float32"
    shapes: Tuple[GNNShape, ...] = GNN_SHAPES

    @property
    def kind(self) -> str:
        return "gnn"


@dataclass(frozen=True)
class RecsysConfig:
    arch: str
    n_sparse: int
    embed_dim: int
    n_attn_layers: int
    n_heads: int
    d_attn: int
    vocab_sizes: Tuple[int, ...] = ()
    mlp_hidden: Tuple[int, ...] = (256, 128)
    dtype: str = "float32"
    shapes: Tuple[RecsysShape, ...] = RECSYS_SHAPES

    def __post_init__(self):
        if not self.vocab_sizes:
            # Criteo-like mix: a few huge tables, many medium/small ones.
            sizes = []
            for i in range(self.n_sparse):
                if i % 8 == 0:
                    sizes.append(2_000_000)
                elif i % 4 == 0:
                    sizes.append(200_000)
                elif i % 2 == 0:
                    sizes.append(20_000)
                else:
                    sizes.append(2_000)
            object.__setattr__(self, "vocab_sizes", tuple(sizes))

    @property
    def kind(self) -> str:
        return "recsys"

    def n_embed_rows(self) -> int:
        return sum(self.vocab_sizes)


@dataclass(frozen=True)
class BFSConfig:
    arch: str = "bfs-rmat"
    # "2d" checkerboard (paper §4) | "1d" row strips, dense bitmap
    # allgather (Alg. 1/2 baseline) | "1ds" row strips, sparse
    # owner-directed frontier exchange with bitmap fallback.
    # 1D has no fold/transpose phases: fold_mode only applies to 2D.
    decomposition: str = "2d"
    storage: str = "csr"          # "csr" | "dcsc"
    # fold: "alltoall" (paper-faithful) | "reduce" (ring RS) |
    #       "bitmap"/"bitmap_pure" (beyond-paper compact fold)
    fold_mode: str = "reduce"
    alpha: float = 14.0           # top-down -> bottom-up switch (Beamer)
    beta: float = 24.0            # bottom-up -> top-down switch
    direction_optimizing: bool = True
    # instrument=True compiles the full counter/level_stats bookkeeping
    # into the search program (Eq. 2 validation, crossover artifacts);
    # instrument=False compiles the counters OUT and fuses the per-level
    # scalar all-reduces the loop genuinely needs into ONE vector psum
    # (+ one pmax under a pod axis) — the latency-lean fast path the
    # paper's depth/time/TEPS runs use; its level_stats keep n_f, m_f,
    # mode and used (NaN expand words).  Parents are identical either way.
    instrument: bool = True
    compact_updates: bool = False  # bottom-up compact (child,parent) sends
    # "1ds" sparse-bucket encoding: "packed" bit-packs local offsets at
    # codec_bits(chunk) bits each behind a count word (~3x fewer bucket
    # bytes; kernels/frontier_codec), "none" ships raw i32 global ids.
    # Parents are bit-identical; only wire volume and the planned cap_x
    # crossover change.  Ignored by "1d"/"2d".
    frontier_codec: str = "packed"
    # Software-pipelined level expand (default 1 = today's schedule).
    # 1d/1ds: split the top-down frontier allgather into expand_chunks
    # sub-chunk collectives, each consumed by local discovery while the
    # next is in flight — same bytes, latency overlapped; must divide
    # the per-strip bitmap extent (chunk/32 words; plan_bfs validates)
    # and, for 1ds, the planned bucket capacity cap_x.  2d: any value
    # > 1 switches the bottom-up systolic rotation to the pipelined R/G
    # split ring (the completed-bitmap permute is issued ahead of the
    # local scan; accumulated finds ride a second permute consumed only
    # for the post-scan exactness filter).  Parents are bit-identical
    # to expand_chunks=1 in every decomposition.
    expand_chunks: int = 1
    rmat_a: float = 0.57
    rmat_b: float = 0.19
    rmat_c: float = 0.19
    shapes: Tuple[BFSShape, ...] = BFS_SHAPES

    @property
    def kind(self) -> str:
        return "bfs"


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

_REGISTRY: Dict[str, Any] = {}


def register(cfg: Any) -> Any:
    if cfg.arch in _REGISTRY:
        raise ValueError(f"duplicate arch {cfg.arch}")
    _REGISTRY[cfg.arch] = cfg
    return cfg


def get_config(arch: str) -> Any:
    _ensure_loaded()
    if arch not in _REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[arch]


def list_archs() -> Sequence[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


def reduced(cfg: Any, **overrides: Any) -> Any:
    """A smoke-test-sized variant of a config (same family, tiny dims)."""
    return dataclasses.replace(cfg, **overrides)


_LOADED = False


def _ensure_loaded() -> None:
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    # Importing the per-arch modules populates the registry.
    from repro.configs import (  # noqa: F401
        stablelm_3b, smollm_135m, starcoder2_7b, qwen3_moe_30b_a3b,
        mixtral_8x22b, mace, gin_tu, gat_cora, meshgraphnet, autoint,
        bfs_rmat,
    )
