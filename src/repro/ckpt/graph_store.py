"""Graph + executable checkpoint store (disk -> traversing in seconds).

Built on ckpt/checkpoint.py's primitives (atomic tmp+rename publish,
step directories, retention, meta validation), this store persists the
two expensive artifacts of a traversal session so a fleet process skips
both the distributed build and the XLA compile:

  * **graph shards** — the device arrays of a ``Blocked1DGraph`` /
    ``BlockedGraph`` (host- or device-built) plus enough metadata to
    reconstruct the dataclass: partition, capacities, per-field
    shapes/dtypes, and the config hash of the BuildSpec that generated
    the edges.  Loading with a mesh lands each array directly in its
    sharded placement (one device_put per field, no repartitioning).
  * **AOT executables** — ``BFSEngine``'s compiled search program via
    ``jax.experimental.serialize_executable``, keyed by a canonical
    config hash over (cfg, partition, statics, mesh axes, shipped keys,
    jax version).  ``BFSPlan.compile(store=...)`` deserializes on hash
    hit and persists on miss; a stale hash or absent serializer just
    recompiles — graph loads, by contrast, FAIL LOUDLY on spec-hash or
    mesh-shape mismatch (a silently wrong graph is worse than a
    recompile).

Store layout (format v2, one file PER SHARD)::

    <root>/graphs/<name>/step_NNNNNNNNNN/{shard_00000.npz, ...,
                                          meta.json}
    <root>/execs/exec_<key>_<hash16>/{payload.bin, trees.pkl, meta.json}

**Content integrity.**  ``meta.json`` carries a CRC32 per shard
(computed over each array's name, dtype, shape, and raw bytes — not
over the npz container, whose zip timestamps are not deterministic).
``load_graph`` verifies every shard's CRC; a corrupted, truncated, or
unreadable shard is *quarantined* (renamed ``*.quarantined``) and
**regenerated in place** from the stored BuildSpec's counter stream
(``graph/dist_build.regen_shard`` — only that shard's slice of the
stream, bit-identical by stream-slice independence).  The regenerated
arrays must reproduce the stored CRC exactly or the load fails loudly;
``store.last_load_report`` records what was checked and repaired.
Writers that crash between ``mkdtemp`` and the atomic rename leak
``.tmp_*`` directories — ``GraphStore.__init__`` sweeps them.
"""
from __future__ import annotations

import json
import os
import pickle
import shutil
import tempfile
import time
import zlib
from dataclasses import asdict, is_dataclass
from typing import Any, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt import checkpoint
from repro.core.partition import Partition1D, Partition2D
from repro.graph.formats import Blocked1DGraph, BlockedGraph
from repro.graph.rmat import VertexRelabel

try:
    from jax.experimental import serialize_executable as _serialize_exec
except Exception:                                    # pragma: no cover
    _serialize_exec = None

FORMAT_VERSION = 2

_GRAPH_KINDS = {"Blocked1DGraph": Blocked1DGraph,
                "BlockedGraph": BlockedGraph}
# dataclass fields that are ints/metadata, not shipped arrays
_SCALAR_FIELDS = {
    "Blocked1DGraph": ("cap", "cap_nzc", "maxdeg_col"),
    "BlockedGraph": ("cap", "cap_seg", "maxdeg_col"),
}


def _mesh_axes(mesh) -> list:
    return [[str(k), int(v)] for k, v in mesh.shape.items()]


def shard_crc32(arrays: Dict[str, np.ndarray]) -> int:
    """CRC32 over one shard's arrays in a canonical byte stream: for
    each field in sorted order, its name, dtype string, shape, and raw
    C-contiguous bytes.  Container-independent on purpose — npz zip
    metadata (timestamps) is not reproducible, array content is."""
    c = 0
    for k in sorted(arrays):
        v = np.ascontiguousarray(arrays[k])
        c = zlib.crc32(k.encode(), c)
        c = zlib.crc32(str(v.dtype).encode(), c)
        c = zlib.crc32(np.asarray(v.shape, np.int64).tobytes(), c)
        c = zlib.crc32(v.tobytes(), c)
    return c & 0xFFFFFFFF


def _n_shards(part) -> int:
    return part.p


def _shard_slice(arrays: Dict[str, np.ndarray], part,
                 k: int) -> Dict[str, np.ndarray]:
    """Shard ``k``'s slice of every field (leading block dims dropped:
    (p, ...) -> (...) for strips, (pr, pc, ...) -> (...) for 2d)."""
    if isinstance(part, Partition1D):
        return {f: v[k] for f, v in arrays.items()}
    return {f: v[k // part.pc, k % part.pc] for f, v in arrays.items()}


def _part_from_meta(meta: Dict) -> Any:
    pm = json.loads(meta["part"])
    if pm["kind"] == "1d":
        return Partition1D(n=pm["n"], n_orig=pm["n_orig"], p=pm["p"])
    return Partition2D(n=pm["n"], n_orig=pm["n_orig"], pr=pm["pr"],
                       pc=pm["pc"])


def plan_exec_hash(plan) -> str:
    """Canonical hash of everything that determines the compiled search
    program: config, partition, static capacities, mesh axes, the keys
    shipped, and the jax version the executable was built by."""
    return checkpoint.config_hash({
        "cfg": plan.cfg, "part": plan.part, "statics": plan.statics,
        "axes": list(plan.axes), "keys": list(plan.keys),
        "mesh": _mesh_axes(plan.mesh), "jax": jax.__version__,
        "format": FORMAT_VERSION})


class GraphStore:
    """One directory of persisted graphs + executables (see module
    docstring for layout).  ``keep`` bounds retained graph steps per
    name, exactly as ckpt.checkpoint.save does."""

    def __init__(self, root: str, keep: int = 3):
        self.root = root
        self.keep = keep
        # forensic state of the most recent load_graph (shards checked,
        # shards repaired + why); None until a graph is loaded
        self.last_load_report: Optional[Dict[str, Any]] = None
        # a writer that died between mkdtemp and the atomic rename left
        # an orphaned .tmp_* dir that can never be published — sweep on
        # open (single-writer discipline: opening a store while another
        # process is mid-save is outside the store's contract)
        self.swept: List[str] = self._sweep_tmp()

    def _sweep_tmp(self) -> List[str]:
        removed = []
        if not os.path.isdir(self.root):
            return removed
        for dirpath, dirnames, _ in os.walk(self.root):
            for d in list(dirnames):
                if d.startswith(".tmp_"):
                    full = os.path.join(dirpath, d)
                    shutil.rmtree(full, ignore_errors=True)
                    dirnames.remove(d)
                    removed.append(full)
        return removed

    # ------------------------------------------------------------------
    # graphs
    # ------------------------------------------------------------------

    def _graph_dir(self, name: str) -> str:
        return os.path.join(self.root, "graphs", name)

    def save_graph(self, name: str, graph, spec=None,
                   step: Optional[int] = None,
                   extra_meta: Optional[Dict] = None) -> str:
        """Persist a graph's device arrays + reconstruction metadata
        under ``graphs/<name>/step_*`` (atomic publish, ``keep``
        retention).  ``spec`` (e.g. dist_build.BuildSpec) is hashed into
        the meta so loads can validate they get the graph they asked
        for."""
        kind = type(graph).__name__
        if kind not in _GRAPH_KINDS:
            raise TypeError(f"cannot store graph of type {kind!r}")
        part = graph.part
        arrays = {k: np.asarray(v)
                  for k, v in graph.device_arrays().items()}
        if isinstance(part, Partition1D):
            part_meta = {"kind": "1d", "n": part.n, "n_orig": part.n_orig,
                         "p": part.p}
        else:
            part_meta = {"kind": "2d", "n": part.n, "n_orig": part.n_orig,
                         "pr": part.pr, "pc": part.pc}
        meta = {
            "graph_kind": kind, "format_version": FORMAT_VERSION,
            "part": json.dumps(part_meta, sort_keys=True),
            "m": int(graph.m), "m_input": int(graph.m_input),
            "scalars": json.dumps(
                {f: int(getattr(graph, f)) for f in _SCALAR_FIELDS[kind]},
                sort_keys=True),
            "fields": json.dumps(
                {k: [list(v.shape), str(v.dtype)]
                 for k, v in sorted(arrays.items())}),
            "relabel": None if graph.relabel is None
            else asdict(graph.relabel),
            **({"spec_hash": checkpoint.config_hash(spec),
                "spec": json.dumps(asdict(spec), sort_keys=True)}
               if is_dataclass(spec) and spec is not None else {}),
            **(extra_meta or {}),
        }
        if step is None:
            latest = checkpoint.latest_step(self._graph_dir(name))
            step = 0 if latest is None else latest + 1
        shards = [_shard_slice(arrays, part, k)
                  for k in range(_n_shards(part))]
        meta["shards"] = len(shards)
        meta["shard_crc32"] = [shard_crc32(s) for s in shards]
        gdir = self._graph_dir(name)
        os.makedirs(gdir, exist_ok=True)
        tmp = tempfile.mkdtemp(dir=gdir, prefix=".tmp_")
        try:
            for k, s in enumerate(shards):
                np.savez(os.path.join(tmp, f"shard_{k:05d}.npz"), **s)
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump({**meta, "step": step, "saved_at": time.time()},
                          f)
            final = os.path.join(gdir, f"step_{step:010d}")
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        checkpoint._retain(gdir, self.keep)
        return final

    def _read_shard(self, path: str) -> Dict[str, np.ndarray]:
        with np.load(path) as z:
            return {k: z[k] for k in z.files}

    def _repair_shard(self, path: str, k: int, meta: Dict, part,
                      want_crc: int) -> Dict[str, np.ndarray]:
        """Quarantine shard ``k``'s file and regenerate its arrays from
        the stored BuildSpec's counter stream; the result must hit the
        stored CRC exactly (stream-slice independence makes the regen
        bit-identical to the original build) or the repair fails."""
        from repro.graph.dist_build import BuildSpec, regen_shard
        if "spec" not in meta:
            raise RuntimeError(
                f"shard {k} of {os.path.dirname(path)} failed its CRC "
                f"check and the graph was stored without a BuildSpec — "
                f"cannot regenerate")
        if os.path.exists(path):
            os.replace(path, path + ".quarantined")
        spec = BuildSpec(**json.loads(meta["spec"]))
        arrs = regen_shard(spec, meta["graph_kind"], part, k,
                           json.loads(meta["scalars"]),
                           json.loads(meta["fields"]))
        got = shard_crc32(arrs)
        if got != want_crc:
            raise RuntimeError(
                f"regenerated shard {k} CRC {got:#010x} does not match "
                f"the stored CRC {want_crc:#010x} — the store meta and "
                f"the BuildSpec disagree; refusing to publish")
        tmp = path + ".tmp_regen.npz"
        try:
            np.savez(tmp, **arrs)
            os.replace(tmp, path)
        except BaseException:
            if os.path.exists(tmp):
                os.remove(tmp)
            raise
        return arrs

    def load_graph(self, name: str, mesh=None,
                   step: Optional[int] = None, expect_spec=None,
                   row_axis: str = "data", col_axis: str = "model",
                   repair: bool = True):
        """Reconstruct a stored graph, verifying every shard's CRC.

        ``expect_spec`` makes a stale graph fail loudly (spec-hash
        mismatch raises instead of handing back the wrong edges);
        ``mesh`` validates its axis sizes against the stored partition
        and lands every array sharded over the graph axes (ready for
        BFSEngine's no-round-trip ship).

        A shard whose file is corrupted, truncated, or missing is
        quarantined and regenerated from the stored BuildSpec
        (``repair=False`` raises instead); the regenerated shard must
        reproduce the stored CRC bit-for-bit.  ``self.last_load_report``
        records the verification outcome either way."""
        gdir = self._graph_dir(name)
        if step is None:
            step = checkpoint.latest_step(gdir)
            if step is None:
                raise FileNotFoundError(f"no graph steps under {gdir}")
        sdir = os.path.join(gdir, f"step_{step:010d}")
        with open(os.path.join(sdir, "meta.json")) as f:
            meta = json.load(f)
        if meta.get("format_version") != FORMAT_VERSION:
            raise ValueError(
                f"graph {name!r} step {step} has format_version="
                f"{meta.get('format_version')}; this reader handles "
                f"{FORMAT_VERSION} (re-save the graph)")
        if expect_spec is not None:
            want = checkpoint.config_hash(expect_spec)
            if meta.get("spec_hash") != want:
                raise ValueError(
                    f"graph {name!r} step {step} spec_hash="
                    f"{meta.get('spec_hash')} does not match the "
                    f"expected spec ({want})")
        part = _part_from_meta(meta)
        fields = json.loads(meta["fields"])
        crcs = meta["shard_crc32"]
        shards = []
        repaired = []
        for k in range(meta["shards"]):
            path = os.path.join(sdir, f"shard_{k:05d}.npz")
            arrs, err = None, None
            try:
                arrs = self._read_shard(path)
                got = shard_crc32(arrs)
                if got != crcs[k]:
                    err = (f"CRC mismatch: {got:#010x} != stored "
                           f"{crcs[k]:#010x}")
            except Exception as e:       # unreadable/truncated npz
                err = f"unreadable shard: {e}"
            if err is not None:
                if not repair:
                    raise RuntimeError(
                        f"graph {name!r} step {step} shard {k}: {err} "
                        f"(repair disabled)")
                arrs = self._repair_shard(path, k, meta, part, crcs[k])
                repaired.append({"shard": k, "reason": err})
            shards.append(arrs)
        self.last_load_report = {
            "name": name, "step": step, "shards": meta["shards"],
            "repaired": repaired,
        }
        arrays = {}
        for fname, (shape, dt) in fields.items():
            stacked = np.stack([s[fname] for s in shards])
            arrays[fname] = stacked.reshape(shape).astype(dt, copy=False)
        if isinstance(part, Partition1D):
            axes, sizes = (row_axis,), (part.p,)
        else:
            axes, sizes = (row_axis, col_axis), (part.pr, part.pc)
        if mesh is not None:
            for ax, want in zip(axes, sizes):
                have = dict(mesh.shape).get(ax)
                if have != want:
                    raise ValueError(
                        f"stored graph {name!r} was partitioned for "
                        f"{ax}={want} but the mesh has {ax}={have} "
                        f"(mesh axes {_mesh_axes(mesh)})")
            sh = NamedSharding(mesh, P(*axes))
            arrays = {k: jax.device_put(v, sh) for k, v in arrays.items()}
        cls = _GRAPH_KINDS[meta["graph_kind"]]
        rl = meta.get("relabel")
        relabel = None if rl is None else VertexRelabel(
            scale=rl["scale"], adds=tuple(rl["adds"]),
            muls=tuple(rl["muls"]))
        return cls(part=part, m_input=meta["m_input"], m=meta["m"],
                   relabel=relabel, **json.loads(meta["scalars"]),
                   **arrays)

    # ------------------------------------------------------------------
    # executables
    # ------------------------------------------------------------------

    def _exec_dir(self, key: str, h: str) -> str:
        return os.path.join(self.root, "execs", f"exec_{key}_{h}")

    def save_executable(self, engine, key: str = "default") -> Optional[str]:
        """Serialize a BFSEngine's compiled single-root search under its
        plan's config hash (atomic publish).  Returns the path, or None
        when jax.experimental.serialize_executable is unavailable (the
        store then persists graphs only)."""
        if _serialize_exec is None:
            return None
        h = plan_exec_hash(engine.plan)
        payload, in_tree, out_tree = _serialize_exec.serialize(engine._exec)
        final = self._exec_dir(key, h)
        os.makedirs(os.path.dirname(final), exist_ok=True)
        tmp = tempfile.mkdtemp(dir=os.path.dirname(final), prefix=".tmp_")
        try:
            with open(os.path.join(tmp, "payload.bin"), "wb") as f:
                f.write(payload)
            with open(os.path.join(tmp, "trees.pkl"), "wb") as f:
                pickle.dump((in_tree, out_tree), f)
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump({"key": key, "hash": h, "jax": jax.__version__,
                           "saved_at": time.time()}, f)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        return final

    def load_executable(self, plan, key: str = "default"):
        """The compiled executable previously saved for an equivalent
        plan (same config hash), or None on miss / absent serializer —
        BFSPlan.compile then falls back to a fresh XLA compile."""
        if _serialize_exec is None:
            return None
        d = self._exec_dir(key, plan_exec_hash(plan))
        if not os.path.isdir(d):
            return None
        with open(os.path.join(d, "payload.bin"), "rb") as f:
            payload = f.read()
        with open(os.path.join(d, "trees.pkl"), "rb") as f:
            in_tree, out_tree = pickle.load(f)
        return _serialize_exec.deserialize_and_load(payload, in_tree,
                                                    out_tree)


def plan_bfs_from_store(store: GraphStore, name: str, cfg, mesh,
                        expect_spec=None, **plan_kw):
    """The disk -> traversal entry point: load a stored graph sharded
    onto ``mesh`` and plan a session over it.  Chain with
    ``.compile(store=store)`` to also reuse the stored executable."""
    from repro.core.engine import plan_bfs
    graph = store.load_graph(name, mesh=mesh, expect_spec=expect_spec,
                             row_axis=plan_kw.get("row_axis", "data"),
                             col_axis=plan_kw.get("col_axis", "model"))
    return plan_bfs(graph, cfg, mesh, **plan_kw)
