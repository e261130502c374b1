"""Parameter paging: pytree <-> fixed-size pages in a host-RAM store.

This is WarmSwap's memory-page layer adapted to model weights (DESIGN.md §2): a
dependency image's "hot memory pages" become fixed-size byte pages of the pre-sharded
parameter pytree, laid out in **layer order** so bulk restore streams pages in the
order the forward pass consumes them (the paper orders checkpoint images on disk for
the same reason, §3.2).

The page table (leaf path -> page span) is part of the image *metadata*: small,
structure-only, and exactly what the migration client needs to restore the pytree —
mirroring CRIU's split between process metadata and memory pages (Table 3).
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np

DEFAULT_PAGE_SIZE = 1 << 22  # 4 MiB


@dataclass
class LeafEntry:
    key: str                 # keystr path of the leaf
    shape: Tuple[int, ...]
    dtype: str               # numpy dtype name ('bfloat16' handled via jnp)
    nbytes: int
    first_page: int
    n_pages: int
    offset: int              # byte offset of this leaf inside its first page == 0 here
    layer_index: int         # streaming order group
    on_device: bool = False  # built as a jax.Array: a restore puts it on the device

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclass
class PageTable:
    page_size: int
    entries: Dict[str, LeafEntry]
    n_pages: int
    order: List[str] = field(default_factory=list)       # leaf keys in streaming order
    tree_order: List[str] = field(default_factory=list)  # leaf keys in tree-flatten order

    @property
    def nbytes_pages(self) -> int:
        return self.n_pages * self.page_size

    @property
    def nbytes_payload(self) -> int:
        return sum(e.nbytes for e in self.entries.values())

    def metadata_bytes(self) -> int:
        """Size of the serialized table — the paper's 'process metadata' size."""
        return len(self.to_json().encode())

    def to_json(self) -> str:
        return json.dumps({
            "page_size": self.page_size,
            "n_pages": self.n_pages,
            "order": self.order,
            "tree_order": self.tree_order,
            "entries": {k: e.to_json() for k, e in self.entries.items()},
        })

    @classmethod
    def from_json(cls, s: str) -> "PageTable":
        d = json.loads(s)
        entries = {k: LeafEntry(**{**v, "shape": tuple(v["shape"])})
                   for k, v in d["entries"].items()}
        return cls(page_size=d["page_size"], entries=entries,
                   n_pages=d["n_pages"], order=list(d["order"]),
                   tree_order=list(d.get("tree_order", [])))


def _np_view(x) -> np.ndarray:
    """Numpy byte view of an array (bf16 -> uint16 reinterpretation)."""
    arr = np.asarray(x)
    return arr.view(np.uint8).reshape(-1) if arr.dtype != object else arr


def _streaming_order(keys: Sequence[str]) -> List[str]:
    """Embed first (needed at step start), then scanned units, remainder, the rest."""
    def rank(k: str) -> Tuple[int, str]:
        if "embed" in k and "tok" in k:
            return (0, k)
        if k.startswith("['unit']") or "['unit']" in k:
            return (1, k)
        if "['rem']" in k:
            return (2, k)
        if "enc" in k:
            return (3, k)
        if "final_norm" in k:
            return (4, k)
        return (5, k)
    return sorted(keys, key=rank)


def paginate(params: Any, page_size: int = DEFAULT_PAGE_SIZE
             ) -> Tuple[np.ndarray, PageTable, Any]:
    """Flatten ``params`` into (page_store (n_pages, page_size) uint8, table, treedef).

    Every leaf starts on a page boundary (pages are the transfer/sharing unit;
    sub-page packing would couple unrelated leaves into one fault).
    """
    leaves_with_paths = jax.tree_util.tree_leaves_with_path(params)
    treedef = jax.tree_util.tree_structure(params)
    by_key = {}
    tree_order = []
    for path, leaf in leaves_with_paths:
        key = jax.tree_util.keystr(path)
        by_key[key] = leaf
        tree_order.append(key)
    order = _streaming_order(list(by_key.keys()))

    entries: Dict[str, LeafEntry] = {}
    chunks: List[np.ndarray] = []
    page_cursor = 0
    for li, key in enumerate(order):
        leaf = by_key[key]
        arr = np.asarray(leaf)
        raw = arr.tobytes()                      # C-order: stacked leaves are unit-major
        n_pages = max(1, -(-len(raw) // page_size))
        buf = np.zeros(n_pages * page_size, np.uint8)
        buf[: len(raw)] = np.frombuffer(raw, np.uint8)
        chunks.append(buf.reshape(n_pages, page_size))
        entries[key] = LeafEntry(
            key=key, shape=tuple(arr.shape), dtype=str(arr.dtype),
            nbytes=len(raw), first_page=page_cursor, n_pages=n_pages,
            offset=0, layer_index=li, on_device=isinstance(leaf, jax.Array))
        page_cursor += n_pages
    store = (np.concatenate(chunks, axis=0) if chunks
             else np.zeros((0, page_size), np.uint8))
    table = PageTable(page_size=page_size, entries=entries,
                      n_pages=page_cursor, order=order, tree_order=tree_order)
    return store, table, treedef


def materialize_leaf(store: np.ndarray, table: PageTable, key: str) -> np.ndarray:
    e = table.entries[key]
    raw = store[e.first_page: e.first_page + e.n_pages].reshape(-1)[: e.nbytes]
    dt = np.dtype(e.dtype) if e.dtype != "bfloat16" else None
    if dt is None:
        import ml_dtypes
        dt = np.dtype(ml_dtypes.bfloat16)
    return np.frombuffer(raw.tobytes(), dtype=dt).reshape(e.shape)


def materialize(store: np.ndarray, table: PageTable, treedef,
                keys: Optional[Iterable[str]] = None) -> Any:
    """Rebuild the full pytree (or, with ``keys``, a {key: array} subset)."""
    if keys is not None:
        return {k: materialize_leaf(store, table, k) for k in keys}
    leaves = [materialize_leaf(store, table, k) for k in table.tree_order]
    return jax.tree_util.tree_unflatten(treedef, leaves)
