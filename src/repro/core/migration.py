"""Live migration of dependency images: the page server and restore policies.

Implements all four prototypes measured in the paper's Table 2:

  * ``BULK``          — WarmSwap bulk ("initiative") restore: on the first page fault
                        the page server streams ALL remaining pages in the
                        background, in layer order (each device leaf on to the
                        device), overlapping with the function's own work.
  * ``LAZY``          — WarmSwap lazy restore: every fault fetches exactly the pages
                        of the faulting leaf, paying per-fault latency each time.
  * ``NO_PAGESERVER`` — copy the whole serialized image into the container, then
                        restore (the paper's "w/o Page Server" variant).
  * ``NO_LAZY``       — transfer every page through the page server *before*
                        execution begins (the paper's "w/o Lazy Migration" variant).

A restore puts each leaf back where it lived when the image was built. A leaf
built as a ``jax.Array`` (model parameters) is read in place from the pages the
page server returned and handed to ``jax.device_put`` as it installs, so its
host-to-device transfer overlaps the fetch of the next leaf; ``as_pytree`` waits
for the transfers, so migration, not the first call that uses the parameters,
pays for them. A leaf built in host memory (the ``py-base`` runtime blob, which
no device code reads) stays in host memory.

The page server models the provider-side transport: a local pool moves pages at
host-memcpy speed; a remote pool adds a configurable per-request latency and
bandwidth (DCN analogue). All timing is wall-clock measured, not simulated — the
sleeps only extend real copies when a remote link is being modelled.
"""
from __future__ import annotations

import enum
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.image import ImageMetadata, LiveDependencyImage
from repro.core.pages import LeafEntry
from repro.runtime.tracing import span


class RestorePolicy(enum.Enum):
    BULK = "bulk"
    LAZY = "lazy"
    NO_PAGESERVER = "no_pageserver"
    NO_LAZY = "no_lazy"


@dataclass
class LinkModel:
    """Transport between a page source and a function container.

    Used twice: by the *measured* migration path (``PageServer`` sleeps to
    extend real copies to the modelled speed) and by the *simulated*
    page-granular cost model (``core/costmodel.py``), so measured and
    simulated transfers share one parameterization.
    """
    latency_s: float = 0.0          # seconds per page-server request (RTT)
    bandwidth_bps: Optional[float] = None  # bytes/second; None = infinite
                                           #   (host memcpy, local pool)

    def delay_for(self, nbytes: int) -> float:
        """Seconds one request moving ``nbytes`` bytes takes on this link:
        ``latency_s`` + ``nbytes / bandwidth_bps`` (no bandwidth term when
        ``bandwidth_bps`` is ``None``)."""
        d = self.latency_s
        if self.bandwidth_bps:
            d += nbytes / self.bandwidth_bps
        return d


@dataclass
class MigrationStats:
    requests: int = 0
    pages_transferred: int = 0
    bytes_transferred: int = 0
    faults: int = 0


class PageServer:
    """Provider-side server bound to one live image (paper §3.2: one per target)."""

    def __init__(self, image: LiveDependencyImage,
                 link: Optional[LinkModel] = None):
        self._image = image
        self._link = link if link is not None else LinkModel()
        self.stats = MigrationStats()
        self._lock = threading.Lock()

    @property
    def table(self):
        return self._image.metadata.page_table

    def fetch_pages(self, first_page: int, n_pages: int) -> np.ndarray:
        """Copy a page span out of the pool (the unit of transfer).

        Args:
            first_page: index of the first page in the image's store.
            n_pages: pages to copy.

        Returns:
            ``(n_pages, page_size)`` uint8 array — a real copy, delayed by
            the link model when one is configured. Stats (requests, pages,
            bytes) are updated under the server lock.
        """
        delay = self._link.delay_for(n_pages * self.table.page_size)
        if delay > 0:
            time.sleep(delay)
        pages = np.array(self._image.store[first_page: first_page + n_pages])  # real copy
        with self._lock:
            self.stats.requests += 1
            self.stats.pages_transferred += n_pages
            self.stats.bytes_transferred += pages.nbytes
        return pages


def _restore_leaf(pages: np.ndarray, e: LeafEntry) -> Any:
    """One leaf, from the pages that hold it, where it lived when the image
    was built.

    The pages are read in place (every leaf starts on a page boundary). A
    device leaf is handed to ``jax.device_put`` with no device, so it stays
    uncommitted as the parameters of a freshly built model are;
    ``device_put`` returns before its transfer ends, and the pages are dropped
    once it has. A host leaf is a view of ``pages``. Either way the leaf may
    alias ``pages`` (``device_put`` aliases host memory on a CPU backend), which
    must therefore be a private copy, never a view of the pool's store."""
    leaf = pages.reshape(-1)[: e.nbytes].view(jnp.dtype(e.dtype)).reshape(e.shape)
    return jax.device_put(leaf) if e.on_device else leaf


class RestoredImage:
    """Container-side restored dependency: leaves materialize through the chosen
    policy; ``wait_all()`` blocks until the image is fully resident."""

    def __init__(self, metadata: ImageMetadata, server: PageServer, treedef,
                 policy: RestorePolicy):
        self.metadata = metadata
        self.treedef = treedef
        self.policy = policy
        self._server = server
        self._table = metadata.page_table
        self._local: Dict[str, Any] = {}   # leaf key -> restored leaf
        self._events: Dict[str, threading.Event] = {k: threading.Event()
                                                    for k in self._table.order}
        self._claim_lock = threading.Lock()
        self._claimed: set = set()         # leaves some thread is installing
        self._install_error: Optional[BaseException] = None
        self._stream_thread: Optional[threading.Thread] = None
        self._streaming_started = False
        self.stats = server.stats

    # -- internals ---------------------------------------------------------------
    def _claim(self, key: str) -> bool:
        """Check-and-set: exactly one thread wins the right to install ``key``.

        ``fault()`` and the background ``_stream_all`` thread can race on the
        same leaf; without the claim both would fetch its pages (double
        transfer, double-counted stats, concurrent ``_local`` writes)."""
        with self._claim_lock:
            if key in self._claimed:
                return False
            self._claimed.add(key)
            if key not in self._local and self._events[key].is_set():
                # stale marker from a failed install: re-arm so waiters block
                # on this retry instead of reading an absent leaf
                self._events[key].clear()
            return True

    def _install_leaf(self, key: str) -> None:
        """Fetch one leaf's pages and restore it (a device leaf's transfer is
        started, not waited for). Caller must have won ``_claim(key)``.

        On failure the claim is released and the event set anyway so waiters
        wake up and surface the error instead of blocking forever."""
        try:
            e = self._table.entries[key]
            self._local[key] = _restore_leaf(
                self._server.fetch_pages(e.first_page, e.n_pages), e)
        except BaseException as exc:
            with self._claim_lock:
                self._claimed.discard(key)
                self._install_error = exc
            self._events[key].set()
            raise
        self._events[key].set()

    def _ensure_leaf(self, key: str) -> None:
        """Make ``key`` resident: install it if we win the claim, else wait for
        the thread that did (and surface its failure, if any)."""
        if self._events[key].is_set() and key in self._local:
            return
        if self._claim(key):
            self._install_leaf(key)
            return
        while True:
            self._events[key].wait()
            if key in self._local:
                return
            with self._claim_lock:
                installing = key in self._claimed
            if not installing:
                # nobody is retrying: the last installer failed for good
                raise RuntimeError(
                    f"leaf {key!r} failed to install in another thread"
                ) from self._install_error
            # an in-flight retry holds the claim; its clear-on-claim re-armed
            # the event, so the next wait() blocks until it resolves

    def _stream_all(self, skip: Sequence[str] = ()) -> None:
        for key in self._table.order:      # layer order == execution order
            if key in skip or key in self._local:
                continue
            if self._claim(key):           # else: a concurrent fault owns it
                try:
                    self._install_leaf(key)
                except Exception:
                    # recorded in _install_error and the claim was released —
                    # keep streaming; wait_all()/fault() retry this leaf
                    continue

    def _start_background_stream(self, skip: Sequence[str] = ()) -> None:
        with self._claim_lock:             # two first-faults must not both stream
            if self._streaming_started:
                return
            self._streaming_started = True
        self._stream_thread = threading.Thread(
            target=self._stream_all, args=(tuple(skip),), daemon=True)
        self._stream_thread.start()

    # -- the fault path ------------------------------------------------------------
    def fault(self, key: str) -> Any:
        """First touch of a leaf by the executing function (userfaultfd
        analogue).

        Args:
            key: leaf path in the image's page table.

        Returns:
            The restored leaf; a device leaf's transfer may still be in
            flight (the consumer's first use, or ``as_pytree``, waits). Under ``BULK`` the first
            fault also kicks off the background stream for the remaining
            leaves.
        """
        if self._events[key].is_set() and key in self._local:
            return self._local[key]
        self.stats.faults += 1
        if self.policy == RestorePolicy.LAZY:
            self._ensure_leaf(key)
        elif self.policy == RestorePolicy.BULK:
            # first fault: fetch the faulting leaf synchronously, then stream the rest
            self._ensure_leaf(key)
            self._start_background_stream(skip=(key,))
        else:
            # NO_LAZY / NO_PAGESERVER should have pre-installed everything
            self._events[key].wait()
        return self._local[key]

    def wait_all(self) -> None:
        """Block until every leaf of the image is resident container-side
        (policy-appropriately: join the BULK stream and retry dead leaves,
        fault everything under LAZY, no-op for the eager policies)."""
        if self.policy == RestorePolicy.BULK:
            self._start_background_stream()
            if self._stream_thread is not None:
                self._stream_thread.join()
            # leaves claimed by concurrent faults finish outside the stream
            # thread, and a died-mid-stream thread leaves some unclaimed:
            # _ensure_leaf waits for live installers, retries dead ones
            # inline, and surfaces persistent failures instead of hanging
            for key in self._table.order:
                self._ensure_leaf(key)
        elif self.policy == RestorePolicy.LAZY:
            for key in self._table.order:
                self.fault(key)
        # NO_LAZY / NO_PAGESERVER are already resident

    def resident_fraction(self) -> float:
        """Fraction of leaves materialized container-side, in [0, 1] — the
        measured counterpart of the cost model's ``resident_pages`` knob."""
        return len(self._local) / max(len(self._events), 1)

    def as_pytree(self) -> Any:
        """Full parameter pytree (blocks until every leaf has been fetched and
        every device leaf's transfer has ended)."""
        t = self._table
        with span("restore", bytes=t.nbytes_payload, pages=t.n_pages):
            self.wait_all()
            leaves = jax.block_until_ready([self._local[k] for k in t.tree_order])
            return jax.tree_util.tree_unflatten(self.treedef, leaves)


class MigrationClient:
    """Container-side orchestrator (paper Fig. 4c)."""

    def __init__(self, link: Optional[LinkModel] = None):
        self.link = link if link is not None else LinkModel()

    def migrate(
        self,
        image: LiveDependencyImage,
        policy: RestorePolicy = RestorePolicy.BULK,
    ) -> RestoredImage:
        """Step 1: metadata transfer. Step 2: page server attach. Step 3: restore
        skeleton (lazy) — pages move on fault / in the background."""
        # step 1 — metadata (small, synchronous; its cost is the communication phase)
        md = image.metadata
        delay = self.link.delay_for(md.nbytes())
        if delay > 0:
            time.sleep(delay)
        # step 2 — page server bound to the image
        server = PageServer(image, self.link)
        restored = RestoredImage(md, server, image.treedef, policy)
        # step 3 — policy-specific eager work
        if policy == RestorePolicy.NO_LAZY:
            restored._stream_all()            # all pages through the server, upfront
        elif policy == RestorePolicy.NO_PAGESERVER:
            # whole-image copy (one giant request), then local restore
            pages = server.fetch_pages(0, md.page_table.n_pages)
            for key in md.page_table.order:
                e = md.page_table.entries[key]
                restored._local[key] = _restore_leaf(
                    pages[e.first_page: e.first_page + e.n_pages], e)
                restored._events[key].set()
            restored._claimed.update(md.page_table.order)
        return restored
