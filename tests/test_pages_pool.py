"""WarmSwap page/image/pool/migration behaviour + hypothesis property tests."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    DependencyManager,
    LinkModel,
    RestorePolicy,
    build_image,
    materialize,
    paginate,
)
from repro.core.pages import materialize_leaf


# ---------------------------------------------------------------------------------
# Property: paginate/materialize round-trips any pytree exactly
# ---------------------------------------------------------------------------------

@st.composite
def pytrees(draw):
    rng = np.random.default_rng(draw(st.integers(0, 2**31)))
    n = draw(st.integers(1, 6))
    tree = {}
    for i in range(n):
        ndim = draw(st.integers(1, 3))
        shape = tuple(draw(st.integers(1, 17)) for _ in range(ndim))
        dt = draw(st.sampled_from(["float32", "int32", "bfloat16", "uint8"]))
        if dt == "bfloat16":
            import ml_dtypes
            arr = rng.standard_normal(shape).astype(ml_dtypes.bfloat16)
        else:
            arr = (rng.standard_normal(shape) * 100).astype(dt)
        tree[f"leaf{i}"] = arr if i % 2 == 0 else {"nested": arr}
    return tree


@given(pytrees(), st.sampled_from([128, 4096, 1 << 20]))
@settings(max_examples=25, deadline=None)
def test_paginate_roundtrip_property(tree, page_size):
    store, table, treedef = paginate(tree, page_size=page_size)
    out = materialize(store, table, treedef)
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(out)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a).view(np.uint8),
                              np.asarray(b).view(np.uint8))


@given(pytrees())
@settings(max_examples=10, deadline=None)
def test_metadata_much_smaller_than_image(tree):
    """Paper Table 3: process metadata << dependency image (for non-trivial images)."""
    store, table, treedef = paginate(tree, page_size=4096)
    if table.nbytes_payload > 100_000:
        assert table.metadata_bytes() < table.nbytes_payload / 5


def _params(seed=0, d=64):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (d, d)),
            "b": {"w": jax.random.normal(k, (d, 4 * d)),
                  "scale": jnp.zeros((d,))}}


# ---------------------------------------------------------------------------------
# Migration policies
# ---------------------------------------------------------------------------------

@pytest.mark.parametrize("policy", list(RestorePolicy))
def test_all_policies_restore_identical_params(policy):
    mgr = DependencyManager()
    mgr.register_image("img", "test", lambda: _params())
    restored = mgr.request_migration("img", policy)
    out = restored.as_pytree()
    for a, b in zip(jax.tree_util.tree_leaves(_params()),
                    jax.tree_util.tree_leaves(out)):
        assert np.allclose(np.asarray(a), np.asarray(b))


def _mixed_params():
    """float32 and bfloat16 leaves built on the device, and a byte blob built in
    host memory (as the py-base runtime image is)."""
    p = _params()
    return {**p, "half": jnp.asarray(p["a"], jnp.bfloat16),
            "blob": np.random.default_rng(0).integers(0, 255, 3000, dtype=np.uint8)}


@pytest.mark.parametrize("policy", list(RestorePolicy))
def test_restore_puts_leaves_back_where_built_byte_equal_and_apart_from_the_pool(policy):
    src = _mixed_params()
    mgr = DependencyManager(page_size=1024)
    mgr.register_image("img", "test", lambda: src)
    restored = mgr.request_migration("img", policy)
    out = restored.as_pytree()
    pairs = list(zip(jax.tree_util.tree_leaves(src), jax.tree_util.tree_leaves(out)))
    for a, b in pairs:
        if isinstance(a, jax.Array):
            assert isinstance(b, jax.Array)
            assert b.devices() == {jax.devices()[0]}
        else:
            assert isinstance(b, np.ndarray)             # stays in host memory
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a).view(np.uint8),
                              np.asarray(b).view(np.uint8))
    # the restored leaves hold no view of the pool: overwriting the store in
    # place leaves every restored value as it was
    mgr._ensure_live("img").store[...] = 0xFF
    for a, b in pairs:
        assert np.array_equal(np.asarray(a).view(np.uint8),
                              np.asarray(b).view(np.uint8))


def test_lazy_restore_transfers_only_touched_pages():
    mgr = DependencyManager(page_size=1024)
    mgr.register_image("img", "test", lambda: _params(d=128))
    restored = mgr.request_migration("img", RestorePolicy.LAZY)
    key = restored.metadata.page_table.order[0]
    restored.fault(key)
    total_pages = restored.metadata.page_table.n_pages
    assert restored.stats.pages_transferred < total_pages
    assert restored.resident_fraction() < 1.0


def test_bulk_restore_streams_everything_after_first_fault():
    mgr = DependencyManager(page_size=1024)
    mgr.register_image("img", "test", lambda: _params(d=128))
    restored = mgr.request_migration("img", RestorePolicy.BULK)
    restored.fault(restored.metadata.page_table.order[0])
    restored.wait_all()
    assert restored.resident_fraction() == 1.0
    assert restored.stats.pages_transferred == restored.metadata.page_table.n_pages


def test_no_pageserver_is_one_big_request():
    mgr = DependencyManager(page_size=1024)
    mgr.register_image("img", "test", lambda: _params())
    restored = mgr.request_migration("img", RestorePolicy.NO_PAGESERVER)
    assert restored.stats.requests == 1
    assert restored.resident_fraction() == 1.0


@pytest.mark.parametrize("policy", [RestorePolicy.BULK, RestorePolicy.LAZY])
def test_restore_fault_storm_fetches_each_leaf_once(policy):
    """Regression: fault() and the background stream used to race on the same
    leaf — double page fetch, double-counted stats, concurrent _local writes.
    The per-leaf claim must keep pages_transferred == n_pages under a storm of
    concurrent faults."""
    import threading

    mgr = DependencyManager(page_size=1024)
    mgr.register_image("img", "test", lambda: _params(d=128))
    restored = mgr.request_migration("img", policy)
    keys = list(restored.metadata.page_table.order)
    errors = []

    def storm(order):
        try:
            for k in order:
                restored.fault(k)
        except Exception as exc:       # pragma: no cover - failure path
            errors.append(exc)

    threads = [threading.Thread(target=storm, args=(keys[::d],))
               for d in (1, -1, 1, -1)]
    for th in threads:
        th.start()
    restored.wait_all()
    for th in threads:
        th.join()
    assert not errors
    assert restored.resident_fraction() == 1.0
    # each leaf's page span crossed the link exactly once
    assert (restored.stats.pages_transferred
            == restored.metadata.page_table.n_pages)
    # and the restored tree is still byte-identical to the source
    for a, b in zip(jax.tree_util.tree_leaves(_params(d=128)),
                    jax.tree_util.tree_leaves(restored.as_pytree())):
        assert np.allclose(np.asarray(a), np.asarray(b))


def test_bulk_stream_death_does_not_deadlock_wait_all():
    """If the background stream thread dies mid-stream, wait_all() must retry
    the unfinished leaves inline instead of waiting forever on events the dead
    thread never set."""
    mgr = DependencyManager(page_size=1024)
    mgr.register_image("img", "test", lambda: _params(d=128))
    restored = mgr.request_migration("img", RestorePolicy.BULK)
    orig = restored._server.fetch_pages
    state = {"calls": 0}

    def flaky(first_page, n_pages):
        state["calls"] += 1
        if state["calls"] == 2:            # first background-stream fetch
            raise IOError("link flap")
        return orig(first_page, n_pages)

    restored._server.fetch_pages = flaky
    restored.fault(restored.metadata.page_table.order[0])   # starts the stream
    restored.wait_all()                    # must not hang; retries inline
    assert restored.resident_fraction() == 1.0


def test_restore_install_failure_surfaces_and_is_retryable():
    """A failed page fetch must release the per-leaf claim and wake waiters
    with an error — never deadlock them — and a retry must succeed."""
    mgr = DependencyManager(page_size=1024)
    mgr.register_image("img", "test", lambda: _params())
    restored = mgr.request_migration("img", RestorePolicy.LAZY)
    key = restored.metadata.page_table.order[0]
    orig = restored._server.fetch_pages
    state = {"fail": True}

    def flaky(first_page, n_pages):
        if state["fail"]:
            state["fail"] = False
            raise IOError("link down")
        return orig(first_page, n_pages)

    restored._server.fetch_pages = flaky
    with pytest.raises(IOError):
        restored.fault(key)
    assert restored.resident_fraction() == 0.0
    out = restored.fault(key)                  # claim released: retry works
    assert out.shape == restored.metadata.page_table.entries[key].shape
    restored.wait_all()
    assert restored.resident_fraction() == 1.0


# ---------------------------------------------------------------------------------
# Pool behaviour
# ---------------------------------------------------------------------------------

def test_pool_shares_one_image_across_functions():
    """Pool memory is O(#images), not O(#functions) — the paper's core claim."""
    mgr = DependencyManager()
    mgr.register_image("shared", "test", lambda: _params(d=128))
    size_one = mgr.pool_bytes()
    for _ in range(10):
        r = mgr.request_migration("shared", RestorePolicy.BULK)
        r.as_pytree()
        mgr.release("shared")
    assert mgr.pool_bytes() == size_one
    assert mgr.stats.builds == 1


def test_pool_evict_to_disk_and_revive():
    with tempfile.TemporaryDirectory() as tmp:
        mgr = DependencyManager(disk_dir=tmp)
        mgr.register_image("img", "test", lambda: _params(seed=3))
        before = mgr.request_migration("img", RestorePolicy.BULK).as_pytree()
        mgr.release("img")
        mgr.evict("img")
        assert not mgr.has_live("img")
        after = mgr.request_migration("img", RestorePolicy.BULK).as_pytree()
        for a, b in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(after)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert mgr.stats.revivals == 1
        assert mgr.stats.builds == 1  # revive did NOT re-run initialization


def test_pool_capacity_lru_eviction():
    with tempfile.TemporaryDirectory() as tmp:
        mgr = DependencyManager(capacity_bytes=1 << 20, disk_dir=tmp,
                                page_size=4096)
        mgr.register_image("a", "t", lambda: _params(seed=1, d=128))  # ~330KB
        mgr.register_image("b", "t", lambda: _params(seed=2, d=128))
        mgr.register_image("c", "t", lambda: _params(seed=3, d=128))
        mgr.register_image("d", "t", lambda: _params(seed=4, d=128))
        assert mgr.pool_bytes() <= 1 << 20
        assert mgr.stats.evictions >= 1


def test_reshard_image_preserves_values():
    mgr = DependencyManager()
    mgr.register_image("img", "test", lambda: _params(seed=5))
    orig = mgr.request_migration("img", RestorePolicy.BULK).as_pytree()
    mgr.release("img")
    mgr.reshard_image("img", lambda p: jax.tree.map(np.asarray, p))
    again = mgr.request_migration("img", RestorePolicy.BULK).as_pytree()
    for a, b in zip(jax.tree_util.tree_leaves(orig),
                    jax.tree_util.tree_leaves(again)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_remote_link_adds_latency():
    mgr = DependencyManager()
    mgr.register_image("img", "test", lambda: _params(d=256))
    import time
    t0 = time.perf_counter()
    r = mgr.request_migration("img", RestorePolicy.NO_LAZY,
                              LinkModel(latency_s=0.005))
    local = time.perf_counter() - t0
    assert local >= 0.005  # at least the per-request latency
