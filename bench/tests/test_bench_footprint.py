"""The harness's own measure of what a pool holds."""
import numpy as np

from bench.lib import footprint


def test_counts_each_buffer_once():
    base = np.zeros(1000, np.uint8)
    raw = np.frombuffer(bytes(64), np.uint8)
    holder = {"a": base, "b": base[10:20], "c": [base.reshape(10, 100), raw, raw]}
    assert footprint.reachable_bytes(holder) == 1000 + 64


def test_does_not_follow_functions():
    big = np.zeros(4096, np.uint8)

    def builder():
        return big
    assert footprint.reachable_bytes({"f": builder, "x": np.zeros(8)}) == 64


def test_matches_the_program_pool(tmp_path):
    from repro.core import DependencyManager
    mgr = DependencyManager(disk_dir=str(tmp_path / "pool"))
    mgr.register_image("a", "a", lambda: {"w": np.ones((3, 5), np.float32)})
    mgr.register_image("b", "b", lambda: {"v": np.arange(7, dtype=np.int32)})
    assert footprint.reachable_bytes(mgr) == mgr.pool_bytes()
    mgr.evict("a")
    assert footprint.pool_bytes([mgr], str(tmp_path / "pool")) > mgr.pool_bytes()
