"""The model seam: a configuration's ``model`` names the module of its
family, which gives the harness the sizes, the program's configuration, the
weights, the reference and the CPU test size."""
import gc
import json
import weakref
from pathlib import Path

import numpy as np
import pytest

from bench import run as bench_run
from bench.lib.counts import DenseShape
from bench.lib.record import Recorder
from bench.models import dense, module_of
from bench.tests import _cells

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def qwen():
    return json.loads((CONFIGS / "qwen1.5-0.5b.json").read_text())


def test_seam_resolves_the_dense_module():
    assert module_of(qwen()) is dense
    shape = dense.shape_of(qwen())
    assert shape == DenseShape.from_config(qwen())
    assert (shape.rope_theta, shape.norm_eps) == (1e6, 1e-6)


def test_arch_takes_its_constants_from_the_shape():
    a = dense.arch(qwen(), dense.shape_of(qwen()))
    assert (a.family, a.n_layers, a.d_model, a.vocab_size) == ("dense", 24, 1024,
                                                              151936)
    assert (a.rope_theta, a.norm_eps, a.qkv_bias) == (1e6, 1e-6, True)


@pytest.mark.parametrize("model", ["no_such_family", "../systems/endpoint"])
def test_unknown_model_fails_at_set_up_naming_its_file(model):
    man, cell, config, mix, limits = bench_run.load_cell(_cells.CELL)
    config = dict(config, model=model)
    with pytest.raises(FileNotFoundError, match=f"bench/models/{model}.py"):
        bench_run.run_cell(config, mix, cell, [], limits, 1, 1.0, False,
                           require_tpu=False)


def test_tiny_comes_from_the_model_module(monkeypatch):
    man, cell, config, mix, limits = bench_run.load_cell(_cells.CELL)
    assert _cells.tiny(config, mix)[0] == dense.tiny(config)
    monkeypatch.setattr(dense, "tiny", lambda c: dict(c, hidden_size=32))
    assert _cells.tiny(config, mix)[0]["hidden_size"] == 32


def test_dropped_replica_is_freed_without_the_cyclic_collector():
    """The harness's wrappers on a replica hold it weakly: a replica that has
    served and is dropped is gone at once, with the cyclic collector off."""
    from bench.systems.endpoint import Cell
    man, cell, config, mix, limits = bench_run.load_cell(_cells.CELL)
    config, mix = _cells.tiny(config, mix)
    c = Cell(config, mix, 2**40 + 3, Recorder())
    c.setup()
    try:
        gc.collect()
        gc.disable()
        engine = c.bring_up()
        engine.submit(np.arange(8, dtype=np.int32))
        engine.run_until_done()
        assert len(engine.completed) == 1
        gone = weakref.ref(engine)
        del engine
        assert gone() is None
    finally:
        gc.enable()
        c.release()
