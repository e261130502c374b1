"""The program's spans (``repro.runtime.tracing``): a replica brought up from
the pool that serves two requests under the profiler leaves the ``hotswap.``
span tree, with its stats, on the calling thread's host line."""
import glob
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import DependencyManager, RestorePolicy
from repro.models.transformer import init_params
from repro.runtime import tracing
from repro.serving import ServeConfig, ServingEngine

CFG = get_reduced("qwen3_1_7b")
SERVE = ServeConfig(max_slots=2, max_seq_len=32, max_new_tokens=3)
PROMPT_LENS = (5, 9)

# the span each program span opens in (``sample`` opens in ``admit`` during
# admission and in ``step`` after a decode)
PARENT = {"from_pool": None, "migrate": "from_pool", "restore": "from_pool",
          "engine_init": "from_pool", "step": None, "admit": "step",
          "prefill": "admit", "splice": "admit", "decode": "step"}


def _params():
    return init_params(jax.random.PRNGKey(0), CFG, jnp.float32)


@dataclass
class Span:
    name: str
    start: float
    end: float
    stats: Dict
    line: Tuple[str, int]
    parent: Optional[str] = None


def _program_spans(path):
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    spans.append(Span(e.name[len(tracing.PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns, dict(e.stats),
                                      (plane.name, i)))
    for s in spans:
        outer = [o for o in spans if o is not s and o.line == s.line
                 and o.start <= s.start and s.end <= o.end]
        if outer:
            s.parent = min(outer, key=lambda o: o.end - o.start).name
    return spans


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    mgr = DependencyManager()
    mgr.register_image("base", CFG.name, _params)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, CFG.vocab_size, n) for n in PROMPT_LENS]
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        eng = ServingEngine.from_pool(mgr, "base", CFG, SERVE,
                                      policy=RestorePolicy.BULK)
        rids = [eng.submit(p) for p in prompts]
        eng.run_until_done()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    return eng, rids, _program_spans(path)


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_span_tree_nests_as_the_served_path_runs(served):
    eng, rids, spans = served
    assert {s.name for s in spans} == set(PARENT) | {"sample"}
    for s in spans:
        if s.name == "sample":
            assert s.parent in ("admit", "step")
        else:
            assert s.parent == PARENT[s.name], s
    (up,) = _named(spans, "from_pool")
    (mig,), (res,), (init,) = (_named(spans, n)
                               for n in ("migrate", "restore", "engine_init"))
    assert up.start <= mig.start < mig.end <= res.start < res.end \
        <= init.start < init.end <= up.end
    assert len(_named(spans, "admit")) == len(rids)
    assert len(_named(spans, "prefill")) == len(_named(spans, "splice")) == len(rids)
    assert len(_named(spans, "decode")) == eng.steps
    assert len(_named(spans, "step")) >= eng.steps
    assert len(_named(spans, "sample")) == len(rids) + eng.steps


def test_span_stats(served):
    eng, rids, spans = served
    param_bytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(eng.params))
    (up,), (mig,), (res,), (init,) = (
        _named(spans, n) for n in ("from_pool", "migrate", "restore", "engine_init"))
    assert up.stats == {"policy": "bulk"}
    assert mig.stats == {"hit": 1}          # built when it was registered
    assert res.stats["bytes"] == param_bytes and res.stats["pages"] > 0
    assert init.stats == {"slots": SERVE.max_slots,
                          "max_seq_len": SERVE.max_seq_len}
    admits = _named(spans, "admit")
    assert sorted(s.stats["rid"] for s in admits) == sorted(rids)
    assert sorted(s.stats["prompt_len"] for s in admits) == sorted(PROMPT_LENS)
    assert all(isinstance(s.stats["queued_us"], int) and s.stats["queued_us"] >= 0
               for s in admits)
    # the restore leaves every parameter on the device: no call copies them
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(eng.params))
    for s in _named(spans, "prefill") + _named(spans, "decode"):
        assert s.stats["h2d_bytes"] == 0
    assert {s.stats["active"] for s in _named(spans, "decode")} <= {1, 2}
    assert sorted(s.stats["rid"] for s in _named(spans, "splice")) == sorted(rids)


def test_every_span_is_on_the_calling_threads_line(served):
    _, _, spans = served
    assert len({s.line for s in spans}) == 1


def test_parameters_on_the_device_copy_nothing_per_call():
    params = _params()
    assert ServingEngine(CFG, params, SERVE).h2d_bytes == 0
    host = jax.tree.map(np.asarray, params)
    assert ServingEngine(CFG, host, SERVE).h2d_bytes == sum(
        x.nbytes for x in jax.tree.leaves(host))


def test_span_without_the_profiler_is_a_plain_context():
    with tracing.span("unit", rid=3, policy="bulk") as s:
        assert s is not None
    assert tracing.PREFIX == "hotswap."
